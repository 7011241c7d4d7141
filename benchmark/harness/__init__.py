"""The yardstick: traffic, metric arithmetic, weights, reference, trace reduction.

Only `manager.py` imports the program; every other module here is the
benchmark's own and stays valid whatever a later PR does to `aios_tpu/`.
"""
