"""The yardstick: traffic, metric arithmetic, seeded leaves, the reference's loop, trace reduction.

Only `manager.py` imports the program; every other module here is the
benchmark's own and stays valid whatever a later PR does to `aios_tpu/`.
Nothing here names an architecture: what knows one is its file under
`benchmark/archs/`, found by the `arch` a configuration file states.
"""
