"""Moving an architecture's code out of the harness changed no number: the
seeded weights, the reference's logits and router margins and the roofline
counts are what the tree at 964e4ed gave (data/golden/, make_golden.py),
bit for bit; and the generic modules name nothing of any architecture."""

import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import make_golden  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "golden", "parent.json")) as fh:
        return json.load(fh), np.load(os.path.join(HERE, "data", "golden", "parent.npz"))


@pytest.fixture(scope="module")
def now():
    return make_golden.compute()


@pytest.mark.parametrize("tree", [f"{n}/{s}" for n in make_golden.SIZES
                                  for s in make_golden.SEEDS])
def test_every_leaf_of_the_seeded_weights_is_the_parent_s_bit_for_bit(recorded, now, tree):
    want, got = recorded[0]["weights"][tree], now[0]["weights"][tree]
    assert sorted(got) == sorted(want)  # the same tree, leaf for leaf
    assert got == want


@pytest.mark.parametrize("config", make_golden.CONFIGS)
def test_the_roofline_counts_are_the_parent_s_bit_for_bit(recorded, now, config):
    assert now[0]["roofline"][config] == recorded[0]["roofline"][config]


@pytest.mark.parametrize("size", list(make_golden.SIZES))
def test_the_reference_s_logits_and_margins_are_the_parent_s(recorded, now, size):
    (doc, arrays), (new_doc, new_arrays) = recorded, now
    for key in (f"{size}_float32", f"{size}_margin"):
        want, got = arrays[key], new_arrays[key]
        assert got.shape == want.shape and got.dtype == want.dtype
        finite = np.isfinite(want)
        assert (np.isfinite(got) == finite).all()
        np.testing.assert_allclose(got[finite], want[finite], rtol=0, atol=2e-6)
    # Where this machine sums a float32 product in the order the recording
    # machine did (the canary), the same arithmetic gives the same bits, the
    # int4 control's too; on another machine equal to rounding is all there is.
    if new_doc["canary"] == doc["canary"]:
        assert new_doc["logits"][size] == doc["logits"][size]
        assert make_golden.digest(arrays[f"{size}_float32"]) == doc["logits"][size]["float32"]


# -- the generic modules name no architecture ------------------------------------

GENERIC = ["run.py", "sweep.py"] + [f"harness/{m}.py" for m in (
    "manager", "readers", "xplane", "reference", "roofline", "weights")]
# a tensor, two config keys, a kernel and a control precision of the Mistral family
NAMES = ("w_qkv", "num_key_value_heads", "num_local_experts",
         "paged_decode_attention", '"int4"')


@pytest.mark.parametrize("file", GENERIC)
def test_a_generic_module_names_no_architecture_s_tensor_key_kernel_or_precision(file):
    with open(os.path.join(REPO, "benchmark", file)) as fh:
        lines = fh.read().splitlines()
    # the one place a precision's name stands outside an architecture's file:
    # beside the definition of the function that re-quantizes to it
    hits = [(n, line) for line in lines for n in NAMES if n in line
            and not (file == "harness/reference.py" and n == '"int4"' and "int4}" in line)]
    assert not hits, hits


def test_the_names_live_in_the_architecture_s_file_which_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "archs", "mistral.py")) as fh:
        text = fh.read()
    for n in NAMES:
        assert n in text, n
    for path in (os.path.join(REPO, "benchmark", "archs", "mistral.py"),
                 os.path.join(HERE, "data", "archs", "toy_shared_moe.py")):
        with open(path) as fh:
            assert not re.search(r"^\s*(from|import)\s+aios_tpu", fh.read(), re.M), path
