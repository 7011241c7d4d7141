"""The control of `correct`: the reference put in the program's place and
computed one step below the configuration's precision (int4 for int8
weights) has to come out as not correct — here at a size a test run holds;
PERF.md has the readings at the cells' own sizes on the chip."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import reference  # noqa: E402
from benchmark.harness.manifest import load_file  # noqa: E402

# the sizes are the architecture's own: its file is found as a run finds it
A = load_file(os.path.join(REPO, "benchmark", "archs", "mistral.py"), "benchmark_arch")

DENSE = A.Dims(layers=4, hidden=256, ffn=512, heads=4, kv_heads=2, head_dim=64,
               vocab=2048, experts=0, top_k=0, rope_theta=1e4, eps=1e-5, window=None)
MOE = A.Dims(layers=3, hidden=256, ffn=256, heads=4, kv_heads=2, head_dim=64,
             vocab=2048, experts=4, top_k=2, rope_theta=1e6, eps=1e-5, window=64)
LIMIT = 0.01  # test size: a sound float32 run reads 0.0, the control below


def _sequences(seed):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, 2048, 200)), list(rng.randint(0, 2048, 130))]


@pytest.mark.parametrize("dims", [DENSE, MOE], ids=["dense", "moe"])
@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 7])
def test_the_int4_control_comes_out_not_correct(dims, seed):
    seqs = _sequences(seed)
    keep = [len(s) - 65 for s in seqs]
    out = reference.logits_for(A, dims, seed, seqs, keep, ("float32", A.CONTROL))
    worst = 0.0
    for ref, low, seq, k in zip(out["float32"], out[A.CONTROL], seqs, keep):
        assert ref.shape == (len(seq) - k, dims.vocab)
        # the reference's own first tokens have gap 0: the comparison is sound
        assert reference.served_gaps(ref[:-1], ref[:-1].argmax(-1)).max() == 0.0
        worst = max(worst, float(reference.control_gaps(ref, low).max()))
    assert worst > LIMIT, worst
    # a router's margin is read per position, positive and finite; without a
    # router every position is kept (infinite margin)
    for margin, seq, k in zip(out["router_margin"], seqs, keep):
        assert margin.shape == (len(seq) - k, dims.layers)
        assert (margin > 0).all() and bool(np.isfinite(margin).all()) == bool(dims.experts)


def test_the_router_margin_is_read_layer_by_layer():
    """Layer 0 is a pure function of the seed, so a one-layer model's margin
    is the deeper model's first column; the least over the layers is smaller."""
    import dataclasses

    seq = list(np.random.RandomState(5).randint(0, 2048, 300))
    deep = reference.logits_for(A, MOE, 5, [seq], [100])["router_margin"][0]
    one = reference.logits_for(A, dataclasses.replace(MOE, layers=1), 5, [seq],
                               [100])["router_margin"][0]
    assert deep.shape == (200, 3) and one.shape == (200, 1)
    np.testing.assert_allclose(deep[:, 0], one[:, 0], rtol=1e-5)
    least = deep.min(-1)
    assert (least < one[:, 0]).mean() > 0.3
    assert least.min() < 0.02 < np.median(one)  # near-ties exist; they are not the rule


def test_the_reference_builds_one_layer_at_a_time_from_the_seed_alone():
    import jax

    full = A.build_params(DENSE, 2 ** 31 + 5)
    for layer in (0, 3):
        one = A.build_layer(DENSE, 2 ** 31 + 5, layer)
        same = jax.tree.map(lambda a, b: bool((a[layer] == b).all()),
                            full["layers"], one)
        assert all(jax.tree.leaves(same))
    top = A.build_top(DENSE, 2 ** 31 + 5)
    assert bool((top["lm_head"]["q"] == full["lm_head"]["q"]).all())
    other = A.build_layer(DENSE, 2 ** 31 + 6, 0)
    assert not bool((other["wo"]["q"] == A.build_layer(DENSE, 2 ** 31 + 5, 0)["wo"]["q"]).all())
    # int8 matrices in the fused layout the configurations state
    assert full["layers"]["w_qkv"]["q"].shape == (4, 256, 256 + 2 * 128)
    assert str(full["layers"]["w_qkv"]["q"].dtype) == "int8"


def test_padding_to_a_bucket_does_not_change_a_causal_model_s_logits():
    seq = list(np.random.RandomState(3).randint(0, 2048, 450))
    a = reference.logits_for(A, DENSE, 3, [seq], [400])["float32"][0]
    b = reference.logits_for(A, DENSE, 3, [seq + [7] * 150], [400])["float32"][0][:50]
    assert reference.bucket(len(seq)) != reference.bucket(len(seq) + 150)
    np.testing.assert_allclose(a, b, atol=1e-5)
