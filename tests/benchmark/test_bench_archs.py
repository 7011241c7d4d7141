"""The seam of `benchmark/archs/` carries layers that differ by index, proved
without the program: a toy architecture (data/archs/toy_shared_moe.py: one
leading dense layer, then a sigmoid router over 16 experts of which 4 are held
here, top-4 with a scale, and one shared expert) that no harness module names
goes through the generic loop of `harness/reference.py`."""

import dataclasses
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import reference  # noqa: E402
from benchmark.harness.manifest import load_file  # noqa: E402

T = load_file(os.path.join(HERE, "data", "archs", "toy_shared_moe.py"), "benchmark_arch")
D = T.Dims()
SEED = 2 ** 31 + 9


def _seq(n=150, seed=3):
    return [int(t) for t in np.random.RandomState(seed).randint(0, D.vocab, n)]


# -- (a) the whole model, written plainly: numpy, float64, one token loop-free --


def _w(leaf):
    return np.asarray(leaf["q"], np.float64) * np.asarray(leaf["s"], np.float64)


def _rms(x, weight, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * np.asarray(weight, np.float64)


def _rope(x, theta):
    t, _, dim = x.shape
    half = dim // 2
    ang = np.arange(t)[:, None] / theta ** (np.arange(half) / half)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, leaves, width):
    gu = h @ _w(leaves["gateup"])
    gate, up = gu[:, :width], gu[:, width:]
    return (gate / (1 + np.exp(-gate)) * up) @ _w(leaves["down"])


def _plain_forward(d, seed, ids):
    top = T.build_top(d, seed)
    x = np.asarray(top["embed"], np.float64)[ids]
    t = len(ids)
    for l in range(d.layers):
        lw = T.build_layer(d, seed, l)
        qd, kd = d.heads * d.head_dim, d.kv_heads * d.head_dim
        qkv = _rms(x, lw["attn_norm"], d.eps) @ _w(lw["w_qkv"])
        q = _rope(qkv[:, :qd].reshape(t, d.heads, d.head_dim), d.rope_theta)
        k = _rope(qkv[:, qd:qd + kd].reshape(t, d.kv_heads, d.head_dim), d.rope_theta)
        v = qkv[:, qd + kd:].reshape(t, d.kv_heads, d.head_dim)
        att = np.zeros((t, d.heads, d.head_dim))
        for h in range(d.heads):
            g = h // (d.heads // d.kv_heads)
            s = q[:, h] @ k[:, g].T / np.sqrt(d.head_dim)
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            att[:, h] = p / p.sum(-1, keepdims=True) @ v[:, g]
        x = x + att.reshape(t, qd) @ _w(lw["wo"])
        h = _rms(x, lw["ffn_norm"], d.eps)
        if l < d.dense_layers:
            x = x + _swiglu(h, lw["ffn"], d.dense_ffn)
            continue
        score = 1 / (1 + np.exp(-(h @ np.asarray(lw["router"], np.float64))))
        y = _swiglu(h, lw["shared"], d.expert_ffn)
        for row in range(t):
            chosen = np.argsort(-score[row])[:d.top_k]
            for e in chosen:
                if d.first <= e < d.first + d.held:  # an absent expert adds nothing here
                    one = {k: {a: np.asarray(b)[e - d.first] for a, b in v.items()}
                           for k, v in lw["experts"].items()}
                    y[row] += (d.scale * score[row, e] / score[row, chosen].sum()
                               * _swiglu(h[row:row + 1], one, d.expert_ffn)[0])
        x = x + y
    return _rms(x, top["final_norm"], d.eps) @ _w(top["lm_head"])


@pytest.fixture(scope="module")
def through_the_loop():
    seq = _seq()
    return seq, reference.logits_for(T, D, SEED, [seq], [100], ("float32", T.CONTROL))


def test_the_generic_loop_gives_the_plainly_written_whole_model_s_logits(through_the_loop):
    seq, out = through_the_loop
    want = _plain_forward(D, SEED, np.asarray(seq))[100:]
    got = out["float32"][0]
    assert got.shape == want.shape == (50, D.vocab)
    # float32 against float64: 3e-7 read at a logit std of 0.17
    assert np.abs(got - want).max() < 1e-5 and want.std() > 0.1


def test_the_margin_is_finite_exactly_in_the_routed_layers(through_the_loop):
    margin = through_the_loop[1]["router_margin"][0]
    assert margin.shape == (50, D.layers)
    for l in range(D.layers):
        assert bool(np.isfinite(margin[:, l]).all()) == D.routed(l)
        assert bool(np.isinf(margin[:, l]).all()) != D.routed(l)
    assert (margin > 0).all()


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_uncut_layer():
    """The guide's tie of share to model (model-configs, section 4): each of
    four chips holds 4 of the 16 experts; what they add, with the shared
    expert that every chip computes alike counted once, is what the layer
    gives with all 16 held."""
    import jax
    import jax.numpy as jnp

    whole = dataclasses.replace(D, held=D.experts, first=0)
    h = jnp.asarray(np.random.RandomState(4).standard_normal((40, D.hidden)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for layer in range(D.dense_layers, D.layers):
            routed, shared, margin = T.moe_parts(whole, h, T.build_layer(whole, SEED, layer), "float32")
            total = np.zeros_like(routed)
            for first in range(0, D.experts, D.held):
                share = dataclasses.replace(D, first=first)
                lw = T.build_layer(share, SEED, layer)
                # a share holds the uncut layer's own bytes for its experts
                assert bool((lw["experts"]["down"]["q"] == T.build_layer(whole, SEED, layer)[
                    "experts"]["down"]["q"][first:first + D.held]).all())
                part, same_shared, same_margin = T.moe_parts(share, h, lw, "float32")
                np.testing.assert_array_equal(same_shared, shared)
                np.testing.assert_array_equal(same_margin, margin)  # ranked over all 16
                assert float(jnp.abs(part).max()) > 0  # every share is routed to
                total += np.asarray(part)
            np.testing.assert_allclose(total + shared, routed + shared, atol=1e-6)
            assert float(jnp.abs(routed).mean()) > 0.1 * float(jnp.abs(shared).mean())


@pytest.mark.parametrize("seed", [1, 2, SEED])
def test_the_int4_control_comes_out_not_correct_for_the_toy(seed):
    seq = _seq(seed=seed % 1000)
    out = reference.logits_for(T, D, seed, [seq], [len(seq) - 65], ("float32", T.CONTROL))
    ref, low = out["float32"][0], out[T.CONTROL][0]
    assert reference.served_gaps(ref[:-1], ref[:-1].argmax(-1)).max() == 0.0
    assert float(reference.control_gaps(ref, low).max()) > 0.01  # test_bench_control's limit
