"""The decode step's expert path (moe.moe_ffn_visit, ops/expert_visit.py): a
step visits the held experts its LIVE rows picked and no others, each over
all the rows, gated per row. The dense path (every held expert over every
token) on the live rows is the reference; a row of an inactive slot picks
nothing. Three shapes of routing: (a) softmax top-2 of 8, all held;
(b) sigmoid x 2.5 top-8 of 256 of which 16 are held from expert 48 on, beside
a shared expert; (c) sigmoid top-4 of 64 under a selection bias. The kernel
runs in interpret mode here; tests/test_mosaic_aot.py compiles it for the
chip.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu import ops
from aios_tpu.engine import model as M
from aios_tpu.engine import moe
from aios_tpu.engine.config import ModelConfig
from aios_tpu.ops import expert_visit

TOP2_OF_8 = ModelConfig(
    name="visit-top2of8", vocab_size=512, hidden_size=128,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=32, max_context=128, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=128,
)
HELD_16_OF_256 = dataclasses.replace(
    TOP2_OF_8, name="visit-16of256", num_experts=256, experts_held=16,
    first_expert=48, num_experts_per_tok=8, moe_scoring="sigmoid",
    routed_scaling_factor=2.5, n_shared_experts=1,
)
TOP4_OF_64_BIASED = dataclasses.replace(
    TOP2_OF_8, name="visit-top4of64", num_experts=64, num_experts_per_tok=4,
    moe_scoring="sigmoid", routed_scaling_factor=2.0,
)
CONFIGS = {"top2of8": TOP2_OF_8, "16of256": HELD_16_OF_256,
           "top4of64-bias": TOP4_OF_64_BIASED}
TOL = 0.02  # of the largest output: tests/test_moe_grouped.py's


@functools.lru_cache(maxsize=None)
def _layer(name: str, leaves: str):
    """One expert layer's tree (two layers stacked for ``leaves`` "whole":
    the serving layout as a decode step's scan hands it, read at layer 1).
    Value ``1 + x`` of a row speaks for held expert ``x`` (``_rows`` crowds a
    step's picks onto chosen experts through it)."""
    cfg = CONFIGS[name]
    E, F, X, Xr = cfg.hidden_size, cfg.expert_dim, cfg.held_experts, cfg.num_experts
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 10)
    bf = jnp.bfloat16
    router = jax.random.normal(ks[0], (E, Xr), jnp.float32) * 0.005
    x = jnp.arange(X)
    router = router.at[1 + x, cfg.first_expert + x].add(0.5)
    L = 2 if leaves == "whole" else 1

    def w(k, *shape):
        return (jax.random.normal(k, (L,) + shape, jnp.float32) * 0.08).astype(bf)

    lp = {"w_router": router, "we_gate": w(ks[1], X, E, F),
          "we_up": w(ks[2], X, E, F), "we_down": w(ks[3], X, F, E)}
    if name == "top4of64-bias":
        lp["router_bias"] = jax.random.normal(ks[4], (Xr,), jnp.float32) * 0.02
    if cfg.n_shared_experts:
        lp.update(ws_gate=w(ks[5], E, F)[0], ws_up=w(ks[6], E, F)[0],
                  ws_down=w(ks[7], F, E)[0])
    if leaves != "bf16":  # the fused int8 serving layout
        gateup = jnp.concatenate([lp.pop("we_gate"), lp.pop("we_up")], axis=-1)
        for key, a in (("we_gateup", gateup), ("we_down", lp["we_down"])):
            q, s = ops.quantize_int8(a, axis=-2)
            lp[key] = {"q": q, "s": s}
    if leaves == "whole":
        return {**lp, "expert_layer": jnp.int32(1)}
    return {k: (jax.tree.map(lambda a: a[0], v) if k.startswith("we_") else v)
            for k, v in lp.items()}


def _dense_layer(lp):
    """The tree the dense path reads: one layer's ``[X, in, out]`` leaves."""
    if "expert_layer" not in lp:
        return lp
    return {k: (jax.tree.map(lambda a: a[1], v) if k.startswith("we_") else v)
            for k, v in lp.items() if k != "expert_layer"}


def _rows(n: int, seed: int = 0, crowd=None):
    """A step's normed rows. ``crowd`` (a config): row r's picks are the held
    experts k*r .. k*r + k - 1 (mod held), so n rows touch min(held, n*k)."""
    h = jax.random.normal(jax.random.PRNGKey(100 + seed), (n, 1, 128), jnp.bfloat16)
    if crowd is not None:
        k, X = crowd.num_experts_per_tok, crowd.held_experts
        r, j = np.meshgrid(np.arange(n), np.arange(k), indexing="ij")
        h = h.at[r, 0, 1 + (k * r + j) % X].set(8.0)
    return h


@functools.lru_cache(maxsize=None)
def _visit_fn(name):
    cfg = CONFIGS[name]
    return jax.jit(lambda h, lp, live: moe.moe_ffn_visit(h, lp, cfg, live))


@functools.lru_cache(maxsize=None)
def _dense_fn(name):
    cfg = CONFIGS[name]
    return jax.jit(lambda h, lp: moe.moe_ffn_dense(h, lp, cfg, with_stats=True))


def _touched(name, h, lp, live):
    """(picks of live rows, those that landed here, the held experts they
    touched) from the router alone."""
    cfg = CONFIGS[name]
    _, _, idx = moe.route(h[:, 0], lp["w_router"], cfg, lp.get("router_bias"))
    idx = np.asarray(idx)[np.asarray(live)]
    rel = idx - cfg.first_expert
    here = (rel >= 0) & (rel < cfg.held_experts)
    return idx.size, int(here.sum()), np.unique(rel[here])


@pytest.fixture
def interpreted(monkeypatch):
    """The chip's choice of form on the CPU: the kernel, interpreted."""
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(
        expert_visit, "expert_visit",
        functools.partial(expert_visit.expert_visit, interpret=True))


LIVE = {
    "none": lambda n: np.zeros(n, bool),
    "one": lambda n: np.arange(n) == n - 3,
    "half": lambda n: np.arange(n) % 2 == 0,
    "all": lambda n: np.ones(n, bool),
}


@pytest.mark.parametrize("form", ["bf16-loop", "int8-loop", "int8-kernel",
                                  "int8-whole-kernel"])
@pytest.mark.parametrize("n", [8, 16, 32])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_visit_equals_dense_on_the_live_rows(name, n, form, request):
    """No row live (zero visits, zero output), one, every other, all; then
    all with the rows' picks crowded onto held experts in turn (every expert
    touched where the rows' picks are that many). The counters: picks of the
    live rows alone, visits = the distinct held experts among them, rows =
    visits x the step's rows."""
    if form.endswith("kernel"):
        request.getfixturevalue("interpreted")
    leaves = {"bf16-loop": "bf16", "int8-whole-kernel": "whole"}.get(form, "int8")
    cfg = CONFIGS[name]
    lp = _layer(name, leaves)
    # traced anew under the fixture: ``_visit_fn`` is cached by name alone
    fn = (jax.jit(lambda h, lp, live: moe.moe_ffn_visit(h, lp, cfg, live))
          if form.endswith("kernel") else _visit_fn(name))
    cases = [(False, k) for k in LIVE] + [(True, "all")]
    seen = set()
    for crowd, which in cases:
        h = _rows(n, crowd=cfg if crowd else None)
        live = LIVE[which](n)
        want = np.asarray(_dense_fn(name)(h, _dense_layer(lp))[0], np.float32)
        got, _, stats = fn(h, lp, jnp.asarray(live))
        got = np.asarray(got, np.float32)
        tol = TOL * np.abs(want).max()
        assert np.abs(got[live] - want[live]).max(initial=0) < tol, (crowd, which)
        assert not got[~live].any(), (crowd, which)
        picks, local, touched = _touched(name, h, lp, live)
        assert stats.tolist() == [picks, local, len(touched) * n, len(touched)]
        assert picks == live.sum() * cfg.num_experts_per_tok
        seen.add(len(touched))
        if crowd:
            assert len(touched) == min(
                cfg.held_experts, n * cfg.num_experts_per_tok)
    assert 0 in seen and len(seen) >= 3


@pytest.mark.parametrize("name", list(CONFIGS))
def test_an_inactive_row_changes_neither_the_visits_nor_the_counters(name):
    """What an inactive slot's row holds (a finished stream's last token, a
    prompt mid-admission) is nobody's to read: other values there leave the
    live rows' results, the visit count and all four counters as they were."""
    lp = _layer(name, "int8")
    live = jnp.asarray(LIVE["half"](16))
    h = _rows(16)
    other = jnp.where(live[:, None, None], h, _rows(16, seed=5))
    assert np.abs(np.asarray(other - h, np.float32)).max() > 1
    a, _, sa = _visit_fn(name)(h, lp, live)
    b, _, sb = _visit_fn(name)(other, lp, live)
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert sa.tolist() == sb.tolist()
    # and with those rows live they are counted
    _, _, s_all = _visit_fn(name)(other, lp, jnp.ones((16,), bool))
    assert s_all.tolist()[0] == 2 * sa.tolist()[0]
    assert s_all.tolist()[3] >= sa.tolist()[3]


def test_pick_stats_has_four_numbers_on_all_three_paths():
    cfg = TOP2_OF_8
    lp = _layer("top2of8", "int8")
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 128), jnp.bfloat16)
    dense = moe.moe_ffn_dense(h, lp, cfg, with_stats=True)[2]
    grouped = moe.moe_ffn_grouped(h, lp, cfg)[2]
    step = _rows(8)
    visit = moe.moe_ffn_visit(step, lp, cfg, jnp.ones((8,), bool))[2]
    assert dense.shape == grouped.shape == visit.shape == (moe.PICK_STATS,) == (4,)
    assert dense.tolist() == [512, 512, 8 * 256, 8]
    assert grouped.tolist()[:2] == [512, 512] and grouped.tolist()[3] == 8
    assert visit.tolist()[:2] == [16, 16]
    assert visit.tolist()[2] == 8 * visit.tolist()[3] and 2 <= visit.tolist()[3] <= 8
    assert M.zero_stats(cfg)[0].shape == (4,)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_ffn_takes_the_visit_when_handed_the_mask_and_only_then(name, monkeypatch):
    """model.ffn: with ``live`` (a decode step's mask) the visit, beside the
    shared expert where the layer has one; without it the path the token
    count gives; with it under a sharding plan (``moe_dense``) dense."""
    cfg = CONFIGS[name]
    lp = _layer(name, "int8")
    h, live = _rows(16), jnp.asarray(LIVE["half"](16))
    seen = []
    for path in ("moe_ffn_dense", "moe_ffn_visit", "moe_ffn_grouped"):
        real = getattr(moe, path)
        monkeypatch.setattr(
            moe, path,
            lambda *a, _r=real, _p=path, **kw: (seen.append(_p), _r(*a, **kw))[1])
    want, _, s_dense = M.ffn(h, lp, cfg)
    got, _, s_visit = M.ffn(h, lp, cfg, live=live)
    planned, _, s_plan = M.ffn(h, lp, cfg, moe_dense=True, live=live)
    assert seen == ["moe_ffn_dense", "moe_ffn_visit", "moe_ffn_dense"]
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    keep = np.asarray(live)
    assert np.abs(got[keep] - want[keep]).max() < TOL * np.abs(want).max()
    np.testing.assert_array_equal(np.asarray(planned, np.float32), want)
    assert s_plan.tolist() == s_dense.tolist()
    assert s_visit.tolist()[3] <= s_dense.tolist()[3] == cfg.held_experts
    if cfg.n_shared_experts:  # an inactive row still has its shared expert's part
        shared = np.asarray(M._swiglu(h, lp, "ws_", cfg.expert_dim), np.float32)
        np.testing.assert_array_equal(got[~keep], shared[~keep])
        assert np.abs(shared).max() > 0


def test_visit_list_is_ascending_then_repeats_the_last():
    touched = jnp.asarray([0, 1, 0, 0, 1, 1, 0, 0], bool)
    visit, n = expert_visit.visit_list(touched)
    assert visit.tolist() == [1, 4, 5, 5, 5, 5, 5, 5] and int(n) == 3
    visit, n = expert_visit.visit_list(jnp.zeros((8,), bool))
    assert visit.tolist() == [0] * 8 and int(n) == 0
    visit, n = expert_visit.visit_list(jnp.ones((64,), bool))
    assert visit.tolist() == list(range(64)) and int(n) == 64
    assert expert_visit.supports_pallas(3584, 1024)
    assert not expert_visit.supports_pallas(64, 32)
