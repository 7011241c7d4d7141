"""Decode engine: prefill/decode consistency, sampling, slot reuse.

The key invariant (teacher-forcing test): running prefill + step-by-step
decode through the slot cache must produce exactly the tokens that greedy
argmax over the full-sequence forward produces — i.e. the incremental KV path
is numerically identical to the non-cached path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine import sampling
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tiny_engine():
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    return TPUEngine(TINY_TEST, params, num_slots=4, max_context=128, cache_dtype=jnp.float32)


def _full_greedy(params, cfg, prompt, n):
    """Reference: greedy generation via repeated full forward (no cache)."""
    toks = list(prompt)
    for _ in range(n):
        logits = M.forward_full(params, cfg, np.asarray([toks], np.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt) :]


def test_greedy_decode_matches_uncached_forward(tiny_engine):
    prompt = [3, 17, 91, 4, 55, 8]
    want = _full_greedy(tiny_engine.params, TINY_TEST, prompt, 10)
    got = tiny_engine.generate(prompt, max_new_tokens=10, temperature=0.0)
    assert got == want


def test_chunked_prefill_matches_monolithic(tiny_engine):
    """Admitting a prompt in 32-token chunks must yield the same first token
    and greedy continuation as one monolithic prefill."""
    prompt = (np.arange(1, 100) % 250 + 1).tolist()  # 99 tokens
    first_a = tiny_engine.prefill(0, prompt, temperature=0.0)
    toks_a = [int(t) for t in tiny_engine.step(8)[:, 0]]
    tiny_engine.release(0)

    pc = tiny_engine.start_chunked_prefill(1, prompt, temperature=0.0, chunk=32)
    steps = 0
    first_b = None
    while first_b is None:
        first_b = pc.step()
        steps += 1
    assert steps == 4 and pc.done  # 32 + 32 + 32 + 3
    toks_b = [int(t) for t in tiny_engine.step(8)[:, 1]]
    tiny_engine.release(1)

    assert first_b == first_a
    assert toks_b == toks_a


def test_chunked_prefill_int8_cache_matches_monolithic():
    """Chunked admission under the int8 KV cache quantizes rows on write
    exactly like the monolithic path (same per-row scales)."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    a = TPUEngine(TINY_TEST, params, num_slots=2, max_context=128,
                  cache_dtype=jnp.int8)
    b = TPUEngine(TINY_TEST, params, num_slots=2, max_context=128,
                  cache_dtype=jnp.int8)
    prompt = (np.arange(1, 80) % 250 + 1).tolist()
    first_a = a.prefill(0, prompt, temperature=0.0)
    toks_a = [int(t) for t in a.step(6)[:, 0]]
    pc = b.start_chunked_prefill(0, prompt, temperature=0.0, chunk=32)
    first_b = None
    while first_b is None:
        first_b = pc.step()
    toks_b = [int(t) for t in b.step(6)[:, 0]]
    assert first_b == first_a
    assert toks_b == toks_a


def test_chunked_prefill_rejects_non_bucket_chunk(tiny_engine):
    with pytest.raises(ValueError):
        tiny_engine.start_chunked_prefill(0, [1, 2, 3], chunk=48)


@pytest.mark.parametrize("chunk", [32, 0])
def test_warmup_compiles_every_bucket_and_step_size(chunk):
    """The readiness gate must leave NO graph the batcher dispatches
    uncompiled: a missing prefill bucket or decode step size compiles for
    seconds on the scheduler thread at first use (the regression behind the
    2s agent TTFT: warmup's old 4-token prompt bucketed to 16 every
    iteration, so larger buckets were never compiled). With chunked
    admission on, a prompt above the chunk size admits in chunks, so the
    whole-prompt graphs stop at the chunk size; with it off they are every
    bucket."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=128, cache_dtype=jnp.float32
    )
    engine.warmup(step_sizes=(1, 2), prefill_chunk=chunk)
    assert engine.admission_chunk(chunk) == chunk
    assert set(engine._prefill_fns) == {
        b for b in engine.buckets if not chunk or b <= chunk}
    assert set(engine._step_fns) == {1, 2}
    # chunked-admission graphs: the mid chunk and every final bucket <= 32
    assert set(engine._chunk_fns) == (
        {(32, False), (16, True), (32, True)} if chunk else set())


def test_close_releases_state():
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=128, cache_dtype=jnp.float32
    )
    engine.prefill(0, [1, 2, 3], temperature=0.0)
    engine.close()
    assert engine.state == {} and engine.params is None
    assert not engine._prefill_fns and not engine._step_fns


def test_generate_respects_stop_tokens(tiny_engine):
    prompt = [3, 17, 91, 4, 55, 8]
    free_run = tiny_engine.generate(prompt, max_new_tokens=10, temperature=0.0)
    stopper = free_run[3]
    stopped = tiny_engine.generate(
        prompt, max_new_tokens=10, temperature=0.0, stop_tokens=(stopper,)
    )
    assert stopped == free_run[: free_run.index(stopper) + 1]


def test_concurrent_slots_are_independent(tiny_engine):
    """Two different prompts decoding in adjacent slots must produce the same
    tokens as each decoding alone (no cross-slot leakage)."""
    p1 = [5, 9, 2, 41]
    p2 = [88, 13, 60, 7, 19]
    solo1 = tiny_engine.generate(p1, max_new_tokens=6)
    solo2 = tiny_engine.generate(p2, max_new_tokens=6)

    t1 = tiny_engine.prefill(1, p1, temperature=0.0)
    t2 = tiny_engine.prefill(2, p2, temperature=0.0)
    got1, got2 = [t1], [t2]
    toks = tiny_engine.step(5)  # [5, S] — one dispatch, five tokens per slot
    got1.extend(int(t) for t in toks[:, 1])
    got2.extend(int(t) for t in toks[:, 2])
    tiny_engine.release(1)
    tiny_engine.release(2)
    assert got1 == solo1
    assert got2 == solo2


def test_slot_reuse_after_release(tiny_engine):
    p = [42, 42, 7]
    a = tiny_engine.generate(p, max_new_tokens=5, slot=3)
    b = tiny_engine.generate(p, max_new_tokens=5, slot=3)
    assert a == b


def test_prompt_bucketing_invariant(tiny_engine):
    """The same prompt must decode identically whatever bucket it lands in
    (padding rows must not leak into attention)."""
    prompt = [9] * 15  # bucket 16
    short = tiny_engine.generate(prompt, max_new_tokens=4)
    prompt_long = [1] * 17 + [9] * 15  # bucket 32; different prefix
    # invariance check: run 15-token prompt again, engine state unchanged
    again = tiny_engine.generate(prompt, max_new_tokens=4)
    assert short == again
    assert len(tiny_engine.generate(prompt_long, max_new_tokens=4)) == 4


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------


def test_top_p_filter_masks_tail():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    out = sampling.top_p_filter(logits, jnp.asarray([0.7]))
    # 0.5 kept (cum before = 0); 0.3 kept (cum before = 0.5 < 0.7);
    # 0.15 dropped (cum before = 0.8 >= 0.7)
    assert np.isfinite(np.asarray(out[0, :2])).all()
    assert np.isneginf(np.asarray(out[0, 2:])).all()


def test_top_k_filter():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = sampling.top_k_filter(logits, jnp.asarray([2]))
    assert np.isneginf(np.asarray(out[0, [0, 3]])).all()
    assert np.isfinite(np.asarray(out[0, [1, 2]])).all()


def test_sample_greedy_vs_stochastic_rows():
    logits = jnp.asarray([[0.0, 10.0, 0.0], [0.0, 10.0, 0.0]])
    toks = sampling.sample(
        logits,
        jax.random.PRNGKey(0),
        temperature=jnp.asarray([0.0, 1.0]),
        top_p=jnp.asarray([1.0, 1.0]),
    )
    assert int(toks[0]) == 1  # greedy row
    assert 0 <= int(toks[1]) < 3


def test_sampling_distribution_statistics():
    """Temperature-1 sampling over a known distribution approximates it."""
    probs = np.asarray([0.6, 0.3, 0.1])
    logits = jnp.broadcast_to(jnp.log(jnp.asarray(probs)), (2000, 3))
    toks = sampling.sample(
        logits,
        jax.random.PRNGKey(1),
        temperature=jnp.ones(2000),
        top_p=jnp.ones(2000),
    )
    counts = np.bincount(np.asarray(toks), minlength=3) / 2000
    np.testing.assert_allclose(counts, probs, atol=0.05)


def test_top_p_excludes_tail_statistically():
    probs = np.asarray([0.55, 0.35, 0.1])
    logits = jnp.broadcast_to(jnp.log(jnp.asarray(probs)), (500, 3))
    toks = sampling.sample(
        logits,
        jax.random.PRNGKey(2),
        temperature=jnp.ones(500),
        top_p=jnp.full(500, 0.6),
    )
    # nucleus at 0.6 keeps tokens 0 and 1 only
    assert set(np.asarray(toks).tolist()) <= {0, 1}


def test_host_params_quantize_before_transfer():
    """GGUF-style host (numpy) params with quantized serving: the engine
    quantizes on the host CPU backend and ships only quantized leaves, so
    dense weights never stage on the accelerator (the 7B-tier OOM guard).
    Tokens must match quantizing from device-resident params."""
    import numpy as np

    from aios_tpu.engine import model as M
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.engine.engine import TPUEngine

    params = M.init_params(TINY_TEST, jax.random.PRNGKey(21), dtype=jnp.float32)
    host_params = jax.tree.map(lambda a: np.asarray(a), params)
    eng_host = TPUEngine(TINY_TEST, host_params, num_slots=2, max_context=64,
                         cache_dtype=jnp.float32, quantize="int8")
    eng_dev = TPUEngine(TINY_TEST, params, num_slots=2, max_context=64,
                        cache_dtype=jnp.float32, quantize="int8")
    assert "q" in eng_host.params["layers"]["w_qkv"]
    out_h = eng_host.generate([1, 5, 9, 2], max_new_tokens=8, temperature=0.0)
    out_d = eng_dev.generate([1, 5, 9, 2], max_new_tokens=8, temperature=0.0)
    assert out_h == out_d
