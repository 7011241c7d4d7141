"""Continuous batcher: correctness under concurrency, streaming, recycling."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.engine.tokenizer import ByteTokenizer, SentencePieceBPE, render_chat

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow


@pytest.fixture()
def batcher():
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=4, max_context=128, cache_dtype=jnp.float32
    )
    b = ContinuousBatcher(engine, chunk_steps=4, admit_chunk_steps=2)
    yield b
    b.shutdown()


def test_single_request_matches_generate(batcher):
    prompt = [3, 17, 91, 4, 55, 8]
    want = batcher.engine.generate(prompt, max_new_tokens=10, temperature=0.0)
    got = batcher.generate(prompt, max_tokens=10, temperature=0.0)
    assert got == want


def test_many_concurrent_requests_greedy_identical(batcher):
    """10 requests over 4 slots: every request must match its solo output."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=rng.integers(3, 20)).tolist() for _ in range(10)]
    solo = [
        batcher.engine.generate(p, max_new_tokens=8, temperature=0.0) for p in prompts
    ]

    results = [None] * len(prompts)

    def worker(i):
        results[i] = batcher.generate(prompts[i], max_tokens=8, temperature=0.0)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, (got, want) in enumerate(zip(results, solo)):
        assert got == want, f"request {i}: {got} != {want}"
    assert batcher.completed == len(prompts)
    assert batcher.active_count == 0


def test_streaming_yields_incrementally(batcher):
    handle = batcher.submit(
        Request(prompt_ids=[5, 6, 7], max_tokens=6, temperature=0.0)
    )
    toks = []
    for tok in handle:
        toks.append(tok)
    assert len(toks) == 6
    assert handle.ttft_ms >= 0.0


def test_stop_tokens_end_request(batcher):
    prompt = [3, 17, 91, 4, 55, 8]
    free_run = batcher.generate(prompt, max_tokens=10, temperature=0.0)
    stopper = free_run[2]
    stopped = batcher.generate(
        prompt, max_tokens=10, temperature=0.0, stop_ids=(stopper,)
    )
    assert stopped == free_run[:3]


def test_max_tokens_respected(batcher):
    out = batcher.generate([1, 2, 3], max_tokens=3, temperature=0.0)
    assert len(out) == 3


def test_scheduler_failure_aborts_requests_instead_of_hanging():
    """If the scheduler thread hits an engine error, every caller's iterator
    must terminate (and the error be inspectable) — not block forever."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=128, cache_dtype=jnp.float32
    )
    b = ContinuousBatcher(engine, chunk_steps=4)
    try:
        def boom(n=1):
            raise RuntimeError("synthetic engine failure")

        engine.step = boom
        handle = b.submit(Request(prompt_ids=[1, 2, 3], max_tokens=8))
        toks = handle.tokens()  # must return, not hang
        assert len(toks) <= 8
        assert isinstance(b.last_error, RuntimeError)
        assert b.active_count == 0
    finally:
        b.shutdown()

    with pytest.raises(ValueError):
        b.submit(Request(prompt_ids=[]))


def test_long_admission_interleaves_decode_and_stays_correct():
    """Admitting a long prompt must NOT stall decode for active slots
    (VERDICT r2 weak #5: prefill head-of-line blocking), and the chunked
    admission must produce exactly the tokens a solo run produces (i.e. the
    interleaved decode dispatches don't corrupt the half-prefilled slot)."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=128, cache_dtype=jnp.float32
    )
    solo = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=128, cache_dtype=jnp.float32
    )
    prompt_a = [1, 2, 3]
    prompt_b = (np.arange(1, 100) % 250 + 1).tolist()  # 99 tokens, 7 chunks
    want_a = solo.generate(prompt_a, max_new_tokens=40, temperature=0.0)
    want_b = solo.generate(prompt_b, max_new_tokens=4, temperature=0.0)

    b = ContinuousBatcher(
        engine, chunk_steps=4, admit_chunk_steps=1, prefill_chunk=16
    )
    events = []
    orig_step = engine.step
    engine.step = lambda n=1: (events.append("decode"), orig_step(n))[1]
    orig_scp = engine.start_chunked_prefill

    def recording_scp(*a, **kw):
        pc = orig_scp(*a, **kw)
        orig = pc.step
        pc.step = lambda: (events.append("chunk"), orig())[1]
        return pc

    engine.start_chunked_prefill = recording_scp
    try:
        ha = b.submit(Request(prompt_ids=prompt_a, max_tokens=40, temperature=0.0))
        it_a = iter(ha)
        got_a = [next(it_a)]  # A is live and decoding
        hb = b.submit(Request(prompt_ids=prompt_b, max_tokens=4, temperature=0.0))
        got_b = hb.tokens()
        got_a += list(it_a)
    finally:
        b.shutdown()

    assert got_b == want_b
    assert got_a == want_a
    chunk_idx = [i for i, e in enumerate(events) if e == "chunk"]
    assert len(chunk_idx) == 7  # 99 tokens / 16-token chunks
    interleaved = [
        e for e in events[chunk_idx[0] + 1 : chunk_idx[-1]] if e == "decode"
    ]
    assert interleaved, "no decode dispatch ran during the long admission"


# ---------------------------------------------------------------------------
# Tokenizers
# ---------------------------------------------------------------------------


def test_byte_tokenizer_roundtrip():
    t = ByteTokenizer()
    ids = t.encode("hello world")
    assert ids[0] == t.bos_id
    assert t.decode(ids) == "hello world"


def test_sentencepiece_bpe_merges_by_score():
    # every longer piece is reachable by pairwise merges:
    # h+e, l+o, l+lo, he+llo, ▁+hello
    tokens = ["<unk>", "<s>", "</s>", "▁", "h", "e", "l", "o",
              "he", "lo", "llo", "hello", "▁hello"]
    scores = [0, 0, 0, -10, -1, -1, -1, -1, -0.9, -1.0, -0.8, -0.3, -0.1]
    types = [2, 3, 3] + [1] * 10
    tok = SentencePieceBPE(tokens=tokens, scores=scores, token_types=types)
    ids = tok.encode("hello", add_bos=False)
    assert ids == [tokens.index("▁hello")]
    assert tok.decode(ids) == "hello"


def test_sentencepiece_byte_fallback():
    tokens = ["<unk>", "<s>", "</s>", "▁"] + [f"<0x{i:02X}>" for i in range(256)]
    scores = [0.0] * len(tokens)
    types = [2, 3, 3, 1] + [6] * 256
    tok = SentencePieceBPE(tokens=tokens, scores=scores, token_types=types)
    ids = tok.encode("hi", add_bos=False)
    # "▁" is in vocab; h and i fall back to bytes
    assert tok.decode(ids) == "hi"


def test_chat_templates():
    assert "[INST]" in render_chat("mistral-7b", "hi", "be brief")
    assert "<|system|>" in render_chat("tinyllama-1.1b", "hi", "be brief")
    assert "<|im_start|>" in render_chat("qwen3-14b", "hi")
    out = render_chat("unknown-model", "hi", "sys")
    assert "User: hi" in out and "System: sys" in out


def test_batcher_serves_int4_engine():
    """The production batcher over an int4-quantized engine: batched greedy
    output must match the same engine's direct generate (slot scheduling is
    weight-format-agnostic)."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(7), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=4, max_context=128,
        cache_dtype=jnp.float32, quantize="int4",
    )
    assert engine.quant_mode == "int4"
    b = ContinuousBatcher(engine, chunk_steps=4, admit_chunk_steps=2)
    try:
        prompt = [3, 17, 91, 4, 55, 8]
        want = engine.generate(prompt, max_new_tokens=10, temperature=0.0)
        got = b.generate(prompt, max_tokens=10, temperature=0.0)
        assert got == want
    finally:
        b.shutdown()


def test_cancel_frees_slot_and_ends_iterator(batcher):
    """cancel() mid-stream releases the request's slot at the next tick and
    its iterator ends — the disconnect-abort path (llama-server parity:
    decode stops when the client goes away)."""
    import time

    h = batcher.submit(Request(
        prompt_ids=[3, 17, 91], max_tokens=10_000, temperature=0.0
    ))
    it = iter(h)
    next(it)  # live: slot held
    assert batcher.active_count == 1
    h.cancel()
    remaining = list(it)  # ends without producing max_tokens
    assert len(remaining) < 10_000
    deadline = time.time() + 5
    while batcher.active_count and time.time() < deadline:
        time.sleep(0.01)
    assert batcher.active_count == 0
    assert batcher.cancellations == 1
    # the cancelled slot itself was recycled, not just the other 3
    assert len(batcher.engine.free_slots()) == batcher.engine.num_slots
    # the engine still serves new requests afterwards
    out = batcher.generate([5, 6, 7], max_tokens=4, temperature=0.0)
    assert len(out) == 4


def test_cancel_queued_request_never_occupies_slot():
    """Cancelling while still queued drops the request from the wait list
    without touching any slot."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(1), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=1, max_context=128,
        cache_dtype=jnp.float32,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    try:
        hog = b.submit(Request(prompt_ids=[1, 2], max_tokens=64,
                               temperature=0.0))
        queued = b.submit(Request(prompt_ids=[3, 4], max_tokens=64,
                                  temperature=0.0))
        assert b.queue_depth() >= 1
        queued.cancel()
        assert queued.tokens() == []  # ended without ever running
        assert len(hog.tokens()) == 64  # the live request is unaffected
        assert b.cancellations == 1
    finally:
        b.shutdown()


def test_grpc_disconnect_cancels_request():
    """Closing the gRPC channel mid-StreamInfer aborts the request server-
    side (context callback -> handle.cancel), freeing the slot."""
    import time

    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve

    mgr = ModelManager(num_slots=2, warm_compile=False)
    # budget the request CANNOT finish quickly (big context, huge
    # max_tokens): the tiny model decodes thousands of tok/s on CPU, so a
    # small context would let out_of_cache complete the request before the
    # client's cancel crosses the wire (measured: 2048 rows lose the race; 8192 wins with seconds to spare)
    mgr.load_model("tiny", "synthetic://tiny-test", context_length=8192)
    server, service, port = serve(address="127.0.0.1:0", manager=mgr,
                                  block=False)
    try:
        channel = rpc.insecure_channel(f"127.0.0.1:{port}")
        stub = services.AIRuntimeStub(channel)
        stream = stub.StreamInfer(runtime_pb2.InferRequest(
            prompt="hello", max_tokens=50_000, temperature=0.5
        ))
        next(stream)  # request is live server-side
        batcher = mgr.models["tiny"].batcher
        stream.cancel()  # client walks away
        channel.close()
        # poll the CANCELLATION counter, not active_count: the live entry
        # is popped before the counter increments (engine.release sits
        # between them), so active_count==0 can be observed in that gap
        deadline = time.time() + 10
        while batcher.cancellations < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert batcher.cancellations >= 1
        assert batcher.active_count == 0
    finally:
        server.stop(grace=None)
        mgr.unload_model("tiny")


def test_gateway_disconnect_propagates_cancel_to_runtime(monkeypatch):
    """The FULL abort chain: agent disconnects from the gateway mid-stream
    -> gateway's generator closes -> it cancels its downstream runtime
    call -> the runtime frees the slot. Without propagation the runtime
    would stream to an abandoned iterator until max_tokens."""
    import time

    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import api_gateway_pb2
    from aios_tpu.gateway.router import RequestRouter
    from aios_tpu.gateway.service import serve as serve_gateway
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve as serve_runtime

    for var in ("CLAUDE_API_KEY", "OPENAI_API_KEY", "QWEN3_API_KEY"):
        monkeypatch.delenv(var, raising=False)
    channel = gw_server = rt_server = None
    mgr = ModelManager(num_slots=2, warm_compile=False)
    try:
        mgr.load_model("tiny", "synthetic://tiny-test", context_length=8192)
        rt_server, _, rt_port = serve_runtime(
            address="127.0.0.1:0", manager=mgr, block=False
        )
        gw_server, _, gw_port = serve_gateway(
            address="127.0.0.1:0",
            router=RequestRouter(runtime_address=f"127.0.0.1:{rt_port}"),
            block=False,
        )
        channel = rpc.insecure_channel(f"127.0.0.1:{gw_port}")
        gw = services.ApiGatewayStub(channel)
        stream = gw.StreamInfer(api_gateway_pb2.ApiInferRequest(
            prompt="hello", max_tokens=50_000, temperature=0.5
        ))
        next(stream)  # live through gateway -> runtime -> engine
        batcher = mgr.models["tiny"].batcher
        stream.cancel()
        deadline = time.time() + 15
        while batcher.cancellations < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert batcher.cancellations >= 1
        assert batcher.active_count == 0
    finally:
        if channel is not None:
            channel.close()
        for server in (gw_server, rt_server):
            if server is not None:
                server.stop(grace=None)
        if mgr.get("tiny") is not None:
            mgr.unload_model("tiny")


def test_gateway_disconnect_while_queued_cancels_without_slot(monkeypatch):
    """Disconnect before ANY delta flows (request still queued behind busy
    slots): no GeneratorExit can reach the gateway handler — the RPC-
    termination callback must cancel the registered downstream call, and
    the queued request must be reaped without ever taking a slot."""
    import time

    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import api_gateway_pb2, runtime_pb2
    from aios_tpu.gateway.router import RequestRouter
    from aios_tpu.gateway.service import serve as serve_gateway
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve as serve_runtime

    for var in ("CLAUDE_API_KEY", "OPENAI_API_KEY", "QWEN3_API_KEY"):
        monkeypatch.delenv(var, raising=False)
    channel = rt_channel = gw_server = rt_server = None
    mgr = ModelManager(num_slots=1, warm_compile=False)
    try:
        mgr.load_model("tiny", "synthetic://tiny-test", context_length=8192)
        # DEFLAKE: the hog must NOT retire while the disconnect is in
        # flight, or the freed slot admits the queued request and the
        # active_count==1 assert races. Two stochastic retirements
        # existed: sampling the EOS stop id at temperature 0.5 (the
        # random-init model emits it eventually — the dominant flake),
        # and hitting the ctx cap / max_tokens on a fast host. Pin both:
        # every decode dispatch is throttled (the hog cannot burn its
        # budget inside any test deadline) and the hog's sampled EOS is
        # rewritten to a benign token, so only its explicit cancel can
        # end it. The first token still flows instantly (it comes from
        # prefill). Budgets are pinned LOW below (3000/512, not 50k) and
        # the observed decode rate is pinned HIGH: the gateway's local
        # stream carries a 300 s gRPC deadline, and the admission
        # feasibility gate ((outstanding + decode_cost) / observed
        # tok/s) otherwise sheds the queued request whenever the first
        # rate window lands before it — with warm_compile=False that
        # window is compile-polluted (~3 tok/s), so the seed test only
        # passed when "queued" won the race against the first
        # measurement. Feasibility is not what this test is about.
        import numpy as np

        eng = mgr.models["tiny"].engine
        eos = mgr.models["tiny"].tokenizer.eos_id
        real_step, real_prefill = eng.step, eng.prefill_async

        def never_stopping_step(n=1):
            time.sleep(0.2)
            toks = np.array(real_step(n))
            toks[toks == eos] = 7
            return toks

        def never_stopping_prefill(slot, ids, temperature=0.0, top_p=1.0,
                                   **kw):
            first = real_prefill(slot, ids, temperature, top_p, **kw)
            if first.wait() == eos:
                eng.force_pending_token(slot, 7)
                first.token = 7
            return first

        monkeypatch.setattr(eng, "step", never_stopping_step)
        monkeypatch.setattr(eng, "prefill_async", never_stopping_prefill)
        batcher0 = mgr.models["tiny"].batcher
        monkeypatch.setattr(batcher0, "tokens_per_second", lambda: 500.0)
        rt_server, _, rt_port = serve_runtime(
            address="127.0.0.1:0", manager=mgr, block=False
        )
        gw_server, _, gw_port = serve_gateway(
            address="127.0.0.1:0",
            router=RequestRouter(runtime_address=f"127.0.0.1:{rt_port}"),
            block=False,
        )
        channel = rpc.insecure_channel(f"127.0.0.1:{gw_port}")
        rt_channel = rpc.insecure_channel(f"127.0.0.1:{rt_port}")
        rt = services.AIRuntimeStub(rt_channel)
        gw = services.ApiGatewayStub(channel)
        batcher = mgr.models["tiny"].batcher

        # occupy the ONLY slot directly on the runtime
        hog = rt.StreamInfer(runtime_pb2.InferRequest(
            prompt="hog", max_tokens=3000, temperature=0.5
        ))
        next(hog)
        # gateway request queues behind it (no delta can flow)
        queued = gw.StreamInfer(api_gateway_pb2.ApiInferRequest(
            prompt="queued", max_tokens=512, temperature=0.5
        ))
        deadline = time.time() + 10
        while batcher.queue_depth() < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert batcher.queue_depth() >= 1
        queued.cancel()  # disconnect with zero deltas received
        deadline = time.time() + 15
        while batcher.cancellations < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert batcher.cancellations >= 1
        assert batcher.queue_depth() == 0
        # the hog stream is untouched and still live
        assert batcher.active_count == 1
        hog.cancel()
    finally:
        for ch in (channel, rt_channel):
            if ch is not None:
                ch.close()
        for server in (gw_server, rt_server):
            if server is not None:
                server.stop(grace=None)
        if mgr.get("tiny") is not None:
            mgr.unload_model("tiny")


def test_shutdown_terminates_outstanding_requests():
    """shutdown() (the UnloadModel path) must end every in-flight and
    queued request's iterator — after the scheduler thread dies nothing
    else will ever deliver their end-of-stream."""
    import queue as _q

    params = M.init_params(TINY_TEST, jax.random.PRNGKey(2), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=1, max_context=8192,
        cache_dtype=jnp.float32,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    live = b.submit(Request(prompt_ids=[1, 2], max_tokens=100_000,
                            temperature=0.0))
    queued = b.submit(Request(prompt_ids=[3, 4], max_tokens=100_000,
                              temperature=0.0))
    results = _q.Queue()

    def consume(h):
        results.put(len(h.tokens()))

    t1 = threading.Thread(target=consume, args=(live,), daemon=True)
    t2 = threading.Thread(target=consume, args=(queued,), daemon=True)
    t1.start(); t2.start()
    # wait until the first request is actually decoding
    deadline = __import__("time").time() + 30
    while b.active_count < 1 and __import__("time").time() < deadline:
        __import__("time").sleep(0.05)
    b.shutdown()
    t1.join(timeout=30); t2.join(timeout=30)
    assert not t1.is_alive() and not t2.is_alive(), (
        "consumers still blocked after shutdown"
    )
    assert results.qsize() == 2  # both iterators ended
    # terminated ≠ completed: both handles carry the abort marker so the
    # serving layer reports an error, not a short success
    assert live.aborted and "unload" in live.abort_reason
    assert queued.aborted
    # and the closed batcher refuses new work instead of stranding it
    with pytest.raises(RuntimeError, match="shut down"):
        b.submit(Request(prompt_ids=[9], max_tokens=4))


def test_unload_mid_stream_surfaces_aborted_to_client():
    """UnloadModel while a StreamInfer is mid-generation: the client gets
    an ABORTED status, not a truncated stream that looks complete."""
    import time

    import grpc as grpc_mod

    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve

    mgr = ModelManager(num_slots=2, warm_compile=False)
    mgr.load_model("tiny", "synthetic://tiny-test", context_length=8192)
    server, _, port = serve(address="127.0.0.1:0", manager=mgr, block=False)
    channel = rpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = services.AIRuntimeStub(channel)
        stream = stub.StreamInfer(runtime_pb2.InferRequest(
            prompt="hello", max_tokens=50_000, temperature=0.5
        ))
        next(stream)  # live
        t = threading.Thread(target=mgr.unload_model, args=("tiny",),
                             daemon=True)
        t.start()
        with pytest.raises(grpc_mod.RpcError) as err:
            deadline = time.time() + 60
            while time.time() < deadline:
                next(stream)
        assert err.value.code() == grpc_mod.StatusCode.ABORTED
        assert "unload" in err.value.details()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        channel.close()
        server.stop(grace=None)


def test_priority_admission_order():
    """Under slot contention, a higher-priority queued request admits
    before earlier lower-priority ones; FIFO holds within a level."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(4), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=1, max_context=128,
        cache_dtype=jnp.float32,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    order = []
    orig_prefill = engine.prefill_async

    def recording_prefill(slot, ids, **kw):
        order.append(tuple(ids[:2]))
        return orig_prefill(slot, ids, **kw)

    engine.prefill_async = recording_prefill
    try:
        import time

        hog = b.submit(Request(prompt_ids=[9, 9], max_tokens=24,
                               temperature=0.0))
        deadline = time.time() + 20
        while b.active_count < 1 and time.time() < deadline:
            time.sleep(0.01)  # the hog must hold the slot before the rest queue
        low_a = b.submit(Request(prompt_ids=[1, 1], max_tokens=4,
                                 temperature=0.0, priority=0))
        low_b = b.submit(Request(prompt_ids=[1, 2], max_tokens=4,
                                 temperature=0.0, priority=0))
        high = b.submit(Request(prompt_ids=[5, 5], max_tokens=4,
                                temperature=0.0, priority=3))
        for h in (hog, high, low_a, low_b):
            h.tokens()
        assert order == [(9, 9), (5, 5), (1, 1), (1, 2)], order
        assert b.completed == 4
    finally:
        b.shutdown()


def test_priority_aging_prevents_starvation():
    """A long-queued low-priority request outranks a fresh high-priority
    one once its age boost exceeds the priority gap (admission uses
    effective priority = priority + age/PRIORITY_AGING_SECS)."""
    import time as _time

    from aios_tpu.engine import batching as batching_mod

    params = M.init_params(TINY_TEST, jax.random.PRNGKey(5), dtype=jnp.float32)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=1, max_context=128,
        cache_dtype=jnp.float32,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    order = []
    orig_prefill = engine.prefill_async

    def recording_prefill(slot, ids, **kw):
        order.append(tuple(ids[:2]))
        return orig_prefill(slot, ids, **kw)

    engine.prefill_async = recording_prefill
    try:
        hog = b.submit(Request(prompt_ids=[9, 9], max_tokens=24,
                               temperature=0.0))
        deadline = _time.time() + 20
        while b.active_count < 1 and _time.time() < deadline:
            _time.sleep(0.01)
        old_low = b.submit(Request(prompt_ids=[1, 1], max_tokens=4,
                                   temperature=0.0, priority=0))
        # age the queued request past the whole priority gap
        old_low._live.submitted_at -= 4 * batching_mod.PRIORITY_AGING_SECS
        fresh_high = b.submit(Request(prompt_ids=[5, 5], max_tokens=4,
                                      temperature=0.0, priority=3))
        for h in (hog, old_low, fresh_high):
            h.tokens()
        assert order == [(9, 9), (1, 1), (5, 5)], order
    finally:
        b.shutdown()
