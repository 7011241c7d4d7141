"""A cell's traffic is a fixed function of its traffic file: --seed chooses
bytes, never the shape of the job."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import loadgen  # noqa: E402
from benchmark.harness.tokenizer import FixedWidthTokenizer  # noqa: E402

MIXES = sorted(glob.glob(os.path.join(REPO, "benchmark", "traffic", "*.json")))
OVERHEAD = {True: 17, False: 15}  # "[INST] s\n\np [/INST]"


def _mix(path):
    with open(path) as fh:
        return json.load(fh)


def _gen(mix, seed):
    return loadgen.LoadGenerator(mix, seed, OVERHEAD, lambda f, deadline_s: iter(()), "m", 4)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_schedule_is_identical_for_two_seeds(path):
    mix = _mix(path)
    a, b = _gen(mix, 11), _gen(mix, 3_000_000_019)
    assert a.lanes == b.lanes
    assert repr(a.lanes).encode() == loadgen.schedule_bytes(mix)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_lengths_stay_inside_the_stated_ranges(path):
    mix = _mix(path)
    lanes = loadgen.build_schedule(mix)
    a_lo, a_hi = mix["answer_tokens"]
    for lane in lanes:
        for t in lane:
            assert a_lo <= t.answer_tokens <= a_hi
            if mix["kind"] == "closed_agents":
                lo, hi = mix["task_tokens"]
                assert t.system_tokens == mix["system_tokens"]
                assert lo <= t.prompt_tokens - t.system_tokens <= hi
            else:
                lo, hi = mix["prompt_tokens"]
                assert lo <= t.prompt_tokens <= hi and t.system_tokens == 0
    if mix["kind"] == "open_arrivals":
        due = [t.due_s for t in lanes[0]]
        assert due == sorted(due) and due[0] > 0
        # the gaps' mean is exactly 1 / rate, and the schedule outlasts
        # the warm traffic plus the longest window
        assert due[-1] == pytest.approx(len(due) / mix["rate_rps"], rel=1e-9)
        assert due[-1] > mix["warm_s"] + 51 + 10


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_agents_do_not_march_in_step_and_greedy_requests_are_mixed_in(path):
    mix = _mix(path)
    lanes = loadgen.build_schedule(mix)
    flat = [t for lane in lanes for t in lane]
    share = sum(t.greedy for t in flat) / len(flat)
    assert share == pytest.approx(1 / mix["greedy_every"], abs=0.02)
    if len(lanes) > 1:
        firsts = {(lane[0].prompt_tokens, lane[0].answer_tokens) for lane in lanes}
        assert len(firsts) > len(lanes) // 2
        # every agent draws the same multiset of lengths in another order
        assert len({tuple(sorted(t.answer_tokens for t in lane)) for lane in lanes}) == 1


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_the_seed_chooses_bytes_and_the_rendered_prompt_has_the_scheduled_length(path):
    mix = _mix(path)
    tok = FixedWidthTokenizer(32000)
    for turn in loadgen.build_schedule(mix)[0][:6]:
        s1, p1 = loadgen.fill(turn, 1, OVERHEAD, tok.width)
        s2, p2 = loadgen.fill(turn, 2, OVERHEAD, tok.width)
        assert p1 != p2 and len(p1) == len(p2) and len(s1) == len(s2)
        text = f"[INST] {s1}\n\n{p1} [/INST]" if s1 else f"[INST] {p1} [/INST]"
        assert len(tok.encode(text)) == turn.prompt_tokens
        # one agent's system prompt is the same in every turn of a run
        again, _ = loadgen.fill(turn, 1, OVERHEAD, tok.width)
        assert again == s1


def test_tokenizer_is_total_and_reads_its_own_text_back():
    tok = FixedWidthTokenizer(32000)
    ids = [0, 1, 255, 256, 257, 31999]
    text = tok.decode(ids)
    assert len(text) == 4 * len(ids) and tok.encode(text) == ids
    assert tok.eos_id is None and tok.bos_id is None
    assert all(0 <= i < 32000 for i in tok.encode("[INST] zzzz ffff [/INST]"))
    assert tok.width == FixedWidthTokenizer(65536).width == 4
    with pytest.raises(ValueError):
        FixedWidthTokenizer(16 ** 5 + 1)


def test_the_tokenizer_s_width_follows_the_vocabulary():
    """Three of the four architectures drawn for the next configuration have
    over 65,536 rows: ids then take 5 hex digits, and the load generator fills
    prompts by the width of the tokenizer in use."""
    tok = FixedWidthTokenizer(153600)
    assert tok.width == 5 and tok.vocab_size == 153600
    ids = [0, 255, 65535, 65536, 153599]
    text = tok.decode(ids)
    assert len(text) == 5 * len(ids) and tok.encode(text) == ids
    assert all(0 <= i < 153600 for i in tok.encode("[INST] zzzzz fffff [/INST]"))
    with open(os.path.join(REPO, "benchmark", "traffic", "longprompt-m7.json")) as fh:
        mix = json.load(fh)
    for turn in loadgen.build_schedule(mix)[0][:4]:
        _, prompt = loadgen.fill(turn, 1, OVERHEAD, tok.width)
        assert len(tok.encode(f"[INST] {prompt} [/INST]")) == turn.prompt_tokens


def test_a_mix_with_a_missing_parameter_or_an_unknown_kind_is_refused():
    mix = _mix(MIXES[0])
    with pytest.raises(ValueError):
        loadgen.build_schedule({**mix, "kind": "poisson"})
    broken = dict(mix)
    del broken["traffic_seed"]
    with pytest.raises(ValueError):
        loadgen.build_schedule(broken)


def test_a_stream_that_never_ends_is_a_failed_request_not_a_crash():
    """The system lost a request once on the chip (PERF.md section 7): the run
    has to report it as failed, in `attempted` and in no latency."""
    import threading
    import time

    from benchmark.harness import metrics

    hang = threading.Event()

    seen = []

    def stream(fields, deadline_s):
        seen.append(deadline_s)
        if fields["task_id"].endswith("-3"):
            hang.wait(30)
        for _ in range(fields["max_tokens"]):
            yield "00ab", False
        yield "", True

    tiny = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
    with open(os.path.join(tiny, "tiny-arrivals.json")) as fh:
        mix = {**json.load(fh), "rate_rps": 50.0}
    assert "deadline_s" not in mix  # no committed mix states a deadline: none is sent
    gen = loadgen.LoadGenerator(mix, 1, OVERHEAD, stream, "m", 4)
    gen.start()
    time.sleep(0.4)
    gen.stop_and_drain(timeout_s=0.5)
    hang.set()
    lost = [r for r in gen.records if r.error]
    assert [r.task_id for r in lost] == ["bench-0-3"] and "never finished" in lost[0].error
    assert "bench-0-3" in gen.error and not lost[0].ok
    _, counts = metrics.end_to_end(gen.records, gen.t0, gen.t0 + 0.4)
    assert counts["failed"] == 1 and counts["attempted"] >= 5
    assert counts["ttft"] == counts["attempted"] - 1
    assert set(seen) == {None}
    timed = loadgen.LoadGenerator({**mix, "deadline_s": 30}, 1, OVERHEAD, stream, "m", 4)
    assert timed.deadline_s == 30.0
