"""Traffic: a mix file -> one fixed schedule -> client threads.

A mix is parameters for a traffic KIND (code here); a later PR adds a mix
by adding a file. Everything that shapes the job — every length, which agent
sends what, the order, every arrival instant — comes from `traffic_seed` IN
THE FILE, as stratified quantiles of the stated ranges, so every run of a cell
offers the same work at the same instants. `--seed` chooses only the bytes of
the prompts (and the weights), never the shape of the job.

This module never imports JAX or the program: it is handed `stream_fn`, which
sends one request and yields `(text, done)` chunks.
"""

from __future__ import annotations

import math
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

KINDS = ("closed_agents", "open_arrivals")
GREEDY_TEMPERATURE = 5e-5  # under the engine's greedy threshold; 0 means "unset" on the wire


@dataclass(frozen=True)
class Turn:
    """One request of the schedule: shape only, no bytes."""

    index: int  # position in the schedule (per agent for closed_agents)
    agent: int  # sending agent, or -1 in an open loop
    due_s: Optional[float]  # open loop: arrival instant from schedule start
    system_tokens: int
    prompt_tokens: int  # whole prompt after the chat template, system included
    answer_tokens: int
    greedy: bool


@dataclass
class Record:
    """What the client saw of one request; instants are time.monotonic()."""

    turn: Turn
    due: float = 0.0
    sent: float = 0.0
    chunks: List[float] = field(default_factory=list)
    texts: List[str] = field(default_factory=list)
    done: bool = False
    returned: bool = False  # the client's call came back, with or without an error
    error: str = ""
    task_id: str = ""
    prompt: str = ""
    system: str = ""

    @property
    def ok(self) -> bool:
        return (
            not self.error and self.done
            and len(self.chunks) == self.turn.answer_tokens
        )


def _stratified(lo: int, hi: int, n: int, rng: random.Random,
                log: bool = False) -> List[int]:
    """n whole numbers at the mid-quantiles of [lo, hi] (uniform or
    log-uniform), in an order drawn from rng: the multiset is fixed."""
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo))) if log \
            else lo + q * (hi - lo)
        out.append(int(round(v)))
    rng.shuffle(out)
    return out


def check_mix(mix: dict) -> None:
    kind = mix.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    need = {
        "closed_agents": ("agents", "turns_per_agent", "system_tokens",
                          "task_tokens"),
        "open_arrivals": ("rate_rps", "n_requests", "prompt_tokens"),
    }[kind] + ("traffic_seed", "answer_tokens", "temperature",
               "greedy_every", "warm_s")
    missing = [k for k in need if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {mix.get('name')!r} lacks {missing}")
    if kind == "open_arrivals" and not float(mix["rate_rps"]) > 0:
        raise ValueError("rate_rps must be a number above 0")


def build_schedule(mix: dict) -> List[List[Turn]]:
    """The whole job of a mix: one lane of turns per agent (closed loop) or
    one lane of arrivals (open loop). A pure function of the mix file."""
    check_mix(mix)
    seed = int(mix["traffic_seed"])
    a_lo, a_hi = mix["answer_tokens"]
    every = int(mix["greedy_every"])
    if mix["kind"] == "closed_agents":
        lanes = []
        n = int(mix["turns_per_agent"])
        sys_tok = int(mix["system_tokens"])
        t_lo, t_hi = mix["task_tokens"]
        for a in range(int(mix["agents"])):
            tasks = _stratified(t_lo, t_hi, n, random.Random(f"{seed}/task/{a}"))
            answers = _stratified(a_lo, a_hi, n, random.Random(f"{seed}/answer/{a}"))
            lanes.append([
                Turn(i, a, None, sys_tok, sys_tok + tasks[i], answers[i],
                     every > 0 and (i + a) % every == 0)
                for i in range(n)
            ])
        return lanes
    n = int(mix["n_requests"])
    p_lo, p_hi = mix["prompt_tokens"]
    prompts = _stratified(p_lo, p_hi, n, random.Random(f"{seed}/prompt"),
                          log=mix.get("prompt_dist", "log_uniform") == "log_uniform")
    answers = _stratified(a_lo, a_hi, n, random.Random(f"{seed}/answer"))
    # exponential gaps at their mid-quantiles, mean exactly 1, order from the seed
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    mean = sum(gaps) / n
    random.Random(f"{seed}/gaps").shuffle(gaps)
    rate = float(mix["rate_rps"])
    lane, t = [], 0.0
    for i in range(n):
        t += gaps[i] / mean / rate
        lane.append(Turn(i, -1, t, 0, prompts[i], answers[i],
                         every > 0 and i % every == 0))
    return [lane]


def schedule_bytes(mix: dict) -> bytes:
    """The schedule as bytes, for the test that two seeds offer the same job."""
    return repr(build_schedule(mix)).encode()


def _hex_text(n_chars: int, rng: random.Random) -> str:
    return f"{rng.getrandbits(4 * n_chars):0{n_chars}x}" if n_chars > 0 else ""


def fill(turn: Turn, seed: int, overhead: Dict[bool, int], width: int
         ) -> Tuple[str, str]:
    """(system, prompt) text of a turn: bytes from --seed, lengths from the
    schedule. `overhead[with_system]` is the chat template's own characters,
    so that the rendered prompt is exactly `turn.prompt_tokens` tokens of
    `width` characters (the tokenizer's in use)."""
    sys_chars = width * turn.system_tokens
    system = _hex_text(sys_chars, random.Random(f"{seed}/system/{turn.agent}"))
    chars = width * turn.prompt_tokens - overhead[bool(system)] - sys_chars
    if chars < 1:
        raise ValueError(f"turn {turn} leaves no room for a prompt")
    prompt = _hex_text(chars, random.Random(f"{seed}/prompt/{turn.agent}/{turn.index}"))
    return system, prompt


StreamFn = Callable[[dict, Optional[float]], Iterator[Tuple[str, bool]]]


class LoadGenerator:
    """Drives one schedule from one process with few threads: one thread per
    closed-loop agent, or one dispatcher and one short-lived thread per
    open-loop request in flight."""

    def __init__(self, mix: dict, seed: int, overhead: Dict[bool, int],
                 stream_fn: StreamFn, model: str, width: int) -> None:
        self.mix = mix
        self.width = width  # characters per token of the tokenizer in use
        self.seed = seed
        self.overhead = overhead
        self.stream_fn = stream_fn
        # the clients' deadline is a parameter of the mix; none unless it states one
        self.deadline_s = float(mix["deadline_s"]) if mix.get("deadline_s") else None
        self.model = model
        self.lanes = build_schedule(mix)
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._workers: List[threading.Thread] = []
        self.t0 = 0.0
        self.error = ""
        self.first_turn_done = [threading.Event() for _ in self.lanes]

    def _fire(self, rec: Record) -> None:
        turn = rec.turn
        fields = dict(
            model=self.model, prompt=rec.prompt, system_prompt=rec.system,
            max_tokens=turn.answer_tokens,
            temperature=GREEDY_TEMPERATURE if turn.greedy
            else float(self.mix["temperature"]),
            requesting_agent=f"agent-{max(turn.agent, 0)}",
            task_id=rec.task_id,
        )
        rec.sent = time.monotonic()
        try:
            for text, done in self.stream_fn(fields, self.deadline_s):
                if done:
                    rec.done = True
                elif text:
                    rec.chunks.append(time.monotonic())
                    rec.texts.append(text)
        except Exception as exc:  # noqa: BLE001 - a failed request is data
            rec.error = f"{type(exc).__name__}: {exc}"[:300]
        finally:
            rec.returned = True

    def _record(self, turn: Turn) -> Record:
        system, prompt = fill(turn, self.seed, self.overhead, self.width)
        return Record(turn=turn, system=system, prompt=prompt,
                      task_id=f"bench-{max(turn.agent, 0)}-{turn.index}")

    def _send(self, rec: Record) -> None:
        with self._lock:
            self.records.append(rec)
        self._fire(rec)

    def _agent(self, lane: List[Turn], done_once: threading.Event) -> None:
        i = 0
        while not self._stop.is_set():
            rec = self._record(lane[i % len(lane)])
            rec.due = time.monotonic()  # a closed loop: due when the last reply came
            self._send(rec)
            done_once.set()
            i += 1

    def _dispatch(self, lane: List[Turn]) -> None:
        for turn in lane:
            rec = self._record(turn)  # built before its instant, sent at it
            rec.due = self.t0 + turn.due_s
            if self._stop.wait(max(rec.due - time.monotonic(), 0.0)):
                return
            th = threading.Thread(target=self._send, args=(rec,), daemon=True)
            th.start()
            self._workers.append(th)
        self.error = ("the schedule ran out before the window closed: "
                      "raise n_requests in the traffic file")

    def start(self) -> None:
        self.t0 = time.monotonic()
        closed = self.mix["kind"] == "closed_agents"
        for lane, ev in zip(self.lanes, self.first_turn_done):
            if not closed:
                ev.set()
            th = threading.Thread(
                target=self._agent if closed else self._dispatch,
                args=(lane, ev) if closed else (lane,), daemon=True,
            )
            th.start()
            self._threads.append(th)

    def stop_and_drain(self, timeout_s: float = 60.0) -> None:
        """Offer nothing more; in-flight requests finish and count. A stream
        that has not ended by then is a request the system lost: it is marked
        failed where it stands (its thread dies with the channel)."""
        self._stop.set()
        deadline = time.monotonic() + timeout_s
        for th in self._threads + self._workers:
            th.join(max(deadline - time.monotonic(), 0.1))
        now = time.monotonic()
        with self._lock:
            lost = [r for r in self.records if r.sent and not r.returned]
        for rec in lost:
            rec.error = (f"never finished: {len(rec.chunks)} of {rec.turn.answer_tokens} chunks "
                         f"{now - rec.sent:.0f} s after it was sent "
                         f"({rec.turn.prompt_tokens} prompt tokens)")
        if lost:
            self.error = f"{len(lost)} request(s) never finished: {[r.task_id for r in lost]}"
