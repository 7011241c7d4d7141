#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the model from the seed, starts the real AIRuntime
gRPC server on a localhost port in this process (one process holds the
chip), drives it with client threads through `StreamInfer`, measures for
`--seconds`, checks what was served against the plain reference, and prints
one JSON object as the last line. Everything a cell is made of is data under
this directory, found by the names in BENCHMARK.json (harness/manifest.py):
its configuration and traffic files, its per-layer metrics' readers, and the
one file of its architecture (`archs/<arch>.py`, named by the configuration's
`arch`), which makes the weights, is the plain reference's forward pass,
counts the roofline's bytes and operations and names the trace's markers.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifest_mod  # noqa: E402
from benchmark.harness import metrics  # noqa: E402

TRACE_SECONDS = 5.0


def say(text: str) -> None:
    print(text, flush=True)


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=REPO,
                   help="directory that holds BENCHMARK.json and its data (tests)")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="also read the gap of the configuration's control, the "
                        "reference one precision below (PERF.md, limits)")
    p.add_argument("--rehearsal-cpu", action="store_true",
                   help="tests only: run on the CPU and print counts, never a time")
    return p.parse_args(argv)


def load_reader(spec: str, metric_path: str):
    """`fn` in harness/readers.py, or `file.py:fn` beside the metric file."""
    if ":" in spec:
        file, fn = spec.split(":", 1)
        path = os.path.join(os.path.dirname(metric_path), file)
        return getattr(manifest_mod.load_file(path, "benchmark_reader"), fn)
    from benchmark.harness import readers

    return getattr(readers, spec)


class Sampler(threading.Thread):
    """The program's counters, a few times a second, through the window."""

    def __init__(self, read, period_s: float = 0.2) -> None:
        super().__init__(daemon=True)
        self.read, self.period = read, period_s
        self.samples = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.period):
            self.samples.append((time.monotonic(), self.read()))

    def halt(self) -> None:
        self._halt.set()
        self.join(5)


def trim_heap() -> float:
    """Hand the heap's freed pages back to the system now, in set-up; the
    seconds it took. Left to itself glibc does it when some free() finds the
    top of the heap empty: from whichever thread, inside a C call that holds
    the GIL, 0.7-0.9 s after LoadModel's compilations, and that fell 4-5 s
    into the window of every checkout's first run (PERF.md section 7)."""
    t = time.monotonic()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # another libc: nothing to hand back
    return time.monotonic() - t


def served_ids(rec, width: int) -> list:
    ids = []
    for text in rec.texts:
        if len(text) != width:
            raise ValueError(f"chunk {text!r} is not one token")
        ids.append(int(text, 16))
    return ids


def check_sample(records, seed: int, n: int) -> list:
    """The longest finished greedy request and n-1 more drawn from the seed."""
    greedy = sorted((r for r in records if r.ok and r.turn.greedy),
                    key=lambda r: r.task_id)
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: r.turn.prompt_tokens + r.turn.answer_tokens)
    rest = [r for r in greedy if r is not longest]
    random.Random(f"{seed}/check").shuffle(rest)
    return [longest] + rest[:max(n - 1, 0)]


def compare_with_reference(served, arch, dims, seed: int, sample, control: str,
                           pad_to: int) -> dict:
    """Per checked position: the served token's gap, the reference's own
    router margin there, and with `control` (a precision below the
    configuration's) the gap of that reference's first token."""
    from benchmark.harness import reference

    seqs, keep, answers = [], [], []
    for rec in sample:
        prompt = served.prompt_ids(rec.prompt, rec.system)
        ids = served_ids(rec, served.tokenizer.width)
        seqs.append(prompt + ids)
        keep.append(len(prompt) - 1)
        answers.append(ids)
    precisions = ("float32", control) if control else ("float32",)
    logits = reference.logits_for(arch, dims, seed, seqs, keep, precisions, pad_to)
    gaps, margins, low_gaps, where, std = [], [], [], [], 0.0
    for i, ids in enumerate(answers):
        ref = logits["float32"][i][:len(ids)]
        gaps.extend(reference.served_gaps(ref, ids).tolist())
        margins.extend(logits["router_margin"][i][:len(ids)].min(-1).tolist())
        where.extend((sample[i].task_id, t) for t in range(len(ids)))
        std = float(ref.std())
        if control:
            low_gaps.extend(reference.control_gaps(
                ref, logits[control][i][:len(ids)]).tolist())
    return {"gaps": gaps, "margins": margins, "control_gaps": low_gaps,
            "where": where, "logit_std": std, "rows": [len(s) for s in seqs]}


def quantiles(values) -> str:
    return ", ".join(f"p{q} {metrics.percentile(values, q):.4f}"
                     for q in (50, 75, 90, 95, 99, 100))


MARGIN_LADDER = (0.0, 0.02, 0.05, 0.08, 0.12, 0.2, 0.3)


def say_by_margin(ref: dict, key: str, label: str) -> None:
    """The gaps among the positions a given least margin keeps: what
    `router_margin_min` and the limit beside it are set from (PERF.md 2)."""
    if min(ref["margins"]) == float("inf"):
        return
    steps = []
    for eps in MARGIN_LADDER:
        kept = [g for g, m in zip(ref[key], ref["margins"]) if m >= eps]
        if kept:
            steps.append(f">={eps:g} {metrics.percentile(kept, 95):.3f} / {max(kept):.3f} "
                         f"/ {100.0 * sum(g > 1 for g in kept) / len(kept):.1f}% ({len(kept)})")
    say(f"{label} gaps by least router margin kept, p95 / widest / share above 1.0 "
        f"(positions): " + ", ".join(steps))


def say_control(ref: dict) -> None:
    say(f"control gaps, all positions: {quantiles(ref['control_gaps'])}")
    say_by_margin(ref, "control_gaps", "control")
    worst = sorted(range(len(ref["gaps"])), key=lambda i: -ref["gaps"][i])[:12]
    say("widest served gaps (request, answer token, gap, router margin): " + "; ".join(
        f"{ref['where'][i][0]} {ref['where'][i][1]} {ref['gaps'][i]:.3f} "
        f"{ref['margins'][i]:.4f}" for i in worst))


def say_waits(records, timelines, w0: float, w1: float) -> None:
    """Whether the wait for admission grows through the window (an open
    loop below its knee keeps it flat): the flight recorder's wait of the
    requests due in each quarter, and who was still unfinished at the close."""
    waits = {t.request_id: t.queue_wait_ms for t in timelines}
    due = [r for r in metrics.in_window(records, w0, w1) if r.task_id in waits]
    if not due:
        return
    quarter = (w1 - w0) / 4.0
    means = []
    for k in range(4):
        v = [waits[r.task_id] for r in due
             if w0 + k * quarter <= r.due < w0 + (k + 1) * quarter]
        means.append(f"{sum(v) / len(v):.0f}" if v else "-")
    every = [waits[r.task_id] for r in due]
    open_at_close = sum(1 for r in records if r.due < w1 and (not r.chunks or r.chunks[-1] > w1))
    say(f"wait for admission ms (flight recorder): mean by quarter of the window "
        f"{' / '.join(means)}; p25 {metrics.percentile(every, 25):.0f} p50 "
        f"{metrics.percentile(every, 50):.0f} p75 {metrics.percentile(every, 75):.0f} max "
        f"{max(every):.0f}; requests unfinished at the close {open_at_close}")


def main(argv=None) -> int:
    args = parse(argv)
    man = manifest_mod.Manifest(args.root)
    manifest_mod.check(man)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    if args.seconds != int(args.seconds) and not args.rehearsal_cpu:
        say("--seconds is a whole number")
        return 2

    if args.rehearsal_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        from benchmark.harness.manager import Served

        arch = man.arch(config)
    except ImportError as exc:
        say(f"the program is not in this directory ({exc}); nothing to measure")
        return 3
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearsal_cpu and (not on_chip(dev) or len(devices) < cell["chips"]):
        say(f"needs {cell['chips']} TPU chip(s); JAX reports {len(devices)} x {dev.platform}")
        return 2
    libtpu = "none"
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - a version line must not stop a run
        pass
    say(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
        f"jax {jax.__version__}, libtpu {libtpu}; cell {cell['name']} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace}")
    t_backend = time.time()

    served = Served(arch, config, args.seed)
    t_loaded = time.time()
    try:
        return measure(args, man, cell, config, mix, arch, served, dev, len(devices),
                       t_backend, t_loaded)
    finally:
        served.close()


def measure(args, man, cell, config, mix, arch, served, dev, n_devices,
            t_backend, t_loaded) -> int:
    import jax

    from benchmark.harness import readers, reference, xplane
    from benchmark.harness.loadgen import LoadGenerator
    from benchmark.harness.peaks import peaks_of

    rehearsal = args.rehearsal_cpu
    dims = arch.dims_of(config)
    peaks = peaks_of(dev.device_kind) if on_chip(dev) else None  # unknown kind: an error
    gen = LoadGenerator(mix, args.seed, served.template_overhead(),
                        served.stream, served.name, served.tokenizer.width)
    trimmed = [trim_heap()]  # what loading and compiling freed, before a request is sent
    gen.start()
    warm_until = gen.t0 + float(mix["warm_s"])
    for ev in gen.first_turn_done:
        if not ev.wait(300):
            raise RuntimeError("an agent never finished its first turn")
    # and what the first requests freed, a second before the window opens: the
    # window still opens at warm_until, on the schedule's own instant
    time.sleep(max(warm_until - 1.0 - time.monotonic(), 0.0))
    trimmed.append(trim_heap())
    time.sleep(max(warm_until - time.monotonic(), 0.0))

    # -- the window ---------------------------------------------------------
    w0 = time.monotonic()
    setup_s = time.time() - T_START
    before = served.counters()
    sampler = Sampler(served.counters)
    sampler.start()
    w1 = w0 + args.seconds
    trace_w0 = trace_w1 = 0.0
    trace_dir = ""
    if args.trace and not rehearsal:
        span = min(TRACE_SECONDS, args.seconds / 3.0)
        time.sleep(max(w0 + min(5.0, args.seconds / 4.0) - time.monotonic(), 0.0))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        trace_w0 = time.monotonic()
        time.sleep(span)
        trace_w1 = time.monotonic()
        jax.profiler.stop_trace()
    time.sleep(max(w1 - time.monotonic(), 0.0))
    after = served.counters()
    sampler.halt()
    gen.stop_and_drain()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    records = list(gen.records)

    # -- end to end ---------------------------------------------------------
    values, counts = metrics.end_to_end(records, w0, w1)
    values["setup_s"] = setup_s
    failed_detail = [r.error or f"{len(r.chunks)} of {r.turn.answer_tokens} chunks"
                     for r in metrics.in_window(records, w0, w1) if not r.ok]
    late = metrics.lateness_ms(records, w0, w1)
    say(f"requests: attempted {counts['attempted']} succeeded "
        f"{counts['attempted'] - counts['failed']} failed {counts['failed']} "
        f"(all offered, warm-up and drain included: {len(records)})")
    if failed_detail or gen.error:
        say(f"failures: {failed_detail[:5]} {gen.error}")
    lost = [r for r in records if r.sent and not r.returned]
    if lost:  # where the system lost them: what the batcher recorded, and its state now
        finished = {t.request_id for t in served.timelines}
        say("lost: " + "; ".join(
            f"{r.task_id} {r.error}, sent {r.sent - w0:.1f} s from the window's start, "
            f"{'finished' if r.task_id in finished else 'not finished'} by the batcher"
            for r in lost) + f"; the program's counters now: {served.counters()}")
    say(f"samples: ttft {counts['ttft']} requests, tpot {counts['tpot']} requests, "
        f"itl {counts['gaps']} gaps, out_tok_s {counts['chunks']} chunks "
        f"in {args.seconds} s")
    if rehearsal:
        say("rehearsal on the CPU: no time is printed")
    else:
        say(f"largest gap between two chunks of a stream: {values.get('itl_max_ms', 0.0):.1f} ms")
        say(f"generator lateness ms: p50 {metrics.percentile(late, 50):.3f} "
            f"p99 {metrics.percentile(late, 99):.3f} max {max(late):.3f}" if late
            else "generator lateness: nothing was due in the window")
        say_waits(records, served.timelines, w0, w1)
        load = served.setup_seconds()
        loaded = sum(load.get(k, 0) for k in ("weights", "engines", "warmup"))
        say("set-up s: imports+backend %.2f, weights %.2f, engine placement %.2f, "
            "LoadModel AOT warm-up %.2f, server+rest %.2f, warm traffic %.2f "
            "(heap trims in it %.2f + %.2f); setup_s %.2f"
            % (t_backend - T_START, load.get("weights", 0), load.get("engines", 0),
               load.get("warmup", 0), t_loaded - t_backend - loaded,
               setup_s - (t_loaded - T_START), trimmed[0], trimmed[1], setup_s))

    # -- per layer ----------------------------------------------------------
    planes = None
    if trace_dir:
        planes = xplane.load(xplane.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = readers.Context(
        arch=arch, dims=dims, mix=mix, slots=int(config["assumed"]["slots"]),
        records=records, w0=w0, w1=w1, before=before, after=after,
        samples=sampler.samples, timelines=list(served.timelines),
        peaks=peaks, peak_bytes=peak_bytes, planes=planes,
        trace_w0=trace_w0, trace_w1=trace_w1,
    )
    layer_values = {}
    for f in man.layer_metrics_of(cell["name"]):
        if rehearsal and f["source"] != "program_counter":
            continue  # a CPU run yields counts, never a time or a device share
        v = load_reader(f["reader"], f["_path"])(ctx)
        if v is not None:
            layer_values[f["name"]] = (float(v), f["unit"])
    if planes is not None:
        inside = [r for r in ctx.due() if r.chunks[-1] > trace_w0 and r.chunks[0] < trace_w1]
        outside = [r for r in ctx.due() if r not in inside]
        if inside and outside:
            traced, untraced = (metrics.percentile(metrics.tpot_ms(rs), 50)
                                for rs in (inside, outside))
            say(f"tracing overhead: tpot_p50_ms traced {traced:.3f} / "
                f"untraced {untraced:.3f} = {traced / untraced:.4f}")

    # -- correct ------------------------------------------------------------
    check = config["check"]
    sample = check_sample(metrics.in_window(records, w0, w1), args.seed,
                          int(check["requests"]))
    t_ref = time.time()
    # one padded length for the whole sample: the schedule is fixed, so the
    # longest greedy request of the window, and with it the compiled block,
    # is the same in every run of a cell
    control = (check.get("control") or arch.CONTROL) if args.control else ""
    ref = compare_with_reference(
        served, arch, dims, args.seed, sample, control,
        reference.bucket(max(r.turn.prompt_tokens + r.turn.answer_tokens for r in sample)),
    ) if sample else None
    t_checked = time.time()
    compiles = after["xla_compiles"] - before["xla_compiles"]
    # A router turns rounding into a change of experts wherever its choice is
    # a near-tie, and the served token then stands far from the reference's
    # best in sound runs too. So where the configuration has a router the gap
    # is read at the positions whose least router margin (the reference's own)
    # is not small, as a high percentile, and the bulk of ALL positions is
    # held to a limit beside it. Without a router every position is kept.
    eps = float(check.get("router_margin_min", 0.0))
    q, limit = float(check["gap_percentile"]), float(check["logit_gap_limit"])
    bulk_q, bulk_limit = check.get("bulk_percentile"), check.get("bulk_gap_limit")
    kept = [i for i, m in enumerate(ref["margins"]) if m >= eps] if ref else []
    gap = metrics.percentile([ref["gaps"][i] for i in kept], q) if kept else None
    bulk = metrics.percentile(ref["gaps"], float(bulk_q)) if ref and bulk_q else None
    correct = bool(kept and gap <= limit and (bulk is None or bulk <= float(bulk_limit))
                   and compiles == 0
                   and counts["failed"] == 0 and counts["attempted"] > 0
                   and not gen.error and (on_chip(dev) or rehearsal))
    # every number compared, beside its limit: last on standard error and last
    # in the result's line, which is all the driver's record keeps of a run
    compared = {f"gap_p{q:g}": [gap, limit], "compiles_in_window": [compiles, 0],
                "failed": [counts["failed"], 0]}
    if bulk_q:
        compared[f"bulk_gap_p{float(bulk_q):g}"] = [bulk, float(bulk_limit)]
    say(f"correct: served-token logit gap p{q:g} {gap} limit {limit} over {len(kept)} of "
        f"{len(ref['gaps']) if ref else 0} greedy tokens of {len(sample)} requests "
        f"(those with router margin >= {eps:g}; rows {ref['rows'] if ref else None}; "
        f"reference logit std {ref['logit_std'] if ref else 0:.4f}); "
        + (f"p{bulk_q:g} of all gaps {bulk} limit {bulk_limit}; " if bulk_q else "")
        + f"compiles in window {compiles} limit 0; failed {counts['failed']} limit 0")
    if not rehearsal:
        say(f"reference comparison took {t_checked - t_ref:.1f} s (after the window, not in setup_s)")
    if ref:
        say(f"served gaps, all positions: {quantiles(ref['gaps'])}")
        say_by_margin(ref, "gaps", "served")
        if ref["control_gaps"]:
            low = metrics.percentile([ref["control_gaps"][i] for i in kept], q)
            say(f"control (the {control} reference's first token in the served token's place): "
                f"gap p{q:g} {low} over the same {len(kept)} positions; has to lie above {limit}")
            say_control(ref)
    # -- the line -----------------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices, "memory_peak_bytes": peak_bytes}
    line = {"correct": correct, "attempted": counts["attempted"],
            "failed": counts["failed"]}
    if args.trace:
        line["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in layer_values.items()}
        if planes is not None:
            device["busy_s"], device["window_s"] = xplane.busy_and_window_seconds(planes)
            gaps = sorted(xplane.module_gaps(planes), key=lambda g: -g[1])[:5]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in xplane.top_ops(planes, 10)],
                "idle_gaps": [[n, s] for n, s in gaps],
            }
    elif rehearsal:
        line["metrics"] = {}
    else:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in man.end_to_end_of(cell["name"]) if m["name"] in values
        }
    if rehearsal:
        line["rehearsal"] = True
    line["device"] = device
    line["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared: {k} {v} limit {lim}", file=sys.stderr, flush=True)
    say(json.dumps(line))
    return 0


def on_chip(dev) -> bool:
    return dev.platform == "tpu"


if __name__ == "__main__":
    sys.exit(main())
