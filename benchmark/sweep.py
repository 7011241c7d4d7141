#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the same cell at several fixed rates
in one process, one window each. The knee is the highest rate swept at which
nothing fails and the wait for admission is still of the size of one decode
dispatch (hundreds of ms); one rate higher it is seconds and the queue at the
close is several deep. The cell's traffic file then carries rate_rps =
0.7 x knee. The two halves of a window are printed, but they cannot show
growth by themselves: the schedule is one fixed, bursty draw, so the halves
hold different bursts at every rate. That the wait does not grow at the
committed rate is read from the cell's own runs: run.py prints the wait by
quarter of the window in every run. Not part of a run:
`python3 benchmark/sweep.py --workload <cell> --rates 2,2.5,3 --seconds 30`.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import manifest as manifest_mod  # noqa: E402
from benchmark.harness import metrics  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()
    man = manifest_mod.Manifest(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cell = man.cell(args.workload)
    config, mix = man.config(cell["config"]), man.traffic(cell["traffic"])
    if mix["kind"] != "open_arrivals":
        print("only an open loop has a knee")
        return 2
    import jax

    from benchmark.harness.loadgen import LoadGenerator
    from benchmark.harness.manager import Served

    if jax.devices()[0].platform != "tpu":
        print("needs the chip")
        return 2
    served = Served(man.arch(config), config, args.seed)
    print("| rate_rps | attempted | failed | ttft_p50_ms | ttft_p90_ms | tpot_p50_ms "
          "| queue_wait_ms 1st half | 2nd half | waiting at close |", flush=True)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            gen = LoadGenerator({**mix, "rate_rps": rate}, args.seed + 1 + k,
                                served.template_overhead(), served.stream, served.name,
                                served.tokenizer.width)
            gen.start()
            w0 = gen.t0 + float(mix["warm_s"])
            w1 = w0 + args.seconds
            time.sleep(max(w1 - time.monotonic(), 0.0))
            waiting = served.counters().get("waiting", 0)
            gen.stop_and_drain(300)
            values, counts = metrics.end_to_end(gen.records, w0, w1)
            tls = {t.request_id: t for t in served.timelines}
            halves = []
            for a, b in ((w0, (w0 + w1) / 2), ((w0 + w1) / 2, w1)):
                waits = [tls[r.task_id].queue_wait_ms
                         for r in metrics.in_window(gen.records, a, b)
                         if r.ok and r.task_id in tls]
                halves.append(sum(waits) / len(waits) if waits else float("nan"))
            served.timelines.clear()
            for r in metrics.in_window(gen.records, w0, w1):
                if not r.ok:
                    print(f"failed: {r.task_id} {r.error or len(r.chunks)} of "
                          f"{r.turn.answer_tokens} chunks, {r.turn.prompt_tokens} rows", flush=True)
            print(f"| {rate} | {counts['attempted']} | {counts['failed']} | "
                  f"{values.get('ttft_p50_ms', float('nan')):.1f} | "
                  f"{values.get('ttft_p90_ms', float('nan')):.1f} | "
                  f"{values.get('tpot_p50_ms', float('nan')):.2f} | "
                  f"{halves[0]:.1f} | {halves[1]:.1f} | {waiting} |", flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
