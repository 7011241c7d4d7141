#!/usr/bin/env python3
"""Fleet status CLI — the operator surface over ``/fleet/members``
(docs/RUNBOOK.md §9 "a host is sick").

Usage:
    scripts/fleetctl.py status      [--target HOST:PORT] [--json]
    scripts/fleetctl.py top         [--target HOST:PORT] [--json]
    scripts/fleetctl.py history METRIC [--target HOST:PORT] [--host H]
                                    [--window S] [--json]
    scripts/fleetctl.py drain-check [--target HOST:PORT] --host HOSTID
    scripts/fleetctl.py drain       [--target HOST:PORT] --host HOSTID
                                    [--timeout S] [--json]

Target is any ONE member's metrics endpoint (``--target``, else
``AIOS_TPU_FLEET_TARGET``, default 127.0.0.1:9100) — membership is
symmetric, so any member renders the whole fleet.

  * ``status``      — the membership table: host, role, state, heartbeat
                      age, rank, version, pid, metrics endpoint; plus
                      the recent transition journal. Exit 0 when every
                      member is "up", 1 when any is suspect/dead (the
                      scriptable health probe), 2 when the target is
                      unreachable.
  * ``top``         — per-host load: pool occupancy / waiting / degrade
                      rung, devprof MFU and device-seconds, and SLO
                      worst burn — sorted worst-burn-first
                      so the sick host is the top row; the worst few
                      tenants by TTFT burn fleet-wide render below the
                      table. Exit codes as ``status``.
  * ``history``     — a sparkline table of METRIC's recent points per
                      host (off ``/debug/tsdb/fleet``; requires
                      ``AIOS_TPU_TSDB`` armed on the members), sorted
                      worst-host-first (highest last value). ``--host``
                      narrows to one host, ``--window`` bounds the range
                      in seconds. Exit 0 with data, 1 when no host
                      returned points (metric unknown / ring unarmed),
                      2 when the target is unreachable.
  * ``drain-check`` — is ``--host`` safe to take down? Exit 0 when every
                      one of its pools reports zero waiting and zero
                      batch occupancy (idle), 1 when it still holds
                      work, 2 when the host is unknown or the target is
                      unreachable.
  * ``drain``       — ACTUALLY drain ``--host``: POST its
                      ``/fleet/drain`` (resolved from the membership
                      table), then poll the table until the host
                      announces the terminal ``leaving`` phase. Exit 0
                      drained, 1 still holding at ``--timeout``, 2 when
                      the host is unknown/unreachable.

Human-readable tables go to stderr; ONE machine-readable JSON verdict
line goes to stdout (the benchdiff.py convention), so scripts can parse
the verdict while operators read the table. ``--json`` (status/top)
replaces the terse verdict with the FULL row set on stdout — the same
fields the table renders, one JSON document — for dashboards and
fleet-aware tooling that want data, not a verdict. Exit codes are
identical either way.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.request
from typing import List, Optional


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def default_target() -> str:
    return os.environ.get("AIOS_TPU_FLEET_TARGET", "127.0.0.1:9100")


def fetch_members(target: str, timeout: float = 5.0) -> dict:
    url = f"http://{target}/fleet/members"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def _table(rows: List[List[str]], header: List[str]) -> None:
    widths = [
        max(len(str(r[i])) for r in [header] + rows)
        for i in range(len(header))
    ]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    log(fmt.format(*header))
    for r in rows:
        log(fmt.format(*(str(c) for c in r)))


def _pool_load(member: dict) -> tuple:
    """(waiting, occupancy, degrade) summed/maxed across the member's
    pools — the load triple top and drain-check read."""
    waiting, occupancy, degrade = 0, 0.0, 0
    for name, stats in (member.get("pools") or {}).items():
        if name == "_error" or not isinstance(stats, dict):
            continue
        waiting += int(stats.get("waiting", 0) or 0)
        occupancy = max(occupancy,
                        float(stats.get("batch_occupancy", 0.0) or 0.0))
        degrade = max(degrade, int(stats.get("degrade_level", 0) or 0))
    return waiting, occupancy, degrade


def _worst_tenants(members: List[dict], limit: int = 5) -> List[dict]:
    """Fleet-wide union of each heartbeat's worst-tenant slice, ranked
    by TTFT burn (the noisy-neighbor answer ``top`` renders)."""
    rows = []
    for m in members:
        for key, burn in ((m.get("slo") or {}).get("tenants") or {}).items():
            model, _, tenant = key.partition("/")
            rows.append({"host": m["host"], "model": model,
                         "tenant": tenant, "burn": float(burn)})
    rows.sort(key=lambda r: -r["burn"])
    return rows[:limit]


def _mfu_secs(member: dict) -> tuple:
    mfu: Optional[float] = None
    secs = 0.0
    for entry in (member.get("capacity") or {}).values():
        if not isinstance(entry, dict):
            continue
        secs += float(entry.get("device_seconds", 0.0) or 0.0)
        if entry.get("mfu") is not None:
            mfu = max(mfu or 0.0, float(entry["mfu"]))
    return mfu, secs


def cmd_status(data: dict, as_json: bool = False) -> int:
    members = data.get("members", [])
    not_up = [m for m in members if m["state"] != "up"]
    if as_json:
        print(json.dumps({
            "cmd": "status", "size": len(members),
            "up": len(members) - len(not_up), "pass": not not_up,
            "members": [
                {k: m.get(k) for k in (
                    "host", "role", "state", "age_secs", "rank",
                    "version", "pid", "metrics_addr", "kvx_addr", "self",
                )}
                for m in members
            ],
            "journal": data.get("journal", [])[-32:],
        }, sort_keys=True))
        return 0 if not not_up else 1
    rows = [
        [m["host"], m["role"], m["state"], f"{m.get('age_secs', 0):.1f}s",
         m.get("rank") or "-", m.get("version") or "-",
         m.get("pid") or "-", m.get("metrics_addr") or "-",
         "*" if m.get("self") else ""]
        for m in members
    ]
    _table(rows, ["HOST", "ROLE", "STATE", "AGE", "RANK", "VERSION",
                  "PID", "METRICS", "SELF"])
    journal = data.get("journal", [])
    if journal:
        log("")
        log("recent transitions:")
        for e in journal[-8:]:
            log(f"  {e['host']}/{e['role']}: "
                f"{e.get('from') or 'new'} -> {e['to']}")
    print(json.dumps({
        "cmd": "status", "size": len(members),
        "up": len(members) - len(not_up),
        "not_up": [{"host": m["host"], "role": m["role"],
                    "state": m["state"]} for m in not_up],
        "pass": not not_up,
    }, sort_keys=True))
    return 0 if not not_up else 1


def cmd_top(data: dict, as_json: bool = False) -> int:
    members = data.get("members", [])

    def burn(m: dict) -> float:
        b = (m.get("slo") or {}).get("worst_burn")
        return float(b) if b is not None else -1.0

    ordered = sorted(members, key=burn, reverse=True)
    not_up = [m for m in members if m["state"] != "up"]
    tenants = _worst_tenants(members)
    if as_json:
        out = []
        for m in ordered:
            waiting, occupancy, degrade = _pool_load(m)
            mfu, secs = _mfu_secs(m)
            b = (m.get("slo") or {}).get("worst_burn")
            out.append({
                "host": m["host"], "role": m["role"], "state": m["state"],
                "worst_burn": b, "occupancy": occupancy,
                "waiting": waiting, "degrade_level": degrade,
                "mfu": mfu, "device_seconds": secs,
            })
        print(json.dumps({
            "cmd": "top", "pass": not not_up, "members": out,
            "tenants": tenants,
        }, sort_keys=True))
        return 0 if not not_up else 1
    rows = []
    for m in ordered:
        waiting, occupancy, degrade = _pool_load(m)
        mfu, secs = _mfu_secs(m)
        b = (m.get("slo") or {}).get("worst_burn")
        rows.append([
            m["host"], m["state"],
            f"{b:.2f}" if b is not None else "-",
            f"{occupancy:.2f}", waiting, degrade,
            f"{mfu:.3f}" if mfu is not None else "-",
            f"{secs:.2f}",
        ])
    _table(rows, ["HOST", "STATE", "BURN", "OCCUP", "WAIT", "DEGRADE",
                  "MFU", "DEV_SECS"])
    if tenants:
        log("")
        log("worst tenants by TTFT burn:")
        for t in tenants:
            log(f"  {t['model']}/{t['tenant']} on {t['host']}: "
                f"burn={t['burn']:.2f}")
    print(json.dumps({
        "cmd": "top",
        "worst": ({"host": ordered[0]["host"], "burn": burn(ordered[0])}
                  if ordered and burn(ordered[0]) >= 0 else None),
        "worst_tenant": tenants[0] if tenants else None,
        "pass": not not_up,
    }, sort_keys=True))
    return 0 if not not_up else 1


_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[float], width: int = 32) -> str:
    """Min-max scaled block sparkline, downsampled to ``width`` by
    bucket-averaging (the whole window must fit one table cell)."""
    if not values:
        return ""
    if len(values) > width:
        step = len(values) / width
        values = [
            sum(chunk) / len(chunk)
            for chunk in (
                values[int(i * step):max(int((i + 1) * step),
                                         int(i * step) + 1)]
                for i in range(width)
            )
        ]
    lo, hi = min(values), max(values)
    span = hi - lo
    return "".join(
        _SPARK_BLOCKS[
            int((v - lo) / span * (len(_SPARK_BLOCKS) - 1)) if span else 0
        ]
        for v in values
    )


def cmd_history(target: str, metric: str, host: str, window: float,
                timeout: float, as_json: bool = False) -> int:
    """Sparkline table of ``metric``'s recent points per host, off the
    target's ``/debug/tsdb/fleet`` federation — worst host (highest last
    value) first, one row per series."""
    url = (f"http://{target}/debug/tsdb/fleet?name={metric}"
           f"&verb=raw&window={max(window, 1.0):g}")
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            data = json.loads(r.read().decode("utf-8"))
    except Exception as exc:  # noqa: BLE001 - unreachable target is the
        # operator's first answer, render it as such
        log(f"history: cannot reach {target}: {exc!r}")
        print(json.dumps({"cmd": "history", "metric": metric,
                          "error": repr(exc)[:200]}, sort_keys=True))
        return 2
    rows = []
    for h, answer in sorted((data.get("hosts") or {}).items()):
        if host and h != host:
            continue
        if not isinstance(answer, dict):
            continue
        for s in answer.get("series") or []:
            values = [pv for _, pv in s.get("points") or []]
            if not values:
                continue
            labels = ",".join(
                f"{k}={v}" for k, v in sorted(s["labels"].items())
            )
            rows.append({
                "host": h, "labels": labels, "points": len(values),
                "last": values[-1], "max": max(values), "values": values,
            })
    # worst host first: the row whose series last sampled highest tops
    # the table (the status/top sick-host-on-top convention)
    rows.sort(key=lambda r: -r["last"])
    if as_json:
        print(json.dumps({
            "cmd": "history", "metric": metric, "window_secs": window,
            "pass": bool(rows),
            "series": [{k: r[k] for k in ("host", "labels", "points",
                                          "last", "max", "values")}
                       for r in rows],
        }, sort_keys=True))
        return 0 if rows else 1
    if rows:
        _table(
            [[r["host"], r["labels"] or "-", r["points"],
              f"{r['last']:g}", f"{r['max']:g}", _sparkline(r["values"])]
             for r in rows],
            ["HOST", "LABELS", "PTS", "LAST", "MAX", "HISTORY"],
        )
    else:
        log(f"history: no points for {metric!r} on any reachable host "
            "(unknown metric, empty window, or AIOS_TPU_TSDB unarmed)")
    print(json.dumps({
        "cmd": "history", "metric": metric, "window_secs": window,
        "hosts": len({r["host"] for r in rows}), "series": len(rows),
        "pass": bool(rows),
    }, sort_keys=True))
    return 0 if rows else 1


def cmd_drain_check(data: dict, host: str) -> int:
    targets = [m for m in data.get("members", []) if m["host"] == host]
    if not targets:
        log(f"drain-check: host {host!r} not in the membership table")
        print(json.dumps({"cmd": "drain-check", "host": host,
                          "error": "unknown host"}, sort_keys=True))
        return 2
    holding = []
    for m in targets:
        waiting, occupancy, _ = _pool_load(m)
        if waiting > 0 or occupancy > 0:
            holding.append({"role": m["role"], "waiting": waiting,
                            "occupancy": occupancy})
    verdict = {"cmd": "drain-check", "host": host,
               "holding": holding, "pass": not holding}
    if holding:
        log(f"drain-check: {host} still holds work: {holding}")
    else:
        log(f"drain-check: {host} is idle — safe to drain")
    print(json.dumps(verdict, sort_keys=True))
    return 0 if not holding else 1


def cmd_drain(target: str, host: str, timeout: float,
              as_json: bool = False) -> int:
    """Drive one host's graceful drain end to end: resolve its metrics
    endpoint off the membership table, POST /fleet/drain, then poll any
    member's table until the host's descriptor announces "leaving" (the
    descriptor outlives the process — membership keeps the last fold)."""
    import time

    try:
        data = fetch_members(target)
    except Exception as exc:  # noqa: BLE001 - see main()'s fetch
        log(f"drain: cannot reach {target}: {exc!r}")
        print(json.dumps({"cmd": "drain", "host": host,
                          "error": repr(exc)[:200]}, sort_keys=True))
        return 2
    rows = [m for m in data.get("members", []) if m["host"] == host]
    addrs = [m.get("metrics_addr") for m in rows if m.get("metrics_addr")]
    if not addrs:
        log(f"drain: host {host!r} not in the membership table (or it "
            "never announced a metrics endpoint)")
        print(json.dumps({"cmd": "drain", "host": host,
                          "error": "unknown host"}, sort_keys=True))
        return 2
    url = f"http://{addrs[0]}/fleet/drain?timeout={max(timeout, 0.1):g}"
    try:
        req = urllib.request.Request(url, data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=5.0) as r:
            started = json.loads(r.read().decode("utf-8"))
    except Exception as exc:  # noqa: BLE001 - a dead drain endpoint is
        # the verdict, not a traceback
        log(f"drain: POST {url} failed: {exc!r}")
        print(json.dumps({"cmd": "drain", "host": host,
                          "error": repr(exc)[:200]}, sort_keys=True))
        return 2
    log(f"drain: {host} acknowledged (phase={started.get('phase')}); "
        "polling for leaving ...")
    deadline = time.monotonic() + max(timeout, 0.1)
    phase = str(started.get("phase") or "")
    while time.monotonic() < deadline and phase != "leaving":
        time.sleep(0.2)
        try:
            data = fetch_members(target, timeout=2.0)
        except Exception:  # noqa: BLE001 - the polled member may be the
            # draining one; keep polling until the deadline decides
            continue
        for m in data.get("members", []):
            if m["host"] == host and m.get("phase"):
                phase = str(m["phase"])
    drained = phase == "leaving"
    verdict = {"cmd": "drain", "host": host, "phase": phase,
               "pass": drained}
    if as_json:
        verdict["members"] = [
            {k: m.get(k) for k in ("host", "role", "state", "phase",
                                   "quarantined")}
            for m in data.get("members", [])
        ]
    log(f"drain: {host} -> {phase or 'unknown'} "
        f"({'drained' if drained else 'still holding at timeout'})")
    print(json.dumps(verdict, sort_keys=True))
    return 0 if drained else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fleetctl", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("cmd", choices=["status", "top", "history",
                                    "drain-check", "drain"])
    ap.add_argument("metric", nargs="?", default="",
                    help="history: the metric name to render")
    ap.add_argument("--target", default=default_target(),
                    help="any member's metrics endpoint (host:port)")
    ap.add_argument("--host", default="",
                    help="host id to drain-check / drain / narrow "
                         "history to")
    ap.add_argument("--window", type=float, default=300.0,
                    help="history: trailing range in seconds")
    ap.add_argument("--timeout", type=float, default=5.0,
                    help="fetch timeout; for drain, also the bound on "
                         "waiting for the leaving phase")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="status/top: full row set as one JSON document "
                         "on stdout instead of the table + verdict")
    args = ap.parse_args(argv)
    if args.cmd == "history":
        if not args.metric:
            ap.error("history requires a metric name")
        return cmd_history(args.target, args.metric, args.host,
                           args.window, args.timeout,
                           as_json=args.as_json)
    if args.cmd == "drain":
        if not args.host:
            ap.error("drain requires --host")
        return cmd_drain(args.target, args.host, args.timeout,
                         as_json=args.as_json)
    try:
        data = fetch_members(args.target, timeout=args.timeout)
    except Exception as exc:  # noqa: BLE001 - unreachable target is the
        # operator's first answer, render it as such
        log(f"fleetctl: cannot reach {args.target}: {exc!r}")
        print(json.dumps({"cmd": args.cmd, "target": args.target,
                          "error": repr(exc)[:200]}, sort_keys=True))
        return 2
    if args.cmd == "status":
        return cmd_status(data, as_json=args.as_json)
    if args.cmd == "top":
        return cmd_top(data, as_json=args.as_json)
    if not args.host:
        ap.error("drain-check requires --host")
    return cmd_drain_check(data, args.host)


if __name__ == "__main__":
    sys.exit(main())
