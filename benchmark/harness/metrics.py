"""End-to-end arithmetic over what the clients saw. Pure Python, no JAX.

Every number is taken over all the work of the window: a rate counts every
chunk that arrived in it, a percentile is over every request due in it (or
every gap arriving in it). A failed request is in `attempted` and `failed`
and in no latency.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from .loadgen import Record


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def in_window(records: Sequence[Record], w0: float, w1: float) -> List[Record]:
    return [r for r in records if w0 <= r.due < w1]


def gaps_in_window(records: Sequence[Record], w0: float, w1: float) -> List[float]:
    """Every gap between consecutive chunks of one stream whose later chunk
    arrived in the window, in seconds."""
    out = []
    for r in records:
        if r.error:
            continue
        for a, b in zip(r.chunks, r.chunks[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out


def chunks_in_window(records: Sequence[Record], w0: float, w1: float) -> int:
    return sum(1 for r in records for t in r.chunks if w0 <= t < w1)


def tpot_ms(records: Sequence[Record]) -> List[float]:
    """Per request: (last chunk - first chunk) / (tokens - 1)."""
    return [(r.chunks[-1] - r.chunks[0]) * 1e3 / (len(r.chunks) - 1)
            for r in records if len(r.chunks) > 1]


def end_to_end(records: Sequence[Record], w0: float, w1: float
               ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """(values, counts): every end-to-end metric the records support, and the
    sample count behind each. The caller keeps those the cell reports."""
    due = in_window(records, w0, w1)
    good = [r for r in due if r.ok]
    values: Dict[str, float] = {}
    counts: Dict[str, int] = {
        "attempted": len(due), "failed": len(due) - len(good),
    }
    ttft = [(r.chunks[0] - r.due) * 1e3 for r in good]
    tpot = tpot_ms(good)
    gaps = [g * 1e3 for g in gaps_in_window(records, w0, w1)]
    n_chunks = chunks_in_window(records, w0, w1)
    if ttft:
        values["ttft_p50_ms"] = percentile(ttft, 50)
        values["ttft_p80_ms"] = percentile(ttft, 80)
        values["ttft_p90_ms"] = percentile(ttft, 90)
    if tpot:
        values["tpot_p50_ms"] = percentile(tpot, 50)
    if gaps:
        values["itl_p99_ms"] = percentile(gaps, 99)
        values["itl_max_ms"] = max(gaps)
    values["out_tok_s"] = n_chunks / (w1 - w0)
    counts.update(ttft=len(ttft), tpot=len(tpot), gaps=len(gaps),
                  chunks=n_chunks)
    return values, counts


def lateness_ms(records: Sequence[Record], w0: float, w1: float) -> List[float]:
    """How late the generator sent each request due in the window."""
    return [(r.sent - r.due) * 1e3 for r in in_window(records, w0, w1)]
