"""The end-to-end arithmetic on hand-made chunk instants."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import metrics  # noqa: E402
from benchmark.harness.loadgen import Record, Turn  # noqa: E402


def rec(due, sent, chunks, n=None, done=True, error=""):
    turn = Turn(0, -1, None, 0, 100, len(chunks) if n is None else n, False)
    return Record(turn=turn, due=due, sent=sent, chunks=list(chunks),
                  texts=["0000"] * len(chunks), done=done, error=error)


W0, W1 = 10.0, 20.0
RECORDS = [
    rec(10.0, 10.5, [11.0, 11.1, 11.2, 11.3]),  # late by 0.5 s: ttft counts from due
    rec(12.0, 12.0, [12.2, 12.6, 13.0]),
    rec(19.0, 19.0, [19.5, 20.5, 21.5]),  # straddles the close: drained, counts
    rec(9.0, 9.0, [9.5, 10.5, 11.5]),  # due before the window: its chunks count, it does not
    rec(15.0, 15.0, [15.1, 15.2], n=3),  # one chunk short: failed
    rec(16.0, 16.0, [], n=3, done=False, error="UNAVAILABLE"),  # refused: failed
]


def test_ttft_is_taken_from_the_due_instant_over_requests_due_in_the_window():
    values, counts = metrics.end_to_end(RECORDS, W0, W1)
    # ok requests due in the window: 1000, 200, 500 ms
    assert values["ttft_p50_ms"] == pytest.approx(500.0)
    assert values["ttft_p80_ms"] == pytest.approx(800.0)  # between 500 and 1000
    assert counts["ttft"] == 3


def test_tpot_is_last_minus_first_over_tokens_less_one():
    values, counts = metrics.end_to_end(RECORDS, W0, W1)
    # 100, 400, 1000 ms per token
    assert values["tpot_p50_ms"] == pytest.approx(400.0)
    assert counts["tpot"] == 3


def test_gap_percentile_is_over_every_gap_arriving_in_the_window():
    gaps = sorted(metrics.gaps_in_window(RECORDS, W0, W1))
    # 3 x 0.1, 2 x 0.4, 1.0 (19.5 -> 20.5 arrives after the close: out),
    # 2 x 1.0 of the early request, 0.1 of the short one; the refused one has none
    assert gaps == pytest.approx([0.1, 0.1, 0.1, 0.1, 0.4, 0.4, 1.0, 1.0])
    values, counts = metrics.end_to_end(RECORDS, W0, W1)
    assert counts["gaps"] == 8
    assert values["itl_p99_ms"] == pytest.approx(1000.0)


def test_out_tok_s_counts_chunks_by_arrival_instant_not_finished_requests():
    values, counts = metrics.end_to_end(RECORDS, W0, W1)
    # 4 + 3 + 1 (19.5) + 2 (10.5, 11.5) + 2 = 12 chunks in 10 s
    assert counts["chunks"] == 12
    assert values["out_tok_s"] == pytest.approx(1.2)


def test_a_failed_request_is_attempted_and_failed_and_in_no_latency():
    values, counts = metrics.end_to_end(RECORDS, W0, W1)
    assert counts["attempted"] == 5 and counts["failed"] == 2
    only_failed = [RECORDS[4], RECORDS[5]]
    values, counts = metrics.end_to_end(only_failed, W0, W1)
    assert counts["attempted"] == 2 and counts["failed"] == 2
    assert "ttft_p50_ms" not in values and "tpot_p50_ms" not in values


def test_lateness_is_sent_minus_due():
    late = metrics.lateness_ms(RECORDS, W0, W1)
    assert sorted(late) == pytest.approx([0.0, 0.0, 0.0, 0.0, 500.0])


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (99, 3.97), (100, 4.0)])
def test_percentile_interpolates_between_closest_ranks(q, want):
    assert metrics.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        metrics.percentile([], 50)
