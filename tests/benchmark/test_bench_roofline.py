"""The architecture's roofline counts against hand sums, for both configurations."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, roofline  # noqa: E402
from benchmark.harness.peaks import peaks_of  # noqa: E402

MAN = manifest.Manifest(REPO)
# both configurations name one architecture; its file holds their counts
A = MAN.arch(MAN.config("mistral-7b-int8"))


def dims(name):
    config = MAN.config(name)
    assert MAN.arch(config) is A
    return A.dims_of(config)


def test_seven_point_two_distinct_experts_at_eight_slots_never_all_eight():
    assert roofline.expected_distinct_experts(8, 2, 8) == pytest.approx(8 * (1 - 0.75 ** 8))
    assert roofline.expected_distinct_experts(8, 2, 8) == pytest.approx(7.199, abs=1e-3)
    assert roofline.expected_distinct_experts(8, 2, 1) == pytest.approx(2.0)
    assert roofline.expected_distinct_experts(8, 2, 4096) < 8.0 + 1e-9


def test_mistral_decode_step_bytes_by_hand():
    d = dims("mistral-7b-int8")
    attn = 4096 * 6144 + 4 * 6144 + 4096 * 4096 + 4 * 4096 + 4 * 4096
    ffn = 4096 * 28672 + 4 * 28672 + 14336 * 4096 + 4 * 4096
    head = 4096 * 32000 + 4 * 32000 + 2 * 4096
    rows = 8 * 1300
    kv = rows * 32 * 2 * 1024 * 2
    want = 32 * (attn + ffn) + head + 8 * 4096 * 2 + kv
    assert A.decode_step_bytes(d, 8, rows) == want
    assert want / 819e9 == pytest.approx(10.35e-3, rel=0.01)


def test_mixtral_decode_step_bytes_use_the_expected_distinct_experts():
    d = dims("mixtral-8x7b-int8-d6")
    attn = 4096 * 6144 + 4 * 6144 + 4096 * 4096 + 4 * 4096 + 4 * 4096
    expert = 4096 * 28672 + 4 * 28672 + 14336 * 4096 + 4 * 4096
    head = 4096 * 32000 + 4 * 32000 + 2 * 4096
    rows = 8 * 1300
    kv = rows * 6 * 2 * 1024 * 2
    distinct = 8 * (1 - 0.75 ** 8)
    want = 6 * (attn + distinct * expert + 2 * 4096 * 8) + head + 8 * 4096 * 2 + kv
    assert A.decode_step_bytes(d, 8, rows) == pytest.approx(want)
    all_eight = 6 * (attn + 8 * expert + 2 * 4096 * 8) + head + 8 * 4096 * 2 + kv
    assert A.decode_step_bytes(d, 8, rows) < all_eight


def test_prefill_operations_by_hand_and_their_bound():
    d = dims("mistral-7b-int8")
    per_row = 32 * 2 * (4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336)
    new, before = 512, 1024
    pairs = new * before + new * (new + 1) / 2
    want = new * per_row + pairs * 32 * 4 * 4096
    assert A.prefill_ops(d, [before + new], [before]) == pytest.approx(want)
    peaks = peaks_of("TPU v5 lite")
    least = roofline.least_seconds(want, A.prefill_bytes(d, new), peaks)
    assert least["bound"] == "operations"
    assert least["seconds"] == pytest.approx(want / 197e12)


def test_decode_is_bound_by_bytes_and_an_unknown_device_is_an_error():
    d = dims("mistral-7b-int8")
    peaks = peaks_of("TPU v5 lite")
    least = roofline.least_seconds(A.decode_step_ops(d, 8, 10400),
                                   A.decode_step_bytes(d, 8, 10400), peaks)
    assert least["bound"] == "bytes"
    with pytest.raises(KeyError):
        peaks_of("TPU v9 imaginary")
