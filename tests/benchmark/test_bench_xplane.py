"""The trace reduction on a small recorded trace: 0.4 s of the first chip
run of `mixtral-d6-agents8` (TPU v5 lite, PR 23), module and operation events
of device 0 around one prefix-hit admission, kept as names + instants."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import make_trace_extract  # noqa: E402
from benchmark.harness import xplane  # noqa: E402

LAYERS = 6
KERNEL = "paged_decode_attention"  # what archs/mistral.py names as its decode step's marker


@pytest.fixture(scope="module")
def planes():
    with gzip.open(os.path.join(HERE, "data", "trace_mixtral_d6_agents8.json.gz"), "rt") as fh:
        return xplane.from_extract(json.load(fh))


def test_programs_are_told_apart_by_name(planes):
    kinds = [xplane.module_kind(n) for n, _, _ in xplane.modules(planes)]
    assert [k for k in kinds if k != "other"] == ["decode", "prefill", "decode"]
    assert xplane.module_kind("jit__prefill_impl_paged(7)") == "prefill"
    assert xplane.module_kind("jit__prefill_chunk_impl(7)") == "prefill"
    assert xplane.module_kind("jit_convert_element_type(7)") == "other"


def test_steps_of_a_decode_program_come_from_its_attention_kernel_events(planes):
    runs = xplane.decode_steps(planes, KERNEL, LAYERS)
    assert [n for _, n in runs] == [2, 16]  # an admission tick, then a full one
    for seconds, n in runs:
        assert 1e3 * seconds / n == pytest.approx(14.85, abs=0.05)


def test_busy_time_is_the_union_not_the_sum_of_nested_operations(planes):
    ops = planes["/device:TPU:0"][xplane.OPS_LINE]
    assert xplane.union_ns(ops) == 373400361
    assert sum(d for _, _, d in ops) > 2 * xplane.union_ns(ops)  # while bodies nest
    # the extract holds operations of programs cut off at its edges: those lie
    # outside the window of whole programs and are clipped away
    assert xplane.busy_and_window_seconds(planes) == (
        pytest.approx(0.297284517), pytest.approx(0.315087237))
    assert xplane.union_ns([("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]) == 20


def test_the_traced_window_is_the_device_s_own_and_operations_are_clipped_to_it(planes):
    """Busy time and the window it is a share of come from one clock: first
    program start to last program end on the device plane."""
    plane = planes["/device:TPU:0"]
    mods = plane[xplane.MODULES_LINE]
    w0, w1 = xplane.window_ns(plane)
    assert w0 == min(s for _, s, _ in mods) and w1 == max(s + d for _, s, d in mods)
    busy, window = xplane.busy_and_window_seconds(planes)
    assert window == pytest.approx((w1 - w0) / 1e9) and 0 < busy <= window
    # an operation the profiler recorded outside the window is clipped, not counted
    stray = {"/device:TPU:0": {
        xplane.MODULES_LINE: [("jit__lambda(1)", 100, 50), ("jit__lambda(1)", 200, 100)],
        xplane.OPS_LINE: [("%a", 0, 120), ("%b", 210, 40), ("%c", 290, 500), ("%d", 900, 5)],
    }}
    assert xplane.busy_and_window_seconds(stray) == (pytest.approx(70e-9), pytest.approx(200e-9))
    assert xplane.window_ns({}) == (0, 0)


def test_control_flow_is_left_out_of_the_ranking(planes):
    top = xplane.top_ops(planes, 10)
    assert len(top) == 10 and all(len(n) <= 64 for n, _ in top)
    assert not any(n.startswith("_while") for n, _ in top)
    assert top[0][0].startswith("_fusion.208") and top[0][1] == pytest.approx(0.157839074)
    assert xplane.is_control("%while.30 = (s32[]{:T(128)}, bf16[8,1,4096]) while(%tuple)")
    assert not xplane.is_control("%fusion.194 = bf16[8,1,28672] fusion(s8[] %while.3)")


def test_prefill_time_and_idle_gaps_named_by_the_programs_around_them(planes):
    assert xplane.prefill_seconds(planes) == pytest.approx(0.029739425)
    gaps = xplane.module_gaps(planes)
    assert [w for w, _ in gaps] == ["during_a_prefill_admission.decode-prefill",
                                   "during_a_prefill_admission.prefill-decode"]
    assert gaps[0][1] == pytest.approx(0.009735488)


def test_a_recorded_xplane_file_loads_with_jax_alone(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = xplane.load(xplane.find(str(tmp_path)))
    assert any(lines for lines in loaded.values())
    name, start, dur = next(e for lines in loaded.values() for evs in lines.values() for e in evs)
    assert isinstance(name, str) and isinstance(start, int) and isinstance(dur, int)
    # a CPU trace has no device plane: nothing is read as device time
    assert xplane.device_planes(loaded) == {}
    assert xplane.busy_and_window_seconds(loaded) == (0.0, 0.0)
    assert xplane.decode_steps(loaded, KERNEL, LAYERS) == [] and xplane.modules(loaded) == []
    with pytest.raises(FileNotFoundError):
        xplane.find(str(tmp_path / "nothing"))


def test_extract_and_from_extract_round_trip(planes):
    again = xplane.from_extract(make_trace_extract.extract(planes, before_s=0.08, after_s=0.32))
    assert xplane.decode_steps(again, KERNEL, LAYERS)[-1][1] == 16
    assert xplane.prefill_seconds(again) == pytest.approx(0.029739425)
