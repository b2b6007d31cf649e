"""The reduction from a trace to numbers, on a small recorded trace; the
roofline arithmetic; the table of peaks."""

import json
import os

import pytest

from benchmark import peaks, roofline, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)
    return {"devices": {int(k): {line: [tuple(e) for e in events]
                                 for line, events in v.items()}
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


@pytest.fixture(scope="module")
def reduced(trace):
    return trace_reduce.reduce_trace(trace)


def test_window_and_busy_union(reduced):
    # device 0: the while spans 100..1100 (its body's ops inside it), then
    # 1200..1400 (an all-reduce and a fusion that overlap), 1600..2000; the event at
    # 2500 lies outside the window 0..2100. Device 1 the same, 10 ns later.
    assert reduced["window_s"] == pytest.approx(2100e-9)
    assert reduced["busy_s"] == pytest.approx((1000 + 200 + 400) * 1e-9)
    assert reduced["devices"] == 2


def test_kernel_time_calls_and_bytes(reduced):
    assert reduced["kernel_s"] == pytest.approx(800e-9)
    assert reduced["kernel_calls"] == 2
    one = roofline.kernel_bytes(4999168, 256, 4)
    assert one == 4999168 * 256 * 4 + 4999168 * 12 + 2 * 256 * 4
    assert reduced["kernel_bytes"] == 2 * one
    assert reduced["kernel_flops"] == 2 * 4 * 4999168 * 256


def test_container_ops_are_not_counted_twice(reduced):
    ops = dict(reduced["device_ops"])
    assert "while" not in ops
    assert ops["_fused_padded"] == pytest.approx(800e-9)
    assert ops["fusion"] == pytest.approx((180 + 150) * 1e-9)
    assert ops["multiply_reduce_fusion"] == pytest.approx(200e-9)
    assert reduced["op_events"] == 6  # per device, the while left out
    # the while's own span (1000 ns) counts as busy, not as an op's time
    assert sum(ops.values()) == pytest.approx((800 + 330 + 200 + 100) * 1e-9)
    modules = dict(reduced["device_modules"])
    assert modules == {"jit__step_impl": pytest.approx(1300e-9),
                       "jit__score_impl": pytest.approx(400e-9)}


def test_idle_gaps_by_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # device 0 idles 0..100 (mid 50: bench:place), 1100..1200 (bench:sweep),
    # 1400..1600 (mid 1500: bench:read), 2000..2100 (bench:read); device 1
    # the same shifted by 10 ns (0..110, mid 55: still bench:place)
    assert gaps["bench:place"] == pytest.approx((100 + 110) / 2 * 1e-9)
    assert gaps["bench:sweep"] == pytest.approx(100e-9)
    assert gaps["bench:read"] == pytest.approx((300 + 290) / 2 * 1e-9)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_layer_metric_readers_on_the_reduced_trace(reduced):
    from benchmark.manifest import layer_metric_reader
    from benchmark.spans import Spans

    spans = Spans()
    spans.closed += [("sweep", 10.0, 11.5), ("sweep", 11.5, 12.5),
                     ("sweep", 1.0, 2.0), ("pack", 0.0, 3.0), ("episode", 1.0, 4.0),
                     ("episode", 10.0, 12.5), ("episode", 12.5, 14.0),
                     ("episode", 14.0, 20.0)]
    ctx = {"trace": reduced, "spans": spans, "window_start": 5.0,
           "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 3 * 2**30},
           "counters": {"compiles_in_window": 0}}
    read = lambda name: layer_metric_reader(name)(ctx)  # noqa: E731
    assert read("pack_s") == 3.0
    assert read("sweeps_solver_evals") == 1.0  # 2 calls over 2 sweeps
    assert read("episode_s") == pytest.approx(2.5)  # the median: a stall is not in it
    assert read("device_idle_pct") == pytest.approx(100 * (1 - 1600 / 2100))
    assert read("sweeps_kernel_time_share_pct") == pytest.approx(50.0)
    assert read("peak_hbm_GiB") == 3.0 and read("compiles_in_window") == 0
    least = 2 * roofline.kernel_bytes(4999168, 256, 4) / 819e9
    assert read("sweeps_glm_kernel_roofline") == pytest.approx(100 * least / 800e-9)
    spans.closed[:] = [s for s in spans.closed if s[0] != "episode"]
    assert read("episode_s") is None  # nothing to read: left out of the line


def test_a_trace_without_device_work_or_window_is_refused(trace):
    with pytest.raises(ValueError, match="no operation ran"):
        trace_reduce.reduce_trace({"devices": {0: {"ops": [], "modules": []}},
                                   "host": trace["host"]})
    with pytest.raises(ValueError, match="bench:window"):
        trace_reduce.reduce_trace({"devices": trace["devices"], "host": []})


@pytest.mark.parametrize("text,name", [
    ("%multiply_reduce_fusion.413 = f32[17700,8] fusion(...)", "multiply_reduce_fusion"),
    ("%while.3121 = (f32[16148,16]{1,0}) while(...)", "while"),
    ("jit__step_impl(17687940369425317445)", "jit__step_impl"),
    ("%all-reduce-start.2 = f32[256] all-reduce-start(...)", "all-reduce-start"),
    ("%copy.1.2 = f32[2] copy(...)", "copy"),
])
def test_instruction_names(text, name):
    assert trace_reduce.instruction(text) == name


def test_roofline_arithmetic_is_not_clipped():
    # 819 MB in 1 ms is the whole of v5e's bandwidth; in half the time the
    # reading is 200 %, and it has to show
    assert roofline.roofline_pct(819e6, 0.0, 1e-3, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.roofline_pct(819e6, 0.0, 2e-3, "TPU v5 lite") == pytest.approx(50.0)
    assert roofline.roofline_pct(819e6, 0.0, 5e-4, "TPU v5 lite") == pytest.approx(200.0)
    # compute-bound when the flops take longer than the bytes
    assert roofline.roofline_pct(1.0, 197e12, 2.0, "TPU v5 lite") == pytest.approx(50.0)
    assert roofline.kernel_roofline_from_trace({"kernel_calls": 0}, "TPU v5 lite") is None


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1.0, 1.0, 1.0, "cpu")
