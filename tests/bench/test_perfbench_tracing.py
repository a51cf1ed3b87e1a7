"""The benchmark's trace reduction on a small hand-made trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.tracing import Event, reduce  # noqa: E402

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
MS = 1e6  # ns


def op(plane, name, start_ms, dur_ms):
    return Event(plane, "XLA Ops", f"%{name} = f32[8] custom-call()",
                 start_ms * MS, dur_ms * MS, {})


def span(name, start_ms, dur_ms, line="python"):
    return Event(HOST, line, name, start_ms * MS, dur_ms * MS, {})


def trace_one_device():
    return [
        span("bench/window", 0, 100),
        span("bench/dispatch", 0, 10),
        span("bench/readback", 40, 30),
        span("serve/dispatch", 60, 5),  # innermost where it overlaps
        span("tpu::System::Execute", 20, 80, line="main"),  # not ours
        op(DEV0, "jvp_jit_lsplm_sparse_fused_forward__.3", 5, 20),
        op(DEV0, "fusion.7", 15, 20),  # overlaps the gather: union 5..35
        op(DEV0, "transpose_jvp_jit_lsplm_sparse_scatter_compact___.2", 45, 10),
        op(DEV0, "while.12", 45, 10),  # a container around the scatter
        op(DEV0, "jvp_jit_lsplm_sparse_fused_forward__.4", 95, 10),  # clipped
        Event(DEV0, "XLA Modules", "jit_step(1)", 0, 100 * MS, {}),
        Event(DEV0, "Async XLA Ops", "%copy-start.1 = ...", 70 * MS, 10 * MS, {}),
    ]


def test_busy_is_union_of_op_intervals_clipped_to_window():
    red = reduce(trace_one_device(), devices=1)
    assert red.window_s == pytest.approx(0.100)
    # 5..35, 45..55, 95..100 (the last op clipped at the window's end)
    assert red.busy_s == pytest.approx(0.030 + 0.010 + 0.005)


def test_kernel_time_sums_each_kernels_events():
    red = reduce(trace_one_device(), devices=1)
    assert red.kernel_s["gather"] == pytest.approx(0.020 + 0.005)
    assert red.kernel_s["scatter"] == pytest.approx(0.010)
    assert "collective" not in red.kernel_s
    names = [n for n, _ in red.ops]
    assert "while.12" not in names  # containers are not listed
    assert names[0] == "jvp_jit_lsplm_sparse_fused_forward__.3"


def test_gaps_go_to_innermost_covering_span():
    red = reduce(trace_one_device(), devices=1)
    gaps = dict(red.gaps)
    # idle 0..5: middle 2.5 in bench/dispatch; 35..45: middle 40 in
    # bench/readback (40..70); 55..95: middle 75 in no span of ours
    assert gaps["bench/dispatch"] == pytest.approx(0.005)
    assert gaps["bench/readback"] == pytest.approx(0.010)
    assert gaps["(no span)"] == pytest.approx(0.040)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    assert "tpu::System::Execute" not in gaps


def test_gap_inside_nested_spans_goes_to_the_inner_one():
    events = [span("bench/window", 0, 10), span("bench/readback", 0, 10),
              span("serve/dispatch", 2, 6), op(DEV0, "fusion.1", 0, 3),
              op(DEV0, "fusion.2", 7, 3)]
    gaps = dict(reduce(events, devices=1).gaps)
    assert gaps == {"serve/dispatch": pytest.approx(0.004)}


def test_collective_exposed_only_where_no_other_op_runs_and_devices_average():
    events = [span("bench/window", 0, 10)]
    for dev in (DEV0, DEV1):
        events += [op(dev, "all-reduce.1", 2, 4),  # 2..6
                   op(dev, "fusion.1", 0, 3),  # hides 2..3
                   op(dev, "while.3", 0, 10)]  # a container hides nothing
    events.append(op(DEV1, "fusion.2", 4, 2))  # hides 4..6 on device 1 only
    red = reduce(events, devices=2)
    assert red.kernel_s["collective"] == pytest.approx(0.004)
    # device 0 exposes 3..6 (3 ms), device 1 exposes 3..4 (1 ms)
    assert red.collective_exposed_s == pytest.approx(0.002)
    assert red.busy_s == pytest.approx(0.010)


@pytest.mark.parametrize("text, collective", [
    ("%psum_invariant.45 = f32[8,24]{1,0} all-reduce(f32[8,24]{1,0} %x), "
     "replica_groups={{0,1}}, to_apply=%add", True),
    ("%p.2 = (f32[8], u32[]) all-gather-start(f32[4] %y), dimensions={0}", True),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.40), kind=kLoop", False),
])
def test_a_collective_is_found_by_its_opcode_whatever_its_name(text, collective):
    events = [span("bench/window", 0, 10),
              Event(DEV0, "XLA Ops", text, 2 * MS, 4 * MS, {})]
    red = reduce(events, devices=1)
    assert ("collective" in red.kernel_s) == collective
    assert red.collective_exposed_s == pytest.approx(0.004 if collective else 0.0)


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError, match="bench/window"):
        reduce([op(DEV0, "fusion.1", 0, 1)], devices=1)


def test_breakdown_keeps_at_most_ten_entries():
    events = [span("bench/window", 0, 100)]
    events += [op(DEV0, f"fusion.{i}", 2 * i, 1) for i in range(30)]
    bd = reduce(events, devices=1).breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(s, float) for _, s in bd["device_ops"])
