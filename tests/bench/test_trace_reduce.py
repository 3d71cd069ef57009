"""The reduction from a profiler trace to the benchmark's numbers: on
hand-made events, and on a small trace recorded on a TPU v5e
(``probe.xplane.pb``: three runs of a jitted program holding the fused
low-rank kernel, each inside a ``bench.tick`` host span, with a 2 ms
``bench.host_wait`` sleep after each)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402,F401

from trace_reduce import Event, Trace, reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "probe.xplane.pb"


def _trace():
    t = Trace(devices=1, window=(0, 100))
    t.ops = [Event("fusion", 10, 30, "fusion"),
             Event("k.lowrank", 20, 40, "k.lowrank custom-call"),
             Event("copy", 60, 70, "copy"),
             # a loop op spans its body's ops: busy time, not a top op
             Event("%while.1 while f32[]", 10, 40, "while")]
    t.modules = [Event("jit__decode", 5, 45), Event("jit_other", 55, 75)]
    t.spans = [Event("bench.engine_tick", 0, 50),
               Event("bench.wait_arrival", 50, 100)]
    return t


def test_busy_is_the_union_of_op_intervals():
    t = _trace()
    assert t.busy_s == pytest.approx(40e-9)        # [10,40) + [60,70)
    assert t.window_s == pytest.approx(100e-9)
    assert t.idle_share == pytest.approx(0.6)


def test_gaps_are_labelled_by_the_overlapping_host_span():
    t = _trace()
    assert t.gaps() == [(0, 10), (40, 60), (70, 100)]
    assert t.top_gaps(2) == [["bench.wait_arrival", pytest.approx(30e-9)],
                             ["bench.engine_tick", pytest.approx(20e-9)]]
    by = t.gap_time_by_span()
    assert by["bench.wait_arrival"] == pytest.approx(30e-9)
    assert by["bench.engine_tick"] == pytest.approx(30e-9)


def test_op_time_inside_a_program():
    t = _trace()
    assert t.op_time("lowrank", "_decode") == (pytest.approx(20e-9), 1)
    assert t.op_time("lowrank", "other") == (0, 0)
    assert t.op_time("copy") == (pytest.approx(10e-9), 1)
    assert t.top_ops(1) == [["fusion", pytest.approx(20e-9)]]


def test_recorded_trace_reduces():
    t = reduce(str(FIXTURE))
    assert len(t.modules) == 3
    assert all(m.name.startswith("jit_decode_probe(") for m in t.modules)
    assert [s.name for s in t.spans].count("bench.tick") == 3
    # device times moved onto the host clock: every program runs inside
    # the host span that launched it
    ticks = [s for s in t.spans if s.name == "bench.tick"]
    for m, s in zip(sorted(t.modules, key=lambda m: m.start), ticks):
        assert s.start <= m.start and m.end <= s.end
    assert 0 < t.busy_s < t.window_s
    # the device idles through the 2 ms sleeps between ticks
    by = t.gap_time_by_span()
    assert by["bench.host_wait"] > 3 * 2e-3
    secs, n = t.op_time(r"lowrank_matmul_fused", r"^jit_decode_probe\(")
    assert n == 3 and 0 < secs < t.busy_s
    assert t.top_ops(1)[0][0] == \
        "%lowrank_matmul_fused.1 custom-call bf16[32,4864]"


def test_roofline_reader_on_the_recorded_kernel():
    import flops
    import harness

    reader = harness.load_module(
        harness.HERE / "metrics" / "lowrank_fwd_roofline.decode.py")
    t = reduce(str(FIXTURE))
    # the recorded program is not the engine's decode step; point the
    # reader at it
    for m in t.modules:
        m.name = "jit__decode(" + m.name.split("(", 1)[1]
    share = reader.read({"trace": t, "peak": flops.peaks("TPU v5 lite")})
    # x (32, 896), R^T (896, 256), L^T (256, 4864) in bf16: 3.32 MB, so
    # 4.05 us at 819 GB/s, against 7.75 us measured per call
    c = flops.lowrank_fwd(32, 896, 256, 4864)
    least = 3 * c["bytes"] / 819e9
    secs, _ = t.op_time(r"lowrank_matmul_fused")
    assert share == pytest.approx(100 * least / secs)
    assert 0 < share < 100
