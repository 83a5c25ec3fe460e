"""The trace reduction and the per-layer readers, on a synthetic trace
with known intervals and on a trace recorded on a TPU v5e."""
import importlib.util
import json
import os

import pytest

from bench.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
KERNEL = ('%closed_call.1 = f32[16,128]{1,0} closed_call(f32[16,128] %p), '
          'custom_call_target="tpu_custom_call"')


def synthetic():
    dev = [("%fusion.1 = bf16[2] fusion()", 10, 10),
           ("%fusion.2 = bf16[2] fusion()", 15, 15),
           ("%while.3 = (s32[]) while()", 50, 10),
           (KERNEL, 70, 10),
           ("%fusion.1 = bf16[2] fusion()", 120, 10)]      # after the window
    host = [("bench.window", 0, 100), ("bench.cycle", 0, 60),
            ("bench.decode", 30, 20)]
    return {"/device:TPU:0": {"XLA Ops": dev},
            "/host:CPU": {"python3": host}}


def test_busy_and_idle_by_hand():
    r = trace.Reduced(synthetic())
    assert r.window_s == pytest.approx(100e-9)
    # union of [10,30], [50,60], [70,80] inside the window
    assert r.mean_busy_s() == pytest.approx(40e-9)
    assert r.op_seconds(r"tpu_custom_call") == pytest.approx(10e-9)
    assert r.op_count(r"tpu_custom_call") == 1


def test_top_ops_and_idle_gaps():
    r = trace.Reduced(synthetic())
    top = dict(r.top_ops())
    assert top["%fusion.1"] == pytest.approx(10e-9)
    assert top["%closed_call.1 [tpu_custom_call]"] == pytest.approx(10e-9)
    gaps = r.idle_gaps()
    # gaps: [0,10] in the cycle, [30,50] in the decode (innermost),
    # [60,70] and [80,100] outside any span but the window
    assert gaps[0] == ["bench.decode", pytest.approx(20e-9)]
    assert gaps[1] == ["outside any span", pytest.approx(20e-9)]
    assert sorted(g[0] for g in gaps[2:]) == ["bench.cycle",
                                              "outside any span"]


def test_no_window_span_raises():
    ev = synthetic()
    ev["/host:CPU"]["python3"] = [("bench.cycle", 0, 60)]
    with pytest.raises(ValueError):
        trace.Reduced(ev)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics",
                                                    name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class FakeRun:
    def __init__(self, reduced, out, conf):
        from bench.peaks import peak
        self.trace, self.out, self.conf = reduced, out, conf
        self.peak, self.chips, self.traffic = peak("TPU v5 lite"), 1, {}


def test_readers_find_nothing_without_a_trace():
    run = FakeRun(None, {}, {})
    for name in ("mfu.train", "idle_share.train", "dbl_merge_roofline",
                 "mfu.serve", "idle_share.serve"):
        assert reader(name)(run) is None


def test_dbl_merge_roofline_by_hand():
    r = trace.Reduced(synthetic())
    run = FakeRun(r, {"flat_rows": 16}, {})
    # one call over a (16, 128) f32 store: 3 * 16 * 128 * 4 bytes
    least = 3 * 16 * 128 * 4 / 819e9
    assert reader("dbl_merge_roofline")(run) == pytest.approx(
        100 * least / 10e-9)


RECORDED = os.path.join(BENCH, "testdata", "trace_train_v5e.json.gz")


def test_recorded_v5e_train_trace():
    """1.5 s of ``phi3_train_hybrid``'s traced window on one TPU v5e: the
    layer scans run back to back, three flat-store updates of 5,079,040
    rows ran in it, each about 11.6 ms against 9.52 ms at 819 GB/s."""
    r = trace.Reduced(trace.read(RECORDED))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(1.5)
    assert 1.0 - r.mean_busy_s() / r.window_s < 0.05
    pattern = r"^%\S+ = f32\[\d+,128\]\S* .*tpu_custom_call"
    assert r.op_count(pattern) == 3
    assert r.op_seconds(pattern) == pytest.approx(0.034870084, rel=1e-6)
    run = FakeRun(r, {"flat_rows": 5079040}, {})
    share = reader("dbl_merge_roofline")(run)
    least = 3 * 3 * 5079040 * 128 * 4 / 819e9
    assert share == pytest.approx(100 * least / 0.034870084, rel=1e-6)
    assert 50 < share < 100
    assert r.top_ops(3)[0][0].startswith("%while")
