"""The control of the output check at a size a test run holds, judged
as a run is judged (``bench.run.judge`` against the cell's own limits in
``bench/limits``): the program has to come out correct, and the control
(the reference put in the program's place in float8, weights and
matmuls) and the planted faults not correct.  On the chip the same code
runs at the cells' own sizes (``bench/control.py``)."""
from bench import control
from bench.rehearse import tiny_conf, tiny_traffic
from bench.run import load_cell

SEED = 2**31 + 99


def tiny_spec(name):
    spec = load_cell(name)
    spec["traffic"] = tiny_traffic(spec["traffic"])
    spec["conf"] = tiny_conf(spec["conf"], spec["traffic"]["kind"])
    return spec


def test_train_control_is_not_correct():
    rows = {r["reading"]: r for r in control.train_readings(
        tiny_spec("phi3_train_hybrid"), SEED)}
    assert rows["program"]["correct"], rows["program"]
    assert not rows["control_fp8"]["correct"], rows["control_fp8"]
    assert not rows["fault_half_batch"]["correct"], rows["fault_half_batch"]


def test_serve_control_is_not_correct():
    rows = {r["reading"]: r for r in control.serve_readings(
        tiny_spec("phi3_serve_contig"), SEED, seconds=1.0)}
    assert rows["program"]["correct"], rows["program"]
    assert not rows["control_fp8"]["correct"], rows["control_fp8"]
