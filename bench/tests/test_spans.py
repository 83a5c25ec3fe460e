"""The program's spans (``bench/lib/spans.py``) and the readers built on
them, on synthetic event lists with known intervals and counters, and
the loader on a real CPU trace."""
import importlib.util
import os

import pytest

from bench.lib import spans, trace
from bench.lib.spans import Span
from bench.run import TRACE_DIR

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SERVE = ("prefill_ms.serve", "host_ms_tick.serve", "prefill_queue.serve")
TRAIN = ("data_wait_ms.train", "boundary_ms.train")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics",
                                                    name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class FakeRun:
    def __init__(self, reduced):
        self.trace, self.out, self.conf, self.traffic = reduced, {}, {}, {}


def reduced(dev):
    """A window [0, 1000] ns over one device's op intervals."""
    return trace.Reduced({
        "/device:TPU:0": {"XLA Ops": [(f"%op.{i}", s, d)
                                      for i, (s, d) in enumerate(dev)]},
        "/host:CPU": {"python3": [("bench.window", 0, 1000)]}})


# device busy: [100, 250] (two ops overlapping), [400, 500], [700, 800],
# [950, 1000] (an op running past the window's end is clipped)
SERVE_DEV = [(100, 100), (150, 100), (400, 100), (700, 100), (950, 150)]
SERVE_SPANS = [
    Span("repro.serve.tick", 50, 350, {"tick": 0, "prefilling": 3}),
    Span("repro.serve.prefill", 90, 220, {"rid": 7}),
    Span("repro.serve.tick", 350, 650, {"tick": 1, "prefilling": 2}),
    Span("repro.serve.prefill", 380, 450, {"rid": 8}),
    Span("repro.serve.tick", 650, 900, {"tick": 2, "prefilling": 1}),
    # crosses the window's end: left out
    Span("repro.serve.tick", 900, 1050, {"tick": 3, "prefilling": 9}),
    Span("repro.serve.prefill", 920, 1020, {"rid": 9}),
]

TRAIN_SPANS = [
    Span("repro.data.wait", -30, 10, {"steps": 2}),     # crosses the start
    Span("repro.train.step", -20, 50, {"phase": 1, "step_num": 6,
                                       "steps": 2}),
    Span("repro.data.wait", 80, 100, {"steps": 2}),
    Span("repro.train.step", 100, 200, {"phase": 0, "step_num": 0,
                                        "steps": 2}),
    Span("repro.data.wait", 200, 210, {"steps": 2}),
    Span("repro.train.step", 210, 300, {"phase": 0, "step_num": 2,
                                        "steps": 2}),
    Span("repro.data.wait", 350, 400, {"steps": 2}),
    # the next phase: 100 ns after the last step of the one before
    Span("repro.train.step", 400, 500, {"phase": 1, "step_num": 4,
                                        "steps": 2}),
    # the next cycle (phase counter moves back): 120 ns
    Span("repro.train.step", 620, 700, {"phase": 0, "step_num": 0,
                                        "steps": 2}),
    # the same phase index, but the step number starts again: 150 ns
    Span("repro.train.step", 850, 900, {"phase": 0, "step_num": 0,
                                        "steps": 2}),
]


@pytest.fixture
def with_spans(monkeypatch):
    def use(events):
        monkeypatch.setattr(spans, "load", lambda log_dir=TRACE_DIR: events)
    return use


def test_busy_inside_intervals_by_hand():
    r = reduced(SERVE_DEV)
    got = spans.busy_s(r.devices, [(0, 1000), (120, 420), (250, 400),
                                   (960, 990), (50, 60)])
    assert got == pytest.approx([400e-9, 150e-9, 0.0, 30e-9, 0.0])


def test_serve_readers_by_hand(with_spans):
    with_spans(SERVE_SPANS)
    run = FakeRun(reduced(SERVE_DEV))
    # prefill busy: [100, 220] = 120 ns and [400, 450] = 50 ns
    assert reader("prefill_ms.serve")(run) == pytest.approx(85e-6)
    # ticks: 300 - 150, 300 - 100, 250 - 100 ns
    assert reader("host_ms_tick.serve")(run) == pytest.approx(500e-6 / 3)
    assert reader("prefill_queue.serve")(run) == pytest.approx(2.0)


def test_train_readers_by_hand(with_spans):
    with_spans(TRAIN_SPANS)
    run = FakeRun(reduced([(100, 100), (400, 100)]))
    # waits inside: 20 + 10 + 50 ns over the 5 steps' 10 steps
    assert reader("data_wait_ms.train")(run) == pytest.approx(8e-6)
    # boundaries: 100, 120 and 150 ns
    assert reader("boundary_ms.train")(run) == pytest.approx(370e-6 / 3)


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_readers_report_nothing_without_program_spans(with_spans, name):
    other = [Span("bench.decode", 10, 20, {})]
    with_spans(other)
    assert reader(name)(FakeRun(reduced(SERVE_DEV))) is None
    assert reader(name)(FakeRun(None)) is None


def test_loader_reads_program_spans_from_a_cpu_trace(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("repro.serve.tick", tick=4) as t:
            with jax.profiler.TraceAnnotation("not.the.program"):
                pass
            t.set_metadata(prefilling=3)
        with jax.profiler.StepTraceAnnotation("repro.train.step",
                                              step_num=8, steps=2):
            pass
    got = spans.load(str(tmp_path))
    assert [s.name for s in got] == ["repro.serve.tick", "repro.train.step"]
    tick, step = got
    assert tick.stats == {"tick": 4, "prefilling": 3}
    assert step.stats["step_num"] == 8 and step.stats["steps"] == 2
    assert tick.t0 < tick.t1 <= step.t0 < step.t1
    assert spans.load(str(tmp_path / "empty")) == []
    assert spans.load.__defaults__ == (TRACE_DIR,)
