"""The program's spans and counters in a profiler trace.

The serving loop and the training path mark their layer boundaries with
``jax.profiler`` annotations named ``repro.*``; their counters are the
events' stats.  These tests capture a CPU trace around a tiny serve and a
tiny two-phase ``repro.api.run`` on ``SpmdBackend`` and check the spans
against what the program logged, and that tracing changes no result.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import models
from repro.api import RunConfig, ScheduleSpec, run
from repro.configs import get_config, reduced
from repro.data import DataPlane
from repro.data.synthetic import SyntheticTokens
from repro.engine.engine import TrainEngine
from repro.models import transformer as tf
from repro.optim import sgd_momentum
from repro.serve import PageSpec, ServeEngine, synthetic_workload


def repro_spans(log_dir):
    """[(name, start, end, stats)] of every ``repro.*`` host event in the
    trace under ``log_dir``, in order of start."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def of(spans, name):
    return [s for s in spans if s[0] == name]


# ------------------------------- serving -----------------------------------
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same requests served with a trace on and with it off."""
    cfg = reduced(get_config("gemma3-4b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    spec = PageSpec(page_len=16, pages_per_slot=4, n_slots=2)
    reqs = synthetic_workload(1, 8, vocab=cfg.vocab_size,
                              prompt_lens=(3, 20), gen_short=(3, 8),
                              gen_long=(12, 20), p_long=0.3)
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    traced = ServeEngine(cfg, params, spec=spec, backend="contig",
                         prefill_chunk=8)
    with jax.profiler.trace(log_dir):
        rec_on = traced.serve(reqs)
    plain = ServeEngine(cfg, params, spec=spec, backend="contig",
                        prefill_chunk=8)
    rec_off = plain.serve(reqs)
    return {"spans": repro_spans(log_dir), "log": traced.log,
            "log_off": plain.log, "on": rec_on, "off": rec_off}


def test_one_tick_span_per_tick(served):
    log = served["log"]
    n_ticks = max(e[1] for e in log) + 1
    ticks = of(served["spans"], "repro.serve.tick")
    assert [s[3]["tick"] for s in ticks] == list(range(n_ticks))


def test_tick_counters_match_the_log(served):
    log = served["log"]
    admitted = {e[2]: e[1] for e in log if e[0] == "admit"}
    last_chunk = {e[2]: e[1] for e in log if e[0] == "prefill" and e[6]}
    decode = {e[1]: len(e[2]) for e in log if e[0] == "decode"}
    for _, _, _, st in of(served["spans"], "repro.serve.tick"):
        t = st["tick"]
        assert st["prefilling"] == sum(
            1 for rid, a in admitted.items() if a <= t <= last_chunk[rid])
        assert st["decoding"] == decode.get(t, 0)
        assert st["queued"] >= 0


def test_prefill_spans_inside_ticks_with_their_request(served):
    spans, log = served["spans"], served["log"]
    ticks = of(spans, "repro.serve.tick")
    prefills = of(spans, "repro.serve.prefill")
    assert [(s[3]["rid"], s[3]["slot"], s[3]["pos"], s[3]["tokens"])
            for s in prefills] == [(e[2], e[3], e[4], e[5])
                                   for e in log if e[0] == "prefill"]
    for _, p0, p1, _ in prefills:
        assert any(t0 <= p0 and p1 <= t1 for _, t0, t1, _ in ticks)
    decodes = of(spans, "repro.serve.decode")
    assert [s[3]["rows"] for s in decodes] == [
        len(e[2]) for e in log if e[0] == "decode"]
    # one token readback per step call: every prefill chunk and decode
    assert len(of(spans, "repro.serve.readback")) == \
        len(prefills) + len(decodes)
    assert sum(s[3]["evicted"] for s in of(spans, "repro.serve.evict")) \
        == sum(1 for e in log if e[0] == "evict")


def test_served_tokens_identical_with_trace_on_and_off(served):
    assert served["log"] == served["log_off"]
    assert [r.tokens for r in served["on"]] == \
        [r.tokens for r in served["off"]]


# ------------------------------- training ----------------------------------
SCAN_CHUNK = 2


def tiny_train(log_dir=None):
    """A two-phase CPL cycle (seq 8 then 16) of the hybrid scheme through
    ``repro.api.run`` on ``SpmdBackend`` with the DataPlane's scan feed;
    traced into ``log_dir`` when given.  Returns (losses, params, phases)."""
    cfg = reduced(get_config("phi3-mini-3.8b"), layers=1, d_model=64,
                  n_heads=2, vocab=64)
    spec = ScheduleSpec(scheme="hybrid", input_size=16, axis="seq_len",
                        batch_size=8, dataset_size=512, n_workers=4,
                        n_small=2, k=1.05, n_steps=6, lr=0.01, tm_a=1.0,
                        tm_b=24.6, sub_sizes=(8, 16), sub_dropouts=(0.0, 0.0),
                        stage_epochs=(2,), stage_lrs=(0.01,), seed=3)
    engine = TrainEngine(cfg, sgd_momentum(0.0), sgd_server=True,
                         interpret=True, scan_chunk=SCAN_CHUNK)
    plane = DataPlane(SyntheticTokens(vocab=64, seed=3, n_examples=512),
                      seed=3, prefetch=True)
    params = models.init_params(cfg, jax.random.PRNGKey(0))

    def go():
        return run(spec, RunConfig(backend="spmd", log_every=1),
                   init_params=params, engine=engine, plane=plane)
    if log_dir is None:
        res = go()
    else:
        with jax.profiler.trace(log_dir):
            res = go()
    return [h["loss"] for h in res.history], res.params, spec.to_phases()


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("train_trace"))
    losses_on, params_on, phases = tiny_train(log_dir)
    losses_off, params_off, _ = tiny_train()
    return {"spans": repro_spans(log_dir), "phases": phases,
            "on": (losses_on, params_on), "off": (losses_off, params_off)}


def chunks(phases):
    return sum(-(-p.n_steps // SCAN_CHUNK) for p in phases)


def test_one_step_span_per_chunk(trained):
    spans, phases = trained["spans"], trained["phases"]
    assert len(phases) == 2
    steps = of(spans, "repro.train.step")
    assert len(steps) == chunks(phases)
    assert sum(s[3]["steps"] for s in steps) == sum(p.n_steps
                                                    for p in phases)
    # step numbers follow on; the phase counter moves at the boundary
    nums = [s[3]["step_num"] for s in steps]
    assert nums == sorted(nums) and nums[0] == 0
    assert [s[3]["phase"] for s in steps] == [
        i for i, p in enumerate(phases)
        for _ in range(-(-p.n_steps // SCAN_CHUNK))]
    for name in ("repro.train.acquire", "repro.train.dispatch",
                 "repro.train.sync"):
        inner = of(spans, name)
        assert len(inner) == len(steps), name
        for (_, c0, c1, _), (_, s0, s1, _) in zip(inner, steps):
            assert s0 <= c0 and c1 <= s1, name


def test_one_phase_span_per_phase(trained):
    spans, phases = trained["spans"], trained["phases"]
    ph = of(spans, "repro.train.phase")
    assert [(s[3]["phase"], s[3]["seq"], s[3]["batch"], s[3]["steps"])
            for s in ph] == [(i, p.input_size, p.batch_size, p.n_steps)
                             for i, p in enumerate(phases)]
    for _, s0, s1, st in of(spans, "repro.train.step"):
        _, p0, p1, _ = ph[st["phase"]]
        assert p0 <= s0 and s1 <= p1
    # the flat store is entered and left once per phase-at-a-time call
    assert len(of(spans, "repro.train.ravel")) == len(phases)
    assert len(of(spans, "repro.train.unravel")) == len(phases)


def test_one_data_wait_per_chunk(trained):
    spans, phases = trained["spans"], trained["phases"]
    waits = of(spans, "repro.data.wait")
    assert len(waits) == chunks(phases)
    assert sum(s[3]["steps"] for s in waits) == sum(p.n_steps
                                                    for p in phases)
    stages = of(spans, "repro.data.stage")
    assert sorted((s[3]["phase"], s[3]["step"]) for s in stages) == sorted(
        (s[3]["phase"], s[3]["step_num"])
        for s in of(spans, "repro.train.step"))


def test_trained_losses_identical_with_trace_on_and_off(trained):
    (l_on, p_on), (l_off, p_off) = trained["on"], trained["off"]
    assert l_on == l_off and len(l_on) > 0
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))

