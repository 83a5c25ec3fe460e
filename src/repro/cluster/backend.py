"""Cluster backends: one entry point for the PS simulator and the SPMD
engine.

A ``Backend`` executes a ``Phase`` schedule and returns a ``RunResult``
with a unified per-phase history, so the paper's accuracy path (the
event-driven simulator, Tables 3/5/8) and its speed path (the SPMD engine)
are two implementations of the same contract instead of two disjoint code
paths joined by ad-hoc glue:

  * ``PsSimBackend``  — each phase is one ``simulate()`` run with workers
    from its dual-batch plan under the phase's input-size-rescaled time
    model(s); params carry across phases, per-epoch history concatenates
    with absolute sim-time offsets, and real per-epoch LR schedules
    (``Phase.lr_for_epoch``) are honored.
  * ``SpmdBackend``   — the compiled ``TrainEngine`` path, one phase at a
    time so phase boundaries are observable.

Both support checkpoint/resume at phase boundaries via ``checkpoint.ckpt``
(save after each completed phase; ``resume=True`` restarts from the latest
saved boundary, bit-for-bit on CPU because per-phase RNG streams depend
only on ``(seed, phase index)``).

Both accept initial params either as the public pytree or as a flat store
(``repro.core.flat.FlatParams``, e.g. restored from a checkpoint into the
fused hot path's representation) — flat input is unwrapped through the
codec at entry, and checkpoints always keep the public pytree format.

Both consume the ``repro.data.DataPlane`` (one resolution-aware input
pipeline): ``PsSimBackend(..., plane=plane)`` replaces the factory's
``data_fn`` with the plane's per-worker counter streams, and
``SpmdBackend`` binds a plane passed as ``batch_fn`` to the schedule and
overlaps the next phase's compile with the current phase's execution
(``TrainEngine.schedule_warm``) so cyclic resolution transitions don't
stall the hot loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import (Any, Callable, List, Optional, Protocol, Sequence,
                    runtime_checkable)

import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.ckpt import restore_latest, save_checkpoint
from repro.cluster.simulator import simulate
from repro.cluster.trace import simulate_traced
from repro.cluster.sync import SyncPolicy, as_policy
from repro.cluster.topology import ClusterEvent, workers_from_plan
from repro.core.flat import FlatParams
from repro.core.time_model import LinearTimeModel


def _as_tree(params):
    """Accept a flat store anywhere a params pytree is expected."""
    return params.to_tree() if isinstance(params, FlatParams) else params


def scaled_time_model(tm: LinearTimeModel, input_size: int, ref_size: int,
                      *, axis: str = "resolution") -> LinearTimeModel:
    """Per-sample cost scales with the input cost (r² or s); overhead b is
    size-independent (paper §4.2).  Thin front over
    ``LinearTimeModel.scaled`` (the canonical rescaling rule)."""
    return tm.scaled(input_size, ref_size, axis=axis)


def phase_seed(seed: int, phase_idx: int) -> int:
    """Per-phase RNG stream depending only on (seed, phase index), so a
    resumed run replays exactly the uninterrupted run's data order."""
    if phase_idx == 0:
        return seed
    return (seed * 1_000_003 + 0x9E3779B1 * phase_idx) % 2**31


def phase_record(idx: int, backend: str, phase, *, steps: int, time_s: float,
                 t0: float, metrics: dict) -> dict:
    """The unified per-phase history record both backends emit."""
    rec = {"phase": idx, "backend": backend,
           "input_size": phase.input_size, "batch_size": phase.batch_size,
           "lr": phase.lr, "steps": steps,
           "time": round(time_s, 6), "t0": round(t0, 6)}
    rec.update({k: v for k, v in metrics.items()
                if k not in ("epoch", "sim_time", "phase", "step")})
    return rec


@dataclass
class RunResult:
    """What every backend returns for a schedule run."""
    backend: str
    params: Any
    opt_state: Any = None
    time: float = 0.0               # sim seconds (ps_sim) / wall s (spmd)
    history: List[dict] = field(default_factory=list)   # concatenated
    phases: List[dict] = field(default_factory=list)    # phase_record()s
    resumed_from: Optional[int] = None   # phase boundary restored, if any

    @property
    def last(self) -> dict:
        return self.history[-1] if self.history else {}


@runtime_checkable
class Backend(Protocol):
    """A cluster backend executes a ``Phase`` schedule end to end."""
    name: str

    def run(self, phases: Sequence, params, *, opt_state=None, seed: int = 0,
            ckpt_dir: Optional[str] = None,
            resume: bool = False) -> RunResult: ...


def _restore(ckpt_dir: Optional[str], resume: bool, like: dict):
    """Latest phase-boundary checkpoint (or None) for a backend run."""
    if not (resume and ckpt_dir):
        return None, None
    return restore_latest(ckpt_dir, like)


class PsSimBackend:
    """Event-driven parameter-server backend (the paper's accuracy path).

    fns_factory(input_size) -> (grad_fn, data_fn, eval_fn); results are
    memoized per input size so cyclic schedules that revisit a size reuse
    the same (already-traced) grad_fn instead of recompiling every phase.

    tm: one ``LinearTimeModel`` or a per-worker sequence (heterogeneous
    cluster); each is rescaled per phase by the input-size cost ratio.
    jitter / events_for_phase: straggler injection and elastic membership
    (see ``repro.cluster.topology``).
    plane: a ``repro.data.DataPlane`` supplying every worker's batches from
    the canonical per-(phase, worker, step) sample streams; when given, the
    factory's ``data_fn`` slot is ignored (it may return None there) and
    the same plane fed to an ``SpmdBackend`` draws from identical
    per-worker streams — sample-for-sample equal in the canonical
    B_L-wide-row geometry (``repro.engine.parity.check_data_plane_parity``).
    traced: run each phase through the trace-compiled simulator
    (``repro.cluster.trace.simulate_traced``: host-side schedule pass +
    fused device chunks) instead of the per-event dispatch loop — same
    timeline/samples/epoch structure, bit-identical for matmul models
    (``engine.parity.check_trace_parity``; see ``repro.cluster.trace``
    for the conv-on-CPU scope note), a fraction of the host overhead;
    ``trace_chunk`` bounds events per compiled chunk and ``trace_update``
    picks the fused update form (``"auto"``: Pallas kernel on TPU, XLA
    elementwise elsewhere).
    precision: ``"f32"`` (default, bit-identical to before the knob) or
    ``"bf16"`` — the trace executor carries the bf16 store + f32 master
    pair per phase (requires ``traced=True``: the per-event dispatch loop
    has no flat store to hold a shadow in).
    """
    name = "ps_sim"

    def __init__(self, fns_factory: Callable, *, tm, axis: str = "resolution",
                 sync: Any = "asp", staleness: int = 3,
                 momentum: float = 0.9, ref_size: Optional[int] = None,
                 jitter=0.0,
                 events_for_phase: Optional[
                     Callable[[int, Any], Sequence[ClusterEvent]]] = None,
                 plane=None, traced: bool = False, trace_chunk: int = 32,
                 trace_update: str = "auto", precision: str = "f32"):
        self._factory = fns_factory
        self._fns_cache: dict = {}
        self.tm = tm
        self.axis = axis
        self.sync: SyncPolicy = as_policy(sync, staleness)
        self.momentum = momentum
        self.ref_size = ref_size
        self.jitter = jitter
        self.events_for_phase = events_for_phase
        self.plane = plane
        self.traced = bool(traced)
        self.trace_chunk = int(trace_chunk)
        self.trace_update = trace_update
        if precision not in ("f32", "bf16"):
            raise ValueError(f"unknown precision {precision!r} "
                             "(expected 'f32' or 'bf16')")
        if precision != "f32" and not self.traced:
            raise ValueError(
                "precision='bf16' requires traced=True: only the "
                "trace-compiled executor carries the bf16 store + f32 "
                "master pair (the per-event loop is pytree-based f32)")
        self.precision = precision

    def _fns(self, input_size: int):
        if input_size not in self._fns_cache:
            self._fns_cache[input_size] = self._factory(input_size)
        return self._fns_cache[input_size]

    def _scaled_tms(self, input_size: int, ref_size: int):
        tms = self.tm if isinstance(self.tm, (list, tuple)) else [self.tm]
        scaled = [scaled_time_model(t, input_size, ref_size, axis=self.axis)
                  for t in tms]
        return scaled if isinstance(self.tm, (list, tuple)) else scaled[0]

    def run(self, phases: Sequence, params, *, opt_state=None, seed: int = 0,
            ckpt_dir: Optional[str] = None,
            resume: bool = False) -> RunResult:
        params = _as_tree(params)
        if self.plane is not None:
            self.plane.bind(phases)
        ref_size = self.ref_size or max(p.input_size for p in phases)
        like = {"params": params, "clock": np.zeros((), np.float64),
                "epochs": np.zeros((), np.int64)}
        start, tree = _restore(ckpt_dir, resume, like)
        t_off, epoch_off, resumed = 0.0, 0, None
        if start is not None:
            params = tree["params"]
            t_off = float(tree["clock"])
            epoch_off = int(tree["epochs"])
            resumed = start
        history: List[dict] = []
        phase_recs: List[dict] = []
        for i in range(start or 0, len(phases)):
            phase = phases[i]
            if phase.plan is None:
                raise ValueError("simulator phases need a dual-batch plan "
                                 "(n_small=0 plans model the baseline)")
            tm_sub = self._scaled_tms(phase.input_size, ref_size)
            workers = workers_from_plan(phase.plan, tm_sub,
                                        jitter=self.jitter)
            grad_fn, data_fn, eval_fn = self._fns(phase.input_size)
            feed = None
            if self.plane is not None:
                if self.traced:
                    # trace staging draws the SAME counter-keyed streams
                    # directly (trace.stream_step), no per-event closure
                    feed = self.plane.trace_feed(i, phase)
                    data_fn = None
                else:
                    data_fn = self.plane.sim_data_fn(i, phase)
            elif data_fn is None:
                raise ValueError("fns_factory returned data_fn=None; pass "
                                 "plane=DataPlane(...) to supply batches")
            lr_fn = phase.lr_for_epoch or (lambda e, lr=phase.lr: lr)
            events = (self.events_for_phase(i, phase)
                      if self.events_for_phase else ())
            kw = dict(epochs=max(1, phase.epochs), lr_for_epoch=lr_fn,
                      sync=self.sync, momentum=self.momentum,
                      eval_fn=eval_fn, seed=phase_seed(seed, i),
                      events=events)
            if self.traced:
                res = simulate_traced(params, grad_fn, data_fn, workers,
                                      feed=feed,
                                      scan_chunk=self.trace_chunk,
                                      update=self.trace_update,
                                      precision=self.precision, **kw)
            else:
                res = simulate(params, grad_fn, data_fn, workers, **kw)
            params = res.params
            for rec in res.history:
                history.append({**rec, "phase": i,
                                "epoch": rec["epoch"] + epoch_off,
                                "sim_time": rec["sim_time"] + t_off})
            phase_recs.append(phase_record(
                i, self.name, phase, steps=res.n_pushes, time_s=res.sim_time,
                t0=t_off,
                metrics=res.history[-1] if res.history else {}))
            t_off += res.sim_time
            epoch_off += max(1, phase.epochs)
            if ckpt_dir:
                save_checkpoint(ckpt_dir, i + 1,
                                {"params": params,
                                 "clock": np.float64(t_off),
                                 "epochs": np.int64(epoch_off)})
        return RunResult(self.name, params, None, t_off, history,
                         phase_recs, resumed)


class SpmdBackend:
    """Compiled SPMD engine backend (the paper's speed path).

    Wraps a ``TrainEngine`` + ``batch_fn`` and runs the schedule one phase
    at a time so the same checkpoint/resume contract as ``PsSimBackend``
    holds at phase boundaries; the engine's compiled-step cache persists
    across phases, so per-phase dispatch adds no recompiles.

    A ``repro.data.DataPlane`` passed as ``batch_fn`` is bound to the full
    schedule up front, and before dispatching each phase the NEXT phase's
    executable is handed to ``TrainEngine.schedule_warm`` — the engine
    AOT-compiles it on a background thread while the current phase trains,
    so phase-at-a-time dispatch keeps the compile overlap a whole-schedule
    ``engine.run`` would have.
    """
    name = "spmd"

    def __init__(self, engine, batch_fn: Callable):
        self.engine = engine
        self.batch_fn = batch_fn

    def run(self, phases: Sequence, params, *, opt_state=None, seed: int = 0,
            ckpt_dir: Optional[str] = None, resume: bool = False,
            log_every: int = 20,
            log_fn: Optional[Callable[[dict], None]] = None) -> RunResult:
        params = _as_tree(params)
        if hasattr(self.batch_fn, "bind"):
            self.batch_fn.bind(phases)
        if opt_state is None:
            opt_state = self.engine.optimizer.init(params)
        like = {"params": params, "opt_state": opt_state}
        start, tree = _restore(ckpt_dir, resume, like)
        resumed = None
        if start is not None:
            params, opt_state = tree["params"], tree["opt_state"]
            resumed = start
        start = start or 0
        gstep = sum(p.n_steps for p in phases[:start])
        samples = sum(p.n_steps * p.batch_size * p.input_size
                      for p in phases[:start])
        history: List[dict] = []
        phase_recs: List[dict] = []
        t_total = 0.0
        for i in range(start, len(phases)):
            phase = phases[i]
            if i + 1 < len(phases) and hasattr(self.engine,
                                               "schedule_warm"):
                # overlap phase i+1's compile with phase i's execution
                self.engine.schedule_warm(phases[i + 1], params, opt_state,
                                          self.batch_fn)
            t0 = time.time()
            with TraceAnnotation("repro.train.phase", phase=i,
                                 seq=phase.input_size,
                                 batch=phase.batch_size,
                                 steps=phase.n_steps):
                params, opt_state, hist = self.engine.run(
                    [phase], params, opt_state, self.batch_fn, seed=seed,
                    start_step=gstep, start_samples=samples,
                    wall_offset=t_total, log_every=log_every, log_fn=log_fn,
                    phase_offset=i)
            dt = time.time() - t0
            for rec in hist:
                history.append({**rec, "phase": i})
            phase_recs.append(phase_record(
                i, self.name, phase, steps=phase.n_steps, time_s=dt,
                t0=t_total,
                metrics={"loss": hist[-1]["loss"]} if hist else {}))
            t_total += dt
            gstep += phase.n_steps
            samples += phase.n_steps * phase.batch_size * phase.input_size
            if ckpt_dir:
                save_checkpoint(ckpt_dir, i + 1,
                                {"params": params, "opt_state": opt_state})
        return RunResult(self.name, params, opt_state, t_total, history,
                         phase_recs, resumed)
