"""Trace-compiled PS simulator: host-side schedule pass + one fused device
scan over the event trace.

The event-driven simulator's timeline is **gradient-independent**: which
worker fires when, at what lr / update factor / batch size, how the sync
policy gates it, where jitter lands and when epoch evaluations fire are all
pure functions of the time models + policy + seed.  The legacy
``simulate()`` interleaves that host-side decision making with one jitted
device dispatch per event — ~0.5 ms of Python/dispatch tax per simulated
iteration that dwarfs the actual math for the CPU-scale models the paper's
accuracy tables run on.

This module splits the simulation into two passes:

  1. **schedule pass** (`schedule_pass`) — the exact event loop
     (``simulator.run_event_loop``) with all device work stripped,
     emitting a dense ``SimTrace``: numpy arrays of per-event
     ``worker_id`` / ``lr`` / ``update_factor`` / ``batch_size`` /
     ``stream_step`` plus epoch-eval markers and the final simulated
     clock.  Because it is the *same* loop, event order is faithful by
     construction.
  2. **execute pass** (`execute_trace`) — one compiled chunk executable
     per power-of-two slice of each eval segment, over pre-staged batch
     chunks, carrying the flat parameter store (``repro.core.flat``) plus
     ONE stacked ``(n_workers, rows, LANE)`` velocity buffer; each event
     runs grad → fused momentum + factor-scaled server push in a single
     ``dbl_apply_worker_flat2d`` kernel launch, with per-event lr /
     factor / wid as traced inputs so one executable serves every event
     of its chunk length.  Chunks default to straight-line unrolled
     bodies (``loop="unroll"``) — on XLA:CPU a backward pass compiled
     into a ``lax.scan`` body picks ~3× slower, bit-shifted conv layouts
     — with ``loop="scan"`` available where loop-body codegen is sound
     (accelerators, matmul-dominated models).

Batches are staged host-side in event order: either through a
``repro.data.DataPlane`` (``plane.trace_feed`` — counter-keyed
``(seed, phase, worker, step)`` streams, ``trace.stream_step`` being
exactly the per-worker counters the event path's ``sim_data_fn`` would
have used) or by calling the legacy ``data_fn(rng, wid, bsz)`` in event
order (reproducing the shared-generator draw sequence draw for draw).
Either way sample selection is bit-identical to the event path, and —
because the per-event float op order matches the legacy jitted update
exactly — so are the final params, history, ``n_pushes`` and ``sim_time``
(asserted across BSP/ASP/SSP with jitter and elastic membership by
``repro.engine.parity.check_trace_parity``).  Two caveats on bit-identity:
it holds for the default ``precision="f32"`` (under ``precision="bf16"``
the carry is the bf16 store + f32 master pair — half the per-candidate
footprint, gradients taken through rounded weights — so bf16 runs are
gated by the TOLERANCE-band parity mode instead; timeline, sample
selection, ``n_pushes`` and ``sim_time`` stay exact either way because
the schedule pass never looks at a gradient), and it assumes the backward
pass itself compiles identically in the chunk graph — true for
matmul-dominated models, but XLA:CPU picks conv-backward algorithms per
graph context at some shapes, which reassociates floats at epsilon level
(~1e-6/step; timeline, sample selection and epoch structure stay exact,
so conv runs are numerically equivalent rather than bit-equal).

The event path remains the right tool when per-event control flow must
*react* to gradients (e.g. loss-adaptive policies) — the trace is only
valid while the timeline stays gradient-independent.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.simulator import SimResult, run_event_loop
from repro.cluster.sync import SyncPolicy, as_policy
from repro.cluster.topology import ClusterEvent, WorkerSpec
from repro.core.flat import flat_spec
from repro.kernels.dbl_merge import (dbl_apply_worker_flat2d,
                                     dbl_apply_worker_xla)


@dataclass(frozen=True)
class SimTrace:
    """The dense, device-free record of one simulated run's timeline.

    Per-event arrays (length ``n_events``, execution order):
      worker_id      which worker fired
      lr             the epoch schedule's rate at that event
      update_factor  the worker's model-update factor (paper §3.4)
      batch_size     the worker's batch size (B_L or B_S)
      stream_step    the worker's own iteration counter at the event — THE
                     ``(seed, phase, worker, step)`` DataPlane stream key,
                     identical to the per-worker counters the event path's
                     ``sim_data_fn`` closures would have advanced

    evals: ``(events_done, epoch, sim_time)`` markers — an epoch eval
    fires after ``events_done`` events have executed.  sim_time /
    n_pushes / n_workers summarize the run (n_workers includes joiners,
    sizing the stacked velocity buffer).
    """
    worker_id: np.ndarray
    lr: np.ndarray
    update_factor: np.ndarray
    batch_size: np.ndarray
    stream_step: np.ndarray
    evals: Tuple[Tuple[int, int, float], ...]
    sim_time: float
    n_pushes: int
    n_workers: int
    sizes: Tuple[int, ...] = field(default=())   # distinct batch sizes

    @property
    def n_events(self) -> int:
        return int(len(self.worker_id))

    def size_class(self) -> np.ndarray:
        """Per-event index into ``sizes`` (the executor's switch branch)."""
        return np.searchsorted(np.asarray(self.sizes),
                               self.batch_size).astype(np.int32)

    def segments(self) -> List[Tuple[int, int, List[Tuple[int, float]]]]:
        """``(e0, e1, fired)`` spans between eval boundaries: events
        [e0, e1) execute, then every ``(epoch, sim_time)`` in ``fired``
        evaluates.  Consecutive evals with no events in between (a slow
        joiner's epochs collapsing) land in one span's ``fired`` list."""
        out: List[Tuple[int, int, List[Tuple[int, float]]]] = []
        e0 = 0
        for done, epoch, t in self.evals:
            if out and out[-1][1] == done:
                out[-1][2].append((epoch, t))
                continue
            out.append((e0, done, [(epoch, t)]))
            e0 = done
        if e0 < self.n_events:
            out.append((e0, self.n_events, []))
        return out


def schedule_pass(workers: Sequence[WorkerSpec], *, epochs: int,
                  lr_for_epoch: Callable[[int], float],
                  sync: Union[str, SyncPolicy] = "asp", staleness: int = 3,
                  seed: int = 0,
                  events: Sequence[ClusterEvent] = ()) -> SimTrace:
    """Run the event loop with all device work stripped -> ``SimTrace``.

    Same loop, same jitter streams, same membership handling as
    ``simulate()`` — the hooks record instead of dispatching, so the trace
    replays the device path's event order faithfully by construction.
    """
    policy = as_policy(sync, staleness)
    wid_l: List[int] = []
    lr_l: List[float] = []
    fac_l: List[float] = []
    bsz_l: List[int] = []
    step_l: List[int] = []
    counters: dict = {}
    evals: List[Tuple[int, int, float]] = []

    def execute(wid: int, w: WorkerSpec, lr: float):
        t = counters.get(wid, 0)
        counters[wid] = t + 1
        wid_l.append(wid)
        lr_l.append(float(lr))
        fac_l.append(float(w.update_factor))
        bsz_l.append(int(w.batch_size))
        step_l.append(t)

    def evaluate(epoch: int, now: float):
        evals.append((len(wid_l), epoch, now))

    n_workers = {"n": len(workers)}

    def on_join(wid: int, spec: WorkerSpec):
        n_workers["n"] = max(n_workers["n"], wid + 1)

    sim_time, n_pushes = run_event_loop(
        workers, epochs=epochs, lr_for_epoch=lr_for_epoch, policy=policy,
        seed=seed, events=events, execute=execute, evaluate=evaluate,
        on_join=on_join)
    return SimTrace(
        worker_id=np.asarray(wid_l, np.int32),
        lr=np.asarray(lr_l, np.float32),
        update_factor=np.asarray(fac_l, np.float32),
        batch_size=np.asarray(bsz_l, np.int32),
        stream_step=np.asarray(step_l, np.int32),
        evals=tuple(evals), sim_time=sim_time, n_pushes=n_pushes,
        n_workers=n_workers["n"],
        sizes=tuple(sorted(set(bsz_l))) if bsz_l else ())


# --------------------------------------------------------------------------
# batch staging: event-order feeds
# --------------------------------------------------------------------------
def stack_event_batches(batches: List, b_max: int):
    """Stack per-event host batches (any pytree whose leaves lead with the
    batch axis — the ``data_fn`` contract) along a new leading axis,
    padding each to ``b_max`` rows (the executor's switch branch slices
    back to the event's true batch size, so pad content is never read)."""
    def stack(*arrs):
        arrs = [np.asarray(a) for a in arrs]
        buf = np.zeros((len(arrs), b_max) + arrs[0].shape[1:],
                       arrs[0].dtype)
        for i, a in enumerate(arrs):
            buf[i, :a.shape[0]] = a
        return buf
    return jax.tree_util.tree_map(stack, *batches)


def data_fn_feed(data_fn: Callable, seed: int, *, prefetch: bool = True):
    """Event-order staging from the legacy ``data_fn(rng, wid, bsz)``
    contract: ONE shared generator seeded like ``simulate()``'s, drawn in
    event order across chunk boundaries — so the staged samples are
    draw-for-draw the ones the event path would have consumed.  With
    ``prefetch`` the next chunk stages on a background thread while the
    compiled scan runs the current one (a single-worker pool keeps the
    draw order sequential)."""
    def feed(trace: SimTrace, ranges: Sequence[Tuple[int, int]]):
        rng = np.random.Generator(np.random.PCG64(seed))
        b_max = int(trace.sizes[-1]) if trace.sizes else 1

        def stage(e0: int, e1: int):
            batches = [data_fn(rng, int(trace.worker_id[e]),
                               int(trace.batch_size[e]))
                       for e in range(e0, e1)]
            return jax.device_put(stack_event_batches(batches, b_max))

        from repro.data.plane import prefetch_iter
        steps = lambda r: r[1] - r[0]
        if not prefetch or len(ranges) <= 1:
            yield from prefetch_iter(stage, ranges, None, steps=steps)
            return
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="trace-feed") as ex:
            yield from prefetch_iter(stage, ranges, ex, steps=steps)
    return feed


# --------------------------------------------------------------------------
# the execute pass
# --------------------------------------------------------------------------
# compiled chunk scans cached weakly on grad_fn identity (like the
# simulator's local-update cache): a schedule revisiting the same grad_fn
# (every phase at a given input size, every simulate_traced call) reuses
# the traced scan instead of rebuilding it; jax.jit handles per-shape
# (chunk length, batch struct, worker count) specialization underneath
_TRACE_SCANS: "weakref.WeakKeyDictionary[Callable, dict]" = \
    weakref.WeakKeyDictionary()


def resolve_update(update: str) -> str:
    """``"auto"`` -> the Pallas kernel on TPU (one fused launch per event,
    in-place row scatter), plain XLA elementwise updates elsewhere — the
    same policy the engine applies to its fused kernels: interpret-mode
    Pallas is a semantics fallback, not a fast path, and off-TPU the
    handful of fused elementwise ops compiles leaner."""
    if update != "auto":
        return update
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _make_event(ref: Callable, spec, sizes: Tuple[int, ...], update: str,
                interpret: Optional[bool]):
    """One simulated-PS event as a pure function of the scan carry:
    grad at the event's (padded) batch, then the fused momentum +
    factor-scaled server push.  Shared verbatim by the sequential chunk
    runner and the batched candidate runner (which vmaps it), so the two
    replay paths cannot drift apart in float op order.

    On a bf16 spec the param carry is the ``(shadow, master)`` pair:
    gradients differentiate through the bf16 shadow (``unravel`` upcasts,
    so only stored weights are rounded) but stay f32 all the way to the
    update (``ravel_master`` shares the geometry) — the master consumes
    them unrounded and no emulated-bf16 elementwise path appears in the
    replay; the fused update writes the f32 master and its re-rounded
    shadow in the same sweep."""
    mixed = spec.store_dtype != jnp.dtype(jnp.float32)

    def event(p2c, vel, b, w, l, f, s, momentum):
        shadow = p2c[0] if mixed else p2c

        def grad_at(k, b):
            # slice the padded event batch back to its true size: each
            # switch branch is shape-static, and the branch taken sees
            # exactly the samples the event path's data_fn handed out
            bk = jax.tree_util.tree_map(lambda v: v[:sizes[k]], b)
            g = ref()(spec.unravel(shadow), bk)
            return spec.ravel_master(g) if mixed else spec.ravel(g)

        if len(sizes) == 1:
            g2 = grad_at(0, b)
        else:
            g2 = jax.lax.switch(
                s, [lambda b, k=k: grad_at(k, b)
                    for k in range(len(sizes))], b)
        if mixed:
            master = p2c[1]
            if update == "pallas":
                sh, ma, vel = dbl_apply_worker_flat2d(
                    shadow, g2, vel, w, l, f, momentum, master2=master,
                    interpret=interpret)
            else:
                sh, ma, vel = dbl_apply_worker_xla(
                    shadow, g2, vel, w, l, f, momentum, master2=master)
            return (sh, ma), vel
        if update == "pallas":
            return dbl_apply_worker_flat2d(p2c, g2, vel, w, l, f, momentum,
                                           interpret=interpret)
        # XLA form of the same update (see dbl_apply_worker_xla): float op
        # order identical to the kernel and to the event path's jitted
        # local_update (bit-parity); the dynamic-update-slice runs in
        # place on the donated buffer.
        return dbl_apply_worker_xla(p2c, g2, vel, w, l, f, momentum)
    return event


def _build_chunk_runner(grad_fn: Callable, spec, sizes: Tuple[int, ...],
                        interpret: Optional[bool], loop: str, update: str,
                        weak: bool = True):
    # hold grad_fn weakly when the runner lives in the weak-keyed cache: a
    # closure holding its own cache key strongly would pin the entry (and
    # its compiled executable) forever — same discipline as
    # simulator._build_local_update.  Re-traces only happen through
    # trace_runner_for, whose caller holds grad_fn, so the ref stays live
    # whenever it is dereferenced.
    ref = weakref.ref(grad_fn) if weak else (lambda: grad_fn)
    event = _make_event(ref, spec, sizes, update, interpret)

    if loop == "scan":
        def run_chunk(p2, vel3, batches, wid, lr, factor, sc, momentum):
            def body(carry, xs):
                b, w, l, f, s = xs
                return event(*carry, b, w, l, f, s, momentum), ()
            (p2, vel3), _ = jax.lax.scan(body, (p2, vel3),
                                         (batches, wid, lr, factor, sc))
            return p2, vel3
    else:
        # straight-line chunk: the Python loop unrolls at trace time, so
        # every event's backward compiles in straight-line position — on
        # XLA:CPU a conv backward inside a while-loop body picks different
        # (and ~3x slower, bit-shifted) layouts than the same backward
        # compiled straight-line, which is exactly the form the event
        # path's per-event jit uses.  Chunk lengths are powers of two
        # (``_chunk_ranges``), bounding distinct executables at
        # log2(scan_chunk) per grad_fn.
        def run_chunk(p2, vel3, batches, wid, lr, factor, sc, momentum):
            for e in range(wid.shape[0]):
                b = jax.tree_util.tree_map(lambda v: v[e], batches)
                p2, vel3 = event(p2, vel3, b, wid[e], lr[e], factor[e],
                                 sc[e], momentum)
            return p2, vel3
    return jax.jit(run_chunk, donate_argnums=(0, 1))


def trace_runner_for(grad_fn: Callable, spec, sizes: Tuple[int, ...],
                     interpret: Optional[bool], loop: str = "unroll",
                     update: str = "auto"):
    """The (cached) compiled chunk runner for ``grad_fn`` under one codec
    spec / batch-size set — weak on grad_fn so dropping it frees the
    executable, mirroring ``simulator.local_update_for``."""
    update = resolve_update(update)
    key = (id(spec), sizes, interpret, loop, update)
    try:
        per_fn = _TRACE_SCANS.get(grad_fn)
    except TypeError:                       # unhashable grad_fn
        return _build_chunk_runner(grad_fn, spec, sizes, interpret, loop,
                                   update, weak=False)
    if per_fn is None:
        per_fn = {}
        try:
            _TRACE_SCANS[grad_fn] = per_fn
        except TypeError:                   # unweakrefable grad_fn
            return _build_chunk_runner(grad_fn, spec, sizes, interpret,
                                       loop, update, weak=False)
    if key not in per_fn:
        per_fn[key] = _build_chunk_runner(grad_fn, spec, sizes, interpret,
                                          loop, update)
    return per_fn[key]


def trace_scan_cache_size() -> int:
    return sum(len(d) for d in _TRACE_SCANS.values())


# --------------------------------------------------------------------------
# batched candidate replay (the autotuner's sweep executor)
# --------------------------------------------------------------------------
def trace_signature(trace: SimTrace) -> tuple:
    """Everything that must match for two traces to share one compiled
    batched replay: worker/batch/stream timeline, eval markers, sizes and
    worker count.  Per-event lr / update_factor are NOT in the signature —
    they are traced operands of the chunk executable, which is exactly
    what lets factor / LR-schedule / seed candidates replay together."""
    return (trace.n_workers, trace.sizes, trace.evals,
            trace.worker_id.tobytes(), trace.batch_size.tobytes(),
            trace.stream_step.tobytes())


def _build_batched_runner(grad_fn: Callable, spec, sizes: Tuple[int, ...],
                          interpret: Optional[bool], loop: str,
                          per_cand_data: bool, weak: bool = True):
    """One compiled chunk executable over a stacked candidate axis: params
    ``(C, rows, LANE)``, velocities ``(C, n_workers, rows, LANE)``, per
    event lr/factor ``(C,)`` — the same ``_make_event`` body as the
    sequential runner, vmapped.  The update form is always the XLA
    elementwise one (``dbl_apply_worker_xla``): it vmaps to clean batched
    HLO with the identical float op order, while a vmapped interpret-mode
    ``pallas_call`` would only multiply emulation overhead.
    ``per_cand_data`` selects whether event batches carry a candidate
    axis (independent data streams) or are broadcast (shared data)."""
    ref = weakref.ref(grad_fn) if weak else (lambda: grad_fn)
    event = _make_event(ref, spec, sizes, "xla", interpret)
    # in_axes: params/velocity/lr/factor/momentum per candidate; wid and
    # size class are timeline facts shared by signature
    vevent = jax.vmap(event, in_axes=(0, 0, 0 if per_cand_data else None,
                                      None, 0, 0, None, 0))

    if loop == "scan":
        def run_chunk(pC, velC, batches, wid, lrC, facC, sc, momC):
            bt = jax.tree_util.tree_map(
                lambda v: jnp.moveaxis(v, 1, 0) if per_cand_data else v,
                batches)

            def body(carry, xs):
                b, w, l, f, s = xs
                return vevent(*carry, b, w, l, f, s, momC), ()
            (pC, velC), _ = jax.lax.scan(
                body, (pC, velC), (bt, wid, lrC.T, facC.T, sc))
            return pC, velC
    else:
        def run_chunk(pC, velC, batches, wid, lrC, facC, sc, momC):
            for e in range(wid.shape[0]):
                b = jax.tree_util.tree_map(
                    lambda v: v[:, e] if per_cand_data else v[e], batches)
                pC, velC = vevent(pC, velC, b, wid[e], lrC[:, e],
                                  facC[:, e], sc[e], momC)
            return pC, velC
    return jax.jit(run_chunk, donate_argnums=(0, 1))


def batched_trace_runner_for(grad_fn: Callable, spec,
                             sizes: Tuple[int, ...],
                             interpret: Optional[bool], loop: str,
                             per_cand_data: bool):
    """Cached batched chunk runner — same weak-keyed cache as the
    sequential runners (one ``"batched"``-tagged key per configuration);
    jit specializes per candidate count underneath."""
    key = (id(spec), sizes, interpret, loop, "batched", per_cand_data)
    try:
        per_fn = _TRACE_SCANS.get(grad_fn)
    except TypeError:
        return _build_batched_runner(grad_fn, spec, sizes, interpret, loop,
                                     per_cand_data, weak=False)
    if per_fn is None:
        per_fn = {}
        try:
            _TRACE_SCANS[grad_fn] = per_fn
        except TypeError:
            return _build_batched_runner(grad_fn, spec, sizes, interpret,
                                         loop, per_cand_data, weak=False)
    if key not in per_fn:
        per_fn[key] = _build_batched_runner(grad_fn, spec, sizes, interpret,
                                            loop, per_cand_data)
    return per_fn[key]


def _zip_feeds(feeds, trace: SimTrace, ranges):
    """Zip per-candidate event-order feeds into candidate-stacked chunks:
    each candidate's staged ``(chunk, b_max, ...)`` leaves gain a leading
    candidate axis.  Every underlying feed keeps its own prefetch thread,
    so staging overlaps the compiled chunk exactly as in the sequential
    path — once, per candidate stream."""
    iters = [iter(f(trace, ranges)) for f in feeds]
    for _ in ranges:
        staged = [next(it) for it in iters]
        yield jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *staged)


def execute_trace_batched(init_params_list, grad_fn: Callable,
                          traces: Sequence[SimTrace], *,
                          feed=None, feeds=None, data_fn=None,
                          momentum=0.9, eval_fn: Optional[Callable] = None,
                          eval_fns: Optional[Sequence[Callable]] = None,
                          seed: int = 0, scan_chunk: int = 32,
                          interpret: Optional[bool] = None,
                          prefetch: bool = True, loop: str = "unroll",
                          precision: str = "f32") -> List[SimResult]:
    """Replay MANY same-timeline traces as ONE stacked device run.

    All traces must share a ``trace_signature`` (same worker/batch/stream
    timeline, evals, sizes — candidates may differ in per-event lr,
    update factor, momentum, initial params and data).  Candidate state is
    stacked along a leading axis — params ``(C, rows, LANE)``, velocities
    ``(C, n_workers, rows, LANE)`` — and each chunk executes as one
    compiled vmapped call, so C candidates cost one dispatch sequence and
    one staging pass instead of C: the autotuner's per-candidate replay
    cost drops well below a single sequential ``execute_trace``.

    Data: ``feed`` (one event-order feed shared by every candidate — the
    factor/LR-sweep case, where sample streams are identical) or
    ``feeds`` (one per candidate — the multi-seed case; staged chunks are
    stacked along the candidate axis) or a legacy shared ``data_fn``.
    Evals: ``eval_fn`` applied to every candidate, or per-candidate
    ``eval_fns``.  ``momentum`` may be a scalar or a per-candidate
    sequence.

    The update form is the XLA-elementwise ``dbl_apply_worker_xla`` under
    ``jax.vmap`` — identical float op order to the sequential replay, so
    for f32 params each candidate's result is bit-identical to its own
    ``execute_trace`` run (asserted by tests/test_tune.py).
    ``precision="bf16"`` stacks a bf16 shadow AND an f32 master per
    candidate (evals/final params read the master).
    Returns one ``SimResult`` per candidate, in input order.
    """
    traces = list(traces)
    if not traces:
        return []
    if len(init_params_list) != len(traces):
        raise ValueError(f"{len(init_params_list)} init params for "
                         f"{len(traces)} traces")
    sig0 = trace_signature(traces[0])
    for i, t in enumerate(traces[1:], 1):
        if trace_signature(t) != sig0:
            raise ValueError(
                f"trace {i} has a different signature (timeline/evals/"
                "sizes) — batched replay shares ONE compiled chunk "
                "executable, so candidates must share the event timeline; "
                "group by trace_signature() and replay groups separately")
    trace = traces[0]
    n_cand = len(traces)
    if feeds is not None and len(feeds) != n_cand:
        raise ValueError(f"{len(feeds)} feeds for {n_cand} traces")
    if feed is None and feeds is None:
        if data_fn is None:
            raise ValueError("execute_trace_batched needs feed, feeds or "
                             "a data_fn")
        feed = data_fn_feed(data_fn, seed, prefetch=prefetch)
    mixed = precision != "f32"
    spec = (flat_spec(init_params_list[0], jnp.bfloat16) if mixed
            else flat_spec(init_params_list[0]))
    shC = jnp.stack([spec.ravel_jit(p) for p in init_params_list])
    pC = ((shC, jnp.stack([spec.ravel_master_jit(p)
                           for p in init_params_list]))
          if mixed else shC)
    velC = spec.zeros_candidates(n_cand, max(1, trace.n_workers))
    lrC = jnp.asarray(np.stack([t.lr for t in traces]))
    facC = jnp.asarray(np.stack([t.update_factor for t in traces]))
    momC = jnp.broadcast_to(
        jnp.asarray(momentum, jnp.float32), (n_cand,))
    if eval_fns is None and eval_fn is not None:
        eval_fns = [eval_fn] * n_cand
    histories: List[List[dict]] = [[] for _ in range(n_cand)]

    def fire(fired):
        buf = pC[1] if mixed else pC         # evals read the f32 master
        for epoch, t in fired:
            for i in range(n_cand):
                rec = {"epoch": epoch, "sim_time": t}
                if eval_fns is not None:
                    rec.update(eval_fns[i](spec.unravel_jit(buf[i])))
                histories[i].append(rec)

    ranges = _chunk_ranges(trace, scan_chunk)
    if ranges:
        run = batched_trace_runner_for(grad_fn, spec, trace.sizes,
                                       interpret, loop,
                                       per_cand_data=feeds is not None)
        sc = trace.size_class()
        chunks = (_zip_feeds(feeds, trace, ranges) if feeds is not None
                  else feed(trace, ranges))
        seg_iter = iter(trace.segments())
        seg = next(seg_iter)
        for (e0, e1), batches in zip(ranges, chunks):
            ev = slice(e0, e1)
            pC, velC = run(pC, velC, batches,
                           jnp.asarray(trace.worker_id[ev]),
                           lrC[:, ev], facC[:, ev],
                           jnp.asarray(sc[ev]), momC)
            while seg is not None and e1 >= seg[1]:
                fire(seg[2])
                seg = next(seg_iter, None)
        while seg is not None:
            fire(seg[2])
            seg = next(seg_iter, None)
    else:
        for _, _, fired in trace.segments():
            fire(fired)
    buf = pC[1] if mixed else pC
    return [SimResult(sim_time=traces[i].sim_time, history=histories[i],
                      params=spec.unravel_jit(buf[i]),
                      n_pushes=traces[i].n_pushes)
            for i in range(n_cand)]


def _chunk_ranges(trace: SimTrace, scan_chunk: int):
    """(e0, e1) chunk spans: eval segments split into power-of-two pieces
    <= scan_chunk (eval boundaries must align with chunk boundaries — the
    executor leaves the device only to evaluate).  Powers of two bound the
    set of distinct chunk lengths — and therefore compiled executables —
    at log2(scan_chunk) + 1 per grad_fn, however ragged the segments."""
    cap = 1
    while cap * 2 <= max(1, scan_chunk):
        cap *= 2
    ranges = []
    for e0, e1, _fired in trace.segments():
        g = e0
        while g < e1:
            c = cap
            while c > e1 - g:
                c //= 2
            ranges.append((g, g + c))
            g += c
    return ranges


def execute_trace(init_params, grad_fn: Callable, trace: SimTrace, *,
                  data_fn: Optional[Callable] = None,
                  feed=None, momentum: float = 0.9,
                  eval_fn: Optional[Callable] = None, seed: int = 0,
                  scan_chunk: int = 32, interpret: Optional[bool] = None,
                  prefetch: bool = True, loop: str = "unroll",
                  update: str = "auto",
                  precision: str = "f32") -> SimResult:
    """Replay a ``SimTrace`` on device as fused chunk executables.

    Carries ``(flat params, stacked velocity)`` through one compiled call
    per chunk (power-of-two lengths bounded by ``scan_chunk`` and eval
    boundaries), leaving the device only at epoch evals — the per-event
    Python/dispatch tax of the legacy path collapses into a handful of
    chunk launches.  ``loop`` picks the chunk body: ``"unroll"`` (default)
    compiles the chunk straight-line, which is what keeps XLA:CPU conv
    backwards at full speed and bit-identical to the event path's
    straight-line jit; ``"scan"`` rolls the chunk into one
    ``jax.lax.scan`` — constant compile cost for long chunks (the right
    trade on accelerators), but loop-body codegen may reassociate CPU
    convs.  Batches come from ``feed(trace, ranges)`` (e.g. a
    ``DataPlane.trace_feed`` binding) or, when only a legacy ``data_fn``
    is given, from ``data_fn_feed`` (event-order draws from one shared
    generator, exactly like ``simulate()``).  ``update`` picks the fused
    per-event server update: the ``dbl_apply_worker_flat2d`` Pallas kernel
    (``"pallas"`` — the accelerator path) or the same math as XLA
    elementwise ops (``"xla"`` — leaner off-TPU, where interpret-mode
    Pallas is emulation overhead); ``"auto"`` resolves by backend.  All
    forms share one float op order, so the choice never moves a bit.
    ``precision="bf16"`` carries the bf16 store + f32 master pair instead
    (half the param-carry bytes; evals and final params read the master).
    """
    if feed is None:
        if data_fn is None:
            raise ValueError("execute_trace needs a feed or a data_fn")
        feed = data_fn_feed(data_fn, seed, prefetch=prefetch)
    mixed = precision != "f32"
    spec = (flat_spec(init_params, jnp.bfloat16) if mixed
            else flat_spec(init_params))
    p2 = ((spec.ravel_jit(init_params), spec.ravel_master_jit(init_params))
          if mixed else spec.ravel_jit(init_params))
    vel3 = spec.zeros_stacked(max(1, trace.n_workers))
    history: List[dict] = []

    def fire(fired):
        for epoch, t in fired:
            rec = {"epoch": epoch, "sim_time": t}
            if eval_fn is not None:
                buf = p2[1] if mixed else p2
                rec.update(eval_fn(spec.unravel_jit(buf)))
            history.append(rec)

    ranges = _chunk_ranges(trace, scan_chunk)
    if ranges:
        run = trace_runner_for(grad_fn, spec, trace.sizes, interpret, loop,
                               update)
        sc = trace.size_class()
        chunks = feed(trace, ranges)
        seg_iter = iter(trace.segments())
        seg = next(seg_iter)
        for (e0, e1), batches in zip(ranges, chunks):
            ev = slice(e0, e1)
            p2, vel3 = run(p2, vel3, batches,
                           jnp.asarray(trace.worker_id[ev]),
                           jnp.asarray(trace.lr[ev]),
                           jnp.asarray(trace.update_factor[ev]),
                           jnp.asarray(sc[ev]),
                           jnp.float32(momentum))
            while seg is not None and e1 >= seg[1]:
                fire(seg[2])
                seg = next(seg_iter, None)
        while seg is not None:              # trailing zero-event segments
            fire(seg[2])
            seg = next(seg_iter, None)
    else:
        for _, _, fired in trace.segments():
            fire(fired)
    return SimResult(sim_time=trace.sim_time, history=history,
                     params=spec.unravel_jit(p2[1] if mixed else p2),
                     n_pushes=trace.n_pushes)


def simulate_traced(init_params, grad_fn: Callable,
                    data_fn: Optional[Callable],
                    workers: Sequence[WorkerSpec], *, epochs: int,
                    lr_for_epoch: Callable[[int], float],
                    sync: Union[str, SyncPolicy] = "asp",
                    staleness: int = 3, momentum: float = 0.9,
                    eval_fn: Optional[Callable] = None, seed: int = 0,
                    events: Sequence[ClusterEvent] = (), feed=None,
                    scan_chunk: int = 32,
                    interpret: Optional[bool] = None,
                    prefetch: bool = True, loop: str = "unroll",
                    update: str = "auto",
                    precision: str = "f32") -> SimResult:
    """Drop-in ``simulate()`` replacement on the trace-compiled path:
    schedule pass (host) + execute pass (fused device scans).  Same
    arguments, same ``SimResult`` — bit-identical to the event path for
    f32 params (``engine.parity.check_trace_parity``); under
    ``precision="bf16"`` the replay carries the bf16 store + f32 master
    pair and matches the event path within the documented tolerance band
    instead."""
    trace = schedule_pass(workers, epochs=epochs,
                          lr_for_epoch=lr_for_epoch, sync=sync,
                          staleness=staleness, seed=seed, events=events)
    return execute_trace(init_params, grad_fn, trace, data_fn=data_fn,
                         feed=feed, momentum=momentum, eval_fn=eval_fn,
                         seed=seed, scan_chunk=scan_chunk,
                         interpret=interpret, prefetch=prefetch, loop=loop,
                         update=update, precision=precision)
