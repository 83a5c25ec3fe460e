"""The DataPlane: ONE resolution-aware input pipeline for both backends.

Before this subsystem the repo had three divergent data paths — the
``data/pipeline`` index math, the PS simulator's
``data_fn(np.random.Generator, wid, bsz)`` closures, and the engine's
host-stacked scan chunks — each re-implementing sampling, resolution
resizing and device staging.  The ``DataPlane`` subsumes all three behind
one object:

  * **canonical sample streams** — every batch is drawn from
    ``pipeline.stream_indices``, keyed on ``(seed, phase, worker, step)``
    and therefore identical between the event-driven PS simulator (draws
    in event order) and the SPMD engine (draws in global-step order)
    whenever both sides request the same per-worker batch size at the
    same ``(phase, worker, step)``.  In the canonical dual-batch geometry
    — worker rows padded to B_L width, i.e. ``global_batch = n_workers ·
    B_L`` so ``per_worker == B_L`` and ``small_valid == B_S`` — worker
    *w*'s *t*-th batch IS the same samples on both backends (asserted
    against the simulator's real ``WorkerSpec`` batch sizes by
    ``repro.engine.parity.check_data_plane_parity``); under a narrower
    SPMD batch the engine consumes a per-worker subset of the same
    stream family;
  * **resolution awareness** — batches materialize host-side at each
    ``Phase.input_size`` (images resize bilinearly, token walks crop to a
    prefix), with ``core.progressive.adapt_batch`` sizing the phase batch
    so the accelerator stays saturated across the cyclic schedule;
  * **double-buffered scan feed** — ``scan_feed`` stages the NEXT chunk
    (host stack + ``jax.device_put``) on a background thread while the
    engine's compiled scan runs the current one, so the hot loop never
    waits on host-side resize/stack;
  * **warm-compile structs** — ``batch_struct`` hands the engine abstract
    ``ShapeDtypeStruct``s for any phase WITHOUT materializing data, which
    is what lets the engine AOT-lower/compile phase *k+1* while phase *k*
    executes (``TrainEngine(overlap_compile=True)``).

Contracts served:

    plane(phase, gstep)            -> batch dict   (engine ``batch_fn``)
    plane.sim_data_fn(i, phase)    -> data_fn      (PS-sim contract)
    plane.scan_feed(phase, g0, n, chunk)           (engine scan path)
    plane.batch_struct(phase[, stacked])           (overlap compile)

``bind(phases)`` pins the schedule so a ``Phase`` object resolves to its
index (and absolute start step); both cluster backends bind automatically.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.data.pipeline import stream_indices


def prefetch_iter(stage, items, executor=None, *, steps):
    """Double-buffered staging: yield ``stage(*item)`` for each item, with
    the NEXT item staged on ``executor`` (a single-worker pool — FIFO, so
    stateful stages keep their call order) while the caller consumes the
    current one.  ``executor=None`` stages synchronously.  The one shared
    prefetch loop behind ``DataPlane.scan_feed`` / ``DataPlane.trace_feed``
    / ``repro.cluster.trace.data_fn_feed``; cancels the in-flight future
    if the consumer abandons the iterator early.

    Where the consumer blocks on an item (the wait for the staging thread,
    or the synchronous stage itself) is a ``repro.data.wait`` span whose
    ``steps`` counter is ``steps(item)``, the steps the item stages."""
    items = list(items)
    wait = lambda it: TraceAnnotation("repro.data.wait", steps=steps(it))
    if executor is None or len(items) <= 1:
        for it in items:
            with wait(it):
                staged = stage(*it)
            yield staged
        return
    fut = executor.submit(stage, *items[0])
    try:
        for i in range(len(items)):
            with wait(items[i]):
                staged = fut.result()
            fut = (executor.submit(stage, *items[i + 1])
                   if i + 1 < len(items) else None)
            yield staged
    finally:
        if fut is not None:
            fut.cancel()


class DataPlane:
    """One input pipeline for every backend (see module docstring).

    source: anything speaking the source contract — ``len(source)``,
      ``batch_at(indices, input_size)``, ``struct(batch, input_size)``
      (``repro.data.synthetic`` datasets do).
    seed: stream seed; per-phase streams depend only on ``(seed, phase
      index)``, so a phase-boundary resume replays the uninterrupted run.
    prefetch: double-buffer ``scan_feed`` chunks on a background thread
      (False = stage synchronously; determinism is identical either way).
    """

    def __init__(self, source, *, seed: int = 0, prefetch: bool = True):
        self.source = source
        self.seed = int(seed)
        self.prefetch = bool(prefetch)
        self._phases: Optional[Tuple] = None
        self._starts: Tuple[int, ...] = ()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()

    # -- schedule binding ------------------------------------------------
    def bind(self, phases: Sequence) -> "DataPlane":
        """Pin the phase list so ``Phase`` objects resolve to stream
        indices/start steps.  Called by the backends; idempotent."""
        phases = tuple(phases)
        starts, ofs = [], 0
        for p in phases:
            starts.append(ofs)
            ofs += p.n_steps
        self._phases = phases
        self._starts = tuple(starts)
        return self

    @property
    def bound(self) -> bool:
        return self._phases is not None

    def _locate(self, phase) -> Tuple[int, int]:
        """(phase index, absolute start step) for ``phase``.  Identity
        wins; the equality fallback (for reconstructed Phase objects, e.g.
        after a checkpoint restore) refuses ambiguous matches — a cyclic
        schedule may legitimately contain equal phases, and silently
        serving the first one's stream would replay its samples."""
        if self._phases is None:
            return 0, 0
        for i, p in enumerate(self._phases):
            if p is phase:
                return i, self._starts[i]
        eq = [i for i, p in enumerate(self._phases) if p == phase]
        if len(eq) == 1:
            return eq[0], self._starts[eq[0]]
        if eq:
            raise ValueError(
                f"phase equals schedule entries {eq} — ambiguous; pass the "
                "bound Phase object itself (identity) to disambiguate")
        raise ValueError("phase not in the bound schedule — rebind the "
                         "DataPlane with the phase list it is serving")

    # -- canonical streams ----------------------------------------------
    def worker_rows(self, phase):
        """Per worker-row block of the global padded batch:
        ``(wid, valid, rows)`` — ``valid`` samples drawn from the worker's
        stream, padded to ``rows`` (padding repeats the last valid sample;
        those rows carry weight 0 / are never indexed by the fused step)."""
        layout = phase.layout
        if layout is None:
            return [(0, phase.batch_size, phase.batch_size)]
        pw = layout.per_worker
        n_large = layout.n_workers - layout.n_small
        return [(w, pw if w < n_large else max(1, layout.small_valid), pw)
                for w in range(layout.n_workers)]

    def worker_indices(self, phase_idx: int, wid: int, step: int,
                       n: int) -> np.ndarray:
        """Worker ``wid``'s ``step``-th draw of ``n`` sample indices in
        phase ``phase_idx`` — THE canonical stream both backends consume."""
        return stream_indices(len(self.source), n, seed=self.seed,
                              phase=phase_idx, wid=wid, step=step)

    def global_indices(self, phase, local_step: int) -> np.ndarray:
        """The SPMD global batch's sample indices at phase-local step
        ``local_step``: per-worker draws concatenated in worker order."""
        pi, _ = self._locate(phase)
        parts = []
        for w, valid, rows in self.worker_rows(phase):
            idx = self.worker_indices(pi, w, local_step, valid)
            if rows > valid:
                idx = np.concatenate(
                    [idx, np.repeat(idx[-1], rows - valid)])
            parts.append(idx)
        return np.concatenate(parts)

    # -- engine batch_fn contract ----------------------------------------
    def __call__(self, phase, gstep: int) -> dict:
        """batch_fn(phase, global_step) -> host batch dict at the phase's
        input size.  Stateless in ``gstep`` (streams are counter-keyed),
        so resumed runs replay the uninterrupted stream exactly."""
        pi, start = self._locate(phase)
        idx = self.global_indices(phase, gstep - start)
        return self.source.batch_at(idx, phase.input_size)

    def batch_struct(self, phase, stacked: Optional[int] = None) -> dict:
        """Abstract batch structure for ``phase`` (leading ``stacked``
        steps axis when given) — no data materialized; feeds the engine's
        overlapped next-phase warm-compile."""
        import jax
        out = {}
        for k, (shape, dt) in self.source.struct(phase.batch_size,
                                                 phase.input_size).items():
            full = ((stacked,) + tuple(shape)) if stacked else tuple(shape)
            out[k] = jax.ShapeDtypeStruct(full, dt)
        return out

    # -- PS-sim contract --------------------------------------------------
    def sim_data_fn(self, phase_idx: int, phase):
        """``data_fn(rng, wid, bsz)`` for one simulator phase.  Ignores the
        simulator's shared rng: draws come from the per-worker counter
        stream instead, so the sample sequence is independent of event
        interleaving — and identical to the SPMD side's worker rows when
        the geometries align (``bsz`` equals the row's valid count; see
        the module docstring)."""
        import jax.numpy as jnp
        counters: dict = {}

        def data_fn(rng, wid, bsz):
            t = counters.get(wid, 0)
            counters[wid] = t + 1
            idx = self.worker_indices(phase_idx, wid, t, bsz)
            b = self.source.batch_at(idx, phase.input_size)
            return {k: jnp.asarray(v) for k, v in b.items()}
        return data_fn

    # -- trace-compiled PS simulator feed ---------------------------------
    def trace_feed(self, phase_idx: int, phase, *,
                   prefetch: Optional[bool] = None):
        """``feed(trace, ranges)`` for ``repro.cluster.trace``'s execute
        pass: stages each event range of a ``SimTrace`` from the canonical
        per-``(seed, phase, worker, step)`` streams — ``trace.stream_step``
        holds exactly the per-worker counters the event path's
        ``sim_data_fn`` closures would have advanced, so sample selection
        is bit-identical to the event-driven run.  Each chunk is
        host-stacked (padded to the largest event batch) and shipped as one
        ``device_put``; with prefetch the next range stages on the
        background thread while the compiled chunk executes."""
        use_prefetch = self.prefetch if prefetch is None else bool(prefetch)

        def feed(trace, ranges):
            import jax
            from repro.cluster.trace import stack_event_batches
            b_max = int(max(trace.sizes)) if trace.sizes else 1

            def stage(e0: int, e1: int):
                batches = [
                    self.source.batch_at(
                        self.worker_indices(phase_idx,
                                            int(trace.worker_id[e]),
                                            int(trace.stream_step[e]),
                                            int(trace.batch_size[e])),
                        phase.input_size)
                    for e in range(e0, e1)]
                return jax.device_put(stack_event_batches(batches, b_max))

            yield from prefetch_iter(
                stage, ranges,
                self._executor() if use_prefetch else None,
                steps=lambda r: r[1] - r[0])
        return feed

    # -- double-buffered scan feed ----------------------------------------
    def _executor(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="dataplane-prefetch")
            return self._pool

    def _stage_chunk(self, phase, g0: int, c: int):
        """Host-build + stack ``c`` consecutive batches and start their
        device upload (one ``device_put`` per key, no device round trip)."""
        import jax
        with TraceAnnotation("repro.data.stage", phase=self._locate(phase)[0],
                             step=g0, steps=c):
            batches = [self(phase, g0 + j) for j in range(c)]
            stacked = {k: np.stack([b[k] for b in batches])
                       for k in batches[0]}
            return jax.device_put(stacked)

    def scan_feed(self, phase, start: int, n_steps: int,
                  chunk: int) -> Iterator[Tuple[int, dict]]:
        """Yield ``(c, device_batches)`` chunks covering ``n_steps`` steps
        from absolute step ``start``.  With ``prefetch`` the next chunk is
        staged on the background thread while the caller's compiled scan
        consumes the current one — the double buffer."""
        items, g0, rem = [], start, n_steps
        while rem:
            c = min(rem, chunk)
            items.append((phase, g0, c))
            g0 += c
            rem -= c
        staged_iter = prefetch_iter(self._stage_chunk, items,
                                    self._executor() if self.prefetch
                                    else None, steps=lambda it: it[2])
        for (_, _, c), staged in zip(items, staged_iter):
            yield c, staged
