"""The unified phase-scheduled training engine.

One engine drives all three paper schemes (baseline / dual-batch / hybrid)
from a list of ``Phase``s, replacing the three step/loop implementations
that used to live in ``launch/train.py`` (inline loop), ``launch/steps.py``
and ``core/spmd_dual_batch.py``:

  * compiled-step cache keyed on
    ``(input_size, batch_size, layout, micro_steps, kind)`` — phases that
    share a shape/layout reuse the same XLA executable across the schedule
    (the cyclic part of CPL revisits sizes under every LR stage);
  * buffer donation throughout (params + optimizer state);
  * the fused Pallas ``dbl_merge`` server update on the SGD dual-batch hot
    path, run over the FLAT parameter store (``repro.core.flat``): one
    kernel launch per step for the whole tree, with the phase's inner loop
    scan-compiled over pre-stacked batch chunks and a donated
    ``(params, velocity)`` flat carry — no per-step Python dispatch
    (``interpret=True`` fallback off-TPU, ``fused_merge=False`` for the
    unfused scale/add/apply sequence, ``scan_loop=False`` for the
    step-at-a-time fused path);
  * **overlapped phase compilation** — while phase *k* executes, phase
    *k+1*'s executable is AOT-lowered/compiled on a background thread
    (``overlap_compile=True``), so cyclic resolution transitions stop
    stalling the hot loop.  Requires a batch-structure provider
    (``DataPlane.batch_struct``) so no data is materialized speculatively;
    the per-boundary stall (cold compile vs warm wait) is recorded in
    ``engine.stall_log`` and gated by ``benchmarks/phase_transition.py``;
  * **DataPlane scan feed** — when ``batch_fn`` is a
    ``repro.data.DataPlane``, scan chunks arrive through its
    double-buffered ``scan_feed`` (next chunk host-staged + device_put
    while the current compiled scan runs) instead of being stacked inline;
  * optional mesh: when given, params / optimizer state / batch shardings
    are derived from ``launch.sharding`` and attached to every compiled
    step, so the same schedule runs SPMD on the production mesh unchanged
    (the scan path is host-loop-free and currently single-device; mesh
    runs keep the per-step loop and skip overlap compile).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.flat import FlatSpec, flat_spec
from repro.engine.phases import Phase
from repro.engine.steps import (make_fused_dbl_step, make_fused_phase_scan,
                                make_micro_step, make_weighted_step)
from repro.launch.mesh import auto_axes
from repro.optim import Optimizer


@dataclass(frozen=True)
class StepKey:
    input_size: int
    batch_size: int
    layout: object            # SpmdDualBatch or None (frozen -> hashable)
    micro_steps: int
    kind: str                 # "weighted" | "micro" | "fused"
    drop_rate: float          # per-phase dropout (baked into the step)


def _sds(x):
    dt = x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype
    return jax.ShapeDtypeStruct(np.shape(x), dt)


def _tree_struct(tree):
    """Pytree of ``ShapeDtypeStruct``s mirroring ``tree`` (None-safe)."""
    return jax.tree_util.tree_map(_sds, tree)


class TrainEngine:
    """Phase-scheduled trainer.

    fused_merge: "auto" (fused dbl_merge whenever the phase has a dual-batch
      layout AND the engine was built for the plain-SGD server update),
      True (force), False (unfused fallback — still two group gradients, but
      the naive scale/add/apply update).
    sgd_server: mark the optimizer as the paper's plain-SGD server update so
      dual-batch phases take the fused kernel path (the optimizer's own
      update is bypassed there; its state passes through untouched unless
      ``server_momentum`` folds it into the kernel).
    scan_loop: "auto" (fused phases off-mesh run as one ``lax.scan`` over
      pre-stacked batch chunks on the flat store), True (same), False
      (step-at-a-time Python loop on every path).
    scan_chunk: max steps stacked per compiled scan call (bounds host-side
      batch staging memory; chunks share one executable per length).
    server_momentum: fold PS-server momentum into the fused kernel pass
      (requires an opt_state with a params-shaped ``"v"`` tree, e.g.
      ``sgd_momentum``; the updated velocity is written back to it).
      Fused phases only — the constructor rejects configurations where the
      fused path would bypass the scan (``scan_loop=False``,
      ``fused_merge=False``, or a mesh), because the per-step loop would
      silently drop the momentum; non-fused phases keep the optimizer's
      own update.
    overlap_compile: AOT-compile the NEXT phase's executable on a
      background thread while the current phase runs (no-mesh paths; needs
      a ``batch_struct``-capable batch_fn such as ``DataPlane``).  The
      boundary stall either way lands in ``engine.stall_log`` as
      ``{"phase", "kind", "stall_s", "warm"}`` records.  A warm compile
      that fails is counted in ``warm_errors`` and what it raised is kept
      in ``warm_exceptions``; the boundary then compiles cold.
    precision: ``"f32"`` (default — every path bit-identical to before the
      knob existed) or ``"bf16"``: the scan loop carries a bf16 flat store
      (half the parameter HBM) plus the donated f32 master carry, and the
      fused kernel writes master + re-rounded shadow in its one launch.
      Like ``server_momentum``, bf16 lives in the fused scan path — the
      constructor rejects configurations that bypass it, and ``run``
      raises on phases that would.
    """

    def __init__(self, cfg, optimizer: Optimizer, *,
                 fused_merge="auto", sgd_server: bool = False,
                 drop_rate: float = 0.0, mesh=None, donate: bool = True,
                 interpret: Optional[bool] = None,
                 scan_loop="auto", scan_chunk: int = 32,
                 server_momentum: float = 0.0,
                 overlap_compile: bool = True,
                 precision: str = "f32"):
        self.cfg = cfg
        self.optimizer = optimizer
        self.fused_merge = fused_merge
        self.sgd_server = sgd_server
        self.drop_rate = drop_rate
        self.mesh = mesh
        self.donate = donate
        self.interpret = interpret
        self.scan_loop = scan_loop
        self.scan_chunk = int(scan_chunk)
        self.server_momentum = float(server_momentum)
        self.overlap_compile = bool(overlap_compile)
        if precision not in ("f32", "bf16"):
            raise ValueError(f"unknown precision {precision!r} "
                             "(expected 'f32' or 'bf16')")
        self.precision = precision
        if self.server_momentum > 0 and (scan_loop is False
                                         or fused_merge is False
                                         or mesh is not None):
            # the velocity lives in the scan path's kernel sweep; the
            # per-step loop would silently train plain SGD instead
            raise ValueError(
                "server_momentum requires the fused scan path "
                "(scan_loop enabled, fused_merge on, no mesh)")
        if precision != "f32" and (scan_loop is False
                                   or fused_merge is False
                                   or mesh is not None):
            # the bf16 store + f32 master pair lives in the scan path's
            # kernel sweep; the per-step paths would silently train f32
            raise ValueError(
                "precision='bf16' requires the fused scan path "
                "(scan_loop enabled, fused_merge on, no mesh)")
        if mesh is not None:
            self.mesh = auto_axes(mesh)
        self._cache: dict = {}
        # mesh runs: the shardings params / opt_state / batch were put on
        self.placement: dict = {}
        self._phase_cache: dict = {}
        self._warm_steps: dict = {}
        self._inflight: dict = {}
        self._lock = threading.Lock()
        self._compiler: Optional[ThreadPoolExecutor] = None
        self.compile_count = 0
        self.warm_scheduled = 0
        self.warm_hits = 0
        self.warm_errors = 0
        self.warm_exceptions: list = []     # what each failed warm raised
        self.stall_log: list = []

    # ------------------------------------------------------------------
    @property
    def _mixed(self) -> bool:
        return self.precision != "f32"

    def _param_spec(self, params) -> FlatSpec:
        """The params codec at the engine's precision (store dtype only —
        f32 engines get exactly the spec they always did)."""
        return (flat_spec(params, jnp.bfloat16) if self._mixed
                else flat_spec(params))

    def _kind_for(self, phase: Phase) -> str:
        if phase.micro_steps and phase.layout is not None:
            return "micro"
        if phase.layout is not None and phase.layout.n_small \
                and phase.layout.small_valid \
                and (self.sgd_server or self.fused_merge is True):
            # paper §3.4 server-update path; make_fused_dbl_step picks the
            # fused kernel or the unfused fallback from self.fused_merge
            return "fused"
        return "weighted"

    def _use_scan(self, kind: str) -> bool:
        """Scan-compile the phase loop?  Only the fused flat-store path is
        scan-shaped; the unfused fallback and mesh runs keep the per-step
        loop (the fallback IS the per-step comparison path)."""
        if kind != "fused" or self.mesh is not None:
            return False
        if self.fused_merge is False or self.scan_loop is False:
            return False
        return True

    def _drop_rate_for(self, phase: Phase) -> float:
        """Per-phase dropout (CPL sub-stage schedule) wins over the engine
        default."""
        return phase.dropout if phase.dropout > 0 else self.drop_rate

    def _step_key(self, phase: Phase) -> StepKey:
        return StepKey(phase.input_size, phase.batch_size, phase.layout,
                       phase.micro_steps, self._kind_for(phase),
                       self._drop_rate_for(phase))

    def _build(self, key: StepKey):
        """Jitted (lazy-compiled) step for ``key`` — the building block
        behind both the classic cache and the AOT warm compile."""
        fn, static, donate = self._step_fn_parts(key)
        kw = {}
        if self.donate:
            kw["donate_argnums"] = donate
        if self.placement:
            # keep params / optimizer state where the engine placed them:
            # the update kernel runs on whole leaves, so unpinned outputs
            # come back replicated and the next step recompiles for them
            kw["out_shardings"] = (self.placement["params"],
                                   self.placement["opt_state"], None)
        jitted = jax.jit(fn, static_argnums=static, **kw)
        self.compile_count += 1
        return jitted

    def _step_fn_parts(self, key: StepKey):
        """(fn, static_argnums, donate_argnums) for a step kind."""
        if key.kind == "micro":
            fn = make_micro_step(self.cfg, self.optimizer,
                                 layout=key.layout,
                                 micro_steps=key.micro_steps,
                                 drop_rate=key.drop_rate)
            return fn, (), (0, 1)
        if key.kind == "fused":
            fn = make_fused_dbl_step(self.cfg, key.layout,
                                     drop_rate=key.drop_rate,
                                     fused=self.fused_merge is not False,
                                     interpret=self.interpret,
                                     mesh=self.mesh)
            return fn, (3,), (0, 1)          # lr baked into the kernel
        fn = make_weighted_step(self.cfg, self.optimizer,
                                layout=key.layout,
                                drop_rate=key.drop_rate)
        return fn, (), (0, 1)

    def step_fn(self, phase: Phase):
        """Compiled step for this phase (cached across phases)."""
        key = self._step_key(phase)
        with self._lock:
            if key not in self._cache:
                self._cache[key] = self._build(key)
            return self._cache[key]

    def _scan_ck(self, phase: Phase, spec: FlatSpec, chunk: int):
        key = StepKey(phase.input_size, phase.batch_size, phase.layout,
                      phase.micro_steps, "fused",
                      self._drop_rate_for(phase))
        return (key, float(phase.lr), id(spec), chunk)

    def _phase_scan_jit(self, phase: Phase, spec: FlatSpec):
        """Fresh jitted whole-chunk scan for a fused phase (uncompiled)."""
        fn = make_fused_phase_scan(self.cfg, phase.layout, spec,
                                   lr=phase.lr,
                                   drop_rate=self._drop_rate_for(phase),
                                   momentum=self.server_momentum,
                                   interpret=self.interpret)
        kw = {"donate_argnums": (0, 1)} if self.donate else {}
        return jax.jit(fn, **kw)

    def phase_fn(self, phase: Phase, spec: FlatSpec, chunk: int):
        """Compiled whole-chunk scan for a fused phase (cached on the step
        key + lr + codec spec + chunk length; same-shaped phases at the
        same lr share one executable)."""
        ck = self._scan_ck(phase, spec, chunk)
        with self._lock:
            if ck not in self._phase_cache:
                self._phase_cache[ck] = self._phase_scan_jit(phase, spec)
                self.compile_count += 1
            return self._phase_cache[ck]

    @property
    def phase_executables(self) -> list:
        """The AOT-compiled whole-phase scans in the cache (their
        ``as_text()`` shows whether the update ran as the Pallas kernel)."""
        with self._lock:
            return [fn for fn in self._phase_cache.values()
                    if not _is_lazy(fn)]

    @property
    def cache_size(self) -> int:
        return len(self._cache) + len(self._phase_cache) \
            + len(self._warm_steps)

    # ---------------------- overlapped warm compile --------------------
    def _compile_pool(self) -> ThreadPoolExecutor:
        if self._compiler is None:
            self._compiler = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="warm-compile")
        return self._compiler

    def _chunk_lengths(self, n_steps: int):
        """Distinct scan-chunk lengths a phase of ``n_steps`` will run."""
        if n_steps <= 0:
            return []
        full = min(n_steps, self.scan_chunk)
        out = [full]
        rem = n_steps % full
        if rem and rem != full:
            out.append(rem)
        return out

    def _rngs_struct(self, drop: float, chunk: Optional[int]):
        if drop <= 0:
            return None
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)   # PRNGKey layout
        return key if chunk is None else \
            jax.ShapeDtypeStruct((chunk, 2), jnp.uint32)

    def schedule_warm(self, phase: Phase, params, opt_state=None,
                      batch_fn=None) -> bool:
        """AOT-lower/compile ``phase``'s executable on the background
        thread.  Call while the PREVIOUS phase is (about to start)
        executing — e.g. the cluster backends call this for phase *k+1*
        right before dispatching phase *k*.  Needs ``batch_fn`` to expose
        ``batch_struct(phase, stacked)`` (``DataPlane`` does); returns
        whether anything was scheduled."""
        if not self.overlap_compile or self.mesh is not None:
            return False
        if batch_fn is None or not hasattr(batch_fn, "batch_struct"):
            return False
        kind = self._kind_for(phase)
        if self._use_scan(kind):
            spec = self._param_spec(params)
            vspec = (self._param_spec(opt_state["v"])
                     if self.server_momentum > 0 and isinstance(opt_state,
                                                                dict)
                     and "v" in opt_state else None)
            return self._schedule_warm_scan(phase, spec, vspec, batch_fn)
        return self._schedule_warm_step(phase, kind,
                                        _tree_struct(params),
                                        _tree_struct(opt_state), batch_fn)

    def _schedule_warm_scan(self, phase: Phase, spec: FlatSpec,
                            vspec: Optional[FlatSpec], batch_fn) -> bool:
        """Background-compile every chunk length the phase will run."""
        drop = self._drop_rate_for(phase)
        scheduled = False
        for c in self._chunk_lengths(phase.n_steps):
            ck = self._scan_ck(phase, spec, c)
            with self._lock:
                cur = self._phase_cache.get(ck)
                if (cur is not None and not _is_lazy(cur)) \
                        or ck in self._inflight:
                    continue
            if self._mixed:
                # the scan carry is the (shadow, master) buffer pair; the
                # velocity is always f32 in the store's geometry
                p2s = (jax.ShapeDtypeStruct(spec.shape, spec.store_dtype),
                       jax.ShapeDtypeStruct(spec.shape, jnp.float32))
            else:
                p2s = jax.ShapeDtypeStruct(spec.shape, jnp.float32)
            v2s = (jax.ShapeDtypeStruct(vspec.shape, jnp.float32)
                   if vspec is not None else None)
            bst = batch_fn.batch_struct(phase, c)
            rst = self._rngs_struct(drop, c)

            def task(phase=phase, spec=spec, ck=ck, p2s=p2s, v2s=v2s,
                     bst=bst, rst=rst):
                try:
                    jitted = self._phase_scan_jit(phase, spec)
                    compiled = jitted.lower(p2s, v2s, bst, rst).compile()
                except Exception as e:      # noqa: BLE001 — warm is advisory
                    self._warm_failed(e)
                    return None
                with self._lock:
                    self._phase_cache[ck] = compiled
                    self.compile_count += 1
                return compiled

            with self._lock:
                self._inflight[ck] = self._compile_pool().submit(task)
                self.warm_scheduled += 1
            scheduled = True
        return scheduled

    def _warm_failed(self, exc: BaseException) -> None:
        """A failed warm compile falls back to a cold compile at the
        boundary; keep what it raised (an out-of-memory compile would
        otherwise vanish into the cold retry)."""
        with self._lock:
            self.warm_errors += 1
            self.warm_exceptions.append(exc)

    def _warm_step_key(self, key: StepKey, phase: Phase):
        # fused per-step executables bake lr in (static argnum); the warm
        # entry must therefore be lr-specific, unlike the classic cache
        return (key, float(phase.lr) if key.kind == "fused" else None)

    def _schedule_warm_step(self, phase: Phase, kind: str, params_struct,
                            opt_struct, batch_fn) -> bool:
        key = self._step_key(phase)
        wkey = self._warm_step_key(key, phase)
        with self._lock:
            if wkey in self._warm_steps or wkey in self._inflight:
                return False
        bst = dict(batch_fn.batch_struct(phase, None))
        if phase.layout is not None and kind == "weighted" \
                and "weight" not in bst:
            bst["weight"] = jax.ShapeDtypeStruct((phase.batch_size,),
                                                 jnp.float32)
        rst = self._rngs_struct(self._drop_rate_for(phase), None)
        lr = float(phase.lr)

        def task(key=key, wkey=wkey, bst=bst, rst=rst, lr=lr):
            try:
                fn, static, donate = self._step_fn_parts(key)
                kw = {"donate_argnums": donate} if self.donate else {}
                jitted = jax.jit(fn, static_argnums=static, **kw)
                compiled = jitted.lower(params_struct, opt_struct, bst, lr,
                                        rst).compile()
                if key.kind == "fused":
                    # Compiled drops static args: adapt to the engine's
                    # uniform step(params, opt, batch, lr, rng) call
                    wrapped = (lambda p, s, b, _lr, rng,
                               c=compiled: c(p, s, b, rng))
                else:
                    wrapped = compiled
            except Exception as e:          # noqa: BLE001 — warm is advisory
                self._warm_failed(e)
                return None
            with self._lock:
                self._warm_steps[wkey] = wrapped
                self.compile_count += 1
            return wrapped

        with self._lock:
            self._inflight[wkey] = self._compile_pool().submit(task)
            self.warm_scheduled += 1
        return True

    def _await_warm(self, wkey):
        """(entry, waited_s): pop any in-flight warm task for ``wkey`` and
        wait it out; None entry means no warm result (caller compiles)."""
        with self._lock:
            fut = self._inflight.pop(wkey, None)
        if fut is None:
            return None, 0.0
        t0 = time.perf_counter()
        try:
            entry = fut.result()
        except Exception as e:              # noqa: BLE001
            self._warm_failed(e)
            entry = None
        return entry, time.perf_counter() - t0

    def _record_stall(self, pi: int, kind: str, stall_s: float, warm: bool):
        self.stall_log.append({"phase": pi, "kind": kind,
                               "stall_s": round(stall_s, 6), "warm": warm})

    def _acquire_phase_fn(self, phase: Phase, spec: FlatSpec, c: int,
                          p2, v2, batches, rngs):
        """(fn, stall_s, warm): an executable for this chunk length —
        warm-compiled (background), cached, or cold AOT-compiled inline.
        ``stall_s`` is the wall time the hot loop waited for it."""
        ck = self._scan_ck(phase, spec, c)
        with self._lock:
            fn = self._phase_cache.get(ck)
            if fn is not None and not _is_lazy(fn):
                self._inflight.pop(ck, None)    # done future, if any
        if fn is not None and not _is_lazy(fn):
            return fn, 0.0, True
        warm, waited = self._await_warm(ck)
        if warm is not None:
            self.warm_hits += 1
            return warm, waited, True
        t0 = time.perf_counter()
        jitted = fn if fn is not None else self._phase_scan_jit(phase, spec)
        compiled = jitted.lower(_tree_struct(p2), _tree_struct(v2),
                                _tree_struct(batches),
                                _tree_struct(rngs)).compile()
        with self._lock:
            self._phase_cache[ck] = compiled
            self.compile_count += 1
        return compiled, waited + (time.perf_counter() - t0), False

    def _acquire_step_fn(self, phase: Phase, params, opt_state, batch,
                         drop_rng):
        """(step, stall_s, warm): an executable for this phase's per-step
        loop — warm-compiled (background), cached, or cold AOT-compiled
        inline from the phase's first batch, so the boundary stall is
        measured on this path exactly like the scan path (mesh runs keep
        the lazily-jitted cache and bypass this)."""
        key = self._step_key(phase)
        wkey = self._warm_step_key(key, phase)
        with self._lock:
            warm = self._warm_steps.get(wkey)
        if warm is not None:
            self.warm_hits += 1
            return warm, 0.0, True
        warm, waited = self._await_warm(wkey)
        if warm is not None:
            self.warm_hits += 1
            return warm, waited, True
        with self._lock:
            cached = self._cache.get(key)
        if cached is not None and not _is_lazy(cached):
            return cached, waited, True     # dynamic-lr Compiled, lr-agnostic
        t0 = time.perf_counter()
        if cached is not None:
            jitted = cached
        else:
            fn, static, donate = self._step_fn_parts(key)
            kw = {"donate_argnums": donate} if self.donate else {}
            jitted = jax.jit(fn, static_argnums=static, **kw)
        compiled = jitted.lower(params, opt_state, batch, float(phase.lr),
                                drop_rng).compile()
        if key.kind == "fused":
            # lr is baked in (static argnum); keep the Compiled in the
            # lr-keyed warm cache and adapt to the uniform call signature
            step = (lambda p, s, b, _lr, rng,
                    c=compiled: c(p, s, b, rng))
            with self._lock:
                self._warm_steps[wkey] = step
                self.compile_count += 1
        else:
            step = compiled
            with self._lock:
                self._cache[key] = compiled
                self.compile_count += 1
        return step, waited + (time.perf_counter() - t0), False

    def _record(self, history, log_fn, *, gstep: int, pi: int, phase: Phase,
                loss, samples_seen: int, t0: float, wall_offset: float):
        """The per-step history record — one schema for both loop forms."""
        rec = {"step": gstep, "phase": pi, "size": phase.input_size,
               "batch": phase.batch_size, "loss": round(float(loss), 4),
               "tokens": samples_seen,
               "wall_s": round(time.time() - t0 + wall_offset, 1),
               "compiled": self.cache_size}
        history.append(rec)
        if log_fn is not None:
            log_fn(rec)

    # ------------------------------------------------------------------
    def _shardings(self, params, opt_state, batch):
        from jax.sharding import NamedSharding
        from repro.launch.sharding import batch_specs, param_specs
        sh = lambda tree: jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), tree)
        return (sh(param_specs(params, self.mesh)),
                sh(param_specs(opt_state, self.mesh)),
                sh(batch_specs(batch, self.mesh)))

    # ------------------------------------------------------------------
    def _chunk_feed(self, phase: Phase, batch_fn, start: int):
        """(c, batches) chunks for the scan path: the DataPlane's
        double-buffered feed when available, else inline host stacking."""
        if hasattr(batch_fn, "scan_feed"):
            yield from batch_fn.scan_feed(phase, start, phase.n_steps,
                                          self.scan_chunk)
            return
        remaining, g0 = phase.n_steps, start
        while remaining:
            c = min(remaining, self.scan_chunk)
            staged = [batch_fn(phase, g0 + j) for j in range(c)]
            batches = {}
            for k in staged[0]:
                vals = [b[k] for b in staged]
                # device arrays stack on device; host arrays stack host-side
                # into ONE upload — neither pays a device->host round trip
                batches[k] = (jnp.stack(vals)
                              if isinstance(vals[0], jax.Array)
                              else jnp.asarray(np.stack(vals)))
            yield c, batches
            remaining -= c
            g0 += c

    def _run_phase_scan(self, phase: Phase, pi: int, spec: FlatSpec, p2, v2,
                        batch_fn, rng, *, gstep: int, samples_seen: int,
                        start_step: int, log_every: int, log_fn, history,
                        t0: float, wall_offset: float,
                        phase_offset: int = 0):
        """One fused phase as scan-compiled chunks on the flat store.

        Takes and returns the flat ``(p2, v2)`` carry — ``run()`` owns
        ravel/unravel at the flat↔pytree boundary, so consecutive scan
        phases share one carry with no interior codec passes.  Drives
        ``scan_chunk``-step compiled calls over batches from the
        ``DataPlane`` double-buffered feed (or inline stacking), with the
        chunk executable acquired AOT — warm from the background compiler
        when the previous phase overlapped it, cold otherwise; either way
        the boundary stall lands in ``stall_log``.
        Returns (p2, v2, gstep, samples_seen).
        """
        drop = self._drop_rate_for(phase)
        first = True
        for c, batches in self._chunk_feed(phase, batch_fn, gstep):
            g0 = gstep
            with StepTraceAnnotation("repro.train.step", step_num=g0,
                                     phase=pi + phase_offset, steps=c):
                rngs = (jax.vmap(lambda s: jax.random.fold_in(rng, s))(
                    jnp.arange(g0, g0 + c)) if drop > 0 else None)
                with TraceAnnotation("repro.train.acquire"):
                    fn, stall, warm = self._acquire_phase_fn(
                        phase, spec, c, p2, v2, batches, rngs)
                if first:
                    self._record_stall(pi + phase_offset, "scan", stall,
                                       warm)
                    first = False
                with TraceAnnotation("repro.train.dispatch"):
                    p2, v2, losses = fn(p2, v2, batches, rngs)
                with TraceAnnotation("repro.train.sync"):
                    losses = np.asarray(losses)  # one device sync per chunk
                for j in range(c):
                    gstep += 1
                    samples_seen += phase.batch_size * phase.input_size
                    if gstep == start_step + 1 or gstep % log_every == 0:
                        self._record(history, log_fn, gstep=gstep, pi=pi,
                                     phase=phase, loss=losses[j],
                                     samples_seen=samples_seen, t0=t0,
                                     wall_offset=wall_offset)
        return p2, v2, gstep, samples_seen

    def _ravel(self, params, opt_state):
        """Enter the flat store: ``(spec, vspec, p2, v2)`` of the params
        and, under server momentum, of the velocity."""
        spec = self._param_spec(params)
        if self._mixed:
            p2 = (spec.ravel_jit(params), spec.ravel_master_jit(params))
        else:
            p2 = spec.ravel_jit(params)
        vspec = v2 = None
        if self.server_momentum > 0:
            if not (isinstance(opt_state, dict) and "v" in opt_state):
                raise ValueError(
                    "server_momentum needs an opt_state with a "
                    'params-shaped "v" tree (e.g. sgd_momentum)')
            vspec = self._param_spec(opt_state["v"])
            # the velocity stays f32 whatever the store dtype
            # (ravel_master IS ravel on an f32 spec)
            v2 = vspec.ravel_master_jit(opt_state["v"])
        return spec, vspec, p2, v2

    def run(self, phases: Sequence[Phase], params, opt_state,
            batch_fn: Callable[[Phase, int], dict], *,
            seed: int = 0, log_every: int = 20,
            log_fn: Optional[Callable[[dict], None]] = None,
            start_step: int = 0, start_samples: int = 0,
            wall_offset: float = 0.0, phase_offset: int = 0):
        """Run the whole schedule.

        batch_fn(phase, global_step) -> batch dict ("tokens"/"labels" or
        "images"/"labels"); the engine attaches the phase layout's weights.
        A ``DataPlane`` works directly as ``batch_fn`` and additionally
        enables the double-buffered scan feed and overlapped next-phase
        warm compile.  ``start_step`` offsets the global step counter (and
        therefore the dropout RNG stream and ``batch_fn`` indices) so a
        backend resuming mid-schedule replays the uninterrupted run
        exactly; ``start_samples``/``wall_offset`` keep the logged
        ``tokens`` and ``wall_s`` counters cumulative under
        phase-at-a-time dispatch, and ``phase_offset`` keeps the
        ``stall_log`` phase indices absolute there too.
        Returns (params, opt_state, history).
        """
        history = []
        rng = jax.random.PRNGKey(seed)
        t0 = time.time()
        gstep = start_step
        samples_seen = start_samples
        placed = None
        mom = self.server_momentum
        flat = None  # (spec, vspec, p2, v2): params/opt_state stale if set
        if hasattr(batch_fn, "bind") and not getattr(batch_fn, "bound",
                                                     True):
            batch_fn.bind(phases)

        def materialize():
            """Leave the flat store: params/opt_state become current."""
            nonlocal params, opt_state, flat
            if flat is None:
                return
            with TraceAnnotation("repro.train.unravel"):
                spec, vspec, p2, v2 = flat
                # mixed precision carries (shadow, master); the f32 master
                # is the value of record — checkpoints and downstream
                # phases see full-precision params
                params = spec.unravel_jit(p2[1] if self._mixed else p2)
                if v2 is not None:
                    # the velocity's OWN spec — its leaf dtypes may differ
                    # from the params' (e.g. f32 state over bf16 params)
                    opt_state = dict(opt_state, v=vspec.unravel_jit(v2))
            flat = None

        def warm_next(pi):
            """Overlap phase pi+1's compile with phase pi's execution."""
            if pi + 1 >= len(phases) or not self.overlap_compile \
                    or self.mesh is not None \
                    or not hasattr(batch_fn, "batch_struct"):
                return
            nxt = phases[pi + 1]
            kind = self._kind_for(nxt)
            if self._use_scan(kind):
                if flat is not None:
                    spec_n, vspec_n = flat[0], flat[1]
                else:
                    spec_n = self._param_spec(params)
                    vspec_n = (self._param_spec(opt_state["v"]) if mom > 0
                               and isinstance(opt_state, dict)
                               and "v" in opt_state else None)
                self._schedule_warm_scan(nxt, spec_n, vspec_n, batch_fn)
                return
            if flat is not None:
                spec_c = flat[0]
                p_struct = jax.eval_shape(
                    spec_c.unravel,
                    jax.ShapeDtypeStruct(spec_c.shape, jnp.float32))
            else:
                p_struct = _tree_struct(params)
            self._schedule_warm_step(nxt, kind, p_struct,
                                     _tree_struct(opt_state), batch_fn)

        for pi, phase in enumerate(phases):
            kind = self._kind_for(phase)
            if self._use_scan(kind):
                if flat is None:
                    with TraceAnnotation("repro.train.ravel"):
                        spec, vspec, p2, v2 = self._ravel(params, opt_state)
                else:
                    spec, vspec, p2, v2 = flat
                flat = (spec, vspec, p2, v2)
                warm_next(pi)
                p2, v2, gstep, samples_seen = self._run_phase_scan(
                    phase, pi, spec, p2, v2, batch_fn, rng,
                    gstep=gstep, samples_seen=samples_seen,
                    start_step=start_step, log_every=log_every,
                    log_fn=log_fn, history=history, t0=t0,
                    wall_offset=wall_offset, phase_offset=phase_offset)
                flat = (spec, vspec, p2, v2)
                continue
            if mom > 0:
                # the non-scan paths never touch the velocity — erroring
                # beats silently training without the configured momentum
                raise ValueError(
                    f"server_momentum is set but phase {pi} ({kind}) "
                    "bypasses the fused scan path; PS-server momentum only "
                    "applies to fused dual-batch phases")
            if self._mixed:
                # likewise: the per-step paths have no bf16 store/master —
                # they would silently train f32
                raise ValueError(
                    f"precision='bf16' is set but phase {pi} ({kind}) "
                    "bypasses the fused scan path; the bf16 store only "
                    "applies to fused dual-batch phases")
            materialize()
            warm_next(pi)
            bsh = None
            drop = self._drop_rate_for(phase)
            attach_w = (phase.layout is not None
                        and self._kind_for(phase) == "weighted")
            weights = (phase.layout.weights().astype(jnp.float32)
                       if attach_w else None)
            step = None
            for j in range(phase.n_steps):
                batch = batch_fn(phase, gstep)
                if attach_w and "weight" not in batch:
                    batch = dict(batch, weight=weights)
                drop_rng = (jax.random.fold_in(rng, gstep)
                            if drop > 0 else None)
                if step is None and self.mesh is None:
                    # acquire an AOT executable from the first batch —
                    # warm (background-compiled), cached, or cold; the
                    # boundary stall is measured either way
                    step, stall, warm = self._acquire_step_fn(
                        phase, params, opt_state, batch, drop_rng)
                    self._record_stall(pi + phase_offset, "step", stall,
                                       warm)
                if self.mesh is not None:
                    if placed is None:
                        psh, osh, bsh = self._shardings(params, opt_state,
                                                        batch)
                        params = jax.device_put(params, psh)
                        opt_state = jax.device_put(opt_state, osh)
                        placed = True
                    elif bsh is None:       # new phase: batch shape changed
                        from repro.launch.sharding import batch_specs
                        from jax.sharding import NamedSharding
                        bsh = jax.tree_util.tree_map(
                            lambda s: NamedSharding(self.mesh, s),
                            batch_specs(batch, self.mesh))
                    batch = jax.device_put(batch, bsh)
                    if step is None:
                        self.placement = {"params": psh, "opt_state": osh,
                                          "batch": bsh}
                        step = self.step_fn(phase)
                params, opt_state, metrics = step(params, opt_state, batch,
                                                  phase.lr, drop_rng)
                gstep += 1
                samples_seen += phase.batch_size * phase.input_size
                if gstep == start_step + 1 or gstep % log_every == 0:
                    self._record(history, log_fn, gstep=gstep, pi=pi,
                                 phase=phase, loss=metrics["loss"],
                                 samples_seen=samples_seen, t0=t0,
                                 wall_offset=wall_offset)
        materialize()
        return params, opt_state, history


def _is_lazy(fn) -> bool:
    """True for a lazily-compiling jitted function (vs an AOT Compiled)."""
    return hasattr(fn, "lower")
