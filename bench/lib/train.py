"""Driver of training mixes (``"kind": "train"``): the paper's schedules
through the program's ``repro.api.run`` on its ``TrainEngine``.

Set-up builds ONE engine and its state and drives it from the seed
through a check cycle: the mix's CPL cycle with one step per sub-stage,
through the same ``repro.api.run`` call, ``SpmdBackend``, data feed and
compiled executables as the window (a step's executable does not depend
on how many steps its phase has), the phase boundary between them
included.  The first call of each phase's executable is read as it runs:
its loss, and the f32 flat store before and after, so the gradient the
update applied is exact (store_0 - store_1 = lr * g).  That same engine
and state then go to the window, which runs whole CPL cycles until
``--seconds`` have passed.

A mix with ``"mesh": {"data": n, "model": m}`` runs the engine on a mesh
of the first n * m chips (the program's per-step path); the check then
reads each phase's first step from the parameter trees before and after.
"""
from __future__ import annotations

import math

import numpy as np

from bench.lib import program, trace, weights


def _spec(traffic: dict, seed: int, steps_per_substage: int = 0):
    from repro.api import ScheduleSpec
    sub = tuple(traffic["sub_sizes"])
    n = len(sub)
    gb = traffic["global_batch"]
    steps = steps_per_substage or traffic["steps_per_substage"]
    return ScheduleSpec(
        scheme=traffic["scheme"], input_size=max(sub), axis="seq_len",
        batch_size=gb, dataset_size=gb * traffic["dataset_rows_per_batch"],
        n_workers=traffic["n_workers"], n_small=traffic["n_small"],
        k=traffic["k"], factor=traffic["factor"],
        n_steps=steps * n, lr=traffic["lr"],
        tm_a=traffic["time_model"]["a"], tm_b=traffic["time_model"]["b"],
        sub_sizes=sub, sub_dropouts=(0.0,) * n, stage_epochs=(n,),
        stage_lrs=(traffic["lr"],), seed=seed)


def valid_rows(phase) -> int:
    """Rows a step trains on: the large workers' rows and the small
    workers' live rows (padding rows are never computed)."""
    lay = phase.layout
    if lay is None:
        return phase.batch_size
    return (lay.n_workers - lay.n_small) * lay.per_worker \
        + lay.n_small * lay.small_valid


def _change_norms(offsets, sizes, names, scale):
    """A jitted ``(before, after) -> {leaf: scale * ||before - after||}``
    over flat-store buffers, leaf by leaf, in float32."""
    import jax
    import jax.numpy as jnp

    def fn(a, b):
        a, b = a.reshape(-1), b.reshape(-1)
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            a[o:o + s].astype(jnp.float32) - b[o:o + s].astype(jnp.float32))))
            for n, o, s in zip(names, offsets, sizes)}
    jitted = jax.jit(fn)
    return lambda a, b: {k: scale * float(v)
                         for k, v in jitted(a, b).items()}


def _tree_change_norms(names, before, after, scale):
    import jax
    import jax.numpy as jnp
    out = {}
    for n, a, b in zip(names, jax.tree_util.tree_leaves(before),
                       jax.tree_util.tree_leaves(after)):
        out[n] = scale * float(jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return out


class FirstSteps:
    """Reads the first call of each phase's executable while the check
    cycle runs through ``repro.api.run``: its loss, and the leaf norms of
    the change it made, over lr.  Read from the f32 flat store (the fused
    scan) that is the gradient the update applied; read from parameter
    trees (the per-step path of a mesh) it is the change as the
    configuration's type holds it.  It wraps how the engine hands out
    executables, on this engine object and only until ``close``; the
    executables themselves are untouched."""

    def __init__(self, engine, names):
        self.engine, self.names = engine, names
        self.steps: dict = {}
        self.layouts: dict = {}
        scan, step = engine._acquire_phase_fn, engine.step_fn
        acquire_step = engine._acquire_step_fn

        def on_scan(phase, spec, c, p2, v2, batches, rngs):
            fn, stall, warm = scan(phase, spec, c, p2, v2, batches, rngs)
            if phase.input_size in self.steps:
                return fn, stall, warm
            norms = _change_norms(spec.offsets, spec.sizes, names,
                                  1.0 / phase.lr)

            def first(p2, v2, batches, rngs):
                import jax.numpy as jnp
                before = jnp.copy(p2)
                p2, v2, losses = fn(p2, v2, batches, rngs)
                self._read(phase, float(np.asarray(losses)[0]),
                           norms(before, p2), "store")
                del before
                return p2, v2, losses
            return first, stall, warm

        def wrap_step(phase, fn):
            if phase.input_size in self.steps:
                return fn

            def first(params, opt_state, batch, lr, rng):
                import jax
                import jax.numpy as jnp
                before = jax.tree_util.tree_map(jnp.copy, params)
                params, opt_state, metrics = fn(params, opt_state, batch,
                                                lr, rng)
                self._read(phase, float(metrics["loss"]), _tree_change_norms(
                    names, before, params, 1.0 / phase.lr), "tree")
                del before
                return params, opt_state, metrics
            return first

        def on_step_fn(phase):
            return wrap_step(phase, step(phase))

        def on_acquire_step(phase, *a):
            fn, stall, warm = acquire_step(phase, *a)
            return wrap_step(phase, fn), stall, warm

        engine._acquire_phase_fn = on_scan
        engine.step_fn = on_step_fn
        engine._acquire_step_fn = on_acquire_step

    def _read(self, phase, loss, grad, read_from):
        self.steps[phase.input_size] = {"loss": loss, "grad": grad,
                                        "read_from": read_from}
        self.layouts[phase.input_size] = {
            "valid_rows": valid_rows(phase),
            "factor": phase.layout.factor_small if phase.layout else None}

    def close(self):
        for name in ("_acquire_phase_fn", "step_fn", "_acquire_step_fn"):
            self.engine.__dict__.pop(name, None)


def _mesh(traffic: dict):
    m = traffic.get("mesh")
    if not m:
        return None
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(m["data"] * m["model"], model=m["model"])


def run(cell) -> dict:
    """Set-up, window and the numbers the output check needs."""
    import jax
    from repro.api import RunConfig
    from repro.api import run as api_run
    from repro.core.flat import flat_spec
    from repro.data import DataPlane
    from repro.engine import TrainEngine
    from repro.optim import make_optimizer

    conf, traffic, seed = cell.conf, cell.traffic, cell.seed
    seed32 = seed % (1 << 31)
    fam = program.adapter(conf)
    cfg = fam.model_config(conf)
    spec = _spec(traffic, seed32)
    phases = spec.to_phases()

    params = fam.params_from(weights.make(conf, seed, conf["torch_dtype"]))
    opt = make_optimizer("sgd", momentum=0.0, weight_decay=0.0)
    opt_state = opt.init(params)
    engine = TrainEngine(cfg, opt, sgd_server=True, fused_merge="auto",
                         scan_loop="auto", scan_chunk=traffic["scan_chunk"],
                         overlap_compile=True, mesh=_mesh(traffic))
    source = program.source(traffic["data"], seed, conf)
    plane = DataPlane(source, seed=seed32, prefetch=True)
    config = RunConfig(backend="spmd", prefetch=True,
                       log_every=traffic["steps_per_substage"])

    # -- the check cycle: one step per sub-stage through the window's
    # entry, feed and executables; its first steps are read as they run
    first = FirstSteps(engine, fam.leaf_names(params))
    source.record = True
    res = api_run(_spec(traffic, seed32, steps_per_substage=1), config,
                  init_params=params, opt_state=opt_state, engine=engine,
                  plane=plane)
    first.close()
    source.record = False
    params, opt_state = res.params, res.opt_state
    jax.block_until_ready(params)
    check = {"steps": first.steps, "layouts": first.layouts,
             "batches": dict(source.recorded)}
    compiles_before = engine.compile_count
    cell.setup_done()

    # -- the window: whole CPL cycles until --seconds have passed --------
    per_cycle = {}
    for ph in phases:
        per_cycle[ph.input_size] = per_cycle.get(ph.input_size, 0) \
            + ph.n_steps * valid_rows(ph)
    steps_per_cycle = sum(ph.n_steps for ph in phases)
    cycles, window_losses = 0, []
    with cell.window() as w:
        while True:
            source.cycle = 1 + cycles
            with trace.span("bench.cycle"):
                res = api_run(spec, config, init_params=params,
                              opt_state=opt_state, engine=engine,
                              plane=plane)
            params, opt_state = res.params, res.opt_state
            window_losses += [r["loss"] for r in res.history]
            cycles += 1
            if w.elapsed() >= cell.seconds:
                jax.block_until_ready(params)
                break
    samples = {s: n * cycles for s, n in per_cycle.items()}
    out = {
        "window_s": w.seconds,
        "samples": sum(samples.values()),
        "samples_by_size": samples,
        "steps": steps_per_cycle * cycles,
        "cycles": cycles,
        "compiles_in_window": engine.compile_count - compiles_before,
        "warm_errors": engine.warm_errors,
        "nonfinite_losses": sum(1 for x in window_losses
                                if not math.isfinite(x)),
        "flat_rows": flat_spec(params).rows,
        "check": check,
    }
    cell.read_memory()
    del params, opt_state, res, engine, plane
    return out


def reference_readings(conf, traffic, seed, batches, mm_name="f32",
                       keep=1.0):
    """The reference through the check cycle: at each sub-stage in turn,
    the merged loss and the leaf norms of the gradient over that
    sub-stage's batch, then the SGD step, the weights held as the
    configuration holds them (bfloat16 matrices, float32 RMSNorm), and
    the leaf norms of that held change over lr (``held``).
    ``mm_name`` "fp8" is the control: every matmul in float8 e4m3 and the
    weights held in float8 (``lower``).  ``keep`` 0.5 plants the fault of
    half the batch left out, the mean taken over the rest."""
    import functools

    import jax

    from bench.reference import dual_batch

    ref = program.reference(conf)
    mm = ref.MATMULS[mm_name]
    rl = functools.partial(ref.row_losses, conf=conf, mm=mm)
    hold = ref.lower if mm_name != "f32" else functools.partial(
        weights.hold, conf)
    steps, layouts = {}, {}
    with jax.default_matmul_precision("highest"):
        w = weights.as_f32(weights.make(conf, seed, conf["torch_dtype"]))
        if mm_name != "f32":
            w = ref.lower(w)
        sizes = traffic["sub_sizes"]
        for i, size in enumerate(sizes):
            lay = dual_batch.layout(traffic, size)
            b = batches[size]
            loss, g = dual_batch.merged_loss_and_grad(
                w, b["tokens"], b["labels"], lay, rl, keep=keep)
            lr = traffic["lr"]
            w_next = hold(dual_batch.sgd(w, g, lr))
            held = jax.tree_util.tree_map(lambda a, b: (a - b) / lr, w,
                                          w_next)
            steps[size] = {"loss": loss, "grad": dual_batch.leaf_norms(g),
                           "held": dual_batch.leaf_norms(held)}
            layouts[size] = {k: lay[k] for k in ("global_batch",
                                                 "small_valid", "factor",
                                                 "valid_rows")}
            w = w_next
            del g, held
    return {"steps": steps, "layouts": layouts}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, for phase i of the check cycle (sub-stage
    i of the CPL cycle): the relative gap of its first step's loss
    (``loss_gap.phase<i>``), and the worst leaf's gap of that step's
    gradient norm (``grad_gap.phase<i>``; leaves whose reference gradient
    is under a thousandth of the median leaf's are left out)."""
    from bench.lib.check import counted_leaves, norm_gap
    nums, leaves = {}, {}
    for i, (size, r) in enumerate(sorted(ref["steps"].items())):
        loss, grad = f"loss_gap.phase{i}", f"grad_gap.phase{i}"
        p = prog["steps"].get(size)
        if p is None:                      # the phase never ran
            nums[loss] = nums[grad] = math.inf
            continue
        counted = counted_leaves(r["grad"])
        # a change read from trees is held in the configuration's type
        want = r["held"] if p.get("read_from") == "tree" else r["grad"]
        g, g_leaf = norm_gap(p["grad"], want, counted)
        nums[loss] = abs(p["loss"] - r["loss"]) / r["loss"]
        nums[grad] = g
        leaves[size] = {"worst": g_leaf, "left_out": sorted(
            set(r["grad"]) - set(counted))}
    return {**nums, "leaves": leaves}


def layout_mismatch(prog: dict, ref: dict) -> float:
    """1 where a phase's dual-batch layout (live rows, update factor)
    differs from the paper's Eq. 4, 6 and 8 at its sub-stage, else 0."""
    for size, r in ref["layouts"].items():
        p = prog["layouts"].get(size)
        if p is None or p["valid_rows"] != r["valid_rows"] \
                or abs(p["factor"] - r["factor"]) > 1e-12:
            return 1.0
    return 0.0


def check(cell, out) -> tuple:
    """(numbers compared, notes) of the output check."""
    prog = out["check"]
    ref = reference_readings(cell.conf, cell.traffic, cell.seed,
                             prog["batches"])
    nums = compare(prog, ref)
    notes = {"leaves": nums.pop("leaves"),
             "loss": {s: [prog["steps"].get(s, {}).get("loss"), r["loss"]]
                      for s, r in ref["steps"].items()},
             "layouts": {"program": prog["layouts"],
                         "reference": ref["layouts"]}}
    nums["layout_mismatch"] = layout_mismatch(prog, ref)
    return nums, notes


def end_to_end(out) -> dict:
    return {"train_samples_per_s": (out["samples"] / out["window_s"],
                                    "samples/s")}
