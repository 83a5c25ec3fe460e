#!/usr/bin/env python3
"""Rehearsals without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py flow [workload ...]
    JAX_PLATFORMS=cpu python3 bench/rehearse.py compile [workload ...]

``flow`` runs each cell's whole control flow (set-up, window, output
check) at a tiny size of its configuration on the CPU, Pallas kernels in
interpret mode.  It prints what the run produced and no metric line.

``compile`` compiles each cell's step programs at the real size for a
described TPU v5e (no chip needed) and prints ``memory_analysis()`` of
each: arguments, outputs and temporaries, in bytes.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench.lib import program  # noqa: E402
from bench.run import load_cell  # noqa: E402

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2}


def tiny_conf(conf: dict, kind: str) -> dict:
    """A configuration at the tiny size.  A serving cell's tiny model
    draws its weights wider (std 0.2): at width 64 the published std
    gives logits a spread of about 0.16, where the real width gives about
    1, and the output check reads gaps of logits."""
    out = dict(conf, **TINY)
    if kind == "serve":
        out["initializer_range"] = 0.2
    return out


def tiny_traffic(t: dict) -> dict:
    t = dict(t)
    if t["kind"] == "train":
        t.update(sub_sizes=[16, 32], global_batch=8, steps_per_substage=2)
    else:
        t.update(slots=4, slot_tokens=128, prefill_chunk=32, backlog=64,
                 prompt=dict(t["prompt"], median=40, min=8, max=64),
                 output=dict(t["output"], median=8, min=2, max=32),
                 check=dict(t["check"], min_tokens=20, max_requests=3))
    return t


def tiny_cell(name: str, seed: int = 2**31 + 12345):
    """(cell, driver, limits) of ``name`` at the tiny size."""
    import importlib

    from bench.lib.cell import Cell
    spec = load_cell(name)
    traffic = tiny_traffic(spec["traffic"])
    cell = Cell(name=name, conf=tiny_conf(spec["conf"], traffic["kind"]),
                traffic=traffic,
                seed=seed, seconds=1.0, tracing=False, trace_dir="", chips=1)
    driver = importlib.import_module("bench.lib." + traffic["kind"])
    return cell, driver, spec["limits"]


def flow(name: str) -> None:
    import jax
    cell, driver, _ = tiny_cell(name)

    def on_event(event, *a, **k):
        if "backend_compile" in event or "cache_retrieval" in event:
            cell.compile_events += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    out = driver.run(cell)
    nums, notes = driver.check(cell, out)
    nums["compiles_in_window"] = float(cell.compiles_in_window
                                       + out["compiles_in_window"])
    e2e = driver.end_to_end(out)
    print(json.dumps({"workload": name, "setup_s(cpu)": cell.setup_s,
                      "e2e(cpu)": e2e, "checks": nums, "notes": notes,
                      "wall_s(cpu)": time.perf_counter() - t0},
                     default=str))


def compile_cell(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    spec = load_cell(name)
    conf, traffic = spec["conf"], spec["traffic"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    fam = program.adapter(conf)
    cfg = fam.model_config(conf)
    from bench.lib import weights
    wshape = jax.eval_shape(lambda: fam.params_from(
        weights.make(conf, 0, conf["torch_dtype"])))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev),
            tree)

    def report(what, compiled):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({"workload": name, "program": what,
                          "argument_bytes": m.argument_size_in_bytes,
                          "output_bytes": m.output_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes,
                          "alias_bytes": m.alias_size_in_bytes,
                          "total_bytes": total,
                          "pallas": "tpu_custom_call" in compiled.as_text()}),
              flush=True)

    if traffic["kind"] == "train":
        from bench.lib.train import _spec
        from repro.core.flat import flat_spec
        from repro.engine import TrainEngine
        from repro.optim import make_optimizer
        engine = TrainEngine(cfg, make_optimizer("sgd", momentum=0.0,
                                                 weight_decay=0.0),
                             sgd_server=True, scan_chunk=traffic["scan_chunk"],
                             interpret=False)
        fspec = flat_spec(wshape)
        c = traffic["scan_chunk"]
        for ph in _spec(traffic, 0).to_phases():
            b = jax.ShapeDtypeStruct((c, ph.batch_size, ph.input_size),
                                     jnp.int32, sharding=dev)
            p2 = jax.ShapeDtypeStruct(fspec.shape, jnp.float32, sharding=dev)
            fn = engine._phase_scan_jit(ph, fspec)
            report(f"phase scan seq {ph.input_size} batch {ph.batch_size}",
                   fn.lower(p2, None, {"tokens": b, "labels": b},
                            None).compile())
    else:
        from repro.serve import paged as pg

        from bench.lib.serve import page_spec
        ps = page_spec(cfg, traffic)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
        caches = sds(jax.eval_shape(lambda: pg.init_contig_cache(cfg, ps)))
        for gather, m, t in ((False, traffic["slots"], 1),
                             (True, 1, traffic["prefill_chunk"])):
            fn = jax.jit(pg.make_token_fn(cfg, ps, "contig",
                                          gather_rows=gather),
                         donate_argnums=(1,))
            report(f"serve step contig m {m} T {t}", fn.lower(
                sds(wshape), caches, i32(m), i32(m), i32(m), i32(m, t),
                i32(m), i32(m)).compile())


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("flow", "compile"):
        print(__doc__, file=sys.stderr)
        return 2
    what, names = argv[0], argv[1:]
    if not program.import_program():
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not names:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            names = [w["name"] for w in json.load(f)["workloads"]]
    for n in names:
        (flow if what == "flow" else compile_cell)(n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
