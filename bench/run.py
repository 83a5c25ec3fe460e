#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, traffic mix and metrics are found by
name from ``BENCHMARK.json`` at the root of the checkout.  Set-up (weights
from the seed, compiles or cache loads, warm-up) is timed as ``setup_s``;
then the window runs for ``--seconds``.  With ``--trace 1`` the window is
traced and the cell's per-layer metrics are reported instead of its
end-to-end ones.  After the window the output check compares what the
timed path produced with the plain reference.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, optionally ``breakdown``, and last
``checks``: each number compared with its limit); the numbers compared
are also the last lines of stderr.  Without a TPU, with fewer chips than
the cell asks for, or without the program under ``src/``, it exits
non-zero before any work and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import cell as cell_mod  # noqa: E402  (starts the clock)
from bench.lib import program  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_cell(name: str) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration, traffic
    mix, limits, and the metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "limits", name + ".json")) as f:
        limits = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in e2e_names]
    return {"workload": w, "conf": conf, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer}


def judge(nums: dict, limits: dict) -> tuple:
    """Each number compared beside its limit, and whether every one is
    within it (a number that is not finite is not)."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return checks, correct


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What a per-layer metric reader gets."""

    def __init__(self, cell, out, reduced, peak):
        self.conf, self.traffic, self.out = cell.conf, cell.traffic, out
        self.trace, self.peak, self.chips = reduced, peak, cell.chips


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="",
                    help="with --trace 1: also write the trace's device op "
                         "events and benchmark spans to this JSON file "
                         "(gzip for a .gz name)")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    chips = spec["workload"]["chips"]
    if not program.import_program():
        print("bench: no program under src/ in this checkout",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (device 0 is {devices[0].platform});"
              " nothing was run", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    from bench.peaks import peak
    kind = devices[0].device_kind
    pk = peak(kind)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return measure(spec, args, chips, pk, kind)


def measure(spec, args, chips, pk, kind) -> int:
    import jax
    cell = cell_mod.Cell(name=args.workload, conf=spec["conf"],
                         traffic=spec["traffic"], seed=args.seed,
                         seconds=args.seconds, tracing=bool(args.trace),
                         trace_dir=TRACE_DIR, chips=chips)

    def on_event(event, *a, **k):
        if "backend_compile" in event or "cache_retrieval" in event:
            cell.compile_events += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    driver = importlib.import_module("bench.lib." + spec["traffic"]["kind"])
    out = driver.run(cell)
    reduced = None
    if cell.tracing:
        from bench.lib import trace
        events = trace.load(cell.trace_dir)
        reduced = trace.Reduced(events)
        if args.dump:
            trace.save(events, args.dump)
    nums, notes = driver.check(cell, out)
    nums["compiles_in_window"] = float(cell.compiles_in_window
                                       + out["compiles_in_window"])
    checks, correct = judge(nums, spec["limits"])

    metrics = {}
    if not cell.tracing:
        metrics["setup_s"] = {"value": cell.setup_s, "unit": "s"}
        for name, (value, unit) in driver.end_to_end(out).items():
            metrics[name] = {"value": value, "unit": unit}
    else:
        run = Run(cell, out, reduced, pk)
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": "tpu", "kind": kind, "count": chips,
              "memory_peak_bytes": cell.memory_peak_bytes}
    result = {"correct": correct,
              "attempted": out.get("attempted", out.get("steps")),
              "failed": out.get("failed", out.get("nonfinite_losses", 0)),
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["notes"] = notes
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
