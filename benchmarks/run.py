"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is simulated
microseconds for PS-sim benches, wall-clock microseconds for timing benches,
or the table's headline number where noted in `derived`).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only tableX] \
      [--seed N]

``--seed`` re-bases every seed-accepting bench: each run's
``ScheduleSpec.seed`` (and everything derived from it — model init,
dataset, data-plane streams, phase jitter) shifts together, so one flag
replays the whole table suite at another seed.
"""
from __future__ import annotations

import argparse
import inspect
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale epochs/sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="run a single module (e.g. table3)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed threaded into every bench's "
                         "ScheduleSpec")
    args = ap.parse_args(argv)

    from benchmarks import (autotune_pareto, engine_step, fig13_max_batch,
                            phase_transition, ps_sim_throughput, roofline,
                            serve_throughput, sync_compare,
                            table3_update_factor, table4_time_prediction,
                            table5_worker_sweep, table8_hybrid_cifar,
                            table10_hybrid_imagenet)
    mods = {
        "table4": table4_time_prediction,   # time model first (cheap)
        "engine": engine_step,              # fused vs unfused server update
        "phase": phase_transition,          # overlapped warm compile win
        "ps_sim": ps_sim_throughput,        # compiled-update cache win
        "table10": table10_hybrid_imagenet,
        "fig13": fig13_max_batch,
        "table3": table3_update_factor,
        "table5": table5_worker_sweep,
        "table8": table8_hybrid_cifar,
        "sync": sync_compare,
        "roofline": roofline,
    }
    if args.full:
        # the autotuner search validates ~9 runs; full tier only
        mods["autotune"] = autotune_pareto
        # serving engine: continuous-vs-static + paged-KV gates; full tier
        mods["serve"] = serve_throughput
    if args.only:
        mods = {args.only: {**mods, "autotune": autotune_pareto,
                            "serve": serve_throughput}[args.only]}

    print("name,us_per_call,derived")
    for name, mod in mods.items():
        t0 = time.time()
        kw = {}
        if "seed" in inspect.signature(mod.run).parameters:
            kw["seed"] = args.seed
        try:
            rows = mod.run(quick=not args.full, **kw)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            raise
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        # .3f, not .1f: fast benches finish in well under 100ms and the
        # old format printed a misleading dead-looking 0.0
        print(f"{name}/bench_wall_s,{time.time() - t0:.3f},", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
