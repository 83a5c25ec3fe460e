#!/usr/bin/env python3
"""Readings that set the limits of the output check, on the chip at the
cell's own size (the benchmark's own runs never run this).

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--controls 0]

Each reading is judged against the cell's limits (``bench/limits``) as a
run would be, and printed as one JSON line with ``correct``: the program
has to come out correct, the control and every fault not correct.

Training cells, for each seed: the program's set-up and check cycle
(through the cell's own driver, with one window cycle), then, with
``--controls 1``, on the same batches: (a) the control, the reference put
in the program's place with its weights held and every matmul run in
float8 e4m3 (per-tensor scales), the precision below the configuration's
bfloat16; (b) the fault of half the batch left out, the mean taken over
the rest, planted in the reference put in the program's place.  A state
left unchanged reads a gradient of 0, a gap of 1 by the measure, and
needs no run.

Serving cells, for each seed: one run of the cell (set-up and a window of
the benchmark's ``run_seconds``), then for the same sample of served
requests the widest logit gap of the served tokens (the program) and,
with ``--controls 1``, of the tokens the float8 reference puts first (the
control).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench.lib import program  # noqa: E402
from bench.run import judge, load_cell  # noqa: E402


def _cell(spec, seed, seconds):
    from bench.lib.cell import Cell
    return Cell(name=spec["workload"]["name"], conf=spec["conf"],
                traffic=spec["traffic"], seed=seed, seconds=seconds,
                tracing=False, trace_dir="", chips=spec["workload"]["chips"])


def _row(spec, seed, what, nums, **extra) -> dict:
    checks, correct = judge(nums, spec["limits"])
    return {"workload": spec["workload"]["name"], "seed": seed,
            "reading": what, "correct": correct,
            "numbers": {k: c["value"] for k, c in checks.items()}, **extra}


def train_readings(spec, seed: int, controls: bool = True,
                   seconds: float = 0.0) -> list:
    from bench.lib import train
    cell = _cell(spec, seed, seconds)
    out = train.run(cell)
    nums, notes = train.check(cell, out)
    rows = [_row(spec, seed, "program", nums, loss=notes["loss"])]
    if not controls:
        return rows
    conf, traffic = spec["conf"], spec["traffic"]
    batches = out["check"]["batches"]
    ref = train.reference_readings(conf, traffic, seed, batches)
    for what, kw in (("control_fp8", {"mm_name": "fp8"}),
                     ("fault_half_batch", {"keep": 0.5})):
        got = train.reference_readings(conf, traffic, seed, batches, **kw)
        nums = train.compare(got, ref)
        nums.pop("leaves")
        nums["layout_mismatch"] = train.layout_mismatch(got, ref)
        rows.append(_row(spec, seed, what, nums))
    return rows


def serve_readings(spec, seed: int, controls: bool = True,
                   seconds: float = 0.0) -> list:
    from bench.lib import serve
    cell = _cell(spec, seed, seconds)
    out = serve.run(cell)
    length = spec["traffic"]["slot_tokens"]
    rows = []
    for what, mm in (("program", "f32"), ("control_fp8", "fp8")):
        if what != "program" and not controls:
            break
        g = serve.gaps(spec["conf"], seed, out["sample"], mm_name=mm,
                       length=length)
        rows.append(_row(spec, seed, what, {"logit_gap": float(g.max())},
                         tokens=int(g.size)))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    spec = load_cell(args.workload)
    if not program.import_program():
        return 2
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if spec["traffic"]["kind"] == "train":
        fn, seconds = train_readings, 0.0      # the readings need no window
    else:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            fn, seconds = serve_readings, json.load(f)["run_seconds"]
    for s in args.seeds.split(","):
        for row in fn(spec, int(s), bool(args.controls), seconds):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
