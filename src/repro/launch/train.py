"""End-to-end training driver — thin front-end over ``repro.engine``.

Runs the paper's three schemes on real (synthetic) data:
  --scheme baseline   single (large) batch size
  --scheme dbl        dual-batch learning (weighted SPMD step)
  --scheme hybrid     dual-batch x cyclic progressive (seq-len scheduled)

Each scheme is ONE declarative ``repro.api.ScheduleSpec`` (``build_spec``)
executed by ``repro.api.run`` on the SPMD backend.

With ``--optimizer sgd`` the dual-batch parameter update takes the fused
Pallas ``dbl_merge`` server-update hot path (paper §3.4); pass
``--no-fused-merge`` to fall back to the unfused scale/add/apply sequence.

Batches come from the resolution-aware ``repro.data.DataPlane`` (one input
pipeline for both backends): per-(phase, worker, step) counter streams,
double-buffered scan staging (``--no-prefetch`` to disable) and overlapped
next-phase warm compile (``--no-overlap-compile``).

Works on any arch config at reduced scale on CPU (examples/ wire it to a
~100M-class model) and on the production mesh unchanged.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
      --reduced --steps 200 --scheme hybrid
"""
from __future__ import annotations

import argparse
import json

import jax

from repro import models
from repro.api import RunConfig, ScheduleSpec
from repro.api import run as api_run
from repro.configs import ARCH_IDS, get_config, reduced
from repro.data import DataPlane, SyntheticTokens
from repro.engine import TrainEngine
from repro.optim import make_optimizer


def build_spec(args) -> ScheduleSpec:
    """The CLI's scheme as ONE declarative ``ScheduleSpec`` (the only
    scheme-specific branch — everything downstream is ``repro.api.run``).
    The time model is shape-relative (a=1, b=24.6): only its ratios reach
    the dual-batch solver."""
    spec = ScheduleSpec(
        scheme=args.scheme, input_size=args.seq, axis="seq_len",
        batch_size=args.global_batch, dataset_size=args.global_batch * 64,
        n_workers=4, n_small=args.n_small, k=args.k, n_steps=args.steps,
        lr=args.lr, micro_steps=args.micro_steps, tm_a=1.0, tm_b=24.6,
        seed=args.seed)
    if args.scheme == "hybrid":
        # CPL sub-stages low -> high seq (paper's 2-sub-stage split), the
        # dual-batch plan re-solved per sub-stage at its memory-maximal B_L
        sub_sizes = (max(16, args.seq // 2), args.seq)
        spec = spec.replace(sub_sizes=sub_sizes,
                            sub_dropouts=(0.0,) * len(sub_sizes),
                            stage_epochs=(len(sub_sizes),),
                            stage_lrs=(args.lr,))
    return spec


def build_phases(args):
    """Legacy view: the spec's lowered Phase list."""
    return build_spec(args).to_phases()


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS),
                    default="phi3-mini-3.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--scheme", choices=("baseline", "dbl", "hybrid"),
                    default="hybrid")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--k", type=float, default=1.05)
    ap.add_argument("--n-small", type=int, default=3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--micro-steps", type=int, default=0,
                    help="micro-update mode: small-group local SGD steps "
                         "per global step")
    ap.add_argument("--no-fused-merge", dest="fused", action="store_false",
                    default=True,
                    help="unfused server update (dual-batch SGD path)")
    ap.add_argument("--no-scan-loop", dest="scan", action="store_false",
                    default=True,
                    help="step-at-a-time loop instead of the scan-compiled "
                         "flat-store phase loop (fused SGD path)")
    ap.add_argument("--server-momentum", type=float, default=0.0,
                    help="PS-server momentum folded into the fused kernel "
                         "pass (dual-batch SGD scan path)")
    ap.add_argument("--no-overlap-compile", dest="overlap",
                    action="store_false", default=True,
                    help="compile each phase cold at its boundary instead "
                         "of AOT-compiling the next phase in the background")
    ap.add_argument("--no-prefetch", dest="prefetch", action="store_false",
                    default=True,
                    help="stage scan chunks synchronously instead of "
                         "double-buffering them on a background thread")
    ap.add_argument("--ckpt", default="",
                    help="checkpoint dir; saves at every phase boundary")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest phase-boundary checkpoint "
                         "in --ckpt")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _sgd_server(args) -> bool:
    """Plain-SGD dual-batch -> the paper §3.4 server update (fused
    dbl_merge hot path).  That update has no momentum/weight-decay state,
    so the optimizer is built to match — otherwise the CLI would silently
    claim momentum it never applies.  Stateful optimizers (adamw) keep the
    weighted-mean path."""
    return (args.optimizer == "sgd" and args.scheme in ("dbl", "hybrid")
            and args.micro_steps == 0)


def parse_args(argv=None) -> argparse.Namespace:
    """The CLI's flags, validated (``train`` takes the result)."""
    ap = _parser()
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt:
        ap.error("--resume requires --ckpt (the directory to resume from)")
    if args.server_momentum and not _sgd_server(args):
        ap.error("--server-momentum needs the dual-batch SGD server path "
                 "(--optimizer sgd, --scheme dbl/hybrid, no --micro-steps)")
    if args.server_momentum and not (args.scan and args.fused):
        ap.error("--server-momentum needs the fused scan loop "
                 "(drop --no-scan-loop / --no-fused-merge)")
    return args


def run(argv=None):
    """CLI entry: parse flags, pick the ``--arch`` config, train."""
    args = parse_args(argv)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    res, _ = train(cfg, args)
    return [_to_cli_rec(r) for r in res.history]


def train(cfg, args, *, log_every: int = 20, mesh=None):
    """Everything after flag parsing, for any ``ModelConfig``.

    ``args`` is a ``parse_args`` namespace; ``cfg`` replaces ``--arch`` /
    ``--reduced``.  ``mesh`` spreads the dual-batch workers over devices
    (``TrainEngine(mesh=...)``, the per-step sharded path).  Prints one
    JSON record every ``log_every`` steps and returns
    ``(RunResult, TrainEngine)``.
    """
    data = SyntheticTokens(vocab=min(cfg.vocab_size, 256), seed=args.seed,
                           n_examples=max(4096, args.global_batch * 64))
    params = models.init_params(cfg, jax.random.PRNGKey(args.seed))

    spec = build_spec(args)
    phases = spec.to_phases()
    sgd_server = _sgd_server(args)
    if sgd_server:
        opt = make_optimizer("sgd", momentum=0.0, weight_decay=0.0)
        mom = (f"server momentum {args.server_momentum} in-kernel"
               if args.server_momentum else "no momentum")
        print("# dual-batch SGD: paper §3.4 server update "
              f"({'fused dbl_merge' if args.fused else 'unfused'} path, "
              f"{mom}, no weight decay)")
    else:
        opt = make_optimizer(args.optimizer, weight_decay=0.01)
    opt_state = opt.init(params)
    engine = TrainEngine(cfg, opt, sgd_server=sgd_server,
                         fused_merge=("auto" if args.fused else False),
                         scan_loop=("auto" if args.scan else False),
                         server_momentum=(args.server_momentum
                                          if sgd_server else 0.0),
                         overlap_compile=args.overlap, mesh=mesh)

    # the DataPlane is the batch_fn: counter-keyed per-(phase, worker,
    # step) streams (stateless in gstep, so a phase-boundary resume
    # replays the uninterrupted run's stream exactly), host-side seq-len
    # cropping, double-buffered scan staging and warm-compile structs
    plane = DataPlane(data, seed=spec.seed, prefetch=args.prefetch)

    def log_fn(rec):
        print(json.dumps(_to_cli_rec(rec)))

    res = api_run(spec,
                  RunConfig(backend="spmd", prefetch=args.prefetch,
                            ckpt_dir=args.ckpt or None, resume=args.resume,
                            log_every=log_every, log_fn=log_fn),
                  init_params=params, opt_state=opt_state, engine=engine,
                  plane=plane)
    if res.resumed_from is not None:
        print(f"# resumed from phase boundary {res.resumed_from}")
    if args.ckpt:
        print(f"saved {len(phases) - (res.resumed_from or 0)} phase-boundary "
              f"checkpoint(s) -> {args.ckpt}")
    return res, engine


def _to_cli_rec(rec: dict) -> dict:
    return {"step": rec["step"], "seq": rec["size"], "batch": rec["batch"],
            "loss": rec["loss"], "tokens": rec["tokens"],
            "wall_s": rec["wall_s"]}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
