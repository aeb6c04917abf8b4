"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2_9b --smoke \
        --steps 50 --batch 8 --seq 256 --mesh 1x1

Runs the full Trainer (checkpoint/restart, straggler guard, fault injection)
on whatever devices exist; ``--smoke`` selects the reduced same-family config
so the loop runs on CPU.  The production 256/512-chip lowering of the same
step function is exercised by ``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse
import json
import logging


def resolve_plan(args, cfg, devices):
    """One parser for every layout flag: ``--plan`` wins (``auto`` runs the
    repro.tune roofline search for this cell); the deprecated
    ``--pipeline-stages``/``--ring-attention`` flags are aliases that build
    the equivalent spec and route through :func:`repro.configs.base.parse_plan`.
    Returns ``None`` (pure data plan) when nothing asked for a fold."""

    from repro.configs import base

    if args.plan:
        if args.plan == "auto":
            from repro import tune as tune_mod

            shape = base.ShapeConfig(
                f"train_{args.seq}", args.seq, args.batch, "train"
            )
            # uncalibrated: the plan depends on committed code only, never
            # on dry-run artifacts a checkout may or may not hold
            result = tune_mod.tune(
                args.arch, shape, devices, config=cfg,
                space=base.plan_space(args.arch), calibrate=False,
            )
            logging.getLogger("repro.launch").info(
                "autotuned plan: %s (predicted %.4fs over %d candidates)",
                result.plan.slug(), result.score.step_s, result.n_candidates,
            )
            return result.plan
        return base.parse_plan(args.plan, devices=devices)
    parts = []
    if args.pipeline_stages > 1:
        parts.append(f"stage={args.pipeline_stages}")
        parts.append(f"micro={args.pipeline_microbatches}")
    if args.ring_attention > 1:
        parts.append(f"ring={args.ring_attention}")
    if parts:
        return base.parse_plan(",".join(parts), devices=devices)
    return None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="auto", help="DxM, e.g. 2x4 (auto: all devices x 1)")
    ap.add_argument(
        "--pset",
        default="repro://world",
        help="session process set the trainer owns (e.g. repro://host/0)",
    )
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument(
        "--async-checkpoint",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="checkpoint writes ride the I/O request engine and overlap the "
        "next persistent step (--no-async-checkpoint joins each save)",
    )
    ap.add_argument(
        "--plan",
        default=None,
        help="the unified parallelism plan: 'auto' (run the repro.tune "
        "roofline autotuner for this cell), positional dims 'DxSxExT' "
        "(e.g. '2x4' = 2-way data x 4 pipeline stages), or key=value pairs "
        "'data=2,ring=4,micro=2,buckets=4,remat=dots'",
    )
    ap.add_argument(
        "--pipeline-stages",
        type=int,
        default=0,
        help="alias for --plan stage=N (same parser; 0/1 = GSPMD step)",
    )
    ap.add_argument(
        "--pipeline-microbatches",
        type=int,
        default=2,
        help="alias for --plan micro=N (with --pipeline-stages)",
    )
    ap.add_argument(
        "--ring-attention",
        type=int,
        default=0,
        help="alias for --plan ring=N: a periodic cart ring folded onto the "
        "model axis; attention shards the sequence over the ring and "
        "rotates KV via cart_shift(+1) permutes (0/1 = dense attn)",
    )
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument(
        "--evict-at",
        default=None,
        metavar="STEP:RANK",
        help="elastic fault drill: evict RANK at STEP; the trainer shrinks "
        "its epoch to the survivors, restores the last committed manifest "
        "and continues — no job restart",
    )
    ap.add_argument(
        "--admit-at",
        default=None,
        metavar="STEP[:COUNT]",
        help="elastic grow drill: hot-join COUNT spare ranks (default 1) at "
        "STEP, re-folding the data axis",
    )
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None, help="write metrics history JSON here")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")

    from repro.configs import base
    from repro.launch import use_compile_cache
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.faults import FaultInjector
    from repro.runtime.trainer import Trainer, TrainerConfig

    use_compile_cache()
    cfg = base.get_smoke_config(args.arch) if args.smoke else base.get_config(args.arch)
    pcfg = base.get_parallel(args.arch)
    if args.mesh == "auto":
        comm = make_host_communicator(pset=args.pset)
    else:
        d, m = (int(t) for t in args.mesh.split("x"))
        comm = make_host_communicator(d, m, pset=args.pset)

    plan = resolve_plan(args, cfg, comm.group().size())
    tcfg = TrainerConfig(
        steps=args.steps,
        lr=args.lr,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every or max(1, args.steps // 2),
        async_checkpoint=args.async_checkpoint,
        log_every=args.log_every,
        plan=plan,
    )
    injector = None
    if args.inject_failure_at is not None:
        injector = FaultInjector(fail_at_steps=(args.inject_failure_at,))
    if args.evict_at is not None:
        step, _, rank = args.evict_at.partition(":")
        injector = (injector or FaultInjector()).evict_rank(int(step), int(rank or 0))
    if args.admit_at is not None:
        step, _, count = args.admit_at.partition(":")
        injector = (injector or FaultInjector()).admit_rank(int(step), int(count or 1))
    trainer = Trainer(
        cfg, pcfg, tcfg, comm, seq_len=args.seq, global_batch=args.batch, injector=injector
    )
    result = trainer.run()
    print(json.dumps({k: v for k, v in result.items() if k != "metrics"}, indent=1))
    if result["metrics"]:
        first, last = result["metrics"][0], result["metrics"][-1]
        print(f"loss: {first['loss']:.4f} -> {last['loss']:.4f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
