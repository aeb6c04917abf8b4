"""Serving launcher: batched prefill + decode with the runtime Server.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2_9b --smoke \
        --requests 8 --prompt-len 64 --new-tokens 16

``--disaggregate`` splits the serving process set into prefill and decode
worker groups (``<pset>/prefill`` / ``<pset>/decode``): prefill ranks
compute the KV cache and stream it into the decode ranks' RMA window
(``--kv-pages`` pages per handoff); decode rides its persistent request.
``--fanout P:D`` makes that split heterogeneous (2:6, 3:5, ...) with the
KV routed along the dist-graph fan-out adjacency.  ``--continuous-batching``
serves through the paged-KV engine instead of one fixed batch: requests are
admitted into the running decode iteration and retire at their stop token.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default="auto")
    ap.add_argument(
        "--pset",
        default="repro://world",
        help="session process set the server owns (e.g. repro://host/1)",
    )
    ap.add_argument(
        "--disaggregate",
        action="store_true",
        help="split the pset into prefill/decode groups; KV crosses via RMA",
    )
    ap.add_argument("--prefill-fraction", type=float, default=0.5)
    ap.add_argument("--kv-pages", type=int, default=4)
    ap.add_argument(
        "--plan",
        default=None,
        help="the unified parallelism plan: 'auto' (repro.tune roofline "
        "search on the prefill cell), 'DxT' dims, or key=value pairs; "
        "'fanout=P:D' selects the heterogeneous disaggregated split",
    )
    ap.add_argument(
        "--fanout",
        default=None,
        metavar="P:D",
        help="alias for --plan fanout=P:D (same parser): heterogeneous "
        "prefill:decode worker split (e.g. 2:6, 3:5); implies "
        "--disaggregate and replaces --prefill-fraction",
    )
    ap.add_argument(
        "--continuous-batching",
        action="store_true",
        help="serve through the continuous-batching engine (paged KV block "
        "pool, in-flight admission) instead of one fixed batch",
    )
    args = ap.parse_args(argv)
    if args.plan and args.fanout:
        ap.error("--fanout is an alias for --plan fanout=P:D; pass one")
    if args.plan and args.mesh != "auto":
        ap.error("--plan subsumes --mesh (the plan's data/model dims are "
                 "the mesh); drop one of the two")
    if args.fanout is not None:
        args.disaggregate = True
    if args.disaggregate and args.mesh != "auto":
        ap.error("--mesh has no effect with --disaggregate (group layouts "
                 "come from --prefill-fraction/--fanout); drop one of the two")

    from repro.configs import base
    from repro.core.session import default_session
    from repro.launch import use_compile_cache
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.server import (
        DisaggregatedServer,
        Request,
        Server,
        ServerConfig,
    )

    use_compile_cache()
    cfg = base.get_smoke_config(args.arch) if args.smoke else base.get_config(args.arch)
    pcfg = base.get_parallel(args.arch)

    # one parser for every layout flag: --plan wins; --fanout routes through
    # the same grammar as "fanout=P:D"
    plan = None
    if args.plan == "auto":
        shape = base.ShapeConfig(
            f"prefill_{args.prompt_len}", args.prompt_len, args.requests,
            "prefill",
        )
        from repro import tune as tune_mod

        # uncalibrated: the plan depends on committed code only, never on
        # dry-run artifacts a checkout may or may not hold
        result = tune_mod.tune(
            args.arch, shape, config=cfg, space=base.plan_space(args.arch),
            calibrate=False,
        )
        plan = result.plan
        print(f"autotuned plan: {plan.slug()} "
              f"(predicted {result.score.step_s:.4f}s)")
    elif args.plan:
        plan = base.parse_plan(
            args.plan, devices=default_session().group().size()
        )
    elif args.fanout is not None:
        plan = base.parse_plan(
            f"fanout={args.fanout}", devices=default_session().group().size()
        )
    if plan is not None and plan.fanout is not None:
        args.disaggregate = True
    if args.continuous_batching and args.disaggregate:
        ap.error("--continuous-batching schedules a single-group Server; "
                 "it does not compose with --disaggregate/--fanout yet")

    comm = None
    if not args.disaggregate:
        if plan is not None:
            d, m = (plan.fold_dims() + (1,))[:2]
            comm = make_host_communicator(d, m, pset=args.pset)
        elif args.mesh == "auto":
            comm = make_host_communicator(pset=args.pset)
        else:
            d, m = (int(t) for t in args.mesh.split("x"))
            comm = make_host_communicator(d, m, pset=args.pset)

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        toks = rng.integers(1, cfg.vocab_size, size=(args.prompt_len,), dtype=np.int32)
        extra = {}
        if cfg.family == "vlm":
            extra["image_embeds"] = rng.standard_normal(
                (cfg.num_image_tokens, 1152), dtype=np.float32
            )
        if cfg.family == "encdec":
            extra["frames"] = rng.standard_normal(
                (args.prompt_len, cfg.d_model), dtype=np.float32
            )
        reqs.append(Request(tokens=toks, extra=extra))

    scfg = ServerConfig(max_batch=min(args.requests, 4) if args.continuous_batching
                        else args.requests,
                        max_new_tokens=args.new_tokens,
                        temperature=args.temperature)
    if args.disaggregate:
        fanout = plan.fanout if plan is not None else None
        server = DisaggregatedServer(
            cfg, pcfg, scfg,
            pset=args.pset,
            prefill_fraction=args.prefill_fraction,
            kv_pages=args.kv_pages,
            fanout=fanout,
        )
    else:
        server = Server(cfg, pcfg, scfg, comm)

    if args.continuous_batching:
        from repro.runtime.engine import Engine, EngineConfig

        eng = Engine(server, EngineConfig(prompt_bucket=args.prompt_len))
        handles = [eng.submit(r) for r in reqs]
        eng.run()
        stats = eng.stats()
        print("generated lengths:", [len(h.generated) for h in handles])
    else:
        tokens, stats = server.generate(reqs)
        print("generated shape:", tokens.shape)
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v for k, v in stats.items()},
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
