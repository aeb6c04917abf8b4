# The multi-pod dry-run driver: it lowers + compiles every (architecture x
# input-shape) cell on the production mesh, prints memory_analysis() /
# cost_analysis(), and records the roofline terms the perf loop consumes.
#
# Usage:
#   python -m repro.launch.dryrun --arch gemma2_9b --shape train_4k
#   python -m repro.launch.dryrun --arch gemma2_9b --shape train_4k --multi-pod
#   python -m repro.launch.dryrun --all [--jobs 3] [--multi-pod]
#   python -m repro.launch.dryrun --all --both   # single- and multi-pod
#
# Each cell writes artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json; the
# orchestrator (--all) skips cells whose artifact already exists (incremental,
# crash-safe), running each cell in a subprocess.

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_ARG_ORDER = {
    "train": ("params", "opt_state", "batch"),
    "prefill": ("params", "batch"),
    "decode": ("params", "cache", "token"),
}
_DONATE = {"train": (0, 1), "prefill": (), "decode": (1,)}


def run_cell(arch: str, shape_name: str, multi_pod: bool, overrides: dict, tag: str,
             plan_spec: str | None = None) -> dict:
    import jax

    from repro.configs import base
    from repro.core import tool
    from repro.launch import mesh as mesh_mod
    from repro.launch import specs as specs_mod
    from repro.launch import steps as steps_mod
    from repro.optim import AdamW

    cfg = base.get_config(arch)
    shape = base.SHAPES[shape_name]
    ok, reason = base.shape_applicable(cfg, shape)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "tag": tag,
        "overrides": overrides,
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    pcfg = base.get_parallel(arch, multi_pod=multi_pod)
    for k, v in overrides.items():
        if not hasattr(pcfg, k):
            raise KeyError(f"unknown ParallelConfig field {k!r}")
        setattr(pcfg, k, v)

    opt = AdamW(lr=1e-4, moment_dtype=pcfg.moment_dtype)
    kind, kwargs, inshard = specs_mod.input_specs(arch, shape_name, mesh, pcfg, opt=opt)
    step = steps_mod.make_step(kind, cfg, pcfg, opt)

    order = _ARG_ORDER[kind]
    args = tuple(kwargs[k] for k in order)
    in_shardings = tuple(inshard[k] for k in order)
    out_shardings = None
    if kind == "train":
        out_shardings = (inshard["params"], inshard["opt_state"], None)
    elif kind == "decode":
        out_shardings = (None, inshard["cache"])
    elif kind == "prefill":
        # pin the output KV/SSM cache sharding (otherwise GSPMD has been
        # observed to replicate it over the model axis — §Perf A3)
        from repro.sharding import rules

        out_struct = jax.eval_shape(step, *args)
        cshard = rules.shardings(
            rules.cache_specs(out_struct[1], mesh, pcfg, cfg), mesh
        )
        out_shardings = (None, cshard)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(
            step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=_DONATE[kind],
        )
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # -- memory analysis (proves it fits) ------------------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            if hasattr(ma, f):
                mem[f] = int(getattr(ma, f))
        mem["peak_bytes_per_device"] = (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
            - mem.get("alias_size_in_bytes", 0)
        )
        print("memory_analysis:", mem)
    except Exception as e:  # pragma: no cover  # lint: allow-broad-except — recorded in the artifact
        mem["error"] = repr(e)

    # -- cost analysis + roofline (per-device module) -------------------------
    # cost_analysis() counts while bodies ONCE (verified; see
    # core/hloanalysis.py) — the corrected, trip-count-aware walk is the
    # number the roofline uses; raw is recorded for comparison.
    from repro.core import hloanalysis

    hlo = compiled.as_text()
    raw = tool.roofline_terms(compiled, hlo_text=hlo, chips=1)
    cost = hloanalysis.analyze_hlo(hlo)
    terms = {
        "compute_s": cost.flops / tool.PEAK_FLOPS_BF16,
        "memory_s": cost.bytes / tool.HBM_BANDWIDTH,
        "collective_s": cost.collectives.total_operand_bytes / tool.ICI_BANDWIDTH,
        "collective_wire_s": cost.collectives.total_wire_bytes / tool.ICI_BANDWIDTH,
        "hlo_flops": cost.flops,
        "hlo_bytes": cost.bytes,
        "collectives": cost.collectives.as_dict(),
    }
    terms["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
    print("corrected: flops=%.3e bytes=%.3e coll=%.3e | raw cost_analysis: flops=%.3e"
          % (cost.flops, cost.bytes, cost.collectives.total_operand_bytes, raw["hlo_flops"]))

    # useful-model-FLOPs ratio
    n_active = cfg.active_param_count()
    tokens = {
        "train": shape.global_batch * shape.seq_len,
        "prefill": shape.global_batch * shape.seq_len,
        "decode": shape.global_batch,
    }[kind]
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_flops_global = terms["hlo_flops"] * chips
    record.update(
        status="ok",
        kind=kind,
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        roofline=terms,
        roofline_raw_uncorrected=raw,
        model_flops=model_flops,
        hlo_flops_global=hlo_flops_global,
        useful_flop_ratio=(model_flops / hlo_flops_global) if hlo_flops_global else None,
        params=cfg.param_count(),
        active_params=n_active,
        n_hlo_lines=hlo.count("\n"),
    )

    # --plan: record the candidate's ANALYTIC roofline next to the measured
    # cell terms, so the tuner's predicted-vs-measured validation
    # (repro.tune.predicted_vs_measured, benchmarks/roofline.py --regret)
    # reads both sides from one artifact
    if plan_spec:
        from repro.tune import score as tune_score

        plan = (
            base.parse_plan(plan_spec, devices=chips)
            if plan_spec != "auto"
            else None
        )
        if plan is None:
            from repro.tune import search as tune_search

            plan = tune_search.search(
                cfg, shape, chips, space=base.plan_space(arch),
                default_remat=pcfg.remat,
            ).plan
        predicted = tune_score.score_plan(
            cfg, shape, plan, default_remat=pcfg.remat
        )
        record.update(
            plan=dataclasses.asdict(plan),
            plan_slug=plan.slug(),
            predicted_roofline=predicted.as_dict(),
        )
    return record


def artifact_path(arch: str, shape: str, multi_pod: bool, tag: str) -> Path:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    stem = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
    return ARTIFACTS / f"{stem}.json"


def _cell_done(path: Path, overrides: dict, tag: str) -> bool:
    """Incremental-skip key: the cell is done only when the artifact on disk
    was produced by the SAME (overrides, tag) request.  Existence alone used
    to be the key, so ``--all --overrides ...`` silently reused artifacts
    recorded under different overrides."""

    if not path.exists():
        return False
    try:
        rec = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return False          # unreadable/torn artifact: rerun the cell
    return rec.get("overrides", {}) == overrides and rec.get("tag", "") == tag


def _cell_cmd(arch, shape, multi_pod, overrides, tag):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch, "--shape", shape]
    if multi_pod:
        cmd.append("--multi-pod")
    if overrides:
        cmd += ["--overrides", json.dumps(overrides)]
    if tag:
        cmd += ["--tag", tag]
    return cmd


def orchestrate(jobs: int, multi_pod_modes: list[bool], overrides: dict, tag: str,
                archs=None, shapes=None, timeout: int = 3600):
    from repro.configs import base

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    cells = []
    for mp in multi_pod_modes:
        for arch in archs or base.ARCHITECTURES:
            for shape in shapes or list(base.SHAPES):
                p = artifact_path(arch, shape, mp, tag)
                if _cell_done(p, overrides, tag):
                    continue
                cells.append((arch, shape, mp))
    print(f"{len(cells)} cells to run ({jobs} workers)")

    def one(cell):
        arch, shape, mp = cell
        t0 = time.time()
        proc = subprocess.run(
            _cell_cmd(arch, shape, mp, overrides, tag),
            capture_output=True, text=True, timeout=timeout,
            env={**os.environ, "PYTHONPATH": "src"},
            cwd=str(Path(__file__).resolve().parents[3]),
        )
        status = "ok" if proc.returncode == 0 else "FAIL"
        print(f"[{status}] {arch} {shape} mp={mp} ({time.time()-t0:.0f}s)")
        if proc.returncode != 0:
            tail = "\n".join(proc.stdout.splitlines()[-5:] + proc.stderr.splitlines()[-15:])
            print(tail)
        return proc.returncode

    with ThreadPoolExecutor(max_workers=jobs) as ex:
        rcs = list(ex.map(one, cells))
    print(f"done: {rcs.count(0)}/{len(rcs)} ok")
    return 0 if all(r == 0 for r in rcs) else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true", help="--all over both meshes")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--overrides", default="{}", help="ParallelConfig overrides (JSON)")
    ap.add_argument("--tag", default="", help="artifact suffix for perf experiments")
    ap.add_argument(
        "--plan",
        default=None,
        help="record this ParallelPlan candidate's analytic roofline terms "
        "in the artifact ('auto' = the repro.tune winner for the cell); "
        "the artifact tag defaults to the plan slug",
    )
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)
    overrides = json.loads(args.overrides)

    if args.all:
        modes = [False, True] if args.both else [args.multi_pod]
        archs = [args.arch] if args.arch else None
        shapes = [args.shape] if args.shape else None
        return orchestrate(args.jobs, modes, overrides, args.tag, archs, shapes, args.timeout)

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    tag = args.tag
    if args.plan and not tag:
        # per-candidate artifacts must not clobber the base cell
        tag = "plan-" + (args.plan if args.plan != "auto" else "auto").replace(
            ",", "_").replace("=", "-").replace(":", "-")
    try:
        record = run_cell(args.arch, args.shape, args.multi_pod, overrides, tag,
                          plan_spec=args.plan)
    except Exception:
        traceback.print_exc()
        return 1
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = artifact_path(args.arch, args.shape, args.multi_pod, tag)
    path.write_text(json.dumps(record, indent=1))
    print("wrote", path, "status:", record["status"])
    return 0


if __name__ == "__main__":
    # the production mesh is 512 virtual CPU devices; jax fixes the device
    # count when its backend starts, which no import above does
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
