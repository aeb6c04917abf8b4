"""Tier-1 CI shard definitions.

The CI matrix splits tier-1 into a core shard (the repro.core interface
layers, fast and mostly in-process), a kernels shard (Pallas kernels and
their oracles — interpret-mode compute-heavy) and a runtime shard
(trainer/server integration, models, dry-run — the subprocess-heavy half),
so the legs run in parallel.  ``--check`` verifies the shards partition the
real test file set, so a new test file cannot silently fall out of CI.

    python tests/shards.py core          # print the shard's files
    python tests/shards.py --check      # verify coverage & disjointness
"""

from __future__ import annotations

import sys
from pathlib import Path

SHARDS = {
    "core": [
        "tests/test_analysis.py",
        "tests/test_analysis_hlo.py",
        "tests/test_cell_specs.py",
        "tests/test_collectives.py",
        "tests/test_datatypes.py",
        "tests/test_epoch.py",
        "tests/test_errors_and_tool.py",
        "tests/test_futures.py",
        "tests/test_hloanalysis.py",
        "tests/test_io.py",
        "tests/test_onesided.py",
        "tests/test_overlap.py",
        "tests/test_requests.py",
        "tests/test_session.py",
        "tests/test_sharding_rules.py",
        "tests/test_topology.py",
    ],
    "kernels": [
        "tests/test_kernels.py",
        "tests/test_ring_attention.py",
        "tests/test_tpu_compile.py",
    ],
    "runtime": [
        "tests/test_checkpoint.py",
        "tests/test_data_pipeline.py",
        "tests/test_distributed_paths.py",
        "tests/test_dryrun_integration.py",
        "tests/test_elastic_multidevice.py",
        "tests/test_elastic_runtime.py",
        "tests/test_engine.py",
        "tests/test_launch.py",
        "tests/test_models.py",
        "tests/test_server.py",
        "tests/test_trainer.py",
        "tests/test_tune.py",
    ],
}


def check() -> int:
    root = Path(__file__).resolve().parents[1]
    actual = {f"tests/{p.name}" for p in (root / "tests").glob("test_*.py")}
    listed: list[str] = [f for files in SHARDS.values() for f in files]
    dupes = {f for f in listed if listed.count(f) > 1}
    missing = actual - set(listed)
    stale = set(listed) - actual
    ok = not (dupes or missing or stale)
    if dupes:
        print(f"files in more than one shard: {sorted(dupes)}", file=sys.stderr)
    if missing:
        print(f"test files missing from every shard: {sorted(missing)}", file=sys.stderr)
    if stale:
        print(f"shard entries with no matching file: {sorted(stale)}", file=sys.stderr)
    if ok:
        print(f"shards cover all {len(actual)} test files, disjointly")
    return 0 if ok else 1


def main(argv: list[str]) -> int:
    if argv == ["--check"]:
        return check()
    if len(argv) == 1 and argv[0] in SHARDS:
        print(" ".join(SHARDS[argv[0]]))
        return 0
    print(f"usage: shards.py --check | {{{','.join(SHARDS)}}}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
