#!/usr/bin/env python
"""Deterministic test-file sharding for the CI full-suite matrix.

The full tier-1 suite is ~11-15 min single-process — too long for one
CI job's timeout with headroom — so the ``full-tests`` matrix splits
the test FILES across workers.  Assignment is longest-processing-time
greedy over a measured weight table (seconds on the dev box; unknown
files get a conservative default so new test files are picked up
automatically and never silently dropped): every file in
``tests/test_*.py`` lands in exactly one shard, deterministically.

    python scripts/ci_shard.py --shard 1 --num-shards 3   # file list
    python scripts/ci_shard.py --list                     # full table

The script is import-free of the repo (pure stdlib) so it runs before
dependencies are installed.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

#: measured single-file wall seconds (dev box, 2026-07); refresh when a
#: shard nears its CI timeout.  Files absent here get DEFAULT_WEIGHT.
WEIGHTS = {
    "test_models.py": 470,
    "test_serving_engine.py": 180,
    "test_chaos.py": 90,
    "test_system.py": 58,
    "test_kernels.py": 53,
    "test_spec.py": 40,
    "test_obs.py": 40,
    "test_gemm_backend.py": 34,
    "test_substrates.py": 24,
    "test_paged_attention.py": 21,
    "test_quant_serving.py": 40,
    "test_moe_distributed.py": 15,
    "test_hloanalysis.py": 7,
    "test_kv_pool.py": 7,
    "test_planner.py": 35,
    "test_policy.py": 5,
    "test_precision.py": 6,
    "test_tiling_sharding.py": 6,
    "test_scheduling.py": 4,
    "test_entry_points.py": 25,
    "test_chip_compile.py": 10,
}
DEFAULT_WEIGHT = 45


def assign(files, num_shards):
    """LPT greedy: heaviest file to the lightest shard; ties broken by
    name order, so the assignment is stable across runs and platforms."""
    loads = [0.0] * num_shards
    shards = [[] for _ in range(num_shards)]
    ranked = sorted(files,
                    key=lambda f: (-WEIGHTS.get(os.path.basename(f),
                                                DEFAULT_WEIGHT), f))
    for f in ranked:
        i = min(range(num_shards), key=lambda j: (loads[j], j))
        loads[i] += WEIGHTS.get(os.path.basename(f), DEFAULT_WEIGHT)
        shards[i].append(f)
    return [sorted(s) for s in shards], loads


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shard", type=int, default=None)
    ap.add_argument("--num-shards", type=int, default=3)
    ap.add_argument("--tests-dir", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print every shard with its modeled load")
    args = ap.parse_args(argv)

    tests_dir = args.tests_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tests")
    files = sorted(os.path.relpath(f)
                   for f in glob.glob(os.path.join(tests_dir, "test_*.py")))
    if not files:
        print("no test files found", file=sys.stderr)
        return 1
    shards, loads = assign(files, args.num_shards)
    # invariant: a file in exactly one shard — the matrix covers the suite
    flat = [f for s in shards for f in s]
    assert sorted(flat) == files, "shard assignment lost/duplicated files"

    if args.list or args.shard is None:
        for i, (s, w) in enumerate(zip(shards, loads)):
            print(f"shard {i} (~{w:.0f}s): {' '.join(s)}")
        return 0
    if not 0 <= args.shard < args.num_shards:
        print(f"--shard must be in [0, {args.num_shards})", file=sys.stderr)
        return 1
    print(" ".join(shards[args.shard]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
