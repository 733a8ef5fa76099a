#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, in one process.

    python3 bench/tools/control.py --workload <cell> --seconds <s> \\
        --program-seeds 1,2,3 --control-seeds 4,5,6 [--gaps out.jsonl]

Runs the cell as ``bench/run.py`` does, once per seed: first the program
as the configuration states it, then the control, the program with its
own int8 path switched on (``quant_serving``: int8 weights and int8 KV),
the step below bfloat16 that a later change could be tempted to take.
Each run prints one line: kind, seed, the widest and the mean logit gap
of the served tokens below the float32 reference's best, the share of
served tokens off the reference's best, and the tokens compared;
``--gaps`` also writes each run's per-request gaps, one JSON line a run.
The limit of the cell goes above the program's largest reading and below
the control's smallest.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seconds: float, seeds: list[int], *,
             control: bool, gaps_out=None, **main_kw) -> list[dict]:
    from bench import run
    from bench.models import qwen2
    got = {}
    orig_check, orig_cfg = run.check_outputs, qwen2.serving_config

    def check(*a, **k):
        got.update(orig_check(*a, **k))
        return got

    def cfg(cj, dtype):
        return dataclasses.replace(orig_cfg(cj, dtype), quant_serving=True)

    def load(path, name):
        return qwen2 if name == "bench_family_qwen2" else orig_load(path,
                                                                    name)

    orig_load = run._load_module
    out = []
    run.check_outputs, run._load_module = check, load
    if control:
        qwen2.serving_config = cfg
    try:
        for seed in seeds:
            got.clear()
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                run.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"],
                         **main_kw)
            res = json.loads(buf.getvalue().strip().splitlines()[-1])
            per = got.pop("per_request", [])
            row = {"kind": "control" if control else "program",
                   "seed": seed, "correct": res["correct"],
                   "failed": res["failed"], **got}
            print(json.dumps(row), flush=True)
            if gaps_out is not None:
                gaps_out.write(json.dumps(
                    {**row, "gaps": [[float(x) for x in g] for g in per]})
                    + "\n")
                gaps_out.flush()
            out.append(row)
    finally:
        run.check_outputs, run._load_module = orig_check, orig_load
        qwen2.serving_config = orig_cfg
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--gaps", help="write per-request gaps here (JSONL)")
    a = ap.parse_args()
    seeds = [[int(s) for s in x.split(",") if s]
             for x in (a.program_seeds, a.control_seeds)]
    with contextlib.ExitStack() as stack:
        out = (stack.enter_context(open(a.gaps, "w")) if a.gaps else None)
        rows = readings(a.workload, a.seconds, seeds[0], control=False,
                        gaps_out=out)
        rows += readings(a.workload, a.seconds, seeds[1], control=True,
                         gaps_out=out)
    for kind in ("program", "control"):
        r = [x for x in rows if x["kind"] == kind]
        if r:
            print(f"{kind}: max_logit_gap over seeds "
                  f"{min(x['max_logit_gap'] for x in r)}.."
                  f"{max(x['max_logit_gap'] for x in r)}, mean_logit_gap "
                  f"{min(x['mean_logit_gap'] for x in r)}.."
                  f"{max(x['mean_logit_gap'] for x in r)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
