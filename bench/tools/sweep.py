#!/usr/bin/env python3
"""The knee sweep behind an open-loop cell's fixed rate, in one process.

    python3 bench/tools/sweep.py --workload <cell> --seconds <s> \\
        --seed <n> --rates 0.5,1,1.5,2

Serves the cell's traffic at each offered rate in turn (``--trace 0``
runs of bench/run.py with the rate changed) and prints, per rate, the
end-to-end metrics and how the backlog moved: the median time to first
token of the window's first and last thirds of requests, and the
requests still unfinished when the window closed.  The highest rate whose
last third waits no longer than its first (within the spread of a
sustained rate) is the knee; the cell's fixed rate is 0.8 of it.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(workload: str, seconds: float, seed: int, rates: list[float],
          **main_kw) -> list[dict]:
    from bench import run, serve
    seen = {}
    orig = serve.run_window

    def spy(*a, **k):
        seen["w"] = orig(*a, **k)
        return seen["w"]

    serve.run_window = spy
    rows = []
    try:
        for rate in rates:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                run.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"],
                         cell_override={"rate_per_s": rate}, **main_kw)
            res = json.loads(buf.getvalue().strip().splitlines()[-1])
            w = seen["w"]
            mine = sorted((r for r in w.records if r.counted),
                          key=lambda r: r.due)
            third = max(1, len(mine) // 3)
            ttft = [(r.first - r.due) * 1e3 for r in mine
                    if r.status == "ok"]
            first = [(r.first - r.due) * 1e3 for r in mine[:third]
                     if r.status == "ok"]
            last = [(r.first - r.due) * 1e3 for r in mine[-third:]
                    if r.status == "ok"]
            open_at_close = sum(1 for r in mine if r.finish > w.end)
            row = {"rate_per_s": rate, "requests": len(mine),
                   "metrics": {k: v["value"]
                               for k, v in res["metrics"].items()},
                   "ttft_median_ms": statistics.median(ttft) if ttft
                   else None,
                   "ttft_median_first_third_ms": statistics.median(first)
                   if first else None,
                   "ttft_median_last_third_ms": statistics.median(last)
                   if last else None,
                   "unfinished_at_close": open_at_close,
                   "drain_s": w.drained - w.end,
                   "correct": res["correct"]}
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        serve.run_window = orig
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args()
    sweep(a.workload, a.seconds, a.seed,
          [float(x) for x in a.rates.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
