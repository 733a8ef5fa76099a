#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  Everything is found by name:

    BENCHMARK.json                the cells and metrics
    <config file>                 sizes of the model, named in BENCHMARK.json;
                                  its "family" names bench/models/<family>.py,
                                  which draws the weights and holds the float32
                                  reference
    bench/mixes/<traffic>.json    one traffic mix (see bench/traffic.py)
    bench/cells/<workload>.json   the cell's engine sizes, its fixed rate and
                                  the gap statistics its correctness check
                                  compares, each with its limit
    bench/metrics/<metric>.py     one reader per metric: read(run) -> number
                                  or None

The run makes its weights and requests from ``--seed``, warms up every
shape the cell's traffic uses (set-up), serves the traffic and measures
for ``--seconds``, follows every request due in the window to its end,
then checks a sample of what was served against the float32 reference.
With ``--trace 1`` the window runs under the profiler and the per-layer
metrics are reported; with ``--trace 0`` the end-to-end ones.  The last
line of standard output is one JSON object; the last lines of standard
error give each number compared beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where the traced run writes its profile (removed once read)
TRACE_DIR = ROOT / ".bench_trace"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(Exception):
    pass


#: the device every line of the run names, once JAX has found it
_DEVICE = ["no device yet"]


def _say(msg: str) -> None:
    print(f"[bench {_DEVICE[0]}] {msg}", file=sys.stderr, flush=True)


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    name: str
    seconds: float
    mix: dict
    cell: dict
    m: dict                 # model dimensions (family.dims)
    window: object          # bench.serve.Window
    counted: list           # the Records due in the window
    setup_s: float
    peaks: dict
    trace: dict | None = None       # bench.trace.reduce(...)
    events: list | None = None      # the engine tracer's events
    counters: dict | None = None    # registry counter deltas (window)


def _metrics_for(spec: dict, cell_name: str, traced: bool) -> list[dict]:
    e2e = [m for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class _CompileCount:
    def __init__(self):
        self.on = False
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **_):
        if self.on and event == _COMPILE_EVENT:
            self.n += 1


def check_outputs(family, cj: dict, seed: int, dtype: str, records: list
                  ) -> dict:
    """How far each served token's logit lies below the float32
    reference's best logit at its position, over every token of
    ``records``: the mean (the number compared), the widest, the share of
    tokens that are not the reference's best, and each request's gaps."""
    import numpy as np
    seqs, rows, toks = [], [], []
    for r in records:
        p = np.asarray(r.req.prompt, np.int32)
        t = np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([p, t]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(t)))
        toks.append(t)
    res = (family.reference_gaps(cj, seed, dtype, seqs, rows, toks)
           if seqs else [])
    per = [g for g, _ in res]
    gaps = np.concatenate(per) if per else np.zeros(0)
    if not len(gaps):
        return {"max_logit_gap": math.inf, "mean_logit_gap": math.inf,
                "tokens": 0, "mismatch_share": 1.0, "per_request": []}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()), "tokens": int(len(gaps)),
            "mismatch_share": float(np.mean(gaps > 0)), "per_request": per}


def main(argv=None, *, bench_file: pathlib.Path | None = None,
         data_dir: pathlib.Path = BENCH_DIR, platform: str = "tpu",
         cell_override: dict | None = None) -> int:
    """One run.  The keyword arguments serve the tests and the tools in
    bench/tools: another BENCHMARK.json and data directory, the CPU in
    place of the chip, and changed cell settings (the knee sweep's
    rates)."""
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((bench_file or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}")
    wl = cells[args.workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    CACHE_DIR.mkdir(exist_ok=True)
    import jax
    import numpy as np

    from bench import costs, serve, traffic
    from bench import trace as tracemod

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != platform or len(devs) < wl["chips"]:
        raise NoDevice(f"needs {wl['chips']} {platform} device(s); JAX "
                       f"found {len(devs)} on platform {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    _DEVICE[0] = f"{dev.platform}:{dev.device_kind} x{len(devs)}"
    _say(f"device {device}; jax {jax.__version__}")
    pk = costs.peaks(dev.device_kind)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import Telemetry
    from repro.serving import ContinuousEngine, Request
    enable_compile_cache()
    compiles = _CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    cj = json.loads((ROOT / conf["file"]).read_text())
    family = _load_module(BENCH_DIR / "models" / f"{cj['family']}.py",
                          f"bench_family_{cj['family']}")
    mix = traffic.load_mix(wl["traffic"], data_dir / "mixes")
    cell = json.loads((data_dir / "cells" / f"{wl['name']}.json")
                      .read_text()) | (cell_override or {})
    dtype = cj["torch_dtype"]
    m = family.dims(cj)

    # -- set-up: traffic, weights, engine, warm-up ------------------------
    t0 = time.perf_counter()
    tr = traffic.generate(mix, seed=args.seed, seconds=args.seconds,
                          rate=cell.get("rate_per_s"), vocab=m["v"],
                          max_len=cell["max_len"])
    t_traffic = time.perf_counter() - t0
    t0 = time.perf_counter()
    cfg = family.serving_config(cj, dtype)
    params = family.to_program_tree(
        cfg, family.make_weights(cj, args.seed, dtype))
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = ContinuousEngine(
        cfg, params, slots=cell["slots"], max_len=cell["max_len"],
        block_size=cell["block_size"], kv_blocks=cell.get("kv_blocks"),
        prefill_chunk=cell["prefill_chunk"], policy="fifo",
        seed=args.seed & 0x7FFFFFFF,
        telemetry=Telemetry.on() if args.trace else None)
    del params

    def make_request(req):
        # eos=-1: every request generates exactly its drawn length
        return Request(rid=req.rid, prompt=req.prompt,
                       max_new_tokens=req.max_new, temperature=0.0, eos=-1)

    # warm-up: one request of two chunks and two decode steps compiles
    # every program the window runs; sessions send their system prompts
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 1])
    warm = [rng.integers(0, m["v"], cell["prefill_chunk"] + 1,
                         dtype=np.int32)] + list(tr.warm_prompts)
    for i, p in enumerate(warm):
        engine.submit(Request(rid=-1 - i, prompt=p, max_new_tokens=3,
                              temperature=0.0, eos=-1))
    n_done = 0
    while n_done < len(warm):
        engine.step()
        n_done += len(engine.drain_results())
    jax.effects_barrier()
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    _say(f"set-up {setup_s:.3f} s: traffic {t_traffic:.3f}, weights "
         f"{t_weights:.3f}, engine + warm-up {t_warm:.3f} "
         f"({len(warm)} warm-up requests)")

    # -- the measured window ------------------------------------------------
    before = dict(engine.metrics.snapshot().get("counters", {}))
    traced = {}

    # The profiler starts as the window opens (a start takes some tens of
    # milliseconds) and stops only once the window's last request has
    # ended: a stop takes a minute or more, and inside the window it
    # would hold up every request in flight.  The bench.window span marks
    # the window inside the trace.  Python's own tracer stays off: it
    # would record every call of the engine's host loop.
    def on_open():
        compiles.on = True
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t0 = time.perf_counter()
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            traced["start_s"] = time.perf_counter() - t0
            traced["span"] = jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN)
            traced["span"].__enter__()

    def on_close():
        compiles.on = False
        if args.trace:
            traced["span"].__exit__(None, None, None)

    win = serve.run_window(engine, tr, make_request, on_open=on_open,
                           on_close=on_close)
    if args.trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        _say(f"profiler started in {traced['start_s']:.3f} s, stopped in "
             f"{time.perf_counter() - t0:.3f} s")
    after = dict(engine.metrics.snapshot().get("counters", {}))
    counted = [r for r in win.records if r.counted]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    _say(f"window {args.seconds} s: {len(counted)} requests due, "
         f"{win.steps} engine steps, drained "
         f"{win.drained - win.end:.3f} s after close; compiles in window "
         f"{compiles.n}; longest step {win.longest_step:.3f} s, longest "
         f"garbage collection {win.longest_gc:.3f} s")

    run = Run(name=wl["name"], seconds=args.seconds, mix=mix, cell=cell,
              m=m, window=win, counted=counted, setup_s=setup_s, peaks=pk,
              counters={k: after.get(k, 0) - before.get(k, 0)
                        for k in after})
    if args.trace:
        run.events = list(engine.obs.tracer.events)
        t0 = time.perf_counter()
        run.trace = tracemod.reduce(tracemod.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        _say(f"trace read in {time.perf_counter() - t0:.3f} s")
    device["memory_peak_bytes"] = peak

    def read_metric(name: str):
        reader = _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                              "bench_metric_" + name.replace(".", "_"))
        return reader.read(run)

    metrics = {}
    for md in _metrics_for(spec, wl["name"], bool(args.trace)):
        v = read_metric(md["name"])
        if v is not None:
            metrics[md["name"]] = {"value": float(v), "unit": md["unit"]}
    if args.trace:
        # what the end-to-end metrics read in this run, to show how far
        # tracing moves the window from the timed runs' (not reported)
        for md in _metrics_for(spec, wl["name"], False):
            _say(f"traced window's {md['name']}: {read_metric(md['name'])}")

    # -- correctness, once the program's state is freed --------------------
    del engine
    gc.collect()
    t0 = time.perf_counter()
    # every request due in the window, each with all its tokens
    done = [r for r in counted if r.status == "ok"]
    chk = check_outputs(family, cj, args.seed, dtype, done)
    short = sum(1 for r in done if len(r.tokens) != r.req.max_new)
    due = sum(r.req.max_new for r in counted)
    # the cell's file names each gap statistic compared and its limit
    checks = {k: {"value": chk[k], "limit": lim}
              for k, lim in cell["check"].items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    checks["replies_of_wrong_length"] = {"value": short, "limit": 0}
    checks["tokens_checked"] = {"value": chk["tokens"], "limit": due}
    correct = correct and short == 0 and chk["tokens"] >= due
    _say(f"reference over {len(done)} requests in "
         f"{time.perf_counter() - t0:.3f} s; tokens off the reference's "
         f"best: {chk['mismatch_share']}, widest gap "
         f"{chk['max_logit_gap']}")
    failed = sum(1 for r in counted if r.status != "ok")
    if args.trace:
        out_breakdown = tracemod.breakdown(run.trace)
    for k, v in checks.items():
        _say(f"check {k}: {v['value']} (limit {v['limit']})")
    result = {"correct": bool(correct), "attempted": len(counted),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = out_breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoDevice as e:
        _say(str(e))
        sys.exit(2)
