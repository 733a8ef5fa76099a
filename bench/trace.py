"""Reduction of a profiler trace to device busy time, time by operation
and by program, kernel events and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (so a small recorded extract can be kept as a test file);
``reduce`` works on that dict.  Times are in seconds, on the trace's own
clock, which the profiler shares between host and device planes.
"""

from __future__ import annotations

import glob
import os

#: the host span that marks the traced window (see bench/run.py)
WINDOW_SPAN = "bench.window"
#: host spans an idle gap may be put down to, innermost first
HOST_SPANS = ("bench.wait", "bench.step")


def load(trace_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: the host's
    ``bench.*`` spans, and the device events that overlap the window."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    win = [(s, s + d) for n, s, d in out["host"] if n == WINDOW_SPAN]
    lo_ns, hi_ns = ((win[0][0] * 1e9, win[0][1] * 1e9) if win
                    else (-float("inf"), float("inf")))
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (_short(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events
                        if e.start_ns < hi_ns
                        and e.start_ns + e.duration_ns > lo_ns]
            out["devices"][plane.name] = {
                "ops": lines.get("XLA Ops", []),
                "modules": lines.get("XLA Modules", [])}
    return out


def _short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.12 = bf16[...] ...``
    becomes ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _overlap(spans: list[tuple[float, float]], a: float, b: float) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in spans)


def reduce(tr: dict) -> dict:
    """Busy/idle, time by op and by program, and idle gaps by host span,
    inside the traced window, averaged over the devices traced."""
    win = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win[0]
    host = [(n, s, s + d) for n, s, d in tr["host"] if n in HOST_SPANS]
    devs = tr["devices"]
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    busy_s = 0.0
    ops: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    busy_all: list[list[tuple[float, float]]] = []
    for d in devs.values():
        clipped = list(_clip(d["ops"], lo, hi))
        busy = _union([(a, b) for _, a, b in clipped])
        busy_all.append(busy)
        busy_s += sum(b - a for a, b in busy)
        for name, a, b in clipped:
            ops[name] = ops.get(name, 0.0) + (b - a) / len(devs)
        for name, a, b in _clip(d["modules"], lo, hi):
            m = modules.setdefault(name, [0.0, 0])
            m[0] += (b - a) / len(devs)
            m[1] += 1
    busy_s /= len(devs)

    # idle gaps of the first device, each put down to the innermost host
    # span that covers its midpoint
    busy = busy_all[0]
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        label = "host: none"
        for name in HOST_SPANS:
            if any(s <= mid < e for n, s, e in host if n == name):
                label = f"host: {name}"
                break
        gaps.append((label, b - a))
    gaps.sort(key=lambda g: -g[1])

    steps = [(s, e) for n, s, e in host if n == "bench.step"
             and s >= lo and e <= hi]
    step_host = [(e - s) - _overlap(busy, s, e) for s, e in steps]
    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "ops": ops,
        "modules": {k: (v[0], v[1]) for k, v in modules.items()},
        "idle_gaps": gaps,
        "step_host_s": step_host,
    }


def ops_matching(red: dict, needle: str) -> tuple[float, int]:
    """Total device seconds of the ops whose name contains ``needle``."""
    t = [v for k, v in red["ops"].items() if needle in k]
    return sum(t), len(t)


def modules_matching(red: dict, needle: str) -> tuple[float, int]:
    """Device seconds and executions of the programs whose name contains
    ``needle``."""
    t = [v for k, v in red["modules"].items() if needle in k]
    return sum(x[0] for x in t), sum(x[1] for x in t)


def breakdown(red: dict, n: int = 10) -> dict:
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:n]]}
