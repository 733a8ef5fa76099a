"""The one traffic generator: a mix file's parameters in, request streams
out.

A mix (``bench/mixes/<name>.json``) is data only.  Its keys:

    loop        "open" (arrivals on a schedule) or "closed" (``clients``
                callers, each sending its next request when its previous
                reply ends)
    arrivals    open loop: {"process": "gamma", "cv": c} or
                {"process": "poisson"}; the rate comes from the cell
    prompt      length of a prompt (or, with ``sessions``, of each user
                message): {"dist": "lognormal", "median", "sigma", "min",
                "max"} or {"dist": "uniform", "min", "max"}
    output      tokens to generate, same forms
    sessions    optional: {"system_prompts", "system_len", "turns": [lo,
                hi], "think_s": [lo, hi]}; each arrival starts a session
                whose turns carry the full history
    limits      {"ttft_ms", "tpot_ms"}: what a request must meet
    lead_in_s   arrivals before the measured window opens
    size_seed   fixes the multiset of sizes and gaps (see below)

Sizes, gaps and their order are drawn once from ``size_seed``, per
segment of the timeline (lead-in, window, tail), so every run seed sends
the same requests at the same times; ``--seed`` draws their token ids
(and the weights).  The engine's work does not depend on token values,
so runs with different seeds do the same work: with a score of requests
in a window, a seed that reordered them would move a 95th percentile by
a third (measured on a TPU v5e at 0.4 requests/s).
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "mixes"

#: arrivals generated past the window, for requests that keep the load up
#: while the window's own requests finish
TAIL_S = 400.0


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    stream: int
    #: seconds after the arrivals start (first request of a stream) or
    #: after the previous reply of the stream ends (later ones)
    offset: float
    follow_up: bool = False


@dataclasses.dataclass
class Traffic:
    mix: dict
    streams: list[list[Req]]
    #: the measured window, in seconds after the arrivals start
    window: tuple[float, float]
    #: prompts sent once during set-up (system prompts of sessions)
    warm_prompts: list[np.ndarray]


def load_mix(name: str, mix_dir: pathlib.Path = MIX_DIR) -> dict:
    return json.loads((mix_dir / f"{name}.json").read_text())


def _draw_len(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, size=n)
    elif spec["dist"] == "lognormal":
        out = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        out = np.clip(np.rint(out), spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return out.astype(np.int64)


def _gaps(rng: np.random.Generator, arrivals: dict, n: int,
          length: float) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``length``."""
    if n == 0:
        return np.zeros(0)
    if arrivals["process"] == "poisson":
        g = rng.exponential(1.0, n)
    elif arrivals["process"] == "gamma":
        shape = 1.0 / arrivals["cv"] ** 2
        g = rng.gamma(shape, 1.0, n)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return g * (length / g.sum())


def generate(mix: dict, *, seed: int, seconds: float, rate: float | None,
             vocab: int, max_len: int) -> Traffic:
    lead = float(mix.get("lead_in_s", 0.0))
    window = (lead, lead + seconds)
    segments = [(0.0, lead), window, (window[1], window[1] + TAIL_S)]
    run = np.random.default_rng(seed)
    streams: list[list[Req]] = []
    rid = 0

    def tokens(n: int) -> np.ndarray:
        return run.integers(0, vocab, size=int(n), dtype=np.int32)

    sess = mix.get("sessions")
    system = ([tokens(sess["system_len"])
               for _ in range(sess["system_prompts"])] if sess else [])

    if mix["loop"] == "closed":
        size = np.random.default_rng(mix["size_seed"])
        n = 64 * mix["clients"]
        p = _draw_len(size, mix["prompt"], n)
        o = _draw_len(size, mix["output"], n)
        order = size.permutation(n)
        for c in range(mix["clients"]):
            stream = []
            for j, k in enumerate(order[c::mix["clients"]]):
                stream.append(Req(rid, tokens(p[k]), int(o[k]), c, 0.0,
                                  follow_up=j > 0))
                rid += 1
            streams.append(stream)
        return Traffic(mix, streams, window, system)

    if rate is None or rate <= 0:
        raise ValueError("an open-loop mix needs the cell's rate_per_s")
    for si, (a, b) in enumerate(segments):
        size = np.random.default_rng([mix["size_seed"], si])
        n = int(round(rate * (b - a)))
        gaps = _gaps(size, mix["arrivals"], n, b - a)
        if sess:
            sys_i = size.integers(0, sess["system_prompts"], n)
            turns = size.integers(sess["turns"][0], sess["turns"][1] + 1, n)
            t_max = sess["turns"][1]
            user = _draw_len(size, mix["prompt"], n * t_max).reshape(n, -1)
            out = _draw_len(size, mix["output"], n * t_max).reshape(n, -1)
            think = size.uniform(*sess["think_s"], (n, t_max))
        else:
            p = _draw_len(size, mix["prompt"], n)
            o = _draw_len(size, mix["output"], n)
        perm = size.permutation(n)
        # the first arrival opens the segment; the last gap runs to its end
        t = a
        for i, k in enumerate(perm):
            if i:
                t += gaps[perm[i - 1]]
            if not sess:
                streams.append([Req(rid, tokens(p[k]), int(o[k]),
                                    len(streams), t)])
                rid += 1
                continue
            history = system[sys_i[k]]
            stream: list[Req] = []
            for j in range(turns[k]):
                prompt = np.concatenate([history, tokens(user[k, j])])
                if len(prompt) + out[k, j] > max_len:
                    break
                stream.append(Req(rid, prompt, int(out[k, j]), len(streams),
                                  t if j == 0 else float(think[k, j]),
                                  follow_up=j > 0))
                rid += 1
                # the reply as recorded in the conversation being replayed
                history = np.concatenate([prompt, tokens(out[k, j])])
            if stream:
                streams.append(stream)
    return Traffic(mix, streams, window, system)
