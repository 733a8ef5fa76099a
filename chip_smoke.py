#!/usr/bin/env python
"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py          # from the repo root, on a host with a TPU

Serves qwen2-0.5b at its full published width (24 layers, d_model 896,
14/2 heads, vocab 151936, bf16, random weights from a fixed seed) through
the normal entry point, ``repro.launch.serve.main``, and checks what comes
out.  Three phases, all in this one process (a chip belongs to one process
at a time, so nothing here starts a child):

  serve     the default path: paged KV pool, XLA projections, the Pallas
            paged-decode kernel.  Every request must end ``ok`` with its
            ``max_new_tokens`` (or stop early at EOS), every token id in
            ``[0, vocab)``.
  kernels   at the same widths: the paged-decode kernel against the gather
            fallback (bf16 KV, and int8 KV with scale sidecars), and the
            scheduled ``ops.matmul`` and int8 ``ops.quant_matmul`` against
            a float32 ``jnp.dot``, at the decode and prefill-chunk rows.
  variants  short serve runs with ``--gemm-backend scheduled`` (the
            paper's scheduled GEMMs) and with ``--quant`` (int8 weights and
            int8 KV), held to the same checks as "serve".

The lines before the last report backend compile seconds, wall time,
tok/s and request status counts: smoke-run readings, not benchmark
numbers.  The last
line is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  The script exits nonzero, without that line, when
JAX finds no TPU or any check fails.  The persistent compile cache goes to
``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the platform every phase must run on (tests rehearse on "cpu")
REQUIRED_PLATFORM = "tpu"
ARCH = "qwen2_0_5b"
#: serve.main sizes: prompts of prompt_len/2..prompt_len tokens, each asking
#: max_new/2..max_new new tokens (``serve.make_requests``)
SERVE = dict(requests=8, prompt_len=512, max_new=32, slots=8, max_len=1024)
VARIANT = dict(requests=4, prompt_len=256, max_new=16, slots=8,
               max_len=1024)
#: kernel-phase KV pool: the engine's block size and a 512-block pool
BLOCK_SIZE, NUM_BLOCKS = 16, 512
#: the engine's prefill-chunk length (ContinuousEngine default)
PREFILL_CHUNK = 32
#: max |got - want| / max |want| allowed against a float32 reference —
#: a few bf16 ulps (2^-8) at the largest output
TOL = 2e-2

#: XLA/Mosaic compile time of each program (tracing and lowering events
#: nest, so summing them would count nested programs twice)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device() -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    _say(f"jax {jax.__version__}; platform {d.platform}, device_kind "
         f"{d.device_kind!r}, {len(devs)} device(s)")
    if d.platform != REQUIRED_PLATFORM:
        raise SmokeFailure(f"JAX found no {REQUIRED_PLATFORM} device "
                           f"(platform {d.platform!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class _CompileClock:
    """Seconds the backend spends compiling programs while attached."""

    def __init__(self):
        self.secs = 0.0

    def __call__(self, event: str, duration_secs: float, **_):
        if event == _COMPILE_EVENT:
            self.secs += duration_secs


def run_phase(name: str, fn) -> None:
    import jax
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    try:
        detail = fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    wall = time.perf_counter() - t0
    _say(f"phase {name}: PASS — {detail}; wall {wall:.1f} s, backend "
         f"compile {clock.secs:.1f} s (smoke-run reading, not a benchmark "
         f"number)")


# ---------------------------------------------------------------------------
# serve / variants
# ---------------------------------------------------------------------------

def serve_checked(sizes: dict, *extra: str) -> str:
    """One ``serve.main`` run at full width, held to the smoke checks."""
    import numpy as np

    from repro import configs
    from repro.launch import serve

    argv = ["--arch", ARCH, *extra]
    for k, v in sizes.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    _say("serve.main " + " ".join(argv))
    t0 = time.perf_counter()
    try:
        results = serve.main(argv)
    except SystemExit as e:
        # serve.main has printed the status counts, the first classified
        # error and the engine's last dispatch error
        raise SmokeFailure(f"serve.main exited with {e.code}") from None
    wall = time.perf_counter() - t0

    vocab = configs.get(ARCH).vocab
    reqs = {r.rid: r for r in serve.make_requests(
        np.random.default_rng(0), sizes["requests"], sizes["prompt_len"],
        sizes["max_new"], vocab)}
    if sorted(r.rid for r in results) != sorted(reqs):
        raise SmokeFailure(f"results for rids {[r.rid for r in results]}, "
                           f"expected {sorted(reqs)}")
    bad = []
    for r in results:
        req, toks = reqs[r.rid], np.asarray(r.tokens)
        if r.status != "ok":
            bad.append(f"rid {r.rid}: status {r.status} ({r.error})")
        early_eos = 0 < len(toks) < req.max_new_tokens \
            and int(toks[-1]) == req.eos
        if len(toks) != req.max_new_tokens and not early_eos:
            bad.append(f"rid {r.rid}: {len(toks)} tokens, asked "
                       f"{req.max_new_tokens}")
        if len(toks) and (toks.min() < 0 or toks.max() >= vocab):
            bad.append(f"rid {r.rid}: token ids outside [0, {vocab})")
    if bad:
        raise SmokeFailure("; ".join(bad))
    status = collections.Counter(r.status for r in results)
    n_tok = sum(len(r.tokens) for r in results)
    return (f"{len(results)} requests, status "
            f"{dict(sorted(status.items()))}, {n_tok} tokens, "
            f"{n_tok / wall:.1f} tok/s over serve.main wall {wall:.1f} s")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise SmokeFailure(f"shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def check_kernels() -> str:
    """The main-path kernels against float32 references at the served
    widths (compiled Pallas on a TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.core.scheduler import ScheduleCache
    from repro.kernels import ops
    from repro.kernels import paged_attention as PA

    cfg = configs.get(ARCH)
    dt = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32
    rng = np.random.default_rng(0)
    errs: dict[str, float] = {}

    # paged decode: B = slots, block table as wide as max_len
    B, KV, hd = SERVE["slots"], cfg.n_kv_heads, cfg.hd
    G = cfg.n_heads // KV
    nbs = SERVE["max_len"] // BLOCK_SIZE
    pool = (NUM_BLOCKS, BLOCK_SIZE, KV, hd)
    q = jnp.asarray(rng.standard_normal((B, KV, G, hd)), dt)
    bt = jnp.asarray(rng.integers(1, NUM_BLOCKS, (B, nbs)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, nbs * BLOCK_SIZE + 1, B), jnp.int32)
    kv_fp = [jnp.asarray(rng.standard_normal(pool), dt) for _ in "kv"]
    kv_i8 = [jnp.asarray(rng.integers(-127, 128, pool), jnp.int8)
             for _ in "kv"]
    scales = [jnp.asarray(rng.uniform(0.005, 0.02, pool[:3]), f32)
              for _ in "kv"]
    for name, (k, v), sc in ((f"paged_decode {dt.name}", kv_fp, {}),
                             ("paged_decode int8+scales", kv_i8,
                              dict(k_scale=scales[0], v_scale=scales[1]))):
        got = PA.paged_decode_kernel(q, k, v, bt, lens, scale=hd ** -0.5,
                                     **sc)
        with jax.default_matmul_precision("highest"):
            want = PA.gather_fallback(q.astype(f32), k if sc else
                                      k.astype(f32),
                                      v if sc else v.astype(f32), bt, lens,
                                      scale=hd ** -0.5, **sc)
        errs[name] = _rel_err(got, want)

    # projections: QKV, down-projection and LM head, at decode rows
    # (one per slot) and prefill-chunk rows (slots x chunk)
    sched = ScheduleCache()
    shapes = {"qkv": ((cfg.n_heads + 2 * KV) * hd, cfg.d_model),
              "down": (cfg.d_model, cfg.d_ff),
              "head": (cfg.vocab, cfg.d_model)}
    for M in (SERVE["slots"], SERVE["slots"] * PREFILL_CHUNK):
        for label, (N, K) in shapes.items():
            a = jnp.asarray(rng.standard_normal((M, K)), dt)
            b = jnp.asarray(rng.standard_normal((K, N)) * K ** -0.5, dt)
            w_q, w_s = ops.quantize_weights(b)
            want = jnp.dot(a.astype(f32), b.astype(f32),
                           precision=jax.lax.Precision.HIGHEST)
            want_q = jnp.dot(a.astype(f32), w_q.astype(f32),
                             precision=jax.lax.Precision.HIGHEST) * w_s
            errs[f"matmul {label} {M}x{N}x{K}"] = _rel_err(
                ops.matmul(a, b, schedule=sched), want)
            errs[f"quant_matmul {label} {M}x{N}x{K}"] = _rel_err(
                ops.quant_matmul(a, w_q, w_s, schedule=sched), want_q)

    for name, e in errs.items():
        _say(f"  {name}: max rel err {e:.3e} "
             f"({'ok' if e <= TOL else 'FAIL'}, tol {TOL})")
    bad = [n for n, e in errs.items() if not e <= TOL]
    if bad:
        raise SmokeFailure(f"kernel checks over tolerance {TOL}: {bad}")
    return (f"{len(errs)} kernel checks within {TOL}, worst "
            f"{max(errs.values()):.3e}")


# ---------------------------------------------------------------------------

def main() -> int:
    try:
        device = check_device()
        from repro.launch.compile_cache import enable_compile_cache
        _say(f"compile cache: {enable_compile_cache()}")
        run_phase("serve", lambda: serve_checked(SERVE))
        run_phase("kernels", check_kernels)
        run_phase("variants", lambda: "; ".join([
            "scheduled: " + serve_checked(VARIANT, "--gemm-backend",
                                          "scheduled"),
            "quant: " + serve_checked(VARIANT, "--quant")]))
    except Exception as e:  # noqa: BLE001 — every failure exits nonzero
        traceback.print_exc()
        _say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
