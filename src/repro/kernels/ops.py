"""Public jit'd kernel API + the GEMM execution layer.

Pads arbitrary shapes to block multiples, picks block configs with the GTA
scheduler bridge (core.tiling — the paper's Σ-squares priority over TPU
block candidates), dispatches to the Pallas kernels, and runs interpret mode
automatically off-TPU.  Everything the model/serving stack calls lives here.

GEMM execution layer
--------------------
:class:`GemmBackend` is the dispatcher that routes MODEL projections
(``models.layers.dense``, float and QuantTensor paths) through the
scheduled Pallas kernels:

  * one :class:`repro.core.scheduler.ScheduleCache` per backend — the first
    sight of a (M, N, K, precision) GEMM runs the paper-§5 exploration, every
    later dispatch (and every re-trace) is a dict hit;
  * batched/stacked LHS support: a ``(B, S, K)`` activation collapses to one
    ``(B*S, K)`` GEMM, so projections share one dispatch instead of
    re-padding per row;
  * block configs are memoized per static shape
    (:func:`cached_block_config`), so the Σ-squares search runs once per
    shape per process, not once per dispatch;
  * the *effective* fold (``mpgemm.effective_fold`` — the kernel degrades
    unrealizable fold requests) is what lands in the applied-schedule log;
  * all dispatches use the FUSED reduction epilogue — no partial-plane
    HBM tensor exists on any dataflow (``kernels.mpgemm``).

``backend_for(cfg)`` memoizes one backend per model config so every engine,
trace, and benchmark over the same config shares one schedule store
(``ModelConfig.gemm_backend == "scheduled"`` opts a model in; the default
``"xla"`` keeps projections on XLA's native fusions — the right call
off-TPU, where Pallas runs in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.dataflow import Dataflow
from repro.core.precision import precision_for_dtype
from repro.core.scheduler import ScheduleCache
from repro.core.tiling import MXU_DIM, BlockConfig, choose_block_config
from repro.kernels import accumulator
from repro.kernels import limb_gemm as _lg
from repro.kernels import mpgemm as _mp
from repro.kernels import quant_matmul as _qm
from repro.kernels.ref import LIMB_BITS, n_limbs_for


def _pad2(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[-2]) % m0
    p1 = (-x.shape[-1]) % m1
    if p0 == 0 and p1 == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, p0), (0, p1)]
    return jnp.pad(x, pad)


@functools.lru_cache(maxsize=4096)
def cached_block_config(M: int, N: int, K: int, abytes: int, bbytes: int,
                        obytes: int, limb_factor: int,
                        allowed: tuple[Dataflow, ...] | None
                        ) -> BlockConfig:
    """Memoized :func:`repro.core.tiling.choose_block_config` on the static
    (M, N, K, operand bytes, allowed-dataflow) key: hot-path ``matmul`` /
    ``quant_matmul`` dispatches stop re-running the Σ-squares search in
    Python per call — a shape's search runs once per process."""
    return choose_block_config(M, N, K, abytes=abytes, bbytes=bbytes,
                               obytes=obytes, limb_factor=limb_factor,
                               allowed=allowed)


def _auto_blocks(M: int, N: int, K: int, abytes: int, bbytes: int,
                 limb_factor: int = 1) -> BlockConfig:
    return cached_block_config(M, N, K, abytes, bbytes, 4, limb_factor,
                               (Dataflow.OS,))


# ---------------------------------------------------------------------------
# Multi-precision exact integer matmul (the paper's technique)
# ---------------------------------------------------------------------------

def limb_matmul(a: jax.Array, b: jax.Array, *,
                in_bits: int | None = None,
                blocks: tuple[int, int, int] | None = None,
                interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Exact integer GEMM via limb decomposition: returns (hi, lo) int32
    pairs = (a @ b) mod 2^64 in two's complement.

    a: (M, K), b: (K, N) — int8/int16/int32 (or int32 holding narrower
    values; pass ``in_bits`` to force the decomposition width).
    """
    if a.dtype != b.dtype and in_bits is None:
        raise ValueError("mixed input dtypes need explicit in_bits")
    bits = in_bits or jnp.dtype(a.dtype).itemsize * 8
    nl = n_limbs_for(bits, LIMB_BITS)

    M, K = a.shape
    _, N = b.shape
    if blocks is None:
        cfg = _auto_blocks(M, N, K, 1, 1, limb_factor=nl * nl)
        bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
    else:
        bm, bn, bk = blocks

    a_l = _pad2(_lg.limb_decompose(a, nl, LIMB_BITS), bm, bk)
    b_l = _pad2(_lg.limb_decompose(b, nl, LIMB_BITS), bk, bn)
    diags = _lg.limb_gemm_diagonals(a_l, b_l, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
    hi, lo = accumulator.combine_diagonals(diags, LIMB_BITS)
    return hi[:M, :N], lo[:M, :N]


def limb_matmul_i32(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """Truncated int32 result (callers guaranteeing no 32-bit overflow)."""
    _, lo = limb_matmul(a, b, **kw)
    return lo


# ---------------------------------------------------------------------------
# Float GEMM with selectable dataflow (schedule demonstrator + default path)
# ---------------------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array, *, dataflow: Dataflow = Dataflow.OS,
           out_dtype=jnp.float32,
           blocks: tuple[int, int, int] | None = None,
           k_fold: int | None = None,
           schedule: ScheduleCache | None = None,
           epilogue: str = "fused",
           interpret: bool | None = None) -> jax.Array:
    """GEMM through the mpgemm kernel (pads to block multiples; already
    block-aligned shapes skip the pad/slice round-trip entirely).

    With ``schedule`` (a :class:`repro.core.scheduler.ScheduleCache`) the
    paper's §5 exploration picks the kernel schedule: the first call with a
    given (M, N, K, precision) explores and memoizes; every later call is a
    cache hit.  The cached dataflow overrides ``dataflow``, the cached
    ``k_fold`` reaches the Pallas dispatch, and the TPU block search is
    narrowed to the chosen stationarity.  Each application is recorded via
    ``schedule.note_applied`` with the EFFECTIVE fold/dataflow that
    executed (fold requests degrade to divisors of the K grid; SIMD maps
    onto the MXU OS pipeline), so callers can verify the choice landed.

    ``k_fold`` forces a fold explicitly (overrides the cached choice);
    ``epilogue`` selects the fused reduction (default) or the legacy
    partial-plane spill baseline (benchmarks only).
    """
    M, K = a.shape
    _, N = b.shape

    fold_req = k_fold
    choice = None
    if schedule is not None:
        prec = precision_for_dtype(a.dtype)
        choice = schedule.resolve(M, N, K, prec)
        # SIMD = "vectorize this p-GEMM": on TPU that is still the MXU OS
        # pipeline (there is no separate vector GEMM unit to fall back to).
        dataflow = (Dataflow.OS if choice.dataflow is Dataflow.SIMD
                    else choice.dataflow)
        if fold_req is None:
            fold_req = choice.k_fold
    fold_req = 1 if fold_req is None else fold_req

    if blocks is None:
        eb = jnp.dtype(a.dtype).itemsize
        allowed = (dataflow,) if schedule is not None else None
        cfg = cached_block_config(M, N, K, eb, eb, 4, 1, allowed)
        bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
        if fold_req > 1 and _mp.effective_fold(K, bk, fold_req) != fold_req:
            # the block search favored a coarse bk whose K grid cannot
            # host the scheduled fold; drop to the MXU granularity the
            # scheduler's realizability filter assumed (the same MXU_DIM
            # both sites share) so the memoized fold executes as modeled
            # instead of silently degrading.
            bk = MXU_DIM
    else:
        bm, bn, bk = blocks

    ap = _pad2(a, bm, bk)
    bp = _pad2(b, bk, bn)
    ef = _mp.effective_fold(ap.shape[-1], bk, fold_req)
    out = _mp.mpgemm(ap, bp, dataflow=dataflow, bm=bm, bn=bn, bk=bk,
                     k_fold=ef, out_dtype=out_dtype, epilogue=epilogue,
                     interpret=interpret)
    if schedule is not None:
        # logged AFTER the dispatch so the applied log records only GEMMs
        # that really executed (a raising dispatch must not leave a
        # phantom application behind)
        schedule.note_applied(M, N, K, prec, choice, effective_k_fold=ef,
                              effective_dataflow=dataflow)
    if out.shape == (M, N):        # aligned fast path: nothing to slice off
        return out
    return out[:M, :N]


# ---------------------------------------------------------------------------
# int8-weight quantized matmul (serving fast path)
# ---------------------------------------------------------------------------

def quantize_weights(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization: w (K, N) ->
    (w_q int8 (K, N), scale f32 (N,))."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.reshape(-1).astype(jnp.float32)


def quant_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                 out_dtype=jnp.float32,
                 blocks: tuple[int, int, int] | None = None,
                 schedule: ScheduleCache | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """x (M, K) @ dequant(w_q (K, N), scale (N,)) -> (M, N).

    With ``schedule`` the shape is resolved through the paper-§5
    exploration under INT8 (GTA's native PE width) and the application is
    logged with the EFFECTIVE execution (the int8 kernel is an OS pipeline
    with the per-channel dequant fused into the accumulator flush, so the
    applied dataflow is OS and the fold is 1 regardless of the modeled
    winner — the honest record of what ran)."""
    M, K = x.shape
    _, N = w_q.shape
    if schedule is not None:
        choice = schedule.resolve(M, N, K, "INT8")
        schedule.note_applied(M, N, K, "INT8", choice, effective_k_fold=1,
                              effective_dataflow=Dataflow.OS)
    if blocks is None:
        eb = jnp.dtype(x.dtype).itemsize
        cfg = cached_block_config(M, N, K, eb, 1, 4, 1, None)
        bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
    else:
        bm, bn, bk = blocks
    xp = _pad2(x, bm, bk)
    wp = _pad2(w_q, bk, bn)
    sp = scale if N % bn == 0 else jnp.pad(scale, (0, (-N) % bn))
    out = _qm.quant_matmul(xp, wp, sp, bm=bm, bn=bn, bk=bk,
                           out_dtype=out_dtype, interpret=interpret)
    if out.shape == (M, N):
        return out
    return out[:M, :N]


# ---------------------------------------------------------------------------
# GemmBackend: the model-projection dispatcher (ScheduleCache -> kernels)
# ---------------------------------------------------------------------------

class GemmBackend:
    """Routes model projections through the scheduled fused-reduction
    kernels (see module docstring).  Stateless apart from its
    :class:`ScheduleCache`; safe to close over in jitted functions — all
    scheduling work happens at trace time against static shapes, so a
    compiled serving step contains only the chosen Pallas dispatches."""

    def __init__(self, schedule: ScheduleCache | None = None,
                 interpret: bool | None = None):
        self.schedule = schedule or ScheduleCache()
        self.interpret = interpret

    def matmul(self, x2: jax.Array, w: jax.Array,
               out_dtype=jnp.float32) -> jax.Array:
        """(M, K) @ (K, N) through the scheduled fused kernel."""
        return matmul(x2, w, out_dtype=out_dtype, schedule=self.schedule,
                      interpret=self.interpret)

    def dense(self, x: jax.Array, w: Any,
              b: jax.Array | None = None) -> jax.Array:
        """The scheduled analogue of ``models.layers.dense``: x (..., K)
        against a float weight (K, N) or a QuantTensor.  Leading dims
        collapse to ONE (B*S, K) GEMM (batched/stacked LHS — no per-row
        re-padding); bias/dequant happen in the epilogue and the result
        returns in x.dtype.

        Numerics mirror the XLA path: the kernel accumulates fp32 and the
        float path EMITS in the compute dtype (one rounding, same as
        ``preferred_element_type=x.dtype`` — §Perf H1's bf16 collective
        payload is preserved), the quant path emits fp32 pre-scale.  On
        fp32 configs (the gated serving setup) both backends round
        identically; bf16 block-accumulation order may still differ from
        XLA's dot at the last bit, which is why serve_bench gates token
        identity on the fp32 config."""
        lead, K = x.shape[:-1], x.shape[-1]
        x2 = x.reshape(-1, K)
        if hasattr(w, "q") and hasattr(w, "scale"):     # QuantTensor
            out2 = quant_matmul(x2, w.q, w.scale, out_dtype=jnp.float32,
                                schedule=self.schedule,
                                interpret=self.interpret)
        else:
            out2 = self.matmul(x2, w.astype(x.dtype), out_dtype=x.dtype)
        if b is not None:
            out2 = out2 + b.astype(jnp.float32)
        return out2.astype(x.dtype).reshape(lead + (out2.shape[-1],))


@functools.lru_cache(maxsize=64)
def _backend_for_key(key: Any) -> GemmBackend:
    return GemmBackend()


def backend_for(cfg) -> GemmBackend | None:
    """The process-wide backend for a model config, or None when the config
    keeps projections on XLA (``gemm_backend != "scheduled"``).  Memoized
    by config equality so every engine/trace/benchmark over the same model
    shares one ScheduleCache — offline exploration, online serving, and
    reporting see a single schedule store."""
    if getattr(cfg, "gemm_backend", "xla") != "scheduled":
        return None
    return _backend_for_key(cfg)
