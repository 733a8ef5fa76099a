"""int8-weight quantized matmul kernel (the GTA INT8 serving path).

The framework's precision policy (repro.quant) can run any projection with
int8 weights — the single-limb fast case of the paper's multi-precision
engine (INT8 is GTA's native PE width; Table 3's 8x throughput row).
Activations stay bf16/f32; weights are symmetric per-output-channel int8.

OS dataflow: fp32 accumulator resident in VMEM across K steps; per-channel
dequantization happens once at flush (the accumulator epilogue, like GTA's
FP coordination units)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _quant_matmul_kernel(x_ref, wq_ref, scale_ref, out_ref, acc_ref, *,
                         gk: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = wq_ref[...].astype(x.dtype)   # int8 -> bf16/f32 upcast on the VPU
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == gk - 1)
    def _flush():
        scale = scale_ref[...].astype(jnp.float32)   # (1, bn)
        out_ref[...] = (acc_ref[...] * scale).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def quant_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 out_dtype=jnp.float32, interpret: bool | None = None
                 ) -> jax.Array:
    """x: (M, K) bf16/f32; w_q: (K, N) int8; scale: (N,) f32 per-channel.

    Returns (M, N) ``out_dtype`` = (x @ w_q) * scale.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    K2, N = w_q.shape
    if K != K2 or scale.shape != (N,):
        raise ValueError(f"shape mismatch x{x.shape} w{w_q.shape} "
                         f"scale{scale.shape}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, N, K)} not divisible by {(bm, bn, bk)}")
    gm, gn, gk = M // bm, N // bn, K // bk

    kernel = functools.partial(_quant_matmul_kernel, gk=gk,
                               out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="quant_matmul",
    )(x, w_q, scale.reshape(1, N))
