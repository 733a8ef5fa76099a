"""Multi-precision integer GEMM on the MXU via limb decomposition — the
paper's §3.1 insight as a Pallas TPU kernel.

GTA maps a w-bit multiplication onto 8-bit PEs by decomposing operands into
limbs and computing the limb cross-products systolically.  On TPU the 8-bit
"PE plane" is the MXU's int8 path: an exact INT16/INT32(/INT64-limb) GEMM
lowers to ``la * lb`` int8 x int8 -> int32 MXU matmuls, grouped by output
anti-diagonal (``d = i + j``) and recombined by the multi-precision
accumulator (``accumulator.combine_diagonals``).

Hardware adaptation note (recorded in DESIGN.md): the paper's PEs multiply
*unsigned* base-256 limbs and fix signs/carries in the accumulator; the MXU
int8 path is signed, so we use balanced base-128 signed digits
(``ref.limb_decompose_ref``) — every digit fits int8, every anti-diagonal
partial sum stays exact in int32 for K up to 2^17.

Dataflow: OS (output-stationary) — the anti-diagonal accumulator planes live
in VMEM scratch across the K grid dimension and are written once, exactly
like the GTA accumulator sits at the array edge.  Grid = (gm, gn, gk), K
innermost ("arbitrary"); M, N parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _limb_gemm_kernel(a_ref, b_ref, out_ref, acc_ref, *, gk: int):
    """One (bm, bn) output tile: accumulate la*lb limb matmuls into
    anti-diagonal planes held in VMEM scratch across the K steps."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    la = a_ref.shape[0]
    lb = b_ref.shape[0]
    for i in range(la):
        a_i = a_ref[i]
        for j in range(lb):
            d = i + j
            acc_ref[d] += jax.lax.dot_general(
                a_i, b_ref[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)

    @pl.when(k == gk - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def limb_gemm_diagonals(a_limbs: jax.Array, b_limbs: jax.Array, *,
                        bm: int = 128, bn: int = 128, bk: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """Anti-diagonal partial sums of the limb GEMM.

    a_limbs: (la, M, K) int8 — balanced digits of A (see ref.py)
    b_limbs: (lb, K, N) int8
    returns: (la + lb - 1, M, N) int32, S_d = sum_{i+j=d} A_i @ B_j.

    M, N, K must be multiples of (bm, bn, bk) — ``ops.limb_matmul`` pads.
    """
    interpret = resolve_interpret(interpret)
    la, M, K = a_limbs.shape
    lb, K2, N = b_limbs.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {K} vs {K2}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, N, K)} not divisible by {(bm, bn, bk)}")
    gm, gn, gk = M // bm, N // bn, K // bk
    n_diag = la + lb - 1

    kernel = functools.partial(_limb_gemm_kernel, gk=gk)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((la, bm, bk), lambda m, n, k: (0, m, k)),
            pl.BlockSpec((lb, bk, bn), lambda m, n, k: (0, k, n)),
        ],
        out_specs=pl.BlockSpec((n_diag, bm, bn), lambda m, n, k: (0, m, n)),
        out_shape=jax.ShapeDtypeStruct((n_diag, M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_diag, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="limb_gemm",
    )(a_limbs, b_limbs)


def limb_decompose(x: jax.Array, n_limbs: int, limb_bits: int = 7
                   ) -> jax.Array:
    """jnp (VPU-path) balanced signed-digit decomposition; mirrors
    ref.limb_decompose_ref.  x: integer array -> (n_limbs, *x.shape) int8."""
    base = 1 << limb_bits
    half = base >> 1
    rem = x.astype(jnp.int32)
    digits = []
    for _ in range(n_limbs):
        r = rem & (base - 1)                       # low digit, 0..base-1
        d = ((r + half) & (base - 1)) - half       # balanced: -half..half-1
        digits.append(d.astype(jnp.int8))
        # rem_next = (rem - d) / base, computed overflow-free:
        # (r - d) is 0 or base, so add its carry to the arithmetic shift.
        rem = (rem >> limb_bits) + ((r - d) >> limb_bits)
    return jnp.stack(digits, axis=0)
