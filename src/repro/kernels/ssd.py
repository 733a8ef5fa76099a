"""SSD (Mamba2) intra-chunk kernel: the paper's p-GEMM classification made
concrete for the SSM family.

The chunked SSD algorithm's hot spot is the intra-chunk piece
    Y_intra = ((C B^T) ⊙ L ⊙ dt) X        per (batch, chunk, head)
where L is the lower-triangular decay matrix — i.e. two back-to-back
(Q x N)·(N x Q) and (Q x Q)·(Q x P) GEMMs with an elementwise mask between:
exactly a p-GEMM chain with vector-path work fused in, which is why GTA's
classification routes SSD to the systolic path.

Grid: one program per (batch·chunk, head-block); the Q x Q score tile and
the decay algebra live in VMEM; dims are MXU-aligned when chunk/state/head
sizes are multiples of 128 (the ref oracle covers arbitrary sizes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_intra_kernel(x_ref, dt_ref, cums_ref, b_ref, c_ref, y_ref):
    """Blocks (one grid step): x (Q, P); dt/cums (Q, H_blk... flattened to
    (Q, 1)); b/c (Q, N).  Computes y (Q, P) for one (batch-chunk, head)."""
    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)          # (Q, 1)
    cums = cums_ref[0].astype(jnp.float32)      # (Q, 1)
    b = b_ref[0].astype(jnp.float32)            # (Q, N)
    c = c_ref[0].astype(jnp.float32)            # (Q, N)

    q = x.shape[0]
    # scores: C_s · B_t  -> (Q, Q)
    s = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # decay L[s,t] = exp(cums[s] - cums[t]) for s >= t, else 0; times dt_t
    seg = cums - cums.T                          # (Q, Q) via broadcast
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    s = s * L * dt.T                             # dt_t along columns
    y_ref[0, :, :] = jax.lax.dot_general(
        s, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(x: jax.Array, dt: jax.Array, cums: jax.Array, b: jax.Array,
              c: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Intra-chunk SSD contributions.

    x    (G, Q, P)  — G = batch*chunks*heads flattened grid dim
    dt   (G, Q)     — step sizes (softplus'd)
    cums (G, Q)     — within-chunk cumulative decay (dt * A summed)
    b, c (G, Q, N)  — input/output state projections (per head)
    returns y (G, Q, P) fp32.
    """
    interpret = resolve_interpret(interpret)
    G, Q, P = x.shape
    N = b.shape[-1]
    return pl.pallas_call(
        _ssd_intra_kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, 1), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, 1), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Q, P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="ssd_intra",
    )(x, dt[..., None], cums[..., None], b, c)


def ssd_intra_ref(x, dt, cums, b, c):
    """Pure-jnp oracle (mirrors models.ssm.ssd_chunked's intra-chunk term
    for pre-broadcast per-head tensors)."""
    seg = cums[:, :, None] - cums[:, None, :]
    Q = x.shape[1]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None], jnp.exp(seg), 0.0)
    s = jnp.einsum("gsn,gtn->gst", c, b) * L * dt[:, None, :]
    return jnp.einsum("gst,gtp->gsp", s, x.astype(jnp.float32))
