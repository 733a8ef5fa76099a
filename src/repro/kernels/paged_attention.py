"""Paged-decode attention: gather K/V through a block table, one kernel.

The paged KV pool (``serving.kv_pool``) stores each attention layer's K/V
as ``(num_blocks, block_size, KV, hd)``; a slot's logical sequence is the
concatenation of the pool blocks named by its block-table row.  Decode
attention over that layout is a *gather-GEMM chain*: for every KV block j
of slot b,

    fetch   k/v block  ``pool[table[b, j]]``            (the gather)
    scores  s_j = q_b · k_j^T        -- p-GEMM (G, block_size, hd)
    output  o_b += softmax-weighted  p_j · v_j          -- p-GEMM (G, hd, block_size)

with the online-softmax (m, l, acc) carry stitching the blocks together.
In the paper's taxonomy both per-block contractions are skinny p-GEMMs —
``resolve_gather_gemms`` resolves them through the §5 schedule
exploration (``core.scheduler.ScheduleCache``) and the engine records an
application (``note_gather_applied``) after every paged-decode dispatch
that consumed them, so the scheduling space demonstrably covers the
paged hot path.

Two implementations, one contract (``decode_attention``):

  * **Pallas kernel** (``paged_decode_kernel``): grid ``(B, nbs)`` with the
    block table and validity lengths as scalar-prefetch operands — the
    K/V BlockSpec index_maps read ``table[b, j]`` so the DMA engine
    fetches exactly the slot's blocks, never a dense stripe.  The
    accumulator lives in VMEM scratch; block j == nbs-1 normalizes and
    writes the output tile.  Unallocated table entries are the NULL block
    (0): their fetch is trash but every lane is masked by ``pos >= length``.
  * **Pure-JAX gather fallback**: ``jnp.take`` materializes the slot's
    KV then one masked softmax — the off-TPU path (and the oracle the
    kernel is tested against).

``decode_attention`` picks the kernel on TPU and the fallback elsewhere;
``use_kernel=True`` with ``interpret=True`` runs the kernel anywhere
(tests).  At qwen2-0.5b widths (hd 64, G 7, KV 2, 16-token blocks: tiles
below the (8, 128) layout) the kernel compiles for a v5e and matches the
float32 gather reference there, bf16 and int8 KV alike (``chip_smoke.py``);
whether padding hd to 128 lanes would be faster is not measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_tpu, resolve_interpret

NEG_INF = -1e30


def _softcap(x: jax.Array, cap: float | None) -> jax.Array:
    return x if cap is None else jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_decode_body(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                       block_size: int, scale: float, window: int | None,
                       logit_cap: float | None, out_dtype,
                       quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    nbs = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    base = j * block_size

    @pl.when(base < length)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (KV, G, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bs, KV, hd)
        v = v_ref[0].astype(jnp.float32)                  # (bs, KV, hdv)
        if quantized:
            # dequant fused into the block fetch: the int8 payload and
            # its per-(position, kv-head) scales arrive in the same DMA
            # schedule, and the fp32 K/V tile never exists in HBM
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        s = jax.lax.dot_general(                          # (KV, G, bs)
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        s = _softcap(s, logit_cap)
        kvpos = base + jax.lax.broadcasted_iota(jnp.int32,
                                                (1, 1, block_size), 2)
        mask = kvpos < length
        if window is not None:
            mask &= (length - 1) - kvpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(                         # (KV, G, hdv)
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(j == nbs - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "logit_cap",
                                             "interpret"))
def paged_decode_kernel(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, lengths: jax.Array, *,
                        scale: float, window: int | None = None,
                        logit_cap: float | None = None,
                        interpret: bool | None = None,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None) -> jax.Array:
    """Pallas paged-decode attention.

    q (B, KV, G, hd); k_pool (nb, bs, KV, hd); v_pool (nb, bs, KV, hdv);
    block_table (B, nbs) int32; lengths (B,) int32 -> out (B, KV, G, hdv).
    With ``k_scale``/``v_scale`` (nb, bs, KV) the pools are int8 and the
    dequant (payload * scale) is fused into the per-block fetch.
    """
    interpret = resolve_interpret(interpret)
    B, KV, G, hd = q.shape
    nb, bs, _, hdv = v_pool.shape
    nbs = block_table.shape[1]
    quantized = k_scale is not None

    in_specs = [
        pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
        pl.BlockSpec((1, bs, KV, hd),
                     lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, KV, hdv),
                     lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
    ]
    args = [block_table.astype(jnp.int32), lengths.astype(jnp.int32),
            q, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, KV),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, bs, KV),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
        ]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, hdv),
                               lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, hdv), jnp.float32),
        ],
    )
    body = functools.partial(_paged_decode_body, block_size=bs, scale=scale,
                             window=window, logit_cap=logit_cap,
                             out_dtype=q.dtype, quantized=quantized)
    # a fixed name: the device trace's op and the benchmark's kernel
    # metrics find the kernel by it
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hdv), q.dtype),
        interpret=interpret,
        name="paged_decode_kernel",
    )(*args)


# ---------------------------------------------------------------------------
# Pure-JAX gather fallback (off-TPU path + kernel oracle)
# ---------------------------------------------------------------------------

def gather_pool_blocks(buf: jax.Array, block_table: jax.Array) -> jax.Array:
    """THE canonical block-table gather: pool (num_blocks, block_size, ...)
    + table (B, nbs) -> contiguous per-row KV (B, nbs * block_size, ...).
    Every paged read path (this module's fallback, the MLA and
    chunked-prefill paths in ``models.attention``) goes through here so
    paged index semantics live in one place."""
    B, nbs = block_table.shape
    bs = buf.shape[1]
    out = jnp.take(buf, block_table.reshape(-1), axis=0)
    return out.reshape((B, nbs * bs) + buf.shape[2:])


def gather_fallback(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, lengths: jax.Array, *,
                    scale: float, window: int | None = None,
                    logit_cap: float | None = None,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None) -> jax.Array:
    """Same contract as :func:`paged_decode_kernel`, dense-math reference:
    gathers each row's blocks into a contiguous (B, T, KV, hd) view and
    runs one masked softmax over the valid prefix."""
    B, KV, G, hd = q.shape
    bs = k_pool.shape[1]
    nbs = block_table.shape[1]
    k = gather_pool_blocks(k_pool, block_table)
    v = gather_pool_blocks(v_pool, block_table)
    if k_scale is not None:
        # int8 pools: dequant through the COMPUTE dtype (q.dtype), never
        # a direct int8->fp32 widen — jaxpr_lint screens quant paths
        # under narrow compute for exactly that promotion
        k = k.astype(q.dtype) * gather_pool_blocks(
            k_scale, block_table).astype(q.dtype)[..., None]
        v = v.astype(q.dtype) * gather_pool_blocks(
            v_scale, block_table).astype(q.dtype)[..., None]

    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = _softcap(s, logit_cap)
    kvpos = jnp.arange(nbs * bs, dtype=jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)[:, None, None, None]
    mask = kvpos[None, None, None, :] < ln
    if window is not None:
        mask &= (ln - 1) - kvpos[None, None, None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_table: jax.Array, lengths: jax.Array, *,
                     scale: float, window: int | None = None,
                     logit_cap: float | None = None,
                     use_kernel: bool | None = None,
                     interpret: bool | None = None,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None) -> jax.Array:
    """Paged-decode dispatch: the Pallas kernel on TPU, the pure-JAX
    gather path elsewhere (``use_kernel``/``interpret`` override for
    tests — the kernel runs anywhere under interpret mode).  Int8 pools
    pass their scale sidecars; both paths fuse the dequant."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return gather_fallback(q, k_pool, v_pool, block_table, lengths,
                               scale=scale, window=window,
                               logit_cap=logit_cap,
                               k_scale=k_scale, v_scale=v_scale)
    return paged_decode_kernel(
        q, k_pool, v_pool, block_table, lengths, scale=scale, window=window,
        logit_cap=logit_cap, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# Schedule-space registration (paper §5 over the gather-GEMM shapes)
# ---------------------------------------------------------------------------

def gather_gemm_shapes(cfg, block_size: int) -> list[tuple[int, int, int]]:
    """The two per-block p-GEMMs of the paged-decode chain, per KV head:
    scores (G, block_size, hd) and weighted-value (G, hd_v, block_size).
    MLA decodes in latent space (absorbed path), so its shapes contract
    over kv_lora_rank + rope dim instead."""
    if cfg.mla is not None:
        r = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        return [(cfg.n_heads, block_size, r),
                (cfg.n_heads, cfg.mla.kv_lora_rank, block_size)]
    G = cfg.n_heads // cfg.n_kv_heads
    return [(G, block_size, cfg.hd), (G, cfg.hd, block_size)]


def resolve_gather_gemms(schedule, cfg, block_size: int, precision: str
                         ) -> list:
    """Resolve the paged-decode gather GEMMs through the paper-§5
    exploration (first call explores, later calls are dict hits).  Does
    NOT mark them applied — call :func:`note_gather_applied` after the
    decode dispatch actually ran, so the applied log stays a faithful
    record of kernel applications rather than of registrations.

    (The choice does not yet steer the Pallas kernel itself — the paged
    kernel has a single block schedule; mapping SIMD-dataflow winners to
    the gather path on TPU is an open follow-on, see ROADMAP.)"""
    return [(M, N, K, schedule.resolve(M, N, K, precision))
            for M, N, K in gather_gemm_shapes(cfg, block_size)]


def note_gather_applied(schedule, cfg, block_size: int,
                        precision: str) -> None:
    """Record one paged-decode application of the gather-GEMM shapes.
    Called by the engine immediately after the decode dispatch that
    consumed them returned, so ``schedule.applied`` entries correspond
    1:1 with real paged-decode steps."""
    for M, N, K, choice in resolve_gather_gemms(schedule, cfg, block_size,
                                                precision):
        schedule.note_applied(M, N, K, precision, choice)
