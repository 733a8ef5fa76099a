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

  * **Pallas kernel** (``paged_decode_kernel``): its grid is a work list,
    sized at run time, of (row, step) items over each row's valid pages
    only: a step takes P pages (``pages_per_step``: at least 128 tokens,
    from ``bs``, ``hd`` and a VMEM budget), so a row of length L costs
    ``cdiv(cdiv(L, bs), P)`` steps whatever the table's width, and a row
    of length 0 costs nothing (no DMA, no compute; its output is a zero
    tile).  The list (``_work_list``) and the rows' lengths are
    scalar-prefetch operands: each of the P page operands' index maps
    reads its pool block from the list, so the pipeline DMAs exactly the
    row's pages, the next step's while this one computes (across rows
    too).  Past a row's last page a step's spare page operands repeat
    that page, masked by ``pos >= length``; with a sliding window the
    list starts at the first page the window reaches.
    The pools are read in place, in the layout the program stores them
    in: the v5e compiler refuses a manual DMA of one page out of a pool
    whose minor dims it pads ((KV, hd) = (2, 64) pads to (2, 128)), while
    the pipeline's whole-block fetches take it.  A step's (T, KV, hd)
    tile is swapped to heads-leading (KV, T, hd) in VMEM before its two
    head-batched matmuls.  The online-softmax (m, l, acc) carry lives in
    VMEM scratch across a row's steps; its last step normalizes and
    writes the output tile.  The call publishes the work list's longest
    run as its ``grid_bound`` metadata, which ``launch.jaxpr_cost``
    scales the body by.
  * **Pure-JAX gather fallback**: ``jnp.take`` materializes the slot's
    KV then one masked softmax — the off-TPU path (and the oracle the
    kernel is tested against).

``decode_attention`` picks the kernel on TPU and the fallback elsewhere;
``use_kernel=True`` with ``interpret=True`` runs the kernel anywhere
(tests).  At qwen2-0.5b widths (hd 64, G 7, KV 2, 16-token blocks: tiles
below the (8, 128) layout) the kernel compiles for a v5e and matches the
float32 gather reference there, bf16 and int8 KV alike (``chip_smoke.py``).
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

# a grid step covers at least this many tokens (fewer pages only where the
# table is narrower, or where a step's pages would outgrow the budget
# below): one step's fixed cost (the pipeline's bookkeeping, the softmax
# update) is then spread over a useful amount of work
_STEP_TOKENS = 128
# VMEM for one buffer of one step's K (or V) pages; the pipeline holds two
_STEP_VMEM_BYTES = 2 << 20


def _padded_page_bytes(bs: int, KV: int, hd: int, itemsize: int) -> int:
    """VMEM bytes of one (bs, KV, hd) page: its two minor dims pad to the
    (sublanes, 128) tile, sublanes packing 32 bits of the dtype."""
    sub = 8 * max(1, 4 // itemsize)
    return bs * (-(-KV // sub) * sub) * (-(-hd // 128) * 128) * itemsize


def pages_per_step(bs: int, KV: int, hd: int, itemsize: int,
                   nbs: int) -> int:
    """P, the pages one grid step fetches and computes: enough for
    ``_STEP_TOKENS`` tokens, no more than the table holds, and no more
    than fit ``_STEP_VMEM_BYTES``."""
    want = -(-_STEP_TOKENS // bs)
    fit = max(1, _STEP_VMEM_BYTES // _padded_page_bytes(bs, KV, hd,
                                                        itemsize))
    return max(1, min(want, fit, nbs))


def _work_list(block_table: jax.Array, lengths: jax.Array, *, bs: int,
               pages: int, window: int | None):
    """The kernel's grid: one item per (row, step) of P pages, rows in
    order, over each row's valid pages only (from the first page the
    sliding window reaches).  Returns the item count and, per item, its
    row, its step within the row and its P pool blocks (flat, P per item);
    past a row's last page the block repeats that page, so nothing beyond
    the row is fetched.  A row of length 0 has no item."""
    B, nbs = block_table.shape
    n_pages = jnp.minimum((lengths + bs - 1) // bs, nbs)
    first = jnp.zeros_like(lengths) if window is None else \
        jnp.minimum(jnp.maximum(lengths - window, 0) // bs, n_pages)
    steps = (n_pages - first + pages - 1) // pages
    ends = jnp.cumsum(steps)
    item = jnp.arange(B * (-(-nbs // pages)), dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1,
                              dtype=jnp.int32), B - 1)
    step = item - (ends - steps)[row]
    page = first[row, None] + step[:, None] * pages \
        + jnp.arange(pages, dtype=jnp.int32)[None, :]
    page = jnp.clip(page, 0, jnp.maximum(n_pages[row, None] - 1, 0))
    blk = block_table.reshape(-1)[row[:, None] * nbs + page]
    return ends[-1], row, step, blk.reshape(-1), first, steps


def _paged_decode_body(row_ref, step_ref, blk_ref, len_ref, first_ref,
                       steps_ref, q_ref, *rest, block_size: int,
                       pages: int, scale: float, window: int | None,
                       logit_cap: float | None, out_dtype, quantized: bool):
    del blk_ref                     # read by the index maps only
    n = 4 if quantized else 2
    pages_in = [rest[i * pages:(i + 1) * pages] for i in range(n)]
    o_ref, m_ref, l_ref, acc_ref = rest[n * pages:]
    i = pl.program_id(0)
    b, step = row_ref[i], step_ref[i]
    length = len_ref[b]

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(refs):
        # the step's P pages side by side: (P * bs, ...) in VMEM
        return jnp.concatenate([r[0] for r in refs], axis=0)

    q = q_ref[0].astype(jnp.float32) * scale              # (KV, G, hd)
    k = tile(pages_in[0]).astype(jnp.float32)             # (T, KV, hd)
    v = tile(pages_in[1]).astype(jnp.float32)             # (T, KV, hdv)
    if quantized:
        # dequant fused into the page fetch: the int8 payload and its
        # per-(position, kv-head) scales arrive in the same pipeline, and
        # the fp32 K/V tile never exists in HBM
        k = k * tile(pages_in[2])[..., None]
        v = v * tile(pages_in[3])[..., None]
    # heads leading, as the matmuls batch them: one swap of the tile here
    # costs far less than contractions batched over a middle dim
    k = jnp.swapaxes(k, 0, 1)                             # (KV, T, hd)
    v = jnp.swapaxes(v, 0, 1)                             # (KV, T, hdv)
    base = (first_ref[b] + step * pages) * block_size
    T = pages * block_size
    s = jax.lax.dot_general(                              # (KV, G, T)
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = _softcap(s, logit_cap)
    kvpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    mask = kvpos < length
    if window is not None:
        mask &= (length - 1) - kvpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    pv = jax.lax.dot_general(                             # (KV, G, hdv)
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(step == steps_ref[b] - 1)
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
    dequant (payload * scale) is fused into the per-page fetch.  Work is
    bounded by each row's ``lengths``, not by the table's width; a row of
    length 0 reads nothing and gets a zero tile.
    """
    interpret = resolve_interpret(interpret)
    B, KV, G, hd = q.shape
    nb, bs, _, hdv = v_pool.shape
    nbs = block_table.shape[1]
    quantized = k_scale is not None
    pages = pages_per_step(bs, KV, max(hd, hdv), k_pool.dtype.itemsize, nbs)
    lengths = lengths.astype(jnp.int32)
    n_items, *sched = _work_list(block_table.astype(jnp.int32), lengths,
                                 bs=bs, pages=pages, window=window)

    def row_map(i, row, *_):
        return (row[i], 0, 0, 0)

    def page_spec(p, shape):
        def index(i, row, step, blk, *_):
            return (blk[i * pages + p],) + (0,) * (len(shape) - 1)
        return pl.BlockSpec(shape, index)

    # every pool is read in place, in the layout the program stores it in:
    # the pipeline fetches a step's P pages as P whole blocks (and the next
    # step's while this one computes)
    pools = [(k_pool, (1, bs, KV, hd)), (v_pool, (1, bs, KV, hdv))]
    if quantized:
        pools += [(k_scale, (1, bs, KV)), (v_scale, (1, bs, KV))]
    in_specs = [pl.BlockSpec((1, KV, G, hd), row_map)]
    args = [q]
    for arr, shape in pools:
        in_specs += [page_spec(p, shape) for p in range(pages)]
        args += [arr] * pages

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(sched) + 1,
        grid=(n_items,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, hdv), row_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, hdv), jnp.float32),
        ],
    )
    body = functools.partial(_paged_decode_body, block_size=bs, pages=pages,
                             scale=scale, window=window, logit_cap=logit_cap,
                             out_dtype=q.dtype, quantized=quantized)
    row, step, blk, first, steps = sched
    # a fixed name: the device trace's op and the benchmark's kernel
    # metrics find the kernel by it
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hdv), q.dtype),
        # a row's items run in order on one core: they share its output
        # block and the softmax carry
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_kernel",
        # the work list's longest run (every row at the full table), for
        # cost models that cannot read a grid sized at run time
        metadata={"grid_bound": str(B * -(-nbs // pages))},
    )(row, step, blk, lengths, first, steps, *args)
    # rows with no item were never written
    return jnp.where((lengths > 0)[:, None, None, None], out, 0)


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
    # a row of length 0 attends nothing: a zero tile, as the kernel's
    return jnp.where(ln > 0, out, 0).astype(q.dtype)


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
