"""Attention: GQA (full / sliding-window / encoder), MLA, prefill & decode.

The full-sequence path is a blockwise online-softmax ("flash") formulation in
pure JAX — a lax.scan over KV blocks with (m, l, acc) carry — so 32k-token
prefill compiles with bounded activation memory on any backend.  In the
paper's taxonomy all of these are p-GEMM chains (QK^T and PV are the
classified GEMMs; softmax is vector-path work), and on TPU the blocks map
onto MXU tiles exactly like core.tiling prescribes.

Shapes: x (B, S, D); q (B, S, H, hd); k/v (B, T, KV, hd); caches are
(B, T_max, KV, hd) with a scalar write position — or, block-paged
(serving.kv_pool layout), a shared pool (num_blocks, block_size, KV, hd)
addressed through a per-slot block table (pass ``block_table`` to the
attention calls; decode then routes through the paged-decode kernel).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.config import BlockKind, MLAConfig, ModelConfig, RopeMode
from repro.models.layers import (ParamDef, apply_rope, dense, rms_norm,
                                 rope_tables, shard_act, softcap)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.hd
    H, KV = cfg.n_heads, cfg.n_kv_heads
    defs = {
        "wq": ParamDef((d, H * hd), ("embed", "heads")),
        "wk": ParamDef((d, KV * hd), ("embed", "kv")),
        "wv": ParamDef((d, KV * hd), ("embed", "kv")),
        "wo": ParamDef((H * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H * hd,), ("heads",), init="zeros")
        defs["bk"] = ParamDef((KV * hd,), ("kv",), init="zeros")
        defs["bv"] = ParamDef((KV * hd,), ("kv",), init="zeros")
    return defs


def mla_defs(cfg: ModelConfig) -> dict:
    assert cfg.mla is not None
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim
    return {
        # query low-rank path
        "wq_a": ParamDef((d, m.q_lora_rank), ("embed", None)),
        "q_norm": ParamDef((m.q_lora_rank,), (None,), init="zeros"),
        "wq_b": ParamDef((m.q_lora_rank, H * (qk + m.qk_rope_head_dim)),
                         (None, "heads")),
        # kv compression
        "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim),
                          ("embed", None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="zeros"),
        "wk_b": ParamDef((m.kv_lora_rank, H * qk), (None, "heads")),
        "wv_b": ParamDef((m.kv_lora_rank, H * m.v_head_dim),
                         (None, "heads")),
        "wo": ParamDef((H * m.v_head_dim, d), ("heads", "embed")),
    }


# ---------------------------------------------------------------------------
# Blockwise online-softmax attention core
# ---------------------------------------------------------------------------

def _flash_triangular(q, k, v, *, scale, window, logit_cap, block):
    """§Perf H2 — causal-block-skipping ("triangular") flash schedule.

    The rectangular scan computes every (q-block, kv-block) pair and masks
    half of it away; here the scan runs only over pairs with kj <= qi (and
    within the sliding window), cutting attention flops ~2x for causal
    training/prefill and by window/seq for local layers.  Applicable when
    q_offset == 0 statically (prefill/train) and Sq % block == 0.

    q (B,Sq,KV,G,hd); k/v (B,T,KV,hd) -> (B,Sq,KV,G,hd).
    """
    B, Sq, KV, G, hd = q.shape
    hd_v = v.shape[-1]
    nqb = Sq // block

    qf = shard_act(q.astype(jnp.float32) * scale, "bm...")
    k = shard_act(k, "br..")
    v = shard_act(v, "br..")
    kb = k.reshape(B, -1, block, KV, hd)
    vb = v.reshape(B, -1, block, KV, hd_v)

    wblk = (None if window is None
            else max(0, -(-window // block)))
    pairs = [(qi, kj) for qi in range(nqb) for kj in range(qi + 1)
             if wblk is None or qi - kj <= wblk]
    qi_arr = jnp.asarray([p[0] for p in pairs], jnp.int32)
    kj_arr = jnp.asarray([p[1] for p in pairs], jnp.int32)

    def step(carry, pair):
        m, l, acc = carry
        qi, kj = pair
        q_blk = jax.lax.dynamic_slice_in_dim(qf, qi * block, block, axis=1)
        kjb = jax.lax.dynamic_index_in_dim(kb, kj, 1, keepdims=False)
        vjb = jax.lax.dynamic_index_in_dim(vb, kj, 1, keepdims=False)
        s = jnp.einsum("bskgd,btkd->bkgst", q_blk, kjb.astype(jnp.float32))
        s = softcap(s, logit_cap)
        qpos = qi * block + jnp.arange(block, dtype=jnp.int32)
        kpos = kj * block + jnp.arange(block, dtype=jnp.int32)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)

        m_q = jax.lax.dynamic_slice_in_dim(m, qi * block, block, axis=3)
        l_q = jax.lax.dynamic_slice_in_dim(l, qi * block, block, axis=3)
        a_q = jax.lax.dynamic_slice_in_dim(acc, qi * block, block, axis=3)
        m_new = jnp.maximum(m_q, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_q - m_new)
        l_new = l_q * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p, vjb.astype(jnp.float32))
        a_new = a_q * corr[..., None] + pv
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, qi * block, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, qi * block, axis=3)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, qi * block,
                                                  axis=3)
        return (m, l, acc), None

    m0 = shard_act(jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32),
                   "brrr" if dec else "b..m")
    l0 = shard_act(jnp.zeros((B, KV, G, Sq), jnp.float32),
                   "brrr" if dec else "b..m")
    a0 = shard_act(jnp.zeros((B, KV, G, Sq, hd_v), jnp.float32),
                   "brrrr" if dec else "b..m.")
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (qi_arr, kj_arr))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)


def _flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
           scale: float, causal: bool, window: int | None,
           q_offset: jax.Array | int, kv_valid: jax.Array | None,
           logit_cap: float | None, block: int) -> jax.Array:
    """q (B,Sq,KV,G,hd); k/v (B,T,KV,hd) -> out (B,Sq,KV,G,hd).

    Scans KV blocks with the online-softmax carry; masks causality, sliding
    window and cache validity by absolute positions.

    NOTE (§Perf H2, refuted): a triangular causal-block-skipping schedule
    (_flash_triangular) cuts flops ~2x but its dynamic carry updates at
    traced offsets made GSPMD all-gather the sequence-sharded carries every
    pair-step (gemma2 train collective term 4.4 s -> 36 s).  The rectangular
    schedule stays; the skipping idea needs a static "band" formulation or a
    Pallas kernel to pay off on TPU (EXPERIMENTS.md §Perf H2).
    """
    B, Sq, KV, G, hd = q.shape
    hd_v = v.shape[-1]
    T = k.shape[1]
    block = min(block, T)
    if T % block:  # pad kv to block multiple; padded keys masked out
        pad = block - T % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_valid = jnp.asarray(T, jnp.int32) if kv_valid is None else kv_valid
        T = k.shape[1]
    nblk = T // block

    # q_offset / kv_valid are scalars (uniform batch) or (B,) vectors
    # (continuous batching: per-slot positions and validity bounds); both
    # shapes flow through one (1|B, Sq) qpos / (1|B, 1, 1) bound layout.
    qpos = (jnp.atleast_1d(jnp.asarray(q_offset, jnp.int32))[:, None]
            + jnp.arange(Sq, dtype=jnp.int32)[None, :])
    kv_bound = (None if kv_valid is None else
                jnp.atleast_1d(jnp.asarray(kv_valid, jnp.int32))[:, None,
                                                                 None])
    qf = q.astype(jnp.float32) * scale

    # Distribution scheme (Megatron-SP style, works for ANY head count):
    # queries/scores shard the Sq dim over the model axis; k/v stay
    # replicated across model (batch-sharded over data), so the KV-block
    # scan runs with ZERO per-step collectives — one reshard at attention
    # entry/exit is the whole cost.  Head-sharding can't serve GQA archs
    # whose KV/G counts don't divide the 16-way model axis.
    #
    # Decode (Sq == 1): EVERY dim is pinned explicitly (§Perf H7) — leaving
    # dims UNCONSTRAINED let GSPMD pick conflicting cache layouts inside
    # the layer scan ("involuntary full rematerialization": 2.7 GB f32
    # cache all-gathers per layer on the GQA decode cells).  Per-step
    # attention compute is tiny; replicating it across model is free.
    dec = Sq == 1
    qf = shard_act(qf, "brrrr" if dec else "bm...")
    k = shard_act(k, "brrr" if dec else "br..")
    v = shard_act(v, "brrr" if dec else "br..")

    kb = k.reshape(B, nblk, block, KV, hd)
    vb = v.reshape(B, nblk, block, KV, hd_v)

    def step(carry, inputs):
        m, l, acc = carry
        jblk, kj, vj = inputs
        kvpos = jblk * block + jnp.arange(block, dtype=jnp.int32)
        # scores: (B, KV, G, Sq, block)
        s = jnp.einsum("bskgd,btkd->bkgst", qf, kj.astype(jnp.float32))
        # scores: Sq over model (train/prefill); fully pinned for decode
        s = shard_act(s, "brrrr" if dec else "b..m.")
        s = softcap(s, logit_cap)
        mask = jnp.ones((1, Sq, block), dtype=bool)
        if causal:
            mask &= kvpos[None, None, :] <= qpos[:, :, None]
        if window is not None:
            mask &= qpos[:, :, None] - kvpos[None, None, :] < window
        if kv_bound is not None:
            mask &= kvpos[None, None, :] < kv_bound
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p, vj.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = shard_act(jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32),
                   "b..m")
    l0 = shard_act(jnp.zeros((B, KV, G, Sq), jnp.float32), "b..m")
    a0 = shard_act(jnp.zeros((B, KV, G, Sq, hd_v), jnp.float32), "b..m.")
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(nblk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (B,KV,G,Sq,hd) -> (B,Sq,KV,G,hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd)


def _cache_write(buf: jax.Array, update: jax.Array, pos) -> jax.Array:
    """Write ``update`` (B, S, ...) into ``buf`` (B, T, ...) at time index
    ``pos``: scalar (uniform batch, the wave engine / teacher-forced paths)
    or (B,) (continuous batching, each slot at its own depth)."""
    update = update.astype(buf.dtype)
    if getattr(pos, "ndim", 0):
        def one(b, u, p):
            return jax.lax.dynamic_update_slice(
                b, u, (p,) + (0,) * (b.ndim - 1))
        return jax.vmap(one)(buf, update, pos)
    return jax.lax.dynamic_update_slice(
        buf, update, (0, pos) + (0,) * (buf.ndim - 2))


# ---------------------------------------------------------------------------
# Paged KV-cache access (block-table indirection, serving.kv_pool layout)
# ---------------------------------------------------------------------------

def paged_flat_index(block_table: jax.Array, pos: jax.Array, block_size: int
                     ) -> jax.Array:
    """Map per-row logical positions to flat pool indices.

    block_table (B, nbs) int32; pos (B, S) int32 -> (B, S) indices into the
    flattened pool ``(num_blocks * block_size, ...)``.  Positions beyond
    the table width resolve to the NULL block (0), like unallocated
    entries: stray writes (inactive slots riding along in a batched step)
    land in the trash block, never in a neighbour's data, and stray reads
    are masked by the validity bound."""
    nbs = block_table.shape[1]
    blk = pos // block_size
    oob = (blk < 0) | (blk >= nbs)
    bid = jnp.take_along_axis(block_table, jnp.clip(blk, 0, nbs - 1),
                              axis=1)
    bid = jnp.where(oob, 0, bid)
    return bid * block_size + pos % block_size


def _paged_write(buf: jax.Array, update: jax.Array, pos,
                 block_table: jax.Array) -> jax.Array:
    """Scatter ``update`` (B, S, ...) into the pool ``buf``
    (num_blocks, block_size, ...) at logical positions ``pos`` (B,) ..
    ``pos + S`` through the block table."""
    nb, bs = buf.shape[0], buf.shape[1]
    B, S = update.shape[0], update.shape[1]
    pos_rows = (jnp.atleast_1d(jnp.asarray(pos, jnp.int32))[:, None]
                + jnp.arange(S, dtype=jnp.int32)[None, :])
    idx = paged_flat_index(block_table, pos_rows, bs).reshape(-1)
    flat = buf.reshape((nb * bs,) + buf.shape[2:])
    upd = update.astype(buf.dtype).reshape((B * S,) + update.shape[2:])
    flat = flat.at[idx].set(upd, mode="drop")
    return flat.reshape(buf.shape)


def _paged_write_quant(qbuf: jax.Array, sbuf: jax.Array, update: jax.Array,
                       pos, block_table: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Quantize-on-write into an int8 pool with a per-(position, kv-head)
    scale sidecar.

    ``update`` (B, S, KV, hd) is symmetrically quantized along ``hd`` —
    one scale per written token per kv head, so a pool block carries its
    own dequant state and COW/truncate/snapshot stay block-local.  The
    all-zero row (padding, trash-block writes) gets scale 1.0 so dequant
    reproduces exact zeros."""
    upf = update.astype(jnp.float32)
    amax = jnp.max(jnp.abs(upf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(upf / scale[..., None]), -127, 127
                 ).astype(jnp.int8)
    return (_paged_write(qbuf, q, pos, block_table),
            _paged_write(sbuf, scale, pos, block_table))


def _paged_gather(buf: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather each row's blocks into a contiguous (B, nbs*block_size, ...)
    view — delegates to the canonical gather in
    ``kernels.paged_attention`` so every paged read path shares one
    implementation (the Pallas kernel performs the same gather
    block-by-block through scalar-prefetched tables instead of
    materializing it)."""
    from repro.kernels import paged_attention as PA
    return PA.gather_pool_blocks(buf, block_table)


def gqa_attention(p: dict, x: jax.Array, cfg: ModelConfig, *,
                  kind: BlockKind,
                  pos_offset: jax.Array | int = 0,
                  cache: dict | None = None,
                  block_table: jax.Array | None = None,
                  pos_advance: jax.Array | None = None,
                  backend=None,
                  ) -> tuple[jax.Array, dict | None]:
    """Full-sequence (cache=None) or cached (prefill/decode) GQA attention.

    With a cache dict {"k","v","pos"}: writes k/v at ``pos`` and attends over
    the valid prefix — one call serves prefill (S>1) and decode (S=1).

    With ``block_table`` (B, nbs) the cache leaves are interpreted as the
    block-paged pool (num_blocks, block_size, KV, hd): writes scatter and
    reads gather through the table (``serving.kv_pool`` layout).  Decode
    steps (S == 1) route through ``kernels.paged_attention.decode_attention``
    — the Pallas paged-decode kernel on TPU, the pure-JAX gather fallback
    elsewhere.  ``pos_advance`` (B,) overrides the cache-pos increment
    (chunked ragged prefill advances by each row's REAL token count, not
    the padded chunk length); at decode, a row whose advance is 0 is not
    decoding and attends nothing."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV

    q = _split_heads(dense(x, p["wq"], p.get("bq"), backend=backend), H, hd)
    k = _split_heads(dense(x, p["wk"], p.get("bk"), backend=backend), KV, hd)
    v = _split_heads(dense(x, p["wv"], p.get("bv"), backend=backend), KV, hd)

    if cfg.rope_mode is not RopeMode.NONE:
        frac = 0.5 if cfg.rope_mode is RopeMode.HALF else 1.0
        cos, sin = rope_tables(S, int(hd * frac), cfg.rope_theta, pos_offset)
        q = apply_rope(q, cos, sin, frac)
        k = apply_rope(k, cos, sin, frac)

    window = cfg.local_window if kind is BlockKind.ATTN_LOCAL else None
    scale = hd ** -0.5

    new_cache = None
    if cache is not None and block_table is not None:
        adv = S if pos_advance is None else jnp.asarray(pos_advance,
                                                        jnp.int32)
        quantized = "k_scale" in cache
        if quantized:
            ck, cks = _paged_write_quant(cache["k"], cache["k_scale"], k,
                                         cache["pos"], block_table)
            cv, cvs = _paged_write_quant(cache["v"], cache["v_scale"], v,
                                         cache["pos"], block_table)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                         "pos": cache["pos"] + adv}
        else:
            cks = cvs = None
            ck = _paged_write(cache["k"], k, cache["pos"], block_table)
            cv = _paged_write(cache["v"], v, cache["pos"], block_table)
            new_cache = {"k": ck, "v": cv, "pos": cache["pos"] + adv}
        kv_valid = cache["pos"] + adv
        if S == 1:
            # decode: the paged-decode kernel walks each row's valid pages
            # only.  Rows with pos_advance 0 are not decoding this step
            # (their token is discarded) but keep their last request's
            # cursor: they attend nothing, so their stale length costs no
            # DMA and no compute.
            from repro.kernels import paged_attention as PA
            lens = jnp.atleast_1d(kv_valid)
            if pos_advance is not None:
                lens = jnp.where(adv > 0, lens, 0)
            out = PA.decode_attention(
                q.reshape(B, KV, G, hd), ck, cv, block_table,
                lens, scale=scale, window=window,
                logit_cap=cfg.attn_logit_softcap,
                k_scale=cks, v_scale=cvs)
            out = out.reshape(B, 1, H * hd)
            return dense(out, p["wo"], backend=backend), new_cache
        k_att = _paged_gather(ck, block_table)
        v_att = _paged_gather(cv, block_table)
        if quantized:
            # dequant to the COMPUTE dtype (never a blanket fp32 widen:
            # analysis.jaxpr_lint screens int8->f32 under narrow compute)
            k_att = k_att.astype(x.dtype) * _paged_gather(
                cks, block_table).astype(x.dtype)[..., None]
            v_att = v_att.astype(x.dtype) * _paged_gather(
                cvs, block_table).astype(x.dtype)[..., None]
    elif cache is not None:
        ck = _cache_write(cache["k"], k, cache["pos"])
        cv = _cache_write(cache["v"], v, cache["pos"])
        new_cache = {"k": ck, "v": cv, "pos": cache["pos"] + S}
        k_att, v_att = ck, cv
        kv_valid = cache["pos"] + S
    else:
        k_att, v_att = k, v
        kv_valid = None

    q5 = q.reshape(B, S, KV, G, hd)
    out = _flash(q5, k_att, v_att, scale=scale, causal=cfg.causal,
                 window=window, q_offset=pos_offset, kv_valid=kv_valid,
                 logit_cap=cfg.attn_logit_softcap, block=cfg.attn_block_kv)
    out = out.reshape(B, S, H * hd)
    return dense(out, p["wo"], backend=backend), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank q, compressed kv cache
# ---------------------------------------------------------------------------

def mla_attention(p: dict, x: jax.Array, cfg: ModelConfig, *,
                  pos_offset: jax.Array | int = 0,
                  cache: dict | None = None,
                  block_table: jax.Array | None = None,
                  pos_advance: jax.Array | None = None,
                  backend=None,
                  ) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention.  Cache stores only (c_kv, k_pe):
    kv_lora_rank + rope_head_dim floats per token (the paper-relevant
    'skinny p-GEMM' decompression happens per block).

    ``block_table`` switches the cache leaves to the block-paged pool
    layout (num_blocks, block_size, dim): writes scatter / reads gather
    through the table.  The latent cache is already the paper's compressed
    'skinny' operand, so the gather fallback (not the GQA paged-decode
    kernel) is the paged hot path here."""
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qk, rp, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    # --- queries (low-rank) ---
    q_lat = rms_norm(dense(x, p["wq_a"], backend=backend), p["q_norm"],
                     cfg.norm_eps)
    q = dense(q_lat, p["wq_b"], backend=backend).reshape(B, S, H, qk + rp)
    q_nope, q_pe = q[..., :qk], q[..., qk:]

    # --- compressed kv ---
    kv_a = dense(x, p["wkv_a"], backend=backend)      # (B,S,rank+rp)
    c_kv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = kv_a[..., m.kv_lora_rank:]                 # (B,S,rp), shared head

    cos, sin = rope_tables(S, rp, cfg.rope_theta, pos_offset)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)[:, :, 0, :]

    new_cache = None
    if cache is not None and block_table is not None:
        adv = S if pos_advance is None else jnp.asarray(pos_advance,
                                                        jnp.int32)
        ckv = _paged_write(cache["c_kv"], c_kv, cache["pos"], block_table)
        cpe = _paged_write(cache["k_pe"], k_pe, cache["pos"], block_table)
        new_cache = {"c_kv": ckv, "k_pe": cpe, "pos": cache["pos"] + adv}
        c_att = _paged_gather(ckv, block_table)
        pe_att = _paged_gather(cpe, block_table)
        kv_valid = cache["pos"] + adv
    elif cache is not None:
        ckv = _cache_write(cache["c_kv"], c_kv, cache["pos"])
        cpe = _cache_write(cache["k_pe"], k_pe, cache["pos"])
        new_cache = {"c_kv": ckv, "k_pe": cpe, "pos": cache["pos"] + S}
        c_att, pe_att = ckv, cpe
        kv_valid = cache["pos"] + S
    else:
        c_att, pe_att = c_kv, k_pe
        kv_valid = None

    if S == 1 and cache is not None:
        # ---- absorbed-MLA decode (§Perf H4) --------------------------------
        # Score in LATENT space: fold wk_b into the query and wv_b into the
        # output so the 32k-token cache is never decompressed per step —
        # per-step flops drop from 2·B·T·r·H·(qk+vd) (decompression) to
        # 2·B·H·T·(r+rp) (latent scores).  Exactly the paper's skinny-GEMM
        # scheduling: same operator, different p-GEMM factorization.
        r = m.kv_lora_rank
        wk_b_arr = (p["wk_b"].dequant(q_nope.dtype)
                    if hasattr(p["wk_b"], "dequant") else p["wk_b"])
        wk_b = wk_b_arr.reshape(r, H, qk).astype(q_nope.dtype)
        q_abs = jnp.einsum("bshq,rhq->bshr", q_nope, wk_b)   # (B,1,H,r)
        q_eff = jnp.concatenate([q_abs, q_pe], axis=-1)      # (B,1,H,r+rp)
        k_eff = jnp.concatenate([c_att, pe_att], axis=-1)    # (B,T,r+rp)
        scale = (qk + rp) ** -0.5
        out_lat = _flash(q_eff.reshape(B, 1, 1, H, r + rp),
                         k_eff[:, :, None, :], c_att[:, :, None, :],
                         scale=scale, causal=cfg.causal, window=None,
                         q_offset=pos_offset, kv_valid=kv_valid,
                         logit_cap=cfg.attn_logit_softcap,
                         block=cfg.attn_block_kv)             # (B,1,1,H,r)
        wv_b_arr = (p["wv_b"].dequant(q_nope.dtype)
                    if hasattr(p["wv_b"], "dequant") else p["wv_b"])
        wv_b = wv_b_arr.reshape(r, H, vd).astype(q_nope.dtype)
        out = jnp.einsum("bshr,rhv->bshv",
                         out_lat.reshape(B, 1, H, r), wv_b)
        out = out.reshape(B, 1, H * vd)
        return dense(out, p["wo"], backend=backend), new_cache

    # decompress k, v per head from the latent (training/prefill: full seq)
    T = c_att.shape[1]
    k_nope = dense(c_att, p["wk_b"], backend=backend).reshape(B, T, H, qk)
    vv = dense(c_att, p["wv_b"], backend=backend).reshape(B, T, H, vd)

    # fold the shared k_pe in as extra head dims so one flash call suffices:
    # k_eff = [k_nope ; k_pe broadcast], q_eff = [q_nope ; q_pe]
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(pe_att[:, :, None, :], (B, T, H, rp))],
        axis=-1)
    q_eff = jnp.concatenate([q_nope, q_pe], axis=-1)

    scale = (qk + rp) ** -0.5
    # MLA is MHA (KV == H): G = 1
    out = _flash(q_eff.reshape(B, S, H, 1, qk + rp), k_eff, vv,
                 scale=scale, causal=cfg.causal, window=None,
                 q_offset=pos_offset, kv_valid=kv_valid,
                 logit_cap=cfg.attn_logit_softcap, block=cfg.attn_block_kv)
    out = out.reshape(B, S, H * vd)
    return dense(out, p["wo"], backend=backend), new_cache


def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
                  ) -> dict:
    """Empty per-layer cache for one attention block."""
    if cfg.mla is not None:
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.mla.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_len, cfg.mla.qk_rope_head_dim),
                              dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def make_paged_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                        dtype) -> dict:
    """Empty per-layer BLOCK-PAGED cache pool for one attention block
    (``serving.kv_pool`` layout: no batch dim — slots map logical
    positions onto pool blocks through the shared block table).  ``pos``
    stays the per-slot write cursor (expanded by
    ``network.expand_cache_pos``)."""
    if cfg.mla is not None:
        return {
            "c_kv": jnp.zeros((num_blocks, block_size,
                               cfg.mla.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((num_blocks, block_size,
                               cfg.mla.qk_rope_head_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.quant_kv:
        # int8 pool + per-(position, kv-head) fp32 scale sidecars; the
        # sidecars share the (num_blocks, block_size) leading layout so
        # the block table, COW copies, and snapshots address them like
        # any other pool leaf (network._POOL_KEYS)
        return {
            "k": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads,
                            cfg.hd), jnp.int8),
            "v": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads,
                            cfg.hd), jnp.int8),
            "k_scale": jnp.ones((num_blocks, block_size, cfg.n_kv_heads),
                                jnp.float32),
            "v_scale": jnp.ones((num_blocks, block_size, cfg.n_kv_heads),
                                jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, cfg.hd),
                       dtype),
        "v": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, cfg.hd),
                       dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
