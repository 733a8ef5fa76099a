"""Paged-attention contracts: the Pallas paged-decode kernel against its
gather-fallback oracle, paged write/gather against the dense cache layout,
COW block copies, and the gather-GEMM schedule registration."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.scheduler import ScheduleCache
from repro.kernels import paged_attention as PA
from repro.models import attention as A

RNG = np.random.default_rng(0)


def _rand(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


def _pool_setup(B=3, KV=2, G=4, hd=32, nb=12, bs=8, nbs=6,
                lens=(5, 23, 48)):
    q = _rand(B, KV, G, hd)
    k = _rand(nb, bs, KV, hd)
    v = _rand(nb, bs, KV, hd)
    bt = jnp.asarray(RNG.integers(1, nb, (B, nbs)), jnp.int32)
    return q, k, v, bt, jnp.asarray(lens, jnp.int32)


def _quantize(pool):
    """int8 payload + per-(position, kv-head) scale, as the pool stores it."""
    amax = jnp.max(jnp.abs(pool), axis=-1)
    sc = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(pool / sc[..., None]).astype(jnp.int8), sc


# bs 8 makes a grid step P = 16 pages (128 tokens) over a 40-page table:
# lengths 0 (no DMA, zero tile), 1, exactly P*bs, one past it, the full
# table
RAGGED = dict(B=5, nbs=40, nb=64, lens=(0, 1, 128, 129, 320))


@pytest.mark.parametrize("window,cap,geom,quant", [
    pytest.param(None, None, {}, False, id="None-None"),
    pytest.param(7, None, {}, False, id="7-None"),
    pytest.param(None, 30.0, {}, False, id="None-30.0"),
    pytest.param(9, 50.0, {}, False, id="9-50.0"),
    pytest.param(None, None, RAGGED, False, id="ragged"),
    pytest.param(150, None, RAGGED, False, id="ragged-window"),
    pytest.param(None, 30.0, RAGGED, False, id="ragged-cap"),
    pytest.param(None, None, RAGGED, True, id="ragged-int8"),
    pytest.param(40, 50.0, RAGGED, True, id="ragged-int8-window-cap"),
    pytest.param(None, None, dict(lens=(0, 0, 0)), False, id="all-idle"),
])
def test_kernel_matches_gather_fallback(window, cap, geom, quant):
    q, k, v, bt, lens = _pool_setup(**geom)
    sc = {}
    if quant:
        (k, ks), (v, vs) = _quantize(k), _quantize(v)
        sc = dict(k_scale=ks, v_scale=vs)
    ref = PA.gather_fallback(q, k, v, bt, lens, scale=0.17,
                             window=window, logit_cap=cap, **sc)
    ker = PA.paged_decode_kernel(q, k, v, bt, lens, scale=0.17,
                                 window=window, logit_cap=cap,
                                 interpret=True, **sc)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # a row of length 0 attends nothing: a zero tile on both paths
    assert (np.asarray(ker)[np.asarray(lens) == 0] == 0).all()


@pytest.mark.parametrize("window", [None, 20], ids=["full", "window"])
def test_work_list_reads_only_valid_pages(window):
    """The grid visits each row's valid pages only, P at a time, rows in
    order; a row of length 0 gets no item, and a step's spare page
    operands repeat the row's last page instead of reading past it."""
    bs, P = 8, 4
    bt = jnp.arange(1, 31, dtype=jnp.int32).reshape(3, 10)
    lens = jnp.asarray([0, 17, 80], jnp.int32)
    n, row, step, blk, first, steps = PA._work_list(
        bt, lens, bs=bs, pages=P, window=window)
    n = int(n)
    blk = np.asarray(blk).reshape(-1, P)[:n]
    if window is None:
        want = [(1, 0, [0, 1, 2, 2]), (2, 0, [0, 1, 2, 3]),
                (2, 1, [4, 5, 6, 7]), (2, 2, [8, 9, 9, 9])]
    else:       # row 2's window starts at position 60, in page 7
        want = [(1, 0, [0, 1, 2, 2]), (2, 0, [7, 8, 9, 9])]
    assert n == len(want)
    for i, (r, st, pages) in enumerate(want):
        assert (int(row[i]), int(step[i])) == (r, st)
        np.testing.assert_array_equal(blk[i], np.asarray(bt)[r, pages])
    assert int(steps[0]) == 0


def test_pages_per_step_from_shapes():
    """P covers >= 128 tokens, never more pages than the table holds, and
    shrinks only when a step's padded pages would outgrow VMEM."""
    assert PA.pages_per_step(16, 2, 64, 2, 256) == 8
    assert PA.pages_per_step(8, 2, 32, 4, 40) == 16
    assert PA.pages_per_step(16, 2, 64, 2, 4) == 4
    assert PA.pages_per_step(16, 20, 128, 2, 256) == 8
    assert PA.pages_per_step(256, 64, 512, 4, 256) == 1


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_idle_rows_attend_nothing_at_the_call_site(monkeypatch, quant):
    """At decode, rows with pos_advance 0 reach the kernel with length 0
    whatever cursor they kept, so a stale cursor leaves the decoding rows'
    outputs bit-identical; without pos_advance every row attends pos + 1."""
    import dataclasses
    from repro import configs as CONFIGS
    from repro.models.config import BlockKind
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    cfg = dataclasses.replace(cfg, quant_serving=quant)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B, bs, nbs = 4, 8, 8
    p = {"wq": _rand(D, H * hd) * D ** -0.5,
         "wk": _rand(D, KV * hd) * D ** -0.5,
         "wv": _rand(D, KV * hd) * D ** -0.5,
         "wo": _rand(H * hd, D) * (H * hd) ** -0.5}
    cache = A.make_paged_kv_cache(cfg, 1 + B * nbs, bs, jnp.float32)
    for name in ("k", "v"):
        pool = _rand(*cache[name].shape)
        if quant:
            cache[name], cache[f"{name}_scale"] = _quantize(pool)
        else:
            cache[name] = pool
    bt = jnp.asarray(1 + RNG.permutation(B * nbs).reshape(B, nbs),
                     jnp.int32)
    x = _rand(B, 1, D)
    adv = jnp.asarray([1, 0, 1, 0], jnp.int32)

    seen, dispatch = [], PA.decode_attention

    def kernel(*args, **kw):
        seen.append(np.asarray(args[4]))
        return dispatch(*args, use_kernel=True, interpret=True, **kw)
    monkeypatch.setattr(PA, "decode_attention", kernel)

    def run(pos, pos_advance):
        c = dict(cache, pos=jnp.asarray(pos, jnp.int32))
        out, _ = A.gqa_attention(p, x, cfg, kind=BlockKind.ATTN,
                                 pos_offset=c["pos"], cache=c,
                                 block_table=bt, pos_advance=pos_advance)
        return np.asarray(out)

    stale = run([37, 5, 50, 60], adv)
    fresh = run([37, 0, 50, 0], adv)
    run([37, 5, 50, 60], None)
    np.testing.assert_array_equal(seen[0], [38, 0, 51, 0])
    np.testing.assert_array_equal(seen[1], [38, 0, 51, 0])
    np.testing.assert_array_equal(seen[2], [38, 6, 51, 61])
    np.testing.assert_array_equal(stale[adv == 1], fresh[adv == 1])


def test_decode_attention_dispatch_off_tpu_is_fallback():
    q, k, v, bt, lens = _pool_setup()
    out = PA.decode_attention(q, k, v, bt, lens, scale=0.17)
    ref = PA.gather_fallback(q, k, v, bt, lens, scale=0.17)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_paged_matches_dense_attention_over_valid_prefix():
    """Scattering a sequence through a shuffled block table and attending
    via the paged path must equal dense contiguous attention."""
    B, T, KV, G, hd, bs = 2, 24, 2, 3, 16, 4
    nbs = T // bs
    kseq = _rand(B, T, KV, hd)
    vseq = _rand(B, T, KV, hd)
    q = _rand(B, KV, G, hd)
    lens = jnp.asarray([T, T - 7], jnp.int32)

    # build the pool by writing each row's sequence through its table
    nb = 1 + B * nbs
    perm = RNG.permutation(np.arange(1, nb)).reshape(B, nbs)
    bt = jnp.asarray(perm, jnp.int32)
    k_pool = jnp.zeros((nb, bs, KV, hd), jnp.float32)
    v_pool = jnp.zeros((nb, bs, KV, hd), jnp.float32)
    k_pool = A._paged_write(k_pool, kseq, jnp.zeros(B, jnp.int32), bt)
    v_pool = A._paged_write(v_pool, vseq, jnp.zeros(B, jnp.int32), bt)

    # gather roundtrip reproduces the contiguous layout
    np.testing.assert_array_equal(
        np.asarray(A._paged_gather(k_pool, bt)), np.asarray(kseq))

    out = PA.gather_fallback(q, k_pool, v_pool, bt, lens, scale=hd**-0.5)
    # dense reference: masked softmax over the contiguous sequence
    s = jnp.einsum("bkgd,btkd->bkgt", q * hd**-0.5, kseq)
    mask = jnp.arange(T)[None, None, None, :] < lens[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    ref = jnp.einsum("bkgt,btkd->bkgd", jax.nn.softmax(s, axis=-1), vseq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    ker = PA.paged_decode_kernel(q, k_pool, v_pool, bt, lens,
                                 scale=hd**-0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_write_beyond_table_lands_in_trash_block():
    """Positions past the table width clamp onto the NULL block — no
    neighbouring block is ever corrupted (the engine's inactive-slot
    writes rely on this)."""
    bs, nbs = 4, 2
    pool = jnp.zeros((4, bs, 1, 2), jnp.float32)
    bt = jnp.asarray([[2, 3]], jnp.int32)
    upd = jnp.ones((1, 1, 1, 2), jnp.float32)
    out = A._paged_write(pool, upd, jnp.asarray([bs * nbs + 5]), bt)
    assert float(jnp.sum(out[2])) == 0 and float(jnp.sum(out[3])) == 0
    assert float(jnp.sum(out[0])) != 0       # trash block absorbed it


def test_copy_paged_blocks_preserves_source():
    from repro.models import network as N
    from repro import configs as CONFIGS
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    NB = 6
    caches = N.init_paged_caches(cfg, slots=2, num_blocks=NB, block_size=4)

    def block_axis(leaf):
        # pool leaves: (NB, bs, ...) or group-stacked (G, NB, bs, ...)
        return 0 if leaf.shape[0] == NB else 1

    # mark block 2 in every pool leaf, then fork it to block 5
    def paint(path, leaf):
        if leaf.ndim < 3:       # pos cursors
            return leaf
        ax = block_axis(leaf)
        return jnp.moveaxis(
            jnp.moveaxis(leaf, ax, 0).at[2].set(7.0), 0, ax)
    caches = jax.tree_util.tree_map_with_path(paint, caches)
    out = N.copy_paged_blocks(caches, jnp.asarray([2]), jnp.asarray([5]))

    def check(path, leaf):
        if leaf.ndim >= 3:
            moved = np.moveaxis(np.asarray(leaf), block_axis(leaf), 0)
            np.testing.assert_array_equal(moved[5], moved[2])  # copied
            assert (moved[2] == 7.0).all()                     # src intact
        return leaf
    jax.tree_util.tree_map_with_path(check, out)


def test_gather_gemm_resolution_and_application_split():
    """resolve explores/memoizes WITHOUT touching the applied log; only
    note_gather_applied (called by the engine after a real paged-decode
    dispatch) records applications — the log is a record of dispatches,
    not registrations."""
    from repro import configs as CONFIGS
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    sc = ScheduleCache()
    shapes = PA.gather_gemm_shapes(cfg, 16)
    choices = PA.resolve_gather_gemms(sc, cfg, 16, "FP32")
    assert len(choices) == len(shapes)
    assert sc.stats()["misses"] == len(shapes)
    assert sc.stats()["applied"] == 0               # resolution != application
    PA.note_gather_applied(sc, cfg, 16, "FP32")
    st = sc.stats()
    assert st["applied"] == len(shapes)
    assert st["misses"] == len(shapes)              # second pass all hits
    applied = {k[:3] for k, _ in sc.applied}
    assert all(tuple(s) in applied for s in shapes)
