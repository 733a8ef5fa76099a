"""The shape functions of bench/costs.py against the program's own jaxpr
walk (``repro.launch.jaxpr_cost``), at a small size where every row
attends its whole table, so that the full-table work the CPU path does
equals the valid work the functions count."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs
from bench.models import qwen2

DATA = pathlib.Path(__file__).resolve().parent / "data"
B, BS, MAX_LEN = 3, 16, 64


def _tiny(dtype="float32"):
    cj = json.loads((DATA / "configs" / "tiny-qwen2.json").read_text())
    cj["torch_dtype"] = dtype
    return cj


def test_decode_step_flops_match_the_jaxpr():
    from repro.launch.jaxpr_cost import step_cost
    from repro.models import network as N
    cj = _tiny()
    m = qwen2.dims(cj)
    cfg = qwen2.serving_config(cj, "float32")
    params = qwen2.to_program_tree(cfg, qwen2.make_weights(cj, 3, jnp.float32))
    nbs = MAX_LEN // BS
    caches = N.expand_cache_pos(
        N.init_paged_caches(cfg, B, B * nbs + 1, BS), B)
    bt = jnp.arange(1, B * nbs + 1, dtype=jnp.int32).reshape(B, nbs)
    pos = jnp.full((B,), MAX_LEN - 1, jnp.int32)
    toks = jnp.zeros((B, 1), jnp.int32)
    got = step_cost(lambda p, t, c, q, b: N.decode_step(
        p, cfg, t, c, q, block_table=b,
        pos_advance=jnp.ones((B,), jnp.int32)),
        params, toks, caches, pos, bt)
    flops, byts = costs.decode_step(m, [MAX_LEN] * B, itemsize=4)
    # the walk also counts one op per element reduced: each RMSNorm's
    # mean, and the softmax's max and sum over every score
    reduced = B * m["d"] * (2 * m["n"] + 1) \
        + m["n"] * 2 * m["h"] * B * MAX_LEN
    assert got["flops"] == flops + reduced
    # the walk also counts activations and the whole-table gather, so it
    # bounds the unavoidable bytes from above
    assert byts <= got["bytes"]
    assert costs.weight_bytes(m, 4) == sum(
        x.size * 4 for x in jax.tree.leaves(params))


def test_paged_kernel_flops_match_the_jaxpr():
    from repro.kernels.paged_attention import paged_decode_kernel
    from repro.launch.jaxpr_cost import step_cost
    m = qwen2.dims(_tiny())
    kv, g, hd = m["kv"], m["h"] // m["kv"], m["hd"]
    nbs = MAX_LEN // BS
    q = jnp.zeros((B, kv, g, hd), jnp.float32)
    pool = jnp.zeros((B * nbs + 1, BS, kv, hd), jnp.float32)
    bt = jnp.arange(1, B * nbs + 1, dtype=jnp.int32).reshape(B, nbs)
    lens = jnp.full((B,), MAX_LEN, jnp.int32)
    got = step_cost(lambda a, k, v, t, n: paged_decode_kernel(
        a, k, v, t, n, scale=hd ** -0.5, interpret=True),
        q, pool, pool, bt, lens)
    one_layer = dict(m, n=1)
    flops, byts = costs.paged_decode_kernel(one_layer, [MAX_LEN] * B, BS,
                                            itemsize=4)
    # plus the softmax's max and sum, one op per score
    assert got["flops"] == flops + 2 * m["h"] * B * MAX_LEN
    assert byts == 4 * (2 * kv * hd * B * MAX_LEN + 2 * B * m["h"] * hd)


def test_partial_blocks_are_fetched_whole():
    m = qwen2.dims(_tiny())
    f1, b1 = costs.paged_decode_kernel(m, [17], 16)
    f2, b2 = costs.paged_decode_kernel(m, [32], 16)
    assert b1 == b2 and f1 < f2


def test_roofline_names_its_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_s(1000.0, 10.0, pk) == (10.0, "compute")
    assert costs.roofline_s(10.0, 1000.0, pk) == (100.0, "memory")


def test_peaks_refuse_an_unknown_device():
    import pytest
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")


def test_weights_drawn_alike_whole_and_by_layer():
    cj = _tiny("bfloat16")
    w = qwen2.make_weights(cj, 2**33 + 3, jnp.bfloat16)
    key = qwen2.seed_key(2**33 + 3)
    for i in range(qwen2.dims(cj)["n"]):
        one = jax.jit(lambda k, j: qwen2.draw_layer(cj, k, j, jnp.bfloat16))(
            key, jnp.uint32(i))
        for name, leaf in one.items():
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(w["layers"][name][i]))
