"""HLO walker + jaxpr cost model validation (the roofline instrumentation
must itself be trustworthy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hloanalysis import HloModule, analyze
from repro.launch.jaxpr_cost import step_cost


def test_walker_matches_costanalysis_loop_free():
    a = jnp.zeros((256, 512), jnp.bfloat16)
    b = jnp.zeros((512, 384), jnp.bfloat16)
    comp = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    r = analyze(comp.as_text(), 1)
    assert r["walked_dot_flops"] == 2 * 256 * 512 * 384


def test_walker_multiplies_scan_trips():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jnp.zeros((128, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    r = analyze(comp.as_text(), 1)
    assert r["walked_dot_flops"] == 10 * 2 * 128 ** 3
    assert max(t for _, t in r["loops"]) == 10


def test_walker_parses_tiled_layout_operands():
    """TPU dumps annotate layouts like ``{1,0:T(8,128)}``; the dot-operand
    parser must still recover the inline LHS shape (regression: the layout
    regex only accepted ``{digits,commas}`` and silently fell back to
    K=1)."""
    mod = HloModule("""
ENTRY %main.1 (p0: f32[4,8], p1: f32[8,16]) -> f32[4,16] {
  %p0 = f32[4,8]{1,0:T(8,128)} parameter(0)
  %p1 = f32[8,16]{1,0:T(8,128)} parameter(1)
  ROOT %dot.1 = f32[4,16]{1,0:T(8,128)} dot(f32[4,8]{1,0:T(8,128)} %p0, f32[8,16]{1,0:T(8,128)} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""")
    flops, _ = mod.dot_flops()
    assert flops == 2 * 4 * 16 * 8


def test_jaxpr_cost_exact_dot():
    a = jnp.zeros((64, 32), jnp.float32)
    b = jnp.zeros((32, 16), jnp.float32)
    c = step_cost(lambda a, b: a @ b, a, b)
    assert c["flops"] == 2 * 64 * 32 * 16


def test_jaxpr_cost_scan_and_grad_remat():
    w = jnp.zeros((64, 64), jnp.float32)
    x = jnp.zeros((64, 64), jnp.float32)

    def g(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=7)
        return jnp.sum(y)

    c = step_cost(jax.grad(g), w, x)
    # fwd + remat-fwd + dgrad + wgrad = 4 matmuls per step
    assert c["flops"] == pytest.approx(4 * 7 * 2 * 64 ** 3, rel=0.02)


def test_jaxpr_cost_counts_batched_dot():
    a = jnp.zeros((4, 32, 16), jnp.float32)
    b = jnp.zeros((4, 16, 8), jnp.float32)
    c = step_cost(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    assert c["flops"] == 2 * 4 * 32 * 16 * 8


def test_collective_parse_on_sharded_program():
    import os
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under dryrun flags)")
    # exercised end-to-end by the dry-run artifacts; unit coverage of the
    # transfer model:
    mod = HloModule("""
ENTRY %main.1 (p0: f32[16,16]) -> f32[16,16] {
  %p0 = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%p0), replica_groups=[2,8]<=[16], to_apply=%add
}
""", 16)
    coll = mod.collective_bytes()
    want = 2 * 16 * 16 * 4 * 7 / 8  # 2*size*(n-1)/n, n=8
    assert coll["per_device_bytes"] == pytest.approx(want)


@pytest.mark.parametrize("dtype,flops", [(jnp.float32, 4 * 6),
                                         (jnp.int32, 0)])
def test_jaxpr_cost_counts_float_reductions_only(dtype, flops):
    x = jnp.zeros((4, 6), dtype)
    c = step_cost(lambda x: jnp.sum(x, axis=1), x)
    assert c["flops"] == flops
    assert c["bytes"] > 0


def test_jaxpr_cost_scales_a_run_time_grid_by_its_bound():
    """The paged-decode kernel's grid is sized at run time; the cost model
    counts its body once per item of the longest work list the call
    publishes (every row at the full table): the two matmuls and the
    softmax's max and sum over each step's tokens."""
    from repro.kernels import paged_attention as PA
    B, KV, G, hd, bs, nbs = 2, 2, 3, 16, 8, 40
    P = PA.pages_per_step(bs, KV, hd, 4, nbs)
    items, T = B * -(-nbs // P), P * bs
    q = jnp.zeros((B, KV, G, hd), jnp.float32)
    pool = jnp.zeros((B * nbs + 1, bs, KV, hd), jnp.float32)
    bt = jnp.arange(1, B * nbs + 1, dtype=jnp.int32).reshape(B, nbs)
    lens = jnp.full((B,), nbs * bs, jnp.int32)
    c = step_cost(lambda *a: PA.paged_decode_kernel(
        *a, scale=0.25, interpret=True), q, pool, pool, bt, lens)
    assert items > B
    assert c["flops"] == items * KV * G * T * (2 * hd + 2 * hd + 2)
