"""Ahead-of-time compiles of the serving path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
(tiles off the (8, 128) layout, VMEM overruns) — which interpret mode
cannot show.  Shapes are qwen2-0.5b's full widths: hd 64, 2 KV heads with
7 query heads each, 16-token blocks, d_model 896, d_ff 4864, vocab 151936,
8 slots, 32-token prefill chunks; the paged-decode kernel also at the
benchmark cell's geometry and at qwen1.5-4b's widths.  Nothing runs, so
these say nothing about numerics (``chip_smoke.py`` checks those on the
chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a test worker that fails
to must skip these tests, not collect different ones.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.scheduler import ScheduleCache
from repro.kernels import ops
from repro.kernels import paged_attention as PA

SLOTS, CHUNK = 8, 32
KV, G, HD, BLOCK, NUM_BLOCKS = 2, 7, 64, 16, 512
D, FF, VOCAB = 896, 4864, 151936


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — whatever the cause, no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (slots, table width, pool blocks, KV, G, hd): the 8-slot geometry above;
# the benchmark's qwen2-0.5b.chat cell (16 slots, 4096 context, 3073
# blocks); qwen1.5-4b's widths, which share the kernel
SERVED = (SLOTS, 1024 // BLOCK, NUM_BLOCKS, KV, G, HD)
CELL = (16, 4096 // BLOCK, 3073, KV, G, HD)
QWEN15_4B = (16, 4096 // BLOCK, 448, 20, 1, 128)


@pytest.mark.parametrize("kv_dtype,geom", [
    pytest.param("bfloat16", SERVED, id="bfloat16"),
    pytest.param("int8", SERVED, id="int8"),
    pytest.param("bfloat16", CELL, id="cell-bfloat16"),
    pytest.param("int8", CELL, id="cell-int8"),
    pytest.param("bfloat16", QWEN15_4B, id="qwen1.5-4b-bfloat16"),
    pytest.param("int8", QWEN15_4B, id="qwen1.5-4b-int8"),
])
def test_paged_decode_kernel_compiles(one_chip, kv_dtype, geom):
    slots, nbs, nb, kv, g, hd = geom
    pool = (nb, BLOCK, kv, hd)
    args = [_sds(one_chip, (slots, kv, g, hd), jnp.bfloat16),
            _sds(one_chip, pool, kv_dtype), _sds(one_chip, pool, kv_dtype),
            _sds(one_chip, (slots, nbs), jnp.int32),
            _sds(one_chip, (slots,), jnp.int32)]
    if kv_dtype == "int8":
        args += [_sds(one_chip, pool[:3], jnp.float32)] * 2

    def step(q, k, v, bt, lens, *scales):
        sc = dict(zip(("k_scale", "v_scale"), scales))
        return PA.paged_decode_kernel(q, k, v, bt, lens, scale=hd ** -0.5,
                                      interpret=False, **sc)

    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [SLOTS, SLOTS * CHUNK],
                         ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("n,k", [((KV * 2 + KV * G) * HD, D), (D, FF),
                                 (VOCAB, D)], ids=["qkv", "down", "head"])
def test_projection_gemms_compile(one_chip, rows, n, k):
    """The scheduled float GEMM and the int8 GEMM in one program, at the
    shapes the engine pre-resolves in its ScheduleCache."""
    sched = ScheduleCache()

    def step(a, b, w_q, scale):
        return (ops.matmul(a, b, schedule=sched, interpret=False),
                ops.quant_matmul(a, w_q, scale, schedule=sched,
                                 interpret=False))

    compiled = jax.jit(step).lower(
        _sds(one_chip, (rows, k), jnp.bfloat16),
        _sds(one_chip, (k, n), jnp.bfloat16),
        _sds(one_chip, (k, n), jnp.int8),
        _sds(one_chip, (n,), jnp.float32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
