"""Pallas TPU kernels for the GTA compute hot-spots (+ jnp oracles).

  limb_gemm    — multi-precision exact integer GEMM via balanced int8 limbs
                 (paper §3.1 on the MXU), OS dataflow, VMEM diagonal planes
  accumulator  — Fig.-3 multi-precision accumulator (uint32-pair shift-adds)
  mpgemm       — fp GEMM with WS / IS / OS selectable block schedules (§5)
  quant_matmul — int8-weight serving path (GTA's native-precision fast case)
  paged_attention — paged-decode attention for the block-paged KV pool
                 (a run-time work list over each row's valid pages,
                 several pages a step, online softmax; pure-JAX gather
                 fallback off-TPU; gather-GEMM shapes registered with the
                 paper-§5 ScheduleCache)
  ops          — public padded/jit'd wrappers + the GEMM execution layer;
                 block shapes chosen by the GTA scheduling bridge
                 (core.tiling)
  ref          — pure-jnp/numpy oracles for all of the above

GEMM execution layer
--------------------
The §5 scheduling space (dataflow x precision x array resize) only pays off
if the chosen schedule is what actually executes.  Two pieces make the
scheduled path the fast path end to end:

  * **Fused reduction** (``mpgemm``): WS/IS and the OS k-fold variants used
    to materialize a ``(gk, M, N)`` fp32 partial-plane tensor in HBM and
    reduce it with a separate ``jnp.sum``.  The default epilogue now
    accumulates IN-KERNEL — revisit-safe output blocks (zero-init on first
    visit, ``+=`` on revisit, ``arbitrary`` semantics on revisited grid
    dims) for WS/IS, a VMEM-resident accumulator across fold bands for OS —
    so no intermediate tensor exists and the only per-instance state is one
    ``(bm, bn)`` fp32 block.  ``k_fold`` is a real fold-banded grid on all
    three dataflows; unrealizable folds degrade via ``effective_fold`` and
    the EFFECTIVE value is what ``ScheduleCache.note_applied`` logs.  The
    legacy spill path survives as ``epilogue="spill"`` for benchmarking
    (``benchmarks/kernels_bench`` gates fused on "no partial plane" and
    compares traffic).

  * **GemmBackend** (``ops``): the dispatcher that routes
    ``models.layers.dense`` (float and QuantTensor) through the scheduled
    kernels when ``ModelConfig.gemm_backend == "scheduled"``.  One backend
    (and one ScheduleCache) per config; stacked ``(B, S, K)`` activations
    collapse to a single GEMM; block configs memoize per static shape; the
    serving engine pre-resolves its decode shapes so the steady-state hot
    path is a pure cache-hit dispatch.  The default ``"xla"`` keeps
    projections on XLA's native fusions (the right call off-TPU, where
    Pallas runs in interpret mode).

Kernels target TPU (BlockSpec VMEM tiling, MXU-aligned blocks) and are
validated on CPU with interpret=True.  Every kernel's ``interpret``
defaults to ``None``, which :func:`resolve_interpret` turns into compiled
Pallas on a TPU and interpret mode anywhere else.
"""

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU — the one platform check
    behind every kernel default and the paged-decode kernel/fallback
    choice."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's ``interpret`` argument: the caller's explicit choice, else
    interpret mode exactly when no TPU is present."""
    return (not on_tpu()) if interpret is None else interpret
