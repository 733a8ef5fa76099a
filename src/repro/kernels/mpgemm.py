"""Dataflow-selectable GEMM kernel: WS / IS / OS as Pallas block schedules.

The paper's §5 schedules one p-GEMM by choosing which operand is stationary.
On TPU "stationary" = the operand block whose BlockSpec index_map is
invariant along the innermost grid dimension (its VMEM copy is not re-fetched
between consecutive grid steps):

  OS  grid (m, n, k), k innermost: the fp32 accumulator tile is resident in
      VMEM scratch across K steps and written once — outputs stationary.
  WS  grid (n, f, kf, m), m innermost: the B (weight) block (k, n) is
      constant while M streams — weights stationary.
  IS  grid (m, f, kf, n), n innermost: the A (input) block (m, k) is
      constant while N streams — inputs stationary.

GEMM execution layer (fused reduction)
--------------------------------------
WS and IS visit each output block once per K step, NON-consecutively.  The
seed implementation materialized one fp32 partial plane per K step — a
``(gk, M, N)`` HBM tensor reduced by a separate ``jnp.sum`` — which made the
spilled partial sums the single largest avoidable traffic term on the
scheduled path.  The default execution now FUSES the reduction into the
kernel: output blocks are revisit-safe accumulators (``@pl.when``-guarded
zero-init on the first visit, ``+=`` on every revisit, ``arbitrary``
dimension semantics on the revisited grid dims so Mosaic round-trips the
block through HBM between non-consecutive visits).  No intermediate tensor
ever exists; the only per-program-instance state is one ``(bm, bn)`` fp32
accumulator block.

``k_fold`` (the paper's Uncover remedy) is a REAL fold-banded variant on all
three dataflows: the K grid splits into ``f`` bands of ``gk / f`` steps each
(``effective_fold`` degrades unrealizable requests to the largest divisor of
``gk``), so the band boundary the scheduler costs is explicit in the grid.
With the fused epilogue a band's partials never leave the chip, so folding
changes only the traversal structure; with ``epilogue="spill"`` the legacy
behavior is kept for benchmarking: WS/IS spill one plane per K step
(``(gk, M, N)``), OS ``k_fold > 1`` spills one plane per band
(``(f, M, N)``), and a ``jnp.sum`` merges them.  ``benchmarks/kernels_bench``
gates the fused path on "no partial plane" (jaxpr peak-intermediate bytes)
and compares both against XLA's native dot.

On-TPU note: non-consecutive output revisits rely on Mosaic's write-back /
re-fetch of out blocks under ``arbitrary`` semantics; interpret mode (the
default off-TPU) has read-modify-write block semantics by construction.

All dataflows compute identical results (tests assert so); they differ in
traffic exactly the way ``core.dataflow`` predicts — ``dispatch_plan``
reports the structural traffic/footprint model for a given dispatch, which
is how the TPU adaptation keeps the paper's scheduling space meaningful.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import Dataflow
from repro.kernels import resolve_interpret

EPILOGUES = ("fused", "spill")


def _fold_bands(gk: int, k_fold: int) -> int:
    """Largest divisor of ``gk`` not exceeding the requested fold."""
    f = max(1, min(k_fold, gk))
    while gk % f:
        f -= 1
    return f


def effective_fold(K: int, bk: int, k_fold: int) -> int:
    """The fold the kernel actually executes for a contraction of ``K``
    elements at block size ``bk``: fold bands must tile the K grid evenly,
    so a requested ``k_fold`` silently degrades to the largest divisor of
    ``gk = ceil(K / bk)``.  Callers recording applied schedules
    (``ScheduleCache.note_applied``) must log THIS value, not the request.
    """
    gk = max(1, -(-K // bk))
    return _fold_bands(gk, k_fold)


# ---------------------------------------------------------------------------
# Fused-reduction kernels (default execution path)
# ---------------------------------------------------------------------------

def _os_kernel(a_ref, b_ref, out_ref, acc_ref, *, gk: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == gk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_dtype)


def _os_fold_fused_kernel(a_ref, b_ref, out_ref, acc_ref, *, f: int,
                          gkf: int, out_dtype):
    """OS with K-folding, reduction fused: the accumulator tile stays
    resident across ALL bands (they are consecutive along the inner grid
    dims), so band partials never leave VMEM."""
    fi = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((fi == 0) & (k == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((fi == f - 1) & (k == gkf - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_dtype)


def _ws_is_fused_kernel(a_ref, b_ref, out_ref):
    """WS/IS fused reduction: the fp32 output block is the accumulator.
    The block is revisited once per (band, K-step) pair — zero it on the
    first visit, accumulate on every revisit (revisit-safe: the revisited
    grid dims carry ``arbitrary`` semantics)."""
    fi = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((fi == 0) & (k == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Legacy spill kernels (kept as the benchmark baseline: epilogue="spill")
# ---------------------------------------------------------------------------

def _partial_kernel(a_ref, b_ref, out_ref):
    """WS/IS spill baseline: emit one partial product plane per K-step (no
    accumulation — the wrapper's ``jnp.sum`` materializes the partial-plane
    traffic the seed implementation paid on every WS/IS dispatch)."""
    out_ref[0, :, :] = jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _os_fold_spill_kernel(a_ref, b_ref, out_ref, acc_ref, *, gkf: int):
    """OS k-fold spill baseline: fold band ``fi`` accumulates its K-segment
    on-chip and spills its own partial plane; the wrapper's reduction
    materializes the extra partial-sum traffic ``core.dataflow`` charges."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == gkf - 1)
    def _flush():
        out_ref[0, :, :] = acc_ref[...]


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dataflow", "bm", "bn", "bk",
                                             "k_fold", "out_dtype",
                                             "interpret", "epilogue"))
def mpgemm(a: jax.Array, b: jax.Array, *, dataflow: Dataflow = Dataflow.OS,
           bm: int = 128, bn: int = 128, bk: int = 128, k_fold: int = 1,
           out_dtype=jnp.float32, interpret: bool | None = None,
           epilogue: str = "fused") -> jax.Array:
    """GEMM with an explicit systolic-dataflow schedule.

    a: (M, K), b: (K, N); M/N/K multiples of bm/bn/bk (ops.matmul pads).
    ``k_fold`` requests the paper's Uncover fold remedy on any dataflow;
    the executed fold is ``effective_fold(K, bk, k_fold)``.
    ``epilogue="fused"`` (default) reduces partial sums in-kernel — no
    intermediate tensor exists; ``"spill"`` keeps the seed's
    materialize-then-``jnp.sum`` baseline for benchmarking.
    """
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {K} vs {K2}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, N, K)} not divisible by {(bm, bn, bk)}")
    if epilogue not in EPILOGUES:
        raise ValueError(f"epilogue {epilogue!r} not in {EPILOGUES}")
    gm, gn, gk = M // bm, N // bn, K // bk
    f = _fold_bands(gk, k_fold)
    gkf = gk // f

    if dataflow is Dataflow.OS or dataflow is Dataflow.SIMD:
        if f > 1 and epilogue == "spill":
            partials = pl.pallas_call(
                functools.partial(_os_fold_spill_kernel, gkf=gkf),
                grid=(gm, gn, f, gkf),
                in_specs=[
                    pl.BlockSpec((bm, bk),
                                 lambda m, n, fi, k: (m, fi * gkf + k)),
                    pl.BlockSpec((bk, bn),
                                 lambda m, n, fi, k: (fi * gkf + k, n)),
                ],
                out_specs=pl.BlockSpec((1, bm, bn),
                                       lambda m, n, fi, k: (fi, m, n)),
                out_shape=jax.ShapeDtypeStruct((f, M, N), jnp.float32),
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "arbitrary",
                                         "arbitrary")),
                interpret=interpret,
                name="mpgemm_os_fold_spill",
            )(a, b)
            return jnp.sum(partials, axis=0).astype(out_dtype)
        if f > 1:
            return pl.pallas_call(
                functools.partial(_os_fold_fused_kernel, f=f, gkf=gkf,
                                  out_dtype=out_dtype),
                grid=(gm, gn, f, gkf),
                in_specs=[
                    pl.BlockSpec((bm, bk),
                                 lambda m, n, fi, k: (m, fi * gkf + k)),
                    pl.BlockSpec((bk, bn),
                                 lambda m, n, fi, k: (fi * gkf + k, n)),
                ],
                out_specs=pl.BlockSpec((bm, bn),
                                       lambda m, n, fi, k: (m, n)),
                out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "arbitrary",
                                         "arbitrary")),
                interpret=interpret,
                name="mpgemm_os_fold",
            )(a, b)
        kernel = functools.partial(_os_kernel, gk=gk, out_dtype=out_dtype)
        return pl.pallas_call(
            kernel,
            grid=(gm, gn, gk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
                pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="mpgemm_os",
        )(a, b)

    if dataflow not in (Dataflow.WS, Dataflow.IS):
        raise ValueError(f"unsupported dataflow {dataflow}")

    if epilogue == "spill":
        # Seed baseline: one partial plane per K-step, reduced by jnp.sum —
        # the (gk, M, N) HBM tensor the fused path exists to kill.
        if dataflow is Dataflow.WS:
            # grid (n, k, m): B block (k, n) invariant along innermost m.
            partials = pl.pallas_call(
                _partial_kernel,
                grid=(gn, gk, gm),
                in_specs=[
                    pl.BlockSpec((bm, bk), lambda n, k, m: (m, k)),
                    pl.BlockSpec((bk, bn), lambda n, k, m: (k, n)),
                ],
                out_specs=pl.BlockSpec((1, bm, bn),
                                       lambda n, k, m: (k, m, n)),
                out_shape=jax.ShapeDtypeStruct((gk, M, N), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary",
                                         "arbitrary")),
                interpret=interpret,
                name="mpgemm_ws_spill",
            )(a, b)
        else:
            # grid (m, k, n): A block (m, k) invariant along innermost n.
            partials = pl.pallas_call(
                _partial_kernel,
                grid=(gm, gk, gn),
                in_specs=[
                    pl.BlockSpec((bm, bk), lambda m, k, n: (m, k)),
                    pl.BlockSpec((bk, bn), lambda m, k, n: (k, n)),
                ],
                out_specs=pl.BlockSpec((1, bm, bn),
                                       lambda m, k, n: (k, m, n)),
                out_shape=jax.ShapeDtypeStruct((gk, M, N), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary",
                                         "arbitrary")),
                interpret=interpret,
                name="mpgemm_is_spill",
            )(a, b)
        return jnp.sum(partials, axis=0).astype(out_dtype)

    # Fused WS/IS: fold-banded grid, fp32 output block as the accumulator.
    if dataflow is Dataflow.WS:
        # grid (n, f, kf, m): B block invariant along innermost m.
        out = pl.pallas_call(
            _ws_is_fused_kernel,
            grid=(gn, f, gkf, gm),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda n, fi, k, m: (m, fi * gkf + k)),
                pl.BlockSpec((bk, bn),
                             lambda n, fi, k, m: (fi * gkf + k, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda n, fi, k, m: (m, n)),
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                     "arbitrary")),
            interpret=interpret,
            name="mpgemm_ws",
        )(a, b)
    else:
        # grid (m, f, kf, n): A block invariant along innermost n.
        out = pl.pallas_call(
            _ws_is_fused_kernel,
            grid=(gm, f, gkf, gn),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda m, fi, k, n: (m, fi * gkf + k)),
                pl.BlockSpec((bk, bn),
                             lambda m, fi, k, n: (fi * gkf + k, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, fi, k, n: (m, n)),
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                     "arbitrary")),
            interpret=interpret,
            name="mpgemm_is",
        )(a, b)
    return out if out.dtype == jnp.dtype(out_dtype) else out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Dispatch telemetry (structural — no wall clock): what a given mpgemm
# dispatch allocates and moves.  benchmarks/kernels_bench gates the fused
# path on intermediate_hbm_bytes == 0 and compares modeled traffic.
# ---------------------------------------------------------------------------

def dispatch_plan(M: int, N: int, K: int, *, dataflow: Dataflow,
                  bm: int, bn: int, bk: int, k_fold: int = 1,
                  epilogue: str = "fused",
                  abytes: int = 4, bbytes: int = 4) -> dict:
    """Structural model of one mpgemm dispatch (block-divisible shapes).

    Returns grid/fold facts plus the two telemetry terms the benchmark
    gates on:

      intermediate_hbm_bytes   bytes of the partial-plane HBM tensor the
                               dispatch materializes (0 on the fused path);
      acc_bytes_per_instance   fp32 accumulator bytes held per program
                               instance (the bounded on-chip state);
      hbm_traffic_bytes        modeled HBM<->VMEM bytes: per-grid-step block
                               fetches by stationarity, output write-backs
                               (revisit round-trips when output blocks are
                               revisited non-consecutively), and the spill
                               path's plane writes + reduction pass;
      out_traffic_bytes        the output/partial-sum term of the above
                               alone — the traffic the fused epilogue
                               attacks (input fetches are identical across
                               epilogues, so skinny decode GEMMs are
                               weight-dominated in the total).
    """
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, N, K)} not divisible by {(bm, bn, bk)}")
    gm, gn, gk = M // bm, N // bn, K // bk
    f = _fold_bands(gk, k_fold)
    obytes = 4  # partials/accumulators are fp32
    out_once = M * N * obytes

    df = Dataflow.OS if dataflow is Dataflow.SIMD else dataflow
    if df is Dataflow.OS:
        grid = (gm, gn, f, gk // f) if (f > 1) else (gm, gn, gk)
        a_traffic = gn * M * K * abytes          # A re-fetched per n-column
        b_traffic = gm * K * N * bbytes          # B re-fetched per m-row
        if epilogue == "spill" and f > 1:
            planes = f
            out_traffic = (2 * planes + 1) * out_once  # write f, reduce, emit
            intermediate = planes * M * N * obytes
        else:
            out_traffic = out_once               # acc resident, one flush
            intermediate = 0
    elif df in (Dataflow.WS, Dataflow.IS):
        stream_tiles = gm if df is Dataflow.WS else gn
        if df is Dataflow.WS:
            a_traffic = gn * M * K * abytes      # A streams per (n, k)
            b_traffic = K * N * bbytes           # B stationary over m
        else:
            a_traffic = M * K * abytes           # A stationary over n
            b_traffic = gm * K * N * bbytes
        if epilogue == "spill":
            grid = (gn, gk, gm) if df is Dataflow.WS else (gm, gk, gn)
            out_traffic = (2 * gk + 1) * out_once  # gk planes + reduce pass
            intermediate = gk * M * N * obytes
        else:
            grid = ((gn, f, gk // f, gm) if df is Dataflow.WS
                    else (gm, f, gk // f, gn))
            # one stream tile => output block revisits are CONSECUTIVE and
            # the block stays resident (the decode-shape specialization);
            # otherwise each revisit round-trips the block through HBM.
            out_traffic = (out_once if stream_tiles == 1
                           else (2 * gk - 1) * out_once)
            intermediate = 0
    else:
        raise ValueError(f"unsupported dataflow {dataflow}")

    steps = 1
    for g in grid:
        steps *= g
    return {
        "dataflow": df.value,
        "epilogue": epilogue,
        "grid": grid,
        "grid_steps": steps,
        "k_fold_requested": k_fold,
        "k_fold_effective": f,
        "intermediate_hbm_bytes": intermediate,
        "acc_bytes_per_instance": bm * bn * 4,
        "hbm_traffic_bytes": float(a_traffic + b_traffic + out_traffic),
        "out_traffic_bytes": float(out_traffic),
    }


def peak_intermediate_bytes(fn, *args) -> int:
    """Trace ``fn(*args)`` and return the byte size of the largest array
    value ANY equation produces, at any nesting depth (pjit/pallas bodies
    included).  This is the benchmark's no-spill gate: a dispatch that
    materializes a ``(gk, M, N)`` partial plane shows it here, while the
    fused path's largest produced value is the fp32 output itself — so
    gating ``peak <= M * N * 4`` proves no partial plane exists."""
    def walk(jaxpr) -> int:
        peak = 0
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape") and \
                        hasattr(aval, "dtype"):
                    size = 1
                    for d in aval.shape:
                        size *= int(d)
                    peak = max(peak, size * jnp.dtype(aval.dtype).itemsize)
        for sub in jax.core.subjaxprs(jaxpr):
            peak = max(peak, walk(sub))
        return peak

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)
