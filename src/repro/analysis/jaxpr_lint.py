"""Pass 2 — jaxpr hygiene for the engine's pre-resolved hot dispatches.

Traces the serving engine's steady-state programs abstractly (decode
step, ``prefill_paged_chunk``, ``verify_paged_chunk``, ``head_apply``)
at the exact shapes the engine dispatches them — parameters and caches
come from ``jax.eval_shape``, so full-scale configs lint without
allocating a byte — and screens the jaxprs for the failure classes that
runtime tests cannot see until they burn a step:

* ``zero-cost-dispatch`` — ``launch.jaxpr_cost.step_cost`` reports no
  FLOPs for a program that must contain the model's GEMMs: some loop or
  call primitive is invisible to the cost walker, so the roofline and
  capacity projections silently exclude the hot path (the
  ``pallas_call`` gap this PR fixes was exactly this).
* ``quant-fp32-promotion`` — an ``int8 -> float32`` convert inside a
  quant-serving dispatch whose compute dtype is narrower: the dequant
  is silently widening the activation path XLA then carries at fp32.
* ``host-transfer`` — callback/transfer primitives inside a hot
  dispatch (a per-step device<->host sync).
* ``baked-constant`` — a large array captured as a trace-time constant
  instead of an argument: it is re-baked (and the program re-compiled)
  whenever the closed-over value changes, the recompilation half of
  Python-scalar leakage.  Scalar leakage proper is also screened: a
  weakly-typed scalar input means a Python number reached the trace.
* ``oversized-intermediate`` — generalizes the kernel benchmarks'
  ``peak_intermediate_bytes`` gate to whole dispatches: no equation may
  produce a value materially larger than the dispatch's own largest
  input/output leaf (a partial-plane-style blowup).

Every dispatch is additionally re-traced through the observability
profiler's wrapper (``obs.profile.profiled_dispatch``, subject suffix
``+profiled``) and held to the same rules plus an equation-count
identity check — instrumentation must never cross the jit boundary.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import Finding
from repro.launch.jaxpr_cost import step_cost
from repro.models import network as N
from repro.models.config import ModelConfig
from repro.serving.kv_pool import blocks_for

#: lint-time engine geometry (ContinuousEngine defaults)
SLOTS = 8
MAX_LEN = 2048
BLOCK_SIZE = 16
PREFILL_CHUNK = 32
SPEC_K = 4

#: primitives that force a device<->host round trip inside a dispatch
_TRANSFER_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "host_callback", "outside_call", "infeed", "outfeed",
                   "copy_to_host_async"}


def _is_committed_device_put(eqn) -> bool:
    """True only for a ``device_put`` that commits to a concrete device
    or sharding.  ``jnp.asarray`` on a Python scalar inside a trace emits
    a placement-free aliasing device_put (``devices=[None]``) — a trace
    artifact, not a transfer (jnp.bincount inside moe_apply does this)."""
    if eqn.primitive.name != "device_put":
        return False
    devices = eqn.params.get("devices", [])
    srcs = eqn.params.get("srcs", [])
    return any(d is not None for d in devices) or \
        any(s is not None for s in srcs)

#: the dispatch intermediate may exceed the largest io leaf by this
#: factor before it is flagged (fp32 partials of a bf16 output are 2x;
#: 4x leaves headroom for fused epilogues without admitting a
#: per-K-step partial plane, which scales with gk >= 8 on these shapes)
_INTERMEDIATE_SLACK = 4.0


@dataclasses.dataclass
class TracedDispatch:
    name: str
    closed: "jax.core.ClosedJaxpr"
    cost: dict[str, float]


def _walk(jaxpr) -> Iterator:
    for eqn in jaxpr.eqns:
        yield eqn
    for sub in jax.core.subjaxprs(jaxpr):
        yield from _walk(sub)


def _leaf_bytes(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n * np.dtype(aval.dtype).itemsize


def abstract_engine_inputs(cfg: ModelConfig, *, slots: int = SLOTS,
                           max_len: int = MAX_LEN,
                           block_size: int = BLOCK_SIZE) -> dict:
    """ShapeDtypeStruct pytrees for params/caches/tables at engine
    geometry — zero allocation, full-scale shapes."""
    per_slot = blocks_for(max_len, block_size)
    kv_blocks = max(per_slot + 1, 1 + (3 * slots * per_slot + 3) // 4)
    params = jax.eval_shape(lambda: N.init(cfg, jax.random.PRNGKey(0)))
    if cfg.quant_serving:
        # mirror the engine: ContinuousEngine rewrites the weight tree
        # through the default QuantPolicy before any jitted program
        # closes over it, so the linted dispatches must trace with the
        # same QuantTensor leaves (that is what arms the
        # quant-fp32-promotion rule on the real int8 dequant paths)
        from repro.quant import serving_quant_params
        params = jax.eval_shape(
            lambda p: serving_quant_params(cfg, p), params)
    caches = jax.eval_shape(lambda: N.expand_cache_pos(
        N.init_paged_caches(cfg, slots, kv_blocks, block_size), slots))
    i32 = jnp.int32
    return {
        "params": params,
        "caches": caches,
        "bt": jax.ShapeDtypeStruct((slots, per_slot), i32),
        "slot_ids": jax.ShapeDtypeStruct((slots,), i32),
        "pos": jax.ShapeDtypeStruct((slots,), i32),
        "key": jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        "temps": jax.ShapeDtypeStruct((slots,), jnp.float32),
    }


def hot_dispatches(cfg: ModelConfig, *, slots: int = SLOTS,
                   max_len: int = MAX_LEN, block_size: int = BLOCK_SIZE,
                   prefill_chunk: int = PREFILL_CHUNK, spec_k: int = SPEC_K
                   ) -> list[tuple[str, Callable, tuple]]:
    """(name, fn, abstract args) for each steady-state program, at the
    exact signatures the engine's jitted wrappers use."""
    if cfg.is_encoder_only:
        return []
    ab = abstract_engine_inputs(cfg, slots=slots, max_len=max_len,
                                block_size=block_size)
    i32 = jnp.int32
    ct = jnp.dtype(cfg.compute_dtype)
    out: list[tuple[str, Callable, tuple]] = []

    def decode_step(params, toks, caches, pos, bt, adv):
        return N.decode_step(params, cfg, toks, caches, pos,
                             block_table=bt, pos_advance=adv)

    out.append(("decode_step", decode_step,
                (ab["params"], jax.ShapeDtypeStruct((slots, 1), i32),
                 ab["caches"], ab["pos"], ab["bt"], ab["pos"])))

    def prefill_chunk_fn(params, toks, caches, slot_ids, bt, lens,
                         last_idx):
        return N.prefill_paged_chunk(params, cfg, toks, caches, slot_ids,
                                     bt, lens, last_idx)

    out.append(("prefill_paged_chunk", prefill_chunk_fn,
                (ab["params"],
                 jax.ShapeDtypeStruct((slots, prefill_chunk), i32),
                 ab["caches"], ab["slot_ids"], ab["bt"], ab["pos"],
                 ab["pos"])))

    if not cfg.has_recurrent_state:     # spec/verify is attention-only
        L = spec_k + 1

        def verify_chunk_fn(params, toks, caches, slot_ids, bt, lens):
            return N.verify_paged_chunk(params, cfg, toks, caches,
                                        slot_ids, bt, lens)

        out.append(("verify_paged_chunk", verify_chunk_fn,
                    (ab["params"], jax.ShapeDtypeStruct((slots, L), i32),
                     ab["caches"], ab["slot_ids"], ab["bt"], ab["pos"])))

    from repro.models.layers import head_apply
    backend = N.gemm_backend(cfg)
    head = (ab["params"]["embed"]["table"] if cfg.tie_embeddings
            else ab["params"]["lm_head"])

    def head_fn(w, x):
        return head_apply(w, x, cfg.final_logit_softcap, backend=backend)

    out.append(("head_apply", head_fn,
                (head, jax.ShapeDtypeStruct((slots, 1, cfg.d_model), ct))))
    return out


def trace_dispatches(cfg: ModelConfig, *, include_profiled: bool = False,
                     **geometry) -> list[TracedDispatch]:
    """Trace every hot dispatch; with ``include_profiled`` each is ALSO
    traced through ``obs.profile.profiled_dispatch`` (subject suffix
    ``+profiled``) — the profiler's timing hooks run at Python level, so
    the wrapped jaxpr must be equation-for-equation identical to the
    bare one (in particular: no new host-transfer primitives)."""
    out = []
    if include_profiled:
        from repro.obs.profile import profiled_dispatch
    for name, fn, args in hot_dispatches(cfg, **geometry):
        closed = jax.make_jaxpr(fn)(*args)
        out.append(TracedDispatch(name, closed, step_cost(fn, *args)))
        if include_profiled:
            closed_p = jax.make_jaxpr(profiled_dispatch(fn))(*args)
            # cost is carried over, not re-walked: the identity check in
            # lint_profiled_pair is what guarantees it still applies
            out.append(TracedDispatch(name + "+profiled", closed_p,
                                      out[-1].cost))
    return out


def _eqn_count(jaxpr) -> int:
    return sum(1 for _ in _walk(jaxpr))


def lint_profiled_pair(cfg: ModelConfig, base: TracedDispatch,
                       profiled: TracedDispatch) -> list[Finding]:
    """The profiled wrapper must leave the program untouched — timing
    runs outside the trace.  A structural mismatch means the wrapper
    leaked something (a callback, an extra convert) INTO the jaxpr."""
    nb = _eqn_count(base.closed.jaxpr)
    np_ = _eqn_count(profiled.closed.jaxpr)
    if nb != np_:
        return [Finding(
            "jaxpr", "profiled-wrapper-changed-jaxpr",
            f"{cfg.name}/{profiled.name}",
            f"profiling wrapper changed the traced program: {np_} "
            f"equations vs {nb} bare — instrumentation crossed the jit "
            f"boundary")]
    return []


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def lint_dispatch(cfg: ModelConfig, td: TracedDispatch) -> list[Finding]:
    out: list[Finding] = []
    subject = f"{cfg.name}/{td.name}"
    jaxpr = td.closed.jaxpr

    if td.cost["flops"] <= 0:
        out.append(Finding(
            "jaxpr", "zero-cost-dispatch", subject,
            f"step_cost sees 0 FLOPs in a dispatch that must contain "
            f"the model GEMMs — a call/loop primitive is invisible to "
            f"launch.jaxpr_cost, so rooflines exclude this hot path"))

    compute = jnp.dtype(cfg.compute_dtype)
    narrow_compute = compute.itemsize < 4
    transfers = set()
    promotions = 0
    for eqn in _walk(jaxpr):
        prim = eqn.primitive.name
        if prim in _TRANSFER_PRIMS or _is_committed_device_put(eqn):
            transfers.add(prim)
        if (prim == "convert_element_type" and cfg.quant_serving
                and narrow_compute):
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (np.dtype(src.dtype) == np.int8
                    and np.dtype(dst.dtype) == np.float32):
                promotions += 1
    if transfers:
        out.append(Finding(
            "jaxpr", "host-transfer", subject,
            f"host round-trip primitives inside the dispatch: "
            f"{sorted(transfers)} — every step pays a device sync"))
    if promotions:
        out.append(Finding(
            "jaxpr", "quant-fp32-promotion", subject,
            f"{promotions} int8->float32 convert(s) in a quant path "
            f"whose compute dtype is {compute.name}: dequant should "
            f"target the compute dtype, not silently widen to fp32"))

    # scalar leakage: weakly-typed inputs mean a Python number was
    # traced as an argument — its VALUE re-specializes the program
    weak = [i for i, v in enumerate(jaxpr.invars)
            if getattr(v.aval, "weak_type", False)]
    if weak:
        out.append(Finding(
            "jaxpr", "scalar-leakage", subject,
            f"weakly-typed scalar inputs at positions {weak[:6]}: a "
            f"Python scalar reached the trace and will retrigger "
            f"compilation per distinct value"))
    # ...and its constant half: a large array baked into the trace
    big_consts = [_leaf_bytes(c) for c in td.closed.consts
                  if _leaf_bytes(c) > 1 << 20]
    if big_consts:
        out.append(Finding(
            "jaxpr", "baked-constant", subject,
            f"{len(big_consts)} closed-over array constant(s) > 1 MiB "
            f"(largest {max(big_consts)} B) baked "
            f"into the program instead of passed as arguments"))

    # oversized intermediates, relative to the dispatch's own io
    io_max = max((_leaf_bytes(v.aval)
                  for v in list(jaxpr.invars) + list(jaxpr.outvars)),
                 default=0)
    allowed = max(int(_INTERMEDIATE_SLACK * io_max), 4 << 20)
    peak, where = 0, ""
    for eqn in _walk(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            b = _leaf_bytes(aval)
            if b > peak:
                peak, where = b, (f"{eqn.primitive.name} -> "
                                  f"{aval.dtype}{tuple(aval.shape)}")
    if peak > allowed:
        out.append(Finding(
            "jaxpr", "oversized-intermediate", subject,
            f"equation {where} materializes {peak} B, over "
            f"{_INTERMEDIATE_SLACK:g}x the largest io leaf "
            f"({io_max} B) — a partial-plane-style blowup"))
    return out


def check_config(cfg: ModelConfig, *, include_profiled: bool = True,
                 **geometry) -> list[Finding]:
    """Pass 2 over every hot dispatch of ``cfg``'s serving engine.

    With ``include_profiled`` (the default — gta-lint runs it), each
    dispatch is re-screened through the obs profiler's wrapper: the
    full rule set runs on the wrapped jaxpr too (host transfers above
    all), plus the wrapper-identity check."""
    findings: list[Finding] = []
    by_name: dict[str, TracedDispatch] = {}
    for td in trace_dispatches(cfg, include_profiled=include_profiled,
                               **geometry):
        findings += lint_dispatch(cfg, td)
        if td.name.endswith("+profiled"):
            findings += lint_profiled_pair(
                cfg, by_name[td.name[:-len("+profiled")]], td)
        else:
            by_name[td.name] = td
    return findings
