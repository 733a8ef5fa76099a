"""Jaxpr-level cost model: exact, loop-aware FLOP and activation-byte
accounting for any step function.

This is the primary source for the roofline compute and memory terms: the
jaxpr sees scans with their ``length`` (no trip-count guessing) and every
dot_general with full dimension numbers, before XLA fusion obscures them.
GSPMD sharding divides the work by the mesh extents of each operand's
sharded dims — we account at GLOBAL shapes and divide by chip count at the
caller, which is exact for the data/tensor-parallel sharding this framework
emits (every dot is fully partitioned along at least one sharded dim).

Byte accounting (HBM traffic proxy):
  * every dot: read A + B, write out (element sizes from avals);
  * every scan: carries + stacked ins/outs once per iteration;
  * elementwise/fusable ops are NOT counted (XLA fuses them) except
    reductions and gathers/scatters, counted as read-in + write-out.

A reduction costs one FLOP per floating-point input element (integer
ones are index bookkeeping).  A ``pallas_call`` costs its body times its
grid steps; a grid sized at run time counts the ``grid_bound`` the call
publishes in its metadata.
This intentionally approximates a well-fused TPU program; DESIGN.md §6
records the convention.
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

_FUSABLE = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "neg", "sign", "floor", "ceil", "round",
    "abs", "and", "or", "not", "xor", "pow", "integer_pow", "select_n",
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "slice", "concatenate", "pad", "rev", "iota", "eq", "ne",
    "lt", "le", "gt", "ge", "stop_gradient", "erf", "erf_inv", "expm1",
    "log1p", "cos", "sin", "clamp", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "rem", "copy", "real", "imag", "is_finite",
    "jit", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "remat2", "checkpoint", "closed_call", "cond", "while", "scan",
    "dot_general", "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax", "cumprod",
}


def _numel(aval) -> int:
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n


def _bytes(aval) -> int:
    return _numel(aval) * np.dtype(aval.dtype).itemsize


class Cost:
    __slots__ = ("flops", "bytes")

    def __init__(self, flops: float = 0.0, bytes_: float = 0.0):
        self.flops = flops
        self.bytes = bytes_

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        return self

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k)


def _dot_cost(eqn) -> Cost:
    (lhs, rhs) = eqn.invars[:2]
    out = eqn.outvars[0]
    dnums = eqn.params["dimension_numbers"]
    (lc, _rc), _ = dnums
    k = 1
    for d in lc:
        k *= int(lhs.aval.shape[d])
    flops = 2.0 * _numel(out.aval) * k
    byts = _bytes(lhs.aval) + _bytes(rhs.aval) + _bytes(out.aval)
    return Cost(flops, byts)


def _jaxpr_cost(jaxpr) -> Cost:
    total = Cost()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            total += _dot_cost(eqn)
        elif prim == "conv_general_dilated":
            out = eqn.outvars[0]
            lhs, rhs = eqn.invars[:2]
            k = _numel(rhs.aval) // max(1, int(rhs.aval.shape[-1]))
            total += Cost(2.0 * _numel(out.aval) * k,
                          _bytes(lhs.aval) + _bytes(rhs.aval)
                          + _bytes(out.aval))
        elif prim == "scan":
            body = eqn.params["jaxpr"].jaxpr
            length = int(eqn.params["length"])
            inner = _jaxpr_cost(body)
            # per-iteration carries move through VMEM/HBM; stacked xs/ys
            # stream one slice per step — already inside inner via slicing?
            # (xs slices appear as body invars; charge their bytes per step)
            per_step_io = sum(_bytes(v.aval) for v in body.invars)
            per_step_io += sum(_bytes(v.aval) for v in body.outvars)
            total += Cost(inner.flops * length,
                          (inner.bytes + per_step_io) * length)
        elif prim == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            total += _jaxpr_cost(body)  # unknown trips: count once, flag
        elif prim == "cond":
            branches = eqn.params["branches"]
            costs = [_jaxpr_cost(b.jaxpr) for b in branches]
            worst = max(costs, key=lambda c: c.flops, default=Cost())
            total += worst
        elif prim == "shard_map":
            # body runs per device on shard-local shapes: global cost =
            # body cost x number of participating devices (full mesh).
            sub = eqn.params.get("jaxpr")
            mesh = eqn.params.get("mesh")
            n = 1
            if mesh is not None:
                for v in dict(mesh.shape).values():
                    n *= int(v)
            if sub is not None:
                inner_jaxpr = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                total += _jaxpr_cost(inner_jaxpr).scaled(float(n))
        elif prim in ("jit", "closed_call", "remat2", "checkpoint",
                      "custom_jvp_call", "custom_vjp_call",
                      "custom_vjp_call_jaxpr"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                total += _jaxpr_cost(inner)
        elif prim == "pallas_call":
            # the kernel body is a jaxpr over one BLOCK; it runs once per
            # grid point, so scale by the grid size.  Without this branch
            # every scheduled mpgemm dispatch costed ZERO flops and the
            # engine-level roofline silently dropped its dominant GEMMs
            # (gta-lint Pass 2 `zero-cost-dispatch` guards the fix).
            sub = eqn.params.get("jaxpr")
            gm = eqn.params.get("grid_mapping")
            steps = 1
            dynamic = False
            if gm is not None:
                for g in getattr(gm, "grid", ()):
                    try:
                        steps *= int(g)
                    except (TypeError, ValueError):
                        dynamic = True
            if dynamic:
                # a grid sized at run time: the kernel may publish the
                # most steps its run-time dims can make as the metadata
                # key "grid_bound"; without it, count them once
                bound = (eqn.params.get("metadata") or {}).get("grid_bound")
                if bound is not None:
                    steps *= int(bound)
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                total += _jaxpr_cost(inner).scaled(float(steps))
            # operands stream HBM<->VMEM once per dispatch (same
            # convention as the dot branch: read ins, write outs)
            byts = sum(_bytes(v.aval) for v in eqn.invars)
            byts += sum(_bytes(v.aval) for v in eqn.outvars)
            total += Cost(0.0, float(byts))
        elif prim in ("reduce_sum", "reduce_max", "reduce_min",
                      "reduce_prod", "cumsum", "argmax", "argmin"):
            # one op per input element, counted as FLOPs only over floats:
            # integer reductions are index bookkeeping
            a = eqn.invars[0].aval
            flops = _numel(a) if jnp.issubdtype(a.dtype, jnp.floating) \
                else 0
            total += Cost(float(flops),
                          _bytes(a) + _bytes(eqn.outvars[0].aval))
        elif prim in ("gather", "scatter", "scatter-add", "scatter_add",
                      "dynamic_slice", "dynamic_update_slice", "sort",
                      "take_along_axis"):
            byts = sum(_bytes(v.aval) for v in eqn.invars)
            byts += sum(_bytes(v.aval) for v in eqn.outvars)
            total += Cost(0.0, byts)
    return total


def step_cost(fn: Callable, *args, **kwargs) -> dict[str, float]:
    """Exact loop-aware (flops, bytes) of ``fn(*args)`` at global shapes.

    args may be ShapeDtypeStructs.  Returns {"flops": ..., "bytes": ...} —
    divide by chip count for per-device roofline terms.
    """
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    c = _jaxpr_cost(closed.jaxpr)
    # inputs are read once and outputs written once per step (params,
    # optimizer state, caches — the weight/state HBM traffic)
    io_bytes = sum(_bytes(v.aval) for v in closed.jaxpr.invars)
    io_bytes += sum(_bytes(v.aval) for v in closed.jaxpr.outvars)
    return {"flops": c.flops, "bytes": c.bytes + io_bytes}
