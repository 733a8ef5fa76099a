"""Mixture-of-Experts FFN with sort-based capacity dispatch (EP-friendly).

The dispatch is the production "dropping" pattern: expand tokens x top_k,
sort by expert id, keep the first ``capacity`` slots per expert (static
shapes throughout — XLA/GSPMD shardable), run ONE batched expert GEMM
einsum('ecd,edf->ecf') whose expert dim shards over the mesh "model" axis
(expert parallelism), and scatter-add the weighted outputs back.

In the paper's taxonomy each expert FFN is a p-GEMM batch; the router and
the combine are vector-path work.  The capacity knob is the usual
utilization-vs-drop tradeoff and the aux loss keeps the router balanced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import ParamDef, activation, dense, shard_act


def moe_defs(cfg: ModelConfig) -> dict:
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    e, f = m.n_experts, m.d_ff_expert
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.006),
        "wi_gate": ParamDef((e, d, f), ("experts", "embed", "ff")),
        "wi_up": ParamDef((e, d, f), ("experts", "embed", "ff")),
        "wo": ParamDef((e, f, d), ("experts", "ff", "embed")),
    }
    if m.n_shared_experts:
        fs = m.d_ff_shared or m.d_ff_expert * m.n_shared_experts
        defs["shared"] = {
            "wi_gate": ParamDef((d, fs), ("embed", "ff")),
            "wi_up": ParamDef((d, fs), ("embed", "ff")),
            "wo": ParamDef((fs, d), ("ff", "embed")),
        }
    return defs


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)  # multiple of 8, floor 8


def _moe_compute(p: dict, x: jax.Array, cfg: ModelConfig, *,
                 constrain: bool = True,
                 backend=None) -> tuple[jax.Array, jax.Array]:
    """Dispatch + expert GEMMs + combine on whatever token set ``x``
    carries (global under GSPMD, shard-local under shard_map)."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    C = _capacity(T, m)

    def sa(t, dims):
        return shard_act(t, dims) if constrain else t

    xf = sa(x.reshape(T, D), "b.")

    # --- routing -------------------------------------------------------------
    logits = dense(xf, p["router"], backend=backend).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # logits: (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)          # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # aux (load-balance) loss, Switch-style
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = m.aux_loss_weight * E * jnp.sum(me * ce)

    # --- sort-based dispatch (static shapes) ----------------------------------
    flat_ids = expert_ids.reshape(T * K)                     # slot s -> expert
    flat_gates = gate_vals.reshape(T * K)
    order = jnp.argsort(flat_ids)                            # stable
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(counts) - counts                     # exclusive
    pos_in_expert = jnp.arange(T * K) - starts[sorted_ids]
    keep = pos_in_expert < C
    slot = sorted_ids * C + jnp.where(keep, pos_in_expert, 0)

    # gather table: slot (E*C) -> expanded index (or T*K = dropped sentinel);
    # dropped entries scatter out of bounds and are discarded by mode="drop".
    gather_idx = jnp.full((E * C,), T * K, jnp.int32).at[
        jnp.where(keep, slot, E * C)].set(order.astype(jnp.int32),
                                          mode="drop")
    token_of = jnp.minimum(gather_idx // K, T)               # sentinel -> T
    pad_x = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    gathered = sa(pad_x[token_of].reshape(E, C, D), "mb.")

    # --- batched expert GEMMs (the EP p-GEMM) ---------------------------------
    g = activation(jnp.einsum("ecd,edf->ecf", gathered,
                              p["wi_gate"].astype(gathered.dtype)), cfg.act)
    u = jnp.einsum("ecd,edf->ecf", gathered,
                   p["wi_up"].astype(gathered.dtype))
    y = jnp.einsum("ecf,efd->ecd", g * u, p["wo"].astype(gathered.dtype))
    y = sa(y, "mb.")

    # --- weighted combine ------------------------------------------------------
    pad_gates = jnp.concatenate(
        [flat_gates, jnp.zeros((1,), flat_gates.dtype)])
    slot_gate = pad_gates[jnp.minimum(gather_idx, T * K)]    # 0 for dropped
    y = y.reshape(E * C, D) * slot_gate[:, None].astype(y.dtype)
    out = jnp.zeros((T + 1, D), y.dtype).at[token_of.reshape(E * C)].add(
        y, mode="drop")[:T]
    out = sa(out, "b.")

    # --- shared experts --------------------------------------------------------
    if "shared" in p:
        sp = p["shared"]
        sg = activation(dense(xf, sp["wi_gate"], backend=backend), cfg.act)
        su = dense(xf, sp["wi_up"], backend=backend)
        out = out + dense(sg * su, sp["wo"], backend=backend)

    return out.reshape(B, S, D).astype(x.dtype), aux


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, *,
              backend=None) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (out (B, S, D), aux_loss scalar fp32).

    §Perf H3: under a distributed activation policy the whole MoE layer runs
    in a FULLY MANUAL shard_map (data + model axes): routing/sort/gather are
    token-shard-local, expert parallelism is an explicit pair of
    all-to-alls around the expert GEMMs (the textbook EP schedule), and the
    shared-expert MLP is Megatron-style ff-sharded with one psum.  The
    pure-GSPMD fallback (no policy / non-divisible dims) re-materializes
    global token buffers per layer — ~60x more collective traffic on
    llama4-scout (EXPERIMENTS.md §Perf H3).
    """
    from repro.models import layers as L
    mesh, dp = L._ACT_MESH, L._DP_AXES
    B = x.shape[0]
    if mesh is not None and dp and "model" in mesh.axis_names:
        sizes = dict(mesh.shape)
        dp_total = 1
        for a in dp:
            dp_total *= sizes[a]
        mp = sizes["model"]
        if (dp_total > 1 and B % dp_total == 0
                and cfg.moe.n_experts % mp == 0):
            # the manual-collective path stays on XLA dots: Pallas
            # dispatches inside shard_map would shard the GEMM grid, which
            # the scheduled backend does not model yet (see ROADMAP).
            return _moe_shardmap(p, x, cfg, mesh, dp, mp)
    return _moe_compute(p, x, cfg, backend=backend)


def _moe_shardmap(p: dict, x: jax.Array, cfg: ModelConfig, mesh, dp,
                  mp: int) -> tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P
    m: MoEConfig = cfg.moe
    dspec = dp if len(dp) > 1 else dp[0]
    E, K = m.n_experts, m.top_k

    def local_fn(p_l, x_l):
        # x_l (B_l, S, D): this data shard's tokens (replicated across
        # model); p_l experts: wi/wu (E/mp, D, F), wo (E/mp, F, D).
        # Each model shard dispatches a DISJOINT 1/mp slice of the local
        # tokens (x is model-replicated, so without the split all mp shards
        # would route the same tokens — 16x redundant compute and a2a, the
        # bug H3's first measurement exposed).
        B_l, S, D = x_l.shape
        T_full = B_l * S
        xf_full = x_l.reshape(T_full, D)
        split = T_full % mp == 0 and T_full >= mp
        if split:
            T = T_full // mp
            midx = jax.lax.axis_index("model")
            xf = jax.lax.dynamic_slice_in_dim(xf_full, midx * T, T, 0)
        else:
            T = T_full          # tiny token counts (decode): redundant but
            xf = xf_full        # correct replicated dispatch
        C = _capacity(T, m)

        # --- routing (full E; router weights replicated) ---
        logits = dense(xf, p_l["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                     dtype=jnp.float32), axis=0)
        aux = m.aux_loss_weight * E * jnp.sum(me * ce)

        # --- local sort-based dispatch (identical to _moe_compute) ---
        flat_ids = expert_ids.reshape(T * K)
        flat_gates = gate_vals.reshape(T * K)
        order = jnp.argsort(flat_ids)
        sorted_ids = flat_ids[order]
        counts = jnp.bincount(flat_ids, length=E)
        starts = jnp.cumsum(counts) - counts
        pos_in_expert = jnp.arange(T * K) - starts[sorted_ids]
        keep = pos_in_expert < C
        slot = sorted_ids * C + jnp.where(keep, pos_in_expert, 0)
        gather_idx = jnp.full((E * C,), T * K, jnp.int32).at[
            jnp.where(keep, slot, E * C)].set(order.astype(jnp.int32),
                                              mode="drop")
        token_of = jnp.minimum(gather_idx // K, T)
        pad_x = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
        gathered = pad_x[token_of].reshape(E, C, D)

        # --- EP all-to-all: expert blocks travel to their owner shard ---
        g4 = gathered.reshape(mp, E // mp, C, D)
        g4 = jax.lax.all_to_all(g4, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        # (mp, E/mp, C, D): dim0 = source data... source model shard
        mine = jnp.moveaxis(g4, 0, 1).reshape(E // mp, mp * C, D)

        def _w(t):
            return (t.dequant(mine.dtype) if hasattr(t, "dequant")
                    else t.astype(mine.dtype))

        gE = activation(jnp.einsum("ecd,edf->ecf", mine,
                                   _w(p_l["wi_gate"])), cfg.act)
        uE = jnp.einsum("ecd,edf->ecf", mine, _w(p_l["wi_up"]))
        yE = jnp.einsum("ecf,efd->ecd", gE * uE, _w(p_l["wo"]))

        # --- reverse all-to-all: outputs return to token owners ---
        y4 = jnp.moveaxis(yE.reshape(E // mp, mp, C, D), 1, 0)
        y4 = jax.lax.all_to_all(y4, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        y = y4.reshape(E * C, D)

        pad_gates = jnp.concatenate(
            [flat_gates, jnp.zeros((1,), flat_gates.dtype)])
        slot_gate = pad_gates[jnp.minimum(gather_idx, T * K)]
        y = y * slot_gate[:, None].astype(y.dtype)
        out = jnp.zeros((T + 1, D), y.dtype).at[
            token_of.reshape(E * C)].add(y, mode="drop")[:T]

        # --- shared experts (Megatron ff-sharded, partial over model) ---
        shared_part = None
        if "shared" in p_l:
            sp = p_l["shared"]
            sg = activation(dense(xf_full, sp["wi_gate"]), cfg.act)
            su = dense(xf_full, sp["wi_up"])
            shared_part = dense(sg * su, sp["wo"])      # (T_full, D) partial

        if split:
            # routed slice back into full token space; ONE psum combines the
            # mp disjoint routed slices and the shared-expert partials.
            routed_full = jnp.zeros((T_full, D), out.dtype)
            routed_full = jax.lax.dynamic_update_slice_in_dim(
                routed_full, out, midx * T, 0)
            comb = routed_full if shared_part is None else (
                routed_full + shared_part.astype(routed_full.dtype))
            out = jax.lax.psum(comb, "model")
        elif shared_part is not None:
            out = out + jax.lax.psum(shared_part.astype(out.dtype), "model")

        # aux differs per model shard in the split path (disjoint tokens):
        # average over every axis so the returned scalar is well-defined.
        aux = jax.lax.pmean(aux, axis_name=tuple(dp) + ("model",))
        return out.reshape(B_l, S, D).astype(x_l.dtype), aux

    # in_specs mirror the stored shardings: experts over model, router and
    # norms replicated, shared-expert MLP ff-sharded over model.  Built
    # per-leaf so QuantTensor (q, scale) children get rank-correct specs.
    def leaf_spec(path, leaf):
        names = [str(getattr(x, "key", "")) for x in path]
        nd = leaf.ndim
        if "router" in names:
            return P(*([None] * nd))
        if "shared" in names:
            if "wo" in names:       # (ff, d) weight / (d,) scale
                return P("model", None) if nd == 2 else P(None)
            # wi_gate / wi_up: (d, ff) weight / (ff,) scale
            return P(None, "model") if nd == 2 else P("model")
        # routed experts: (E, d, f) weight / (E, f) scale
        return P("model", *([None] * (nd - 1)))

    p_specs = jax.tree_util.tree_map_with_path(leaf_spec, p)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(p_specs, P(dspec, None, None)),
                       out_specs=(P(dspec, None, None), P()),
                       axis_names=set(dp) | {"model"}, check_vma=False)
    return fn(p, x)
