"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
derive the roofline terms from the compiled artifact.

CPU-only tool.  Importing it overwrites ``XLA_FLAGS`` (512 placeholder
host devices) and ``--all`` starts one subprocess per cell, so never
import it from a process that drives a chip: a TPU belongs to one process
at a time.  The serving path and ``chip_smoke.py`` do not import it.

For each cell this proves, without hardware:
  * the sharding config is coherent (no GSPMD conflicts),
  * the program fits per-device memory (memory_analysis),
  * the FLOP/byte/collective profile (cost_analysis + HLO collective scan)
    that EXPERIMENTS.md §Roofline reports.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k \
        --mesh single
    python -m repro.launch.dryrun --all            # full matrix (subprocess
                                                   # per cell, resumable)
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
#   512 placeholder host devices let jax.make_mesh build the production
#   meshes (16x16 single-pod slice of the fleet, 2x16x16 multi-pod).

import argparse
import functools
import json
import subprocess
import sys
import time
from typing import Any

# TPU v5e-class hardware constants (targets; this container is CPU-only)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

def _active_param_counts(cfg, params_sds) -> tuple[int, int]:
    """(total_params, active_params) from the eval_shape tree; active
    discounts routed-expert weights by top_k / n_experts (MoE)."""
    import jax

    total = active = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(params_sds)
    frac = (cfg.moe.top_k / cfg.moe.n_experts) if cfg.moe else 1.0
    for path, leaf in flat:
        names = [str(getattr(p, "key", "")) for p in path]
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        if "moe" in names and any(x in names for x in
                                  ("wi_gate", "wi_up", "wo")) \
                and "shared" not in names:
            active += int(n * frac)
        elif "embed" in names or "lm_head" in names:
            pass  # 6ND convention: exclude embedding/unembedding
        else:
            active += n
    return total, active


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             verbose: bool = True, serve_opt: bool = False
             ) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro import configs as CONFIGS
    from repro.configs import shapes as SHP
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.models import network as N
    from repro.optim import adamw

    cfg = CONFIGS.get(arch)
    shape = SHP.SHAPES[shape_name]
    skip = SHP.skip_reason(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh_chips(mesh)
    from repro.models.layers import set_activation_mesh
    set_activation_mesh(mesh)   # activation constraints for GSPMD
    t0 = time.time()

    if serve_opt and shape.mode == "decode":
        # §Perf H5: int8 serving path — QuantTensor weights, stationary on
        # the model axis (fsdp off): decode batches cannot amortize per-step
        # FSDP weight all-gathers, and int8 halves the weight-read bytes.
        from repro.quant.policy import quantize_params
        param_sh = SH.quantized_param_shardings(cfg, mesh, fsdp=False)

        def _qinit(key):
            return quantize_params(N.init(cfg, key))

        params_sds = jax.eval_shape(_qinit, jax.random.PRNGKey(0))
    else:
        params_sds = jax.eval_shape(functools.partial(N.init, cfg),
                                    jax.random.PRNGKey(0))
        # §Perf H6: FSDP only when needed.  If params + AdamW moments fit
        # the model axis alone (bf16 p + f32 m/v = 10 B/param), keep the
        # weights model-stationary: the FSDP all-gathers (re-paid under
        # remat) were the dominant collective on every <=9B train cell.
        n_params = sum(s_.size for s_ in jax.tree.leaves(params_sds))
        mp = dict(mesh.shape)["model"]
        fsdp = (n_params * 10 / mp) > 12e9
        param_sh = SH.shardings_for_params(cfg, mesh, fsdp=fsdp)
    params_sds = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_sds, param_sh)
    specs = SHP.input_specs(cfg, shape_name)

    if shape.mode == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_sds = jax.eval_shape(functools.partial(adamw.init, opt_cfg),
                                 params_sds)
        opt_sh = adamw.AdamWState(step=SH.replicated(mesh), m=param_sh,
                                  v=param_sh, master=None)
        batch_sds = specs["batch"]
        batch_sh = SH.batch_shardings(batch_sds, mesh)

        def loss(p, b):
            return N.loss_fn(p, cfg, b)

        def step(params, opt_state, batch):
            (lossv, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)
            p2, o2, om = adamw.update(opt_cfg, grads, opt_state, params)
            return p2, o2, {"loss": lossv, **metrics, **om}

        jitted = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        lower_args = (params_sds, opt_sds, batch_sds)
        lowered = jitted.lower(*lower_args)
        tokens = shape.global_batch * shape.seq_len
        flops_factor = 6
    else:
        max_len = shape.seq_len
        caches_sds = jax.eval_shape(
            functools.partial(N.init_caches, cfg, shape.global_batch,
                              max_len, jnp.bfloat16))
        cache_sh = SH.cache_shardings(caches_sds, mesh, shape.global_batch)
        caches_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            caches_sds, cache_sh)
        if shape.mode == "prefill":
            batch_sds = specs["batch"]
            batch_sh = SH.batch_shardings(batch_sds, mesh)

            def step(params, batch, caches):
                return N.prefill(params, cfg, batch, caches)

            jitted = jax.jit(step, in_shardings=(param_sh, batch_sh,
                                                 cache_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lower_args = (params_sds, batch_sds, caches_sds)
            lowered = jitted.lower(*lower_args)
            tokens = shape.global_batch * shape.seq_len
        else:  # decode
            tok_sds = specs["tokens"]
            tok_sh = SH.batch_shardings(tok_sds, mesh)

            def step(params, tok, caches, pos):
                return N.decode_step(params, cfg, tok, caches, pos)

            jitted = jax.jit(step, in_shardings=(param_sh, tok_sh, cache_sh,
                                                 None),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lower_args = (params_sds, tok_sds, caches_sds,
                          jax.ShapeDtypeStruct((), jnp.int32))
            lowered = jitted.lower(*lower_args)
            tokens = shape.global_batch
        flops_factor = 2

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops_once = float(cost.get("flops", 0.0))
    xla_bytes_once = float(cost.get("bytes accessed", 0.0))

    # loop-aware accounting (XLA's cost_analysis counts while bodies ONCE):
    #  * flops/bytes: jaxpr walk at global shapes (exact scan lengths)
    #  * collectives: optimized-HLO walk with trip-count multipliers
    from repro.launch.hloanalysis import analyze as hlo_analyze
    from repro.launch.jaxpr_cost import step_cost
    jc = step_cost(step, *lower_args)
    flops = jc["flops"] / chips          # per-device
    bytes_accessed = jc["bytes"] / chips
    hlo = hlo_analyze(compiled.as_text(), chips)
    coll = hlo["collectives"]

    total_p, active_p = _active_param_counts(cfg, params_sds)
    model_flops = flops_factor * active_p * tokens

    # Roofline terms (seconds); flops/bytes from HLO are per-device.
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll["per_device_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": "serve_opt" if serve_opt else "baseline",
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "per_device_flops": flops,
        "per_device_bytes": bytes_accessed,
        "xla_cost_analysis_once": {"flops": xla_flops_once,
                                   "bytes": xla_bytes_once},
        "hlo_walked_dot_flops_per_device": hlo["walked_dot_flops"],
        "hlo_loops": hlo["loops"],
        "collectives": coll,
        "params_total": total_p,
        "params_active": active_p,
        "tokens_per_step": tokens,
        "model_flops_global": model_flops,
        "model_flops_per_device": model_flops / chips,
        "useful_flops_fraction": (model_flops / chips) / max(flops, 1.0),
        "roofline": {**terms, "bottleneck": bottleneck,
                     "step_time_bound_s": max(terms.values())},
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_kind}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  flops/dev {flops:.3e}  bytes/dev {bytes_accessed:.3e}  "
              f"coll/dev {coll['per_device_bytes']:.3e}")
        print(f"  roofline: compute {compute_s*1e3:.2f}ms  "
              f"memory {memory_s*1e3:.2f}ms  "
              f"collective {collective_s*1e3:.2f}ms  -> {bottleneck}")
        print(f"  MODEL_FLOPS/HLO_FLOPS = "
              f"{result['useful_flops_fraction']:.3f}")
    return result


def _result_path(arch: str, shape: str, mesh: str,
                 suffix: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    arch = arch.replace("-", "_").replace(".", "_")   # canonical id
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh}{suffix}.json")


def run_all(force: bool = False, meshes=("single", "multi"),
            archs: list | None = None, timeout_s: int = 3000):
    """Full matrix via one subprocess per cell (fresh XLA, resumable)."""
    from repro import configs as CONFIGS
    from repro.configs import shapes as SHP

    archs = archs or list(CONFIGS.ARCH_IDS)
    cells = [(a, s, m) for a in archs for s in SHP.SHAPE_IDS for m in meshes]
    done = failed = skipped = 0
    for a, s, m in cells:
        path = _result_path(a, s, m)
        if os.path.exists(path) and not force:
            done += 1
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", s, "--mesh", m]
        print(f"--- {a} x {s} x {m}", flush=True)
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout_s,
                               env={**os.environ,
                                    "PYTHONPATH": os.environ.get(
                                        "PYTHONPATH", "src")})
            if r.returncode != 0:
                failed += 1
                with open(path + ".err", "w") as f:
                    f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                print(f"    FAILED (see {path}.err)", flush=True)
            else:
                done += 1
                print(r.stdout.strip()[-400:], flush=True)
        except subprocess.TimeoutExpired:
            failed += 1
            with open(path + ".err", "w") as f:
                f.write(f"timeout after {timeout_s}s")
            print("    TIMEOUT", flush=True)
    print(f"matrix: {done} ok, {failed} failed, {skipped} skipped")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--serve-opt", action="store_true",
                    help="decode cells: int8 weights + model-stationary "
                         "sharding (§Perf H5)")
    args = ap.parse_args(argv)

    if args.all:
        run_all(force=args.force)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    res = run_cell(args.arch, args.shape, args.mesh,
                   serve_opt=args.serve_opt)
    suffix = "__servopt" if args.serve_opt else ""
    with open(_result_path(args.arch, args.shape, args.mesh, suffix),
              "w") as f:
        json.dump(res, f, indent=2)
    if res["status"] == "skip":
        print(f"SKIP: {res['reason']}")


if __name__ == "__main__":
    main()
