"""Training driver: pjit train step, restart-exact loop, fault tolerance.

``make_train_step`` builds the jitted/sharded step for any (arch, mesh);
``train`` runs the loop with async checkpointing, heartbeat monitoring,
failure-injection drills and elastic restart.  The same function serves the
CPU quickstart (examples/quickstart.py), the multi-pod dry-run (lower-only)
and a real TPU deployment.

CLI:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 50 --global-batch 8 --seq 256 --scaled-down \
        --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

from repro import configs as CONFIGS
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, make_batch
from repro.launch import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_chips
from repro.models import network as N
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.optim import compression as comp
from repro.runtime.faults import (FailureInjector, HeartbeatMonitor,
                                  RestartPolicy, run_with_restarts)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 256
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    log_every: int = 10
    accum: int = 1                     # gradient-accumulation microbatches
    compress_grads: bool = False       # int8 DP all-reduce (pure-DP mode)
    seed: int = 0
    fsdp: bool = True


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, mesh, *,
                    fsdp: bool = True, accum: int = 1, donate: bool = True):
    """Returns (jitted_step, param_shardings, opt_shardings).

    step(params, opt_state, batch) -> (params, opt_state, metrics)
    """
    if mesh_chips(mesh) > 1:
        from repro.models.layers import set_activation_mesh
        set_activation_mesh(mesh)
    param_sh = SH.shardings_for_params(cfg, mesh, fsdp=fsdp)
    opt_sh = adamw.AdamWState(
        step=SH.replicated(mesh),
        m=param_sh, v=param_sh,
        master=param_sh if opt_cfg.master_copy else None)

    def loss(p, b):
        return N.loss_fn(p, cfg, b)

    def step(params, opt_state, batch):
        if accum > 1:
            def micro(carry, mb):
                gsum, lsum = carry
                (l, _m), g = jax.value_and_grad(loss, has_aux=True)(params, mb)
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), None
            mbs = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            lossv = lsum / accum
            metrics: dict[str, jax.Array] = {}
        else:
            (lossv, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)
        params2, opt2, om = adamw.update(opt_cfg, grads, opt_state, params)
        out_metrics = {"loss": lossv, **metrics, **om}
        return params2, opt2, out_metrics

    jitted = jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, None),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, param_sh, opt_sh


def make_compressed_dp_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                            mesh):
    """Pure-DP training with int8 error-feedback gradient all-reduce via
    shard_map (the distributed-optimization feature).  Params replicated;
    batch sharded over 'data'.  step(params, opt, err, key, batch) -> ..."""
    from jax.sharding import PartitionSpec as P

    def loss(p, b):
        return N.loss_fn(p, cfg, b)

    def dp_step(params, opt_state, err, key, batch):
        (lossv, _m), grads = jax.value_and_grad(loss, has_aux=True)(
            params, batch)
        q, scale, new_err = comp.compress_tree(grads, err, key)
        # int32-safe summation of int8 payloads + max of scales
        qsum = jax.tree.map(
            lambda x: jax.lax.psum(x.astype(jnp.int32), "data"), q)
        smax = jax.tree.map(lambda s: jax.lax.pmax(s, "data"), scale)
        n = jax.lax.psum(1, "data")
        ghat = jax.tree.map(lambda qs, s: qs.astype(jnp.float32) * s / n,
                            qsum, smax)
        params2, opt2, om = adamw.update(opt_cfg, ghat, opt_state, params)
        lossm = jax.lax.pmean(lossv, "data")
        return params2, opt2, new_err, {"loss": lossm, **om}

    rep = P()
    bspec = jax.tree.map(lambda _: P("data"), {"tokens": 0, "labels": 0})
    smapped = jax.shard_map(
        dp_step, mesh=mesh,
        in_specs=(rep, rep, rep, rep, bspec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False)
    return jax.jit(smapped)


def make_eval_step(cfg: ModelConfig, mesh, fsdp: bool = True):
    param_sh = SH.shardings_for_params(cfg, mesh, fsdp=fsdp)

    def step(params, batch):
        loss, metrics = N.loss_fn(params, cfg, batch)
        return {"loss": loss, **metrics}

    return jax.jit(step, in_shardings=(param_sh, None)), param_sh


# ---------------------------------------------------------------------------
# Loop with fault tolerance
# ---------------------------------------------------------------------------

def train(cfg: ModelConfig, tc: TrainConfig, *, mesh=None,
          injector: FailureInjector | None = None,
          restart_policy: RestartPolicy | None = None,
          log: Callable[[str], None] = print) -> dict[str, float]:
    mesh = mesh or make_local_mesh()
    opt_cfg = adamw.AdamWConfig(total_steps=tc.steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=tc.seq_len,
                          global_batch=tc.global_batch, seed=tc.seed)
    step_fn, param_sh, opt_sh = make_train_step(
        cfg, opt_cfg, mesh, fsdp=tc.fsdp, accum=tc.accum)

    mgr = CheckpointManager(tc.ckpt_dir) if tc.ckpt_dir else None
    monitor = HeartbeatMonitor(n_hosts=jax.process_count())

    state: dict[str, Any] = {}

    def fresh_state():
        with jax.default_device(jax.devices()[0]):
            params = N.init(cfg, jax.random.PRNGKey(tc.seed))
        params = jax.device_put(params, param_sh)
        opt = jax.device_put(adamw.init(opt_cfg, params), opt_sh)
        return params, opt

    def restore_state() -> int:
        assert mgr is not None
        latest = mgr.latest_step()
        if latest is None:
            state["params"], state["opt"] = fresh_state()
            return 0
        tmpl = {"params": jax.eval_shape(
            functools.partial(N.init, cfg), jax.random.PRNGKey(tc.seed))}
        tmpl["opt"] = jax.eval_shape(
            functools.partial(adamw.init, opt_cfg), tmpl["params"])
        restored, _ = mgr.restore(
            tmpl, shardings={"params": param_sh, "opt": opt_sh})
        state["params"], state["opt"] = restored["params"], restored["opt"]
        log(f"[restore] resumed from step {latest}")
        return latest

    last_metrics: dict[str, float] = {}

    def loop(start_step: int) -> int:
        for step in range(start_step, tc.steps):
            if injector is not None:
                injector.maybe_fail(step)
            t0 = time.perf_counter()
            batch_np = make_batch(cfg, data_cfg, step)
            batch = jax.tree.map(jnp.asarray, batch_np)
            state["params"], state["opt"], metrics = step_fn(
                state["params"], state["opt"], batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            monitor.beat(jax.process_index(), dt)
            last_metrics.update({k: float(v) for k, v in metrics.items()})
            if step % tc.log_every == 0 or step == tc.steps - 1:
                log(f"step {step:5d} loss {last_metrics['loss']:.4f} "
                    f"lr {last_metrics['lr']:.2e} "
                    f"gnorm {last_metrics['grad_norm']:.2f} {dt*1e3:.0f}ms")
            if mgr is not None and ((step + 1) % tc.ckpt_every == 0
                                    or step == tc.steps - 1):
                mgr.save(step + 1,
                         {"params": state["params"], "opt": state["opt"]},
                         extra={"step": step + 1})
        return tc.steps

    def on_restart(step: int, exc: Exception) -> int:
        log(f"[fault] {exc}; restarting from last checkpoint")
        if mgr is not None:
            mgr.wait()
            return restore_state()
        state["params"], state["opt"] = fresh_state()
        return 0

    if mgr is not None and mgr.latest_step() is not None:
        start = restore_state()
    else:
        state["params"], state["opt"] = fresh_state()
        start = 0

    run_with_restarts(loop, start_step=start, final_step=tc.steps,
                      policy=restart_policy or RestartPolicy(),
                      on_restart=on_restart)
    if mgr is not None:
        mgr.wait()
    return last_metrics


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--scaled-down", action="store_true",
                    help="reduced same-family config (CPU runs)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (with --scaled-down)")
    ap.add_argument("--n-layers", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = CONFIGS.get(args.arch)
    if args.scaled_down:
        over = {}
        if args.d_model:
            over["d_model"] = args.d_model
        if args.n_layers:
            over["n_layers"] = args.n_layers
        cfg = cfg.scaled_down(**over)
    tc = TrainConfig(steps=args.steps, global_batch=args.global_batch,
                     seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, accum=args.accum)
    metrics = train(cfg, tc)
    print("final:", {k: round(v, 4) for k, v in metrics.items()})


if __name__ == "__main__":
    main()
