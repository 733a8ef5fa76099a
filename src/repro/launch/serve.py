"""Serving driver: loads (or initializes) a model, optionally quantizes it
with the GTA precision policy, and serves requests through the
continuous-batching engine (or the wave baseline for comparison).

Requests are submitted through the engine's async queue API with an
arrival process (``--arrival-ms`` mean inter-arrival gap) so the
continuous engine actually interleaves admissions with in-flight decode —
the scenario the slot-level design exists for.

Admission scheduling is pluggable (``--policy``): ``fifo`` keeps arrival
order; ``best_fit`` admits the queued request whose block reservation
(prefix-credited) best fits the pool's free list; ``slo_preempt`` adds
TTFT deadlines (``--ttft-slo``, seconds) with preempt-by-eviction — an
at-risk request may evict the decoding victim with the most reclaimable
blocks, which resumes later via prefix-cache skip-prefill with its
produced tokens intact; ``model_fit`` / ``model_preempt`` admit and
evict on the capacity planner's modeled step costs instead of raw
block counts (``repro.planner``, docs/PLANNER.md).

Speculative decoding (``--spec ngram`` / ``--spec model:<arch>``,
``--spec-k``): the paged engine verifies up to k drafted tokens per
dispatch (token-identical greedy output, fewer engine steps; see
``serving.spec``).

The run fails loudly: ``main`` returns the Results, and exits nonzero
(after printing the status counts and the first error) when any request
ends in a status other than ``ok`` or the run emits no tokens — the
engine's step watchdog keeps serving past a failing dispatch, so this is
where a broken kernel surfaces.

CLI (CPU demo sizes):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --scaled-down --requests 8 --max-new 16 --quant
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --scaled-down --requests 8 --spec ngram --spec-k 4
"""

from __future__ import annotations

import argparse
import collections
import time
import traceback

import jax
import numpy as np

from repro import configs as CONFIGS
from repro.checkpoint.manager import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.models import network as N
from repro.obs import Telemetry, render_report
from repro.quant.policy import quantize_params
from repro.serving.engine import (ContinuousEngine, Request, Result,
                                  WaveEngine)
from repro.serving.policy import POLICY_NAMES


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def make_requests(rng: np.random.Generator, n: int, prompt_len: int,
                  max_new: int, vocab: int, temperature: float = 0.0,
                  ttft_slo: float | None = None) -> list[Request]:
    """The CLI's synthetic trace: prompts of ``prompt_len // 2 ..
    prompt_len`` random tokens, each asking ``max_new // 2 .. max_new`` new
    tokens.  ``main`` draws it from ``np.random.default_rng(0)``."""
    return [Request(rid=i,
                    prompt=rng.integers(
                        3, vocab,
                        max(1, int(rng.integers(
                            prompt_len // 2, prompt_len + 1)))).astype(
                                np.int32),
                    max_new_tokens=max(1, int(rng.integers(
                        max_new // 2, max_new + 1))),
                    temperature=temperature,
                    ttft_slo=ttft_slo)
            for i in range(n)]


def main(argv=None) -> list[Result]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scaled-down", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--engine", choices=("continuous", "dense", "wave"),
                    default="continuous",
                    help="continuous = paged KV pool (default); dense = "
                         "continuous batching over dense stripes; wave = "
                         "seed baseline")
    ap.add_argument("--arrival-ms", type=float, default=0.0,
                    help="mean inter-arrival gap (continuous engine only); "
                         "0 = offered all at once")
    ap.add_argument("--policy", choices=POLICY_NAMES, default="fifo",
                    help="admission scheduling policy (paged engine): "
                         "fifo = arrival order; best_fit = admit the "
                         "request whose block reservation best fits the "
                         "free list (age-capped against starvation); "
                         "slo_preempt = FIFO + TTFT-deadline jump-the-"
                         "queue with preempt-by-eviction (victims resume "
                         "via prefix-cache skip-prefill)")
    ap.add_argument("--ttft-slo", type=float, default=0.0,
                    help="per-request TTFT deadline in seconds (0 = no "
                         "SLO); only the slo_preempt policy acts on it")
    ap.add_argument("--spec", default=None, metavar="ngram|model:<arch>",
                    help="speculative decoding (paged engine, greedy "
                         "requests only): 'ngram' = prompt-lookup drafting "
                         "from each slot's own token history (model-free); "
                         "'model:<arch>' = a small draft model proposes "
                         "(e.g. model:qwen2-0.5b; the draft shares the "
                         "target's KV-pool block tables — same arch as "
                         "--arch self-drafts with the target weights, "
                         "other archs run freshly initialized as a demo). "
                         "Output stays token-identical to vanilla decode; "
                         "accepted drafts cut engine dispatches")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per engine step "
                         "(the verify batch is slots x (k+1); default 4)")
    ap.add_argument("--quant", action="store_true",
                    help="int8 GTA serving path: QuantTensor weights "
                         "through a QuantPolicy, int8 paged KV blocks "
                         "with scale sidecars where the arch allows, and "
                         "the §5 explorer binding per-GEMM precision "
                         "(docs/QUANTIZATION.md; wave keeps the legacy "
                         "weights-only rewrite)")
    ap.add_argument("--gemm-backend", choices=("xla", "scheduled"),
                    default="xla",
                    help="scheduled = route model projections through the "
                         "fused-reduction scheduled Pallas GEMMs (the "
                         "paper-§5 schedule cache picks dataflow/fold per "
                         "shape); xla = native XLA dot fusions (default)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(open in Perfetto / chrome://tracing); enables "
                         "the lifecycle tracer")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics-registry snapshot (.prom suffix "
                         "= Prometheus text exposition, else JSON)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the four hot dispatches with synced timing "
                         "and modeled-cost cross-checks (see "
                         "scripts/trace_report.py); implies tracing")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import dataclasses

    cfg = CONFIGS.get(args.arch)
    if args.scaled_down:
        cfg = cfg.scaled_down()
    if args.gemm_backend != "xla":
        cfg = dataclasses.replace(
            cfg, gemm_backend=args.gemm_backend).validate()
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    base_cfg = cfg
    quant_policy = None
    if args.quant and args.engine != "wave":
        # the end-to-end serving path (docs/QUANTIZATION.md): the engine
        # rewrites the weight tree through the policy at construction and
        # — on the paged engine, where the arch allows — stores int8 KV
        # blocks with scale sidecars.  Scaled-down geometry sits below
        # the production min_size floor, so drop it there.
        from repro.quant import QuantPolicy
        cfg = dataclasses.replace(
            cfg, quant_serving=True, name=cfg.name + "+int8").validate()
        quant_policy = (QuantPolicy(min_size=0) if args.scaled_down
                        else QuantPolicy())

    params = N.init(cfg, jax.random.PRNGKey(0))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        if mgr.latest_step() is not None:
            restored, _ = mgr.restore({"params": params})
            params = restored["params"]
            print(f"[serve] restored step {mgr.latest_step()}")
    if args.quant:
        if args.engine == "wave":
            # the seed baseline predates QuantPolicy: weights-only rewrite
            params = quantize_params(params)
        kv = ("int8 KV blocks"
              if cfg.quant_kv and args.engine == "continuous" else "fp KV")
        print(f"[serve] int8 serving path: QuantTensor weights + {kv}")

    rng = np.random.default_rng(0)
    reqs = make_requests(rng, args.requests, args.prompt_len, args.max_new,
                         cfg.vocab, args.temperature, args.ttft_slo or None)

    spec = None
    if args.spec:
        if args.spec == "ngram":
            spec = "ngram"
        elif args.spec.startswith("model:"):
            from repro.serving.spec import ModelDraft
            draft_arch = args.spec.split(":", 1)[1]
            draft_cfg = CONFIGS.get(draft_arch)
            if args.scaled_down:
                draft_cfg = draft_cfg.scaled_down()
            if draft_cfg.name in (cfg.name, base_cfg.name):
                # self-draft: share the target weights (full acceptance —
                # the mechanism demo without trained checkpoints).  Under
                # --quant the draft stays on the base fp config: it keeps
                # its OWN cache tree (only block tables are shared), and
                # the engine quantizes its own copy of the weights.
                draft_cfg, draft_params = base_cfg, params
            else:
                draft_params = N.init(draft_cfg, jax.random.PRNGKey(1))
            spec = ModelDraft(draft_cfg, draft_params)
        else:
            raise SystemExit(f"--spec {args.spec!r}: expected 'ngram' or "
                             f"'model:<arch>'")
        if args.temperature > 0:
            raise SystemExit("--spec is greedy-only: drop --temperature")

    want_telemetry = bool(args.trace_out or args.metrics_out
                          or args.profile)
    if want_telemetry and args.engine == "wave":
        raise SystemExit("--trace-out/--metrics-out/--profile need the "
                         "continuous engine (the wave baseline is "
                         "uninstrumented)")
    if args.profile and args.engine == "dense":
        raise SystemExit("--profile wraps the paged dispatches: use the "
                         "continuous (paged) engine")
    obs = (Telemetry.on(profile=args.profile) if want_telemetry
           else None)

    t0 = time.perf_counter()
    if args.engine == "wave":
        if spec is not None:
            raise SystemExit("--spec needs the continuous paged engine")
        eng = WaveEngine(cfg, params, slots=args.slots, max_len=args.max_len)
        results: list[Result] = eng.run(reqs)
    else:
        if spec is not None and args.engine == "dense":
            raise SystemExit("--spec needs the paged engine (KV rollback "
                             "lives in the block pool)")
        eng = ContinuousEngine(cfg, params, slots=args.slots,
                               max_len=args.max_len,
                               paged=args.engine != "dense",
                               policy=args.policy,
                               spec=spec, spec_k=args.spec_k,
                               telemetry=obs, quant_policy=quant_policy)
        eng.start()
        for r in reqs:
            if args.arrival_ms > 0:
                time.sleep(rng.exponential(args.arrival_ms / 1e3))
            eng.submit(r)
        results = [eng.get_result(timeout=600) for _ in reqs]
        eng.stop()
    dt = time.perf_counter() - t0

    toks = sum(len(r.tokens) for r in results)
    lats = [r.latency_s for r in results]
    print(f"[serve:{args.engine}] {len(results)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks / max(dt, 1e-9):.1f} tok/s)  "
          f"latency p50={_percentile(lats, 50)*1e3:.0f}ms "
          f"p99={_percentile(lats, 99)*1e3:.0f}ms")
    if args.engine != "wave":
        st = eng.schedule.stats()
        print(f"[serve] schedule cache: {st['entries']} schedules, "
              f"{st['hits']} hits / {st['misses']} misses")
        if eng.paged:
            ps = eng.pool.stats()
            kv = eng.kv_bytes()
            print(f"[serve] kv pool: peak {ps['peak_used']}/"
                  f"{ps['num_blocks']} blocks, "
                  f"{ps['shared_token_hits']} shared-prefix token hits, "
                  f"peak KV {kv['peak']} / allocated {kv['allocated']} B")
            print(f"[serve] policy {eng.policy.name}: mean pool util "
                  f"{eng.avg_pool_util():.2f}, {eng.preemptions} "
                  f"preemptions, {ps['backoffs']} admission backoffs")
            if eng.spec is not None:
                sp = eng.spec_stats()
                print(f"[serve] spec {sp['provider']} k={sp['k']}: "
                      f"{sp['tokens_emitted']} tokens in "
                      f"{sp['verify_steps']} verify dispatches "
                      f"(avg accept len {sp['avg_accept_len']:.2f}, "
                      f"{sp['draft_steps']} draft dispatches)")
    for r in sorted(results, key=lambda r: r.rid)[:4]:
        print(f"  rid={r.rid} new_tokens={len(r.tokens)} "
              f"prefill={r.prefill_s*1e3:.0f}ms decode={r.decode_s*1e3:.0f}ms")

    if args.engine != "wave" and want_telemetry:
        print(render_report(eng.metrics, wall_s=dt))
        if args.trace_out:
            eng.obs.export_trace(args.trace_out)
            print(f"[serve] trace -> {args.trace_out} "
                  f"({len(eng.obs.tracer)} events, "
                  f"{eng.obs.tracer.dropped} dropped)")
        if args.metrics_out:
            eng.obs.export_metrics(args.metrics_out)
            print(f"[serve] metrics -> {args.metrics_out}")

    status = collections.Counter(r.status for r in results)
    print("[serve] request status: "
          + ", ".join(f"{k}={v}" for k, v in sorted(status.items())))
    if toks == 0 or set(status) != {"ok"}:
        first = next((r.error for r in results if r.error), None)
        print(f"[serve] FAILED: {toks} tokens emitted; first classified "
              f"error: {first}")
        exc = getattr(eng, "last_dispatch_error", None)
        if exc is not None:
            print("[serve] last dispatch error:\n"
                  + "".join(traceback.format_exception(exc)))
        raise SystemExit(1)
    return results


if __name__ == "__main__":
    main()
