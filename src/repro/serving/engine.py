"""Serving engines: paged continuous batching (v3) + dense + wave baselines.

``ContinuousEngine`` (the default ``Engine``) admits requests per SLOT:
the moment a slot finishes its request, the next queued request is
prefilled into that slot while the other slots keep decoding — no wave
barrier.  The design:

  * **Per-slot KV validity.**  Caches carry a per-slot ``pos`` vector
    (``network.expand_cache_pos``); attention masks each slot at its own
    bound and decode writes each slot at its own depth, so slots at
    different sequence depths batch into one jitted decode step.
  * **Block-paged KV cache (default, ``paged=True``).**  Attention KV
    lives in a shared block pool (``serving.kv_pool`` — free-list
    allocator, ref-counted blocks, per-slot block tables) instead of a
    dense ``slots x max_len`` stripe: the memory ceiling becomes "blocks
    actually used", identical prompt prefixes are stored ONCE (full
    prompt blocks are content-addressed and their prefill is skipped on a
    hit), and decode attention gathers K/V through the table — the
    Pallas paged-decode kernel on TPU, a pure-JAX gather elsewhere
    (``kernels.paged_attention``).  When the pool cannot host a new
    request it stays queued (clean admission backoff, never a crash).
  * **Chunked prefill + batched admission (paged path).**  Prompts are
    prefilled in fixed-size decode-interleaved chunks: every engine step
    runs at most ONE chunk batch (all admitting slots advance together in
    a single jitted call — batched admission) and then one decode step,
    so resident slots never stall longer than one chunk.  Cache cursors
    advance by each row's REAL token count; the SSM masked-update scan
    keeps hybrid recurrent state exact under the chunk's pad tail.
  * **Bucketed ragged prefill (dense fallback, ``paged=False``).**  A new
    prompt is right-padded to the next bucket length and prefilled alone
    (batch=1) through a per-bucket jit cache (``network.prefill_ragged``
    gathers the logits of the last REAL token), then spliced into its
    slot with ``network.insert_slot_caches`` with pos = the true prompt
    length.  Since the masked-update scan (models/ssm.py) landed, hybrid
    archs take this exact ragged path too — the right-aligned fallback is
    gone.
  * **Async queue API.**  ``submit`` enqueues from any thread;
    ``serve_forever``/``start`` pump admission+decode on a background
    thread; results arrive on a thread-safe queue (``get_result``).
    ``run(requests)`` is the synchronous convenience wrapper.

**Scheduling policies (paged path).**  Admission order and preemption
are delegated to a pluggable :class:`repro.serving.policy.SchedulerPolicy`
(``policy="fifo" | "best_fit" | "slo_preempt"``, or any instance):

  * Every step the engine snapshots the pending queue (with a
    side-effect-free ``KVPool.probe`` reservation probe per request) and
    asks the policy which request to admit into the next free slot —
    ``fifo`` keeps arrival order, ``best_fit`` picks the reservation
    that best fits the current free list (prefix-credited,
    starvation-bounded by an age cap).
  * ``slo_preempt`` adds **preempt-by-eviction**: when a queued request
    with a ``Request.ttft_slo`` deadline is at risk and cannot be
    admitted, the policy names a decoding victim (most reclaimable
    blocks, least progress).  ``_preempt`` registers the victim's FULL
    sequence (prompt + produced tokens) in the prefix cache before
    releasing the slot, so its resident KV survives as evictable cached
    blocks; the victim re-queues carrying its produced tokens
    (restart-safe ``_Pending`` state) and re-admission skip-prefills the
    cached blocks — preempted work is not recomputed, and greedy output
    is token-identical to a never-preempted run (KV written by prefill
    equals KV written by decode position-for-position).  Preemption
    advances the engine's sample-key stream differently, so only
    temperature-0 output is reproducible across policies.
  * Policies are decision functions over immutable views; all state
    mutation stays in the engine, and ``pool.check()`` holds after every
    step (``audit=True`` asserts it).  Telemetry: ``engine.preemptions``,
    ``engine.avg_pool_util()`` (mean fraction of usable blocks in use,
    sampled once per step), and per-result ``ttft_steps`` (engine
    dispatches before the first token — the deterministic TTFT proxy
    serve_bench gates on).

**Speculative decoding (paged path, ``spec=``).**  With a
:class:`repro.serving.spec.DraftProvider` (``spec="ngram"`` prompt-lookup
drafting, or a ``ModelDraft`` running a small draft config over the SAME
pool block tables), decode becomes DRAFT/VERIFY rounds: every step the
provider proposes up to ``spec_k`` tokens per slot and ONE jitted
``verify_chunk`` dispatch scores ``[cur_tok, drafts...]`` for all slots
at once — the chunked-prefill masked ragged layout at a fixed
``(slots, spec_k + 1)`` shape, pre-registered in the ScheduleCache at
construction.  The host accepts the longest draft prefix matching the
target's own argmax (greedy-only; sampled requests are rejected at
``submit``), so output is token-identical to vanilla greedy decode while
each dispatch can emit up to ``spec_k + 1`` tokens.  Rejected tails are
rolled back: cache cursors via ``network.set_slot_pos``, pool blocks via
``KVPool.truncate`` — spec admissions reserve the decode span LAZILY
(``KVPool.extend``, one verify span ahead) so rollback genuinely returns
blocks; a slot whose span cannot be hosted is preempted through the PR-4
machinery and resumes exactly.  Hybrid (SSM) configs are rejected at
construction: recurrent state has no truncate.  Telemetry:
``spec_stats()`` / ``avg_accept_len()``.

**ScheduleCache contract.**  The engine owns a
:class:`repro.core.scheduler.ScheduleCache` and, on every admission and
decode-shape change, resolves the step's dominant p-GEMMs
(qkv/out/mlp/head projections at the current token count) through the
paper-§5 exploration — first sight of a (M, N, K, precision) explores and
memoizes the (dataflow, arrangement, k_fold) winner; afterwards the hot
path is a dict hit.  The same cache object plugs into
``kernels.ops.matmul(..., schedule=...)``, which applies the memoized
choice to the Pallas dispatch, so offline exploration and online serving
share one schedule store (``engine.schedule.stats()`` reports hit rates).
Steady-state shapes (decode at M = slots, the chunk batch, the paged
gather GEMMs) are pre-resolved at engine construction.  With
``cfg.gemm_backend == "scheduled"`` the engine adopts the per-config
``GemmBackend``'s cache, so the model-interior projections that dispatch
through the fused scheduled Pallas kernels and the engine's own
registrations share one store — serve_bench gates a 100% hit rate after
warmup on that path.

``WaveEngine`` keeps the seed behavior (whole wave prefilled together,
drained together) as the benchmark baseline.
"""

from __future__ import annotations

import collections
import dataclasses
import queue as _queue
import threading
import time
from collections.abc import Sequence
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pgemm import PGEMM
from repro.core.precision import INT8, precision_for_dtype
from repro.core.scheduler import ScheduleCache
from repro.kernels import paged_attention as PA
from repro.models import network as N
from repro.models.config import ModelConfig
from repro.obs import Telemetry
from repro.obs.events import NO_SPAN
from repro.quant import QuantPolicy, choose_precision, serving_quant_params
from repro.serving.kv_pool import KVPool, PoolAuditError, blocks_for
from repro.serving.policy import (PendingView, SchedulerPolicy, SlotView,
                                  make_policy)
from repro.serving.resilience import (EngineCrash, FaultPlane,
                                      InjectedFault, ResilienceConfig,
                                      classify_error)
from repro.serving.spec import DraftProvider, make_provider

PyTree = Any

#: histogram bucket bounds for wall-clock request latencies (seconds)
_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


# ---------------------------------------------------------------------------
# Jitted serving programs, cached PER CONFIG (not per engine instance):
# spinning up a fresh engine over the same model must not recompile, and
# sampling is fused into each program so one step = one dispatch + one sync.
# ---------------------------------------------------------------------------

def _sample_traced(key, logits, temps):
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(
        sub, logits / jnp.maximum(temps, 1e-6)[:, None])
    return jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32), key


#: (id(cfg), max_len) -> (cfg strong-ref, {name: jitted fn}); the strong
#: ref pins the id so the cache key stays valid.  LRU-bounded: a process
#: sweeping many configs must not accumulate compiled executables forever.
_FN_CACHE: "collections.OrderedDict[tuple[int, int], tuple[ModelConfig, dict[str, Any]]]" = (
    collections.OrderedDict())
_FN_CACHE_MAX = 8


def _engine_fns(cfg: ModelConfig, max_len: int) -> dict[str, Any]:
    ent = _FN_CACHE.get((id(cfg), max_len))
    if ent is not None and ent[0] is cfg:
        _FN_CACHE.move_to_end((id(cfg), max_len))
        return ent[1]
    dt = jnp.dtype(cfg.compute_dtype)

    def decode_sample(params, toks, caches, pos, key, temps):
        logits, caches = N.decode_step(params, cfg, toks, caches, pos)
        tok, key = _sample_traced(key, logits, temps)
        return tok, caches, key

    def admit_ragged(params, toks, caches, slot, pos0, last_idx, key, temp):
        small = N.init_caches(cfg, 1, max_len, dt)
        logits, small = N.prefill_ragged(params, cfg, {"tokens": toks},
                                         small, last_idx)
        caches = N.insert_slot_caches(caches, small, slot, pos0)
        tok, key = _sample_traced(key, logits, temp[None])
        return tok[0], caches, key

    def decode_sample_paged(params, toks, caches, pos, bt, adv, key, temps):
        logits, caches = N.decode_step(params, cfg, toks, caches, pos,
                                       block_table=bt, pos_advance=adv)
        tok, key = _sample_traced(key, logits, temps)
        return tok, caches, key

    def prefill_chunk(params, toks, caches, slot_ids, bt, lens, last_idx,
                      key, temps):
        logits, caches = N.prefill_paged_chunk(params, cfg, toks, caches,
                                               slot_ids, bt, lens, last_idx)
        tok, key = _sample_traced(key, logits, temps)
        return tok, caches, key

    def verify_chunk(params, toks, caches, slot_ids, bt, lens):
        # speculative verify is greedy-only (the engine rejects
        # temperature > 0 at submit), so argmax happens on-device and one
        # (slots, k+1) int32 array crosses to the host per step.
        logits, caches = N.verify_paged_chunk(params, cfg, toks, caches,
                                              slot_ids, bt, lens)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    fns = {
        "verify_chunk": jax.jit(verify_chunk),
        "set_pos": jax.jit(N.set_slot_pos),
        "decode_sample": jax.jit(decode_sample),
        "admit_ragged": jax.jit(admit_ragged),
        "decode_sample_paged": jax.jit(decode_sample_paged),
        "prefill_chunk": jax.jit(prefill_chunk),
        "reset_slot": jax.jit(N.reset_slot_state),
        "copy_blocks": jax.jit(N.copy_paged_blocks),
        "prefill": jax.jit(lambda p, b, c: N.prefill(p, cfg, b, c)),
        "decode": jax.jit(
            lambda p, t, c, pos: N.decode_step(p, cfg, t, c, pos)),
    }
    _FN_CACHE[(id(cfg), max_len)] = (cfg, fns)
    while len(_FN_CACHE) > _FN_CACHE_MAX:
        _FN_CACHE.popitem(last=False)
    return fns


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0    # 0 => greedy
    eos: int = 2
    #: TTFT deadline in seconds (None = best effort).  Only the
    #: ``slo_preempt`` policy acts on it — a request at risk of missing
    #: its deadline may evict a decoding victim to get admitted.
    ttft_slo: float | None = None
    #: policy hint: higher-priority requests admit first under
    #: ``best_fit`` and are never preempted for a lower-priority one.
    priority: int = 0
    #: hard lifecycle deadlines (seconds from submit; None = none).
    #: Unlike ``ttft_slo`` (a scheduling *hint*), these TERMINATE the
    #: request: past ``deadline_s`` (total wall) or past
    #: ``ttft_deadline_s`` without a first token, it finishes with
    #: ``Result.status == "timeout"`` carrying whatever tokens exist.
    ttft_deadline_s: float | None = None
    deadline_s: float | None = None


@dataclasses.dataclass
class Result:
    rid: int
    tokens: np.ndarray
    prefill_s: float
    decode_s: float
    latency_s: float = 0.0      # submit -> finish (continuous engine)
    ttft_s: float = 0.0         # submit -> first token
    #: engine dispatches (decode + chunk batches) before the first
    #: token — the deterministic TTFT proxy (wall-clock ttft_s is noisy)
    ttft_steps: int = 0
    preemptions: int = 0        # times this request was evicted mid-flight
    #: terminal status — ok | cancelled | timeout | shed | failed
    #: (docs/RELIABILITY.md).  Every submitted request produces exactly
    #: one Result; non-"ok" Results still carry the tokens produced.
    status: str = "ok"
    #: error classification when status == "failed" (classify_error)
    error: str | None = None


def _bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclasses.dataclass
class _Pending:
    """Restart-safe queue entry: everything needed to (re-)admit a
    request, including the produced tokens of a preempted one — the
    entry, not the slot, is the durable unit of scheduling state."""

    req: Request
    t_submit: float
    #: prompt plus any tokens produced before a preemption — the
    #: sequence a (re-)admission actually prefills (the resume tail's KV
    #: usually skip-prefills via the prefix cache).
    full_prompt: np.ndarray = None  # type: ignore[assignment]
    resume_tokens: list[int] = dataclasses.field(default_factory=list)
    t_first: float = 0.0            # preserved across preemptions
    ttft_steps: int = -1            # -1 = first token not yet produced
    preemptions: int = 0
    prefill_s: float = 0.0          # prefill wall time from prior admissions
    #: failed admission attempts (resilience: bounded retry-with-backoff)
    admit_failures: int = 0
    #: engine dispatch index before which admission is not retried
    retry_at: int = 0

    def __post_init__(self):
        if self.full_prompt is None:
            self.full_prompt = np.asarray(self.req.prompt, np.int32)


@dataclasses.dataclass
class _Slot:
    """Host-side state of one in-flight request."""

    req: Request
    produced: list[int]
    cur_tok: int
    t_submit: float
    t_admit: float
    t_prefill_done: float
    t_first: float
    #: paged path: "prefill" while chunks remain, then "decode"
    phase: str = "decode"
    #: pending chunk token arrays (paged chunked prefill), consumed in order
    chunks: list[np.ndarray] = dataclasses.field(default_factory=list)
    #: the admission prompt (original prompt + resume tokens) — what
    #: prefix registration must content-address
    full_prompt: np.ndarray = None  # type: ignore[assignment]
    #: len(resume tokens): produced[:resume_len] predate this admission
    resume_len: int = 0
    preemptions: int = 0
    ttft_steps: int = -1
    prefill_s_prev: float = 0.0

    def __post_init__(self):
        if self.full_prompt is None:
            self.full_prompt = np.asarray(self.req.prompt, np.int32)


class ContinuousEngine:
    """Slot-level continuous-batching engine (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params: PyTree, *, slots: int = 8,
                 max_len: int = 2048, seed: int = 0,
                 prefill_buckets: Sequence[int] | None = None,
                 schedule_cache: ScheduleCache | None = None,
                 paged: bool = True, block_size: int = 16,
                 kv_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 share_prefixes: bool = True,
                 policy: str | SchedulerPolicy = "fifo",
                 spec: str | DraftProvider | None = None,
                 spec_k: int = 4,
                 audit: bool = False,
                 telemetry: Telemetry | None = None,
                 faults: FaultPlane | None = None,
                 resilience: ResilienceConfig | None = None,
                 quant_policy: QuantPolicy | None = None):
        if cfg.is_encoder_only:
            raise ValueError(f"{cfg.name} is encoder-only: no decode serving")
        # telemetry bundle: the metrics registry is ALWAYS real — its
        # counters back engine.steps & co. (the old attributes live on as
        # property shims below); the tracer ring and the dispatch
        # profiler are the opt-in parts (Telemetry.on()).
        self.obs = telemetry if telemetry is not None else Telemetry.off()
        self.metrics = self.obs.metrics
        self._tr = self.obs.tracer
        self.spec: DraftProvider | None = None
        if spec is not None:
            if not paged:
                raise ValueError(
                    "speculative decoding serves through the paged KV pool "
                    "(lazy extend + truncate rollback); the dense "
                    "(paged=False) engine has no pool — drop spec= or use "
                    "paged=True")
            if cfg.has_recurrent_state:
                raise ValueError(
                    f"{cfg.name} is a hybrid (SSM) arch: the verify step "
                    f"rolls rejected tokens back by cursor truncation, and "
                    f"recurrent state cannot be rolled back (ROADMAP 'SSM "
                    f"state checkpointing' is the missing half) — spec= is "
                    f"attention-only for now")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self.spec = make_provider(spec)
        self.spec_k = spec_k
        # speculative telemetry (spec_emitted & co. are property shims):
        # tokens emitted by verify steps, draft tokens proposed, draft
        # tokens accepted (emitted - verify steps), (slot, verify) events
        m = self.metrics
        self._c_spec_emitted = m.counter(
            "spec.tokens_emitted", "tokens emitted by verify steps")
        self._c_spec_drafted = m.counter(
            "spec.drafted", "draft tokens proposed")
        self._c_spec_accepted = m.counter(
            "spec.accepted", "draft tokens accepted")
        self._c_spec_verifies = m.counter(
            "spec.slot_verifies", "(slot, verify-step) events")
        self.cfg = cfg
        self.policy = (make_policy(policy) if isinstance(policy, str)
                       else policy)
        if self.policy.requires_pool and not paged:
            raise ValueError(
                f"policy {self.policy.name!r} schedules over KV-pool block "
                f"reservations; the dense (paged=False) engine has no pool "
                f"— use policy='fifo'")
        self._audit = audit
        # quantized serving (cfg.quant_serving): the weight tree is
        # rewritten through the policy HERE, before any jitted program
        # closes over it — dense()/head_apply() dispatch on the
        # QuantTensor leaves transparently.  serving_quant_params is
        # idempotent, so callers may pass an already-quantized tree.
        self.quant_policy = quant_policy
        if cfg.quant_serving:
            params = serving_quant_params(cfg, params, quant_policy)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        # scheduled-backend configs: the engine and the model interior
        # share ONE schedule store (the per-config GemmBackend's cache), so
        # the stats/applied log cover the projections that actually
        # dispatch through the scheduled kernels — and a restarted engine
        # over the same config inherits a warm cache.
        backend = N.gemm_backend(cfg)
        if backend is not None:
            if schedule_cache is not None and \
                    schedule_cache is not backend.schedule:
                raise ValueError(
                    "gemm_backend='scheduled' configs dispatch the model "
                    "interior through the per-config GemmBackend's "
                    "ScheduleCache; passing a different schedule_cache "
                    "would split the store (engine stats would not cover "
                    "the projections that actually execute)")
            self.schedule = backend.schedule
        else:
            self.schedule = schedule_cache or ScheduleCache()
        self.schedule.bind_metrics(m)
        self.paged = paged
        self._prec = precision_for_dtype(cfg.compute_dtype,
                                         default="FP32").name
        #: (M, N, K) -> §5 explorer precision choice for the serving
        #: p-GEMMs (memoized: _register_gemms runs per step, the
        #: explorer must only ever run at construction / first sight)
        self.precision_plan: dict[tuple[int, int, int], str] = {}

        if prefill_buckets is None:
            prefill_buckets, b = [], 16
            while b < max_len:
                prefill_buckets.append(b)
                b *= 2
        # every admissible prompt (<= max_len) must have a bucket: drop
        # oversize buckets, always keep max_len as the terminal bucket.
        self.buckets = sorted(
            {b for b in prefill_buckets if b <= max_len} | {max_len})

        self._fns = _engine_fns(cfg, max_len)

        if paged:
            # chunk length: one jitted chunk program serves every prefill.
            # Any length is valid for every arch — ssd_chunked pads its
            # scan tail internally (dt=0 no-ops), so no ssm.chunk
            # quantization is needed here.
            self.prefill_chunk = min(prefill_chunk or 32, max_len)
            per_slot = blocks_for(max_len, block_size)
            if kv_blocks is None:
                # ~3/4 of the dense ceiling: real savings while every test
                # trace still fits (the pool backs off, never deadlocks,
                # as long as ONE request fits when the pool drains).
                kv_blocks = max(per_slot + 1,
                                1 + (3 * slots * per_slot + 3) // 4)
            if kv_blocks < per_slot + 1:
                raise ValueError(
                    f"kv_blocks {kv_blocks} cannot host one full-window "
                    f"request ({per_slot} blocks of {block_size})")
            # prefix sharing reuses KV *blocks*; hybrid (SSM) archs also
            # carry recurrent state the pool cannot reconstruct from
            # blocks, so sharing (= skipping the shared prefill) would
            # silently drop the prefix from the SSM recurrence.  Disable.
            share_prefixes = (share_prefixes
                              and not cfg.has_recurrent_state)
            self.pool: KVPool | None = KVPool(
                kv_blocks, block_size, slots=slots, max_len=max_len,
                share_prefixes=share_prefixes, metrics=m,
                quantized=cfg.quant_kv)
            self.caches = N.expand_cache_pos(
                N.init_paged_caches(cfg, slots, kv_blocks, block_size),
                slots)
            self._bt = jnp.asarray(self.pool.tables)
            self._slot_ids = jnp.arange(slots, dtype=jnp.int32)
        else:
            self.pool = None
            self.caches = N.expand_cache_pos(
                N.init_caches(cfg, slots, max_len), slots)
        self._slots: list[_Slot | None] = [None] * slots
        self._pos = np.zeros(slots, np.int32)   # mirror of cache pos leaves

        self._pending: "collections.deque[_Pending]" = collections.deque()
        self._results: "_queue.Queue[Result]" = _queue.Queue()
        self._cv = threading.Condition()
        self._stop = False
        self._thread: threading.Thread | None = None
        self._loop_error: BaseException | None = None

        # resilience plane (docs/RELIABILITY.md): lifecycle guards +
        # fault injection.  A default ResilienceConfig is a behavioral
        # no-op, and with faults=None every hook below is dormant.
        self.resilience = resilience or ResilienceConfig()
        self.faults = faults
        self._cancels: set[int] = set()     # rids awaiting cancellation
        self._deadlines = False             # any live request has one
        self._ticks = 0     # step() invocations — advances even when a
        # dispatch fails or nothing runs, so admission backoff (below)
        # can never hold the whole queue forever on an idle engine
        self._fail_streak: dict[str, int] = {}   # consecutive per kind
        self._spec_k_live = spec_k          # degradable speculation depth
        self._spec_clean_steps = 0
        self.last_dispatch_error: BaseException | None = None
        self._c_res_faults = m.counter(
            "resilience.faults_injected", "fault-plane firings")
        self._c_res_retries = m.counter(
            "resilience.retries", "transient failures retried")
        self._c_res_cancelled = m.counter(
            "resilience.cancelled", "requests cancelled")
        self._c_res_timeouts = m.counter(
            "resilience.timeouts", "requests past a hard deadline")
        self._c_res_shed = m.counter(
            "resilience.shed", "submissions rejected at the queue bound")
        self._c_res_quarantined = m.counter(
            "resilience.quarantined",
            "requests failed by the step watchdog")
        self._c_res_admit_fail = m.counter(
            "resilience.admit_failures", "failed admission attempts")
        self._c_res_degrades = m.counter(
            "resilience.spec_degrades", "spec_k halvings under pressure")
        self._c_res_restores = m.counter(
            "resilience.restored", "snapshot entries re-admitted")
        if faults is not None:
            faults.on_fire = self._note_fault
            if paged:
                faults.attach_pool(self.pool)
        # step/lifecycle telemetry — registry-backed; the old attributes
        # (engine.steps, .prefills, .chunk_steps, .preemptions,
        # .decode_times, .chunk_durations) remain readable as property
        # shims over these metrics
        self._c_steps = m.counter(
            "engine.steps", "decode/verify dispatches executed")
        self._c_prefills = m.counter(
            "engine.prefills", "prompts fully prefilled")
        self._c_chunk_steps = m.counter(
            "engine.chunk_steps", "prefill-chunk batches executed")
        self._c_preemptions = m.counter(
            "engine.preemptions", "victim evictions (slo_preempt)")
        self._c_admissions = m.counter(
            "engine.admissions", "slot admissions (fresh + resumed)")
        self._c_resumes = m.counter(
            "engine.resumes", "re-admissions of preempted requests")
        self._c_tokens = m.counter(
            "engine.tokens_emitted", "tokens delivered in Results")
        self._c_finished = m.counter(
            "engine.requests_finished", "Results emitted")
        # per-step pool-utilization samples (used/usable blocks) — the
        # block-aware admission win serve_bench gates on
        self._c_util_sum = m.counter(
            "engine.pool_util_sum", "sum of per-step pool-util samples")
        self._c_util_samples = m.counter(
            "engine.pool_util_samples", "pool-util samples taken")
        self._g_pool_util = m.gauge(
            "engine.pool_util", "pool utilization at the last step")
        self._g_occupancy = m.gauge(
            "engine.batch_occupancy", "active slots at the last step")
        self._h_ttft_steps = m.histogram(
            "engine.ttft_steps",
            "engine dispatches before each request's first token")
        self._h_ttft_s = m.histogram(
            "engine.ttft_s", "submit -> first token (s)",
            buckets=_LATENCY_BUCKETS)
        self._h_latency = m.histogram(
            "engine.request_latency_s", "submit -> finish (s)",
            buckets=_LATENCY_BUCKETS)
        #: deterministic interleave bound: max chunk batches run between
        #: two decode steps while some slot was decoding.  The chunked-
        #: prefill construction guarantees <= 1 (one chunk batch per
        #: engine step, decode follows); serve_bench gates on it.
        self.max_chunk_gap = 0
        self._chunks_since_decode = 0
        # perf_counter stamps of decode-step completions — serve_bench
        # derives the max decode gap from these to verify chunked prefill
        # bounds the admission stall; chunk durations are the wall times
        # of the chunk batches (the bound itself).
        self._s_decode = m.series(
            "engine.decode_step_stamps",
            "perf_counter stamps of decode-step completions")
        self._s_chunk = m.series(
            "engine.chunk_duration_s", "prefill-chunk batch wall times")

        # Pre-resolve the steady-state serving shapes (decode step with
        # M = active slots, the prefill-chunk batch, and the paged-decode
        # gather GEMMs) so the hot path never explores: every per-step
        # ``resolve`` — and every trace of the scheduled backend at these
        # shapes — is a pure cache-hit dispatch from the first request on.
        self._register_gemms(self.slots, self.slots)
        if self.paged:
            self._register_gemms(self.slots * self.prefill_chunk, self.slots)
            for M, Nn, K in PA.gather_gemm_shapes(cfg, block_size):
                self.schedule.resolve(M, Nn, K, self._prec)
        if self.spec is not None:
            # the verify-step shape family (slots * (k+1) interior tokens,
            # and the head over ALL of them): pre-resolved here so
            # steady-state speculative serving is a pure cache-hit dispatch
            L = self.spec_k + 1
            self._register_gemms(self.slots * L, self.slots * L)
            self.spec.bind(self)
        if self.obs.profiler is not None:
            # wraps the hot dispatches with block_until_ready timing and
            # runs the calibration pass (all four drift-table dispatches)
            self.obs.profiler.attach(self)

    # -- property shims over the metrics registry -----------------------------
    # One-PR deprecation surface: these keep the pre-registry attribute
    # API alive (tests, serve_bench, smoke asserts) while the registry
    # is the single backing store.  Read the ``engine.*``/``spec.*``
    # metrics directly in new code.

    @property
    def steps(self) -> int:
        return int(self._c_steps.value)

    @property
    def prefills(self) -> int:
        return int(self._c_prefills.value)

    @property
    def chunk_steps(self) -> int:
        return int(self._c_chunk_steps.value)

    @property
    def preemptions(self) -> int:
        return int(self._c_preemptions.value)

    @property
    def spec_emitted(self) -> int:
        return int(self._c_spec_emitted.value)

    @property
    def spec_drafted(self) -> int:
        return int(self._c_spec_drafted.value)

    @property
    def spec_accepted(self) -> int:
        return int(self._c_spec_accepted.value)

    @property
    def spec_slot_verifies(self) -> int:
        return int(self._c_spec_verifies.value)

    @property
    def decode_times(self) -> "collections.deque[float]":
        return self._s_decode.values

    @property
    def chunk_durations(self) -> "collections.deque[float]":
        return self._s_chunk.values

    # -- async request/result API -------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request (thread-safe); admitted at the next step.
        Raises immediately (in the caller's thread) on requests that can
        never be served, so the background loop stays healthy."""
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"prompt {len(req.prompt)} exceeds max_len {self.max_len}")
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        if self.spec is not None and req.temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only (accept-longest-prefix "
                "against the target argmax; sampled verification needs "
                "rejection sampling) — submit temperature=0 requests or "
                "serve without spec=")
        if req.ttft_deadline_s is not None or req.deadline_s is not None:
            self._deadlines = True
        bound = self.resilience.max_pending
        with self._cv:
            shed = bound is not None and len(self._pending) >= bound
            if not shed:
                self._pending.append(_Pending(req=req,
                                              t_submit=time.perf_counter()))
                self._cv.notify()
        if shed:
            # load shedding: terminal Result NOW, in the caller's thread
            # — the explicit backpressure signal (see backpressure()).
            self._c_res_shed.inc()
            if self._tr.enabled:
                self._tr.event("shed", rid=req.rid,
                               step=self.steps + self.chunk_steps,
                               pending=bound)
            self._emit_terminal(req, t_submit=time.perf_counter(),
                               status="shed",
                               error=f"pending queue at bound {bound}")
            return
        if self._tr.enabled:
            self._tr.event("submit", rid=req.rid,
                           step=self.steps + self.chunk_steps,
                           prompt_len=len(req.prompt),
                           max_new=req.max_new_tokens)

    def cancel(self, rid: int) -> bool:
        """Request cancellation of ``rid`` (thread-safe, idempotent).

        Serviced at the start of the next engine step: a queued entry
        terminates immediately; a running slot tears down through the
        generalized preempt/finish machinery — exclusively-owned blocks
        freed, pending COW copies scrubbed, produced tokens
        prefix-registered — and the request gets a terminal
        ``status="cancelled"`` Result carrying the tokens produced so
        far.  Returns False when ``rid`` is not currently queued or
        running (already finished, or never submitted)."""
        with self._cv:
            known = any(e.req.rid == rid for e in self._pending)
            known = known or any(s is not None and s.req.rid == rid
                                 for s in self._slots)
            if not known:
                return False
            self._cancels.add(rid)
            self._cv.notify()
        return True

    def backpressure(self) -> bool:
        """Explicit load-shedding signal: True when the pending queue is
        at the ``ResilienceConfig.max_pending`` bound — callers should
        stop submitting (further submits return ``status="shed"``
        Results immediately).  Always False when no bound is set."""
        bound = self.resilience.max_pending
        if bound is None:
            return False
        with self._cv:
            return len(self._pending) >= bound

    def drain_results(self) -> list[Result]:
        """Every finished Result available right now (non-blocking)."""
        out: list[Result] = []
        while True:
            try:
                out.append(self._results.get_nowait())
            except _queue.Empty:
                return out

    def get_result(self, timeout: float | None = None) -> Result:
        """Blocks until the next finished request (completion order).
        Raises RuntimeError if the serve loop died instead of hanging —
        but drains already-finished results first."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        while True:
            step_timeout = (0.1 if deadline is None else
                            min(0.1, max(0.0, deadline - time.perf_counter())))
            try:
                return self._results.get(timeout=step_timeout)
            except _queue.Empty:
                if self._loop_error is not None:
                    raise RuntimeError(
                        "serve loop died") from self._loop_error
                if deadline is not None and time.perf_counter() >= deadline:
                    raise

    def start(self) -> None:
        """Pump admission + decode on a background thread."""
        if self._thread is not None:
            return
        self._stop = False
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="engine-serve", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _serve_loop(self) -> None:
        while True:
            with self._cv:
                if self._stop:
                    return
                idle = (not self._pending
                        and all(s is None for s in self._slots))
                if idle:
                    self._cv.wait(timeout=0.05)
                    continue
            try:
                self.step()
            except BaseException as e:  # noqa: BLE001 - surface via getters
                self._loop_error = e
                raise

    # -- scheduling-space wiring --------------------------------------------

    def _register_gemms(self, m_tokens: int, head_rows: int) -> None:
        """Resolve the step's dominant p-GEMMs through the schedule cache
        (memoized: only the first sight of a shape explores).  ``m_tokens``
        is the block-interior token count; ``head_rows`` the rows reaching
        the LM head (1 for a single-request prefill, ``slots`` for a
        decode step — the head sees one row per batched sequence)."""
        cfg = self.cfg
        prec = self._prec
        d = cfg.d_model
        shapes = [(m_tokens, cfg.n_heads * cfg.hd, d),
                  (m_tokens, cfg.n_kv_heads * cfg.hd, d),
                  (m_tokens, d, cfg.n_heads * cfg.hd)]
        if cfg.moe is not None:
            shapes.append((m_tokens, cfg.moe.d_ff_expert, d))
            shapes.append((m_tokens, d, cfg.moe.d_ff_expert))
        else:
            shapes.append((m_tokens, cfg.d_ff, d))
            shapes.append((m_tokens, d, cfg.d_ff))
        shapes.append((head_rows, cfg.vocab, d))
        for M, Nn, K in shapes:
            # attention-free archs legitimately zero out dims (mamba2:
            # d_ff == 0 — no MLP); a degenerate GEMM has no schedule and
            # crashes the §5 cost model (K == 0 -> zero reduction chunks),
            # so skip rather than resolve.  gta-lint Pass 1 flags any
            # degenerate shape that would reach the cache.
            if M <= 0 or Nn <= 0 or K <= 0:
                continue
            self.schedule.resolve(M, Nn, K, prec)
            if cfg.quant_serving:
                # quantized leaves dispatch through kernels.ops.
                # quant_matmul, which resolves under INT8 (the native PE
                # width); non-quantized leaves and the scale-folded head
                # stay on ``prec``.  Registering both here keeps the
                # steady-state 100%-cache-hit gate independent of which
                # leaves the policy actually rewrote.
                if prec != "INT8":
                    self.schedule.resolve(M, Nn, K, "INT8")
                chosen = self._gemm_precision(M, Nn, K)
                if chosen not in (prec, "INT8"):
                    self.schedule.resolve(M, Nn, K, chosen)

    def _gemm_precision(self, M: int, N: int, K: int) -> str:
        """§5 explorer (choose_precision) verdict for one serving p-GEMM,
        memoized per shape so the exploration cost is paid once at
        construction (``_register_gemms`` runs on every decode step)."""
        key = (M, N, K)
        name = self.precision_plan.get(key)
        if name is None:
            p = choose_precision(
                PGEMM("serve", M=M, N=N, K=K, precision=INT8))
            name = self.precision_plan[key] = p.name
        return name

    # -- policy views ---------------------------------------------------------

    def _pending_view(self, index: int, ent: _Pending, now: float,
                      evictable_hint: int | None = None) -> PendingView:
        remaining = ent.req.max_new_tokens - len(ent.resume_tokens)
        probe = (self.pool.probe([int(t) for t in ent.full_prompt],
                                 self._reserve_horizon(remaining),
                                 evictable_hint=evictable_hint)
                 if self.paged and self.policy.needs_probes else None)
        return PendingView(index=index, rid=ent.req.rid,
                           prompt_len=len(ent.full_prompt),
                           new_tokens=remaining,
                           priority=ent.req.priority,
                           ttft_slo=ent.req.ttft_slo,
                           waited_s=now - ent.t_submit,
                           resumed=bool(ent.resume_tokens),
                           preemptions=ent.preemptions, probe=probe)

    def _slot_view(self, index: int) -> SlotView | None:
        st = self._slots[index]
        if st is None:
            return None
        return SlotView(index=index, rid=st.req.rid, phase=st.phase,
                        priority=st.req.priority, produced=len(st.produced),
                        remaining=st.req.max_new_tokens - len(st.produced),
                        reclaimable_blocks=(
                            self.pool.reclaimable_blocks(index)
                            if self.paged else 0),
                        preemptions=st.preemptions,
                        has_slo=st.req.ttft_slo is not None)

    def avg_pool_util(self) -> float:
        """Mean fraction of usable pool blocks in use, one sample per
        engine step (0.0 on the dense path / before the first step)."""
        return (self._c_util_sum.value
                / max(self._c_util_samples.value, 1))

    # -- memory accounting ----------------------------------------------------

    def kv_bytes(self) -> dict[str, int]:
        """Attention-KV memory: ``allocated`` = bytes of the KV leaves
        (pool or dense stripes); ``peak`` = high-watermark of bytes holding
        live data (paged: peak used blocks x per-block bytes across all
        layers; dense: the whole stripe, it is committed up front)."""
        alloc = N.kv_cache_bytes(self.caches)
        if not self.paged:
            return {"allocated": alloc, "peak": alloc}
        per_block = alloc // self.pool.num_blocks
        return {"allocated": alloc,
                "peak": per_block * self.pool.peak_used}

    # -- admission -----------------------------------------------------------

    def _reserve_horizon(self, remaining_new: int) -> int:
        """Decode positions an admission reserves up front: the whole
        remaining budget normally (decode can never fail mid-flight), ONE
        position under speculative decoding (the verify loop extends and
        truncates the span per step — lazy reservation is what makes
        rollback return real blocks)."""
        return 1 if self.spec is not None else remaining_new

    def _free_slot(self) -> int | None:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _admit_one(self, slot: int, ent: _Pending) -> None:
        """Dense path: one-shot bucketed ragged prefill (batch=1).  The
        masked-update SSM scan makes this exact for hybrid archs too, so
        the old right-aligned fallback is gone."""
        req = ent.req
        assert not ent.resume_tokens, "dense path never preempts"
        plen = len(req.prompt)
        bucket = _bucket_for(plen, self.buckets)
        t0 = time.perf_counter()
        self._register_gemms(bucket, 1)

        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        pos0 = plen
        tok, self.caches, self.key = self._fns["admit_ragged"](
            self.params, jnp.asarray(toks), self.caches,
            jnp.asarray(slot, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray([plen - 1], jnp.int32), self.key,
            jnp.asarray(req.temperature, jnp.float32))
        self._pos[slot] = pos0
        self._c_prefills.inc()
        self._c_admissions.inc()

        tok0 = int(np.asarray(tok))
        t1 = time.perf_counter()
        st = _Slot(req=req, produced=[tok0], cur_tok=tok0,
                   t_submit=ent.t_submit, t_admit=t0, t_prefill_done=t1,
                   t_first=t1, ttft_steps=self.steps + self.chunk_steps)
        self._slots[slot] = st
        if self._tr.enabled:
            self._tr.event("admit", rid=req.rid, slot=slot,
                           step=st.ttft_steps, ts=t0, prompt_len=plen)
            self._tr.event("first_token", rid=req.rid, slot=slot,
                           step=st.ttft_steps, ts=t1)
        # pos0 == max_len means zero decode headroom: the next write would
        # clamp onto the last real token, so finish with the prefill token.
        if (st.cur_tok == req.eos
                or len(st.produced) >= req.max_new_tokens
                or pos0 >= self.max_len):
            self._finish(slot)

    def _admit_one_paged(self, slot: int, ent: _Pending) -> bool:
        """Paged path: reserve blocks (shared prefix mapped in, its
        prefill SKIPPED), queue the remaining prompt as chunks.  For a
        preempted entry the admission prompt is prompt + produced tokens
        — the resident part skip-prefills via the prefix cache, so
        preempted work is not recomputed.  Returns False on pool
        exhaustion — the request goes back to the queue.

        Speculative mode reserves LAZILY: only the prompt (plus one
        decode position) is reserved here, and each verify step extends
        the table one speculative span ahead (``KVPool.extend``) so that
        ``KVPool.truncate`` can genuinely return rejected-tail blocks —
        the non-spec engine keeps the reserve-everything-up-front
        guarantee unchanged."""
        req = ent.req
        remaining_new = req.max_new_tokens - len(ent.resume_tokens)
        tr = self._tr
        with (tr.span("pool_admit", rid=req.rid, slot=slot) if tr.enabled
              else NO_SPAN):
            plan = self.pool.admit(slot, [int(t) for t in ent.full_prompt],
                                   self._reserve_horizon(remaining_new))
            if plan is not None:
                t0 = time.perf_counter()
                self._bt = jnp.asarray(self.pool.tables)
        if plan is None:
            return False
        # fresh recurrent state + cursor at the resident prefix length
        self.caches = self._fns["reset_slot"](
            self.caches, jnp.asarray(slot, jnp.int32),
            jnp.asarray(plan.shared_tokens, jnp.int32))
        if self.spec is not None:
            self.spec.on_reset_slot(self, slot, plan.shared_tokens)
        self._pos[slot] = plan.shared_tokens
        rest = np.asarray(ent.full_prompt[plan.shared_tokens:], np.int32)
        L = self.prefill_chunk
        chunks = [rest[j:j + L] for j in range(0, len(rest), L)]
        self._slots[slot] = _Slot(
            req=req, produced=list(ent.resume_tokens), cur_tok=-1,
            t_submit=ent.t_submit, t_admit=t0, t_prefill_done=0.0,
            t_first=ent.t_first, phase="prefill", chunks=chunks,
            full_prompt=ent.full_prompt,
            resume_len=len(ent.resume_tokens),
            preemptions=ent.preemptions, ttft_steps=ent.ttft_steps,
            prefill_s_prev=ent.prefill_s)
        self._c_admissions.inc()
        resumed = bool(ent.resume_tokens)
        if resumed:
            self._c_resumes.inc()
        if self._tr.enabled:
            self._tr.event("resume" if resumed else "admit",
                           rid=req.rid, slot=slot,
                           step=self.steps + self.chunk_steps, ts=t0,
                           prompt_len=len(ent.full_prompt),
                           shared_tokens=plan.shared_tokens,
                           chunks=len(chunks))
        return True

    def _admit(self) -> None:
        """Admit what the policy picks while a slot is free; one
        ``admit_pass`` span when there is a queue to pick from."""
        tr = self._tr
        if tr.enabled and self._pending and None in self._slots:
            with tr.span("admit_pass"):
                self._admit_loop()
        else:
            self._admit_loop()

    def _admit_loop(self) -> None:
        while True:
            slot = self._free_slot()
            if slot is None:
                return
            with self._cv:
                if not self._pending:
                    return
                now = time.perf_counter()
                hint = (self.pool.evictable_cached()
                        if self.paged and self.policy.needs_probes else None)
                views = [self._pending_view(i, e, now, hint)
                         for i, e in enumerate(self._pending)]
                idx = self.policy.select_admission(views, now)
                if idx is None:
                    return                  # policy holds the whole queue
                ent = self._pending[idx]
                if ent.retry_at > self._ticks:
                    return                  # admission backoff in effect
                del self._pending[idx]
            if self.paged:
                if not self._admit_one_paged(slot, ent):
                    if self._admit_failed(ent):
                        continue            # terminally failed: next entry
                    with self._cv:          # backoff: retry later
                        self._pending.insert(idx, ent)
                    return
            else:
                self._admit_one(slot, ent)

    def _admit_failed(self, ent: _Pending) -> bool:
        """Bookkeeping for one failed (pool-denied) admission attempt.
        Default config keeps the legacy behavior: retry every step,
        forever.  With ``max_admit_retries`` set the request eventually
        fails terminally (True = do not re-queue); with
        ``admit_backoff_steps`` set retries space out exponentially."""
        res = self.resilience
        ent.admit_failures += 1
        self._c_res_admit_fail.inc()
        if (res.max_admit_retries is not None
                and ent.admit_failures > res.max_admit_retries):
            self._c_res_quarantined.inc()
            if self._tr.enabled:
                self._tr.event("quarantine", rid=ent.req.rid,
                               step=self.steps + self.chunk_steps,
                               error="admission retries exhausted",
                               attempts=ent.admit_failures)
            self._emit_terminal(
                ent.req, t_submit=ent.t_submit, status="failed",
                error=f"admission failed {ent.admit_failures}x "
                      f"(pool exhausted)",
                tokens=ent.resume_tokens, preemptions=ent.preemptions,
                ttft_steps=ent.ttft_steps, t_first=ent.t_first,
                prefill_s=ent.prefill_s)
            return True
        if res.admit_backoff_steps > 0:
            hold = res.admit_backoff_steps * (
                2 ** min(ent.admit_failures - 1, 6))
            ent.retry_at = self._ticks + hold
            self._c_res_retries.inc()
            if self._tr.enabled:
                self._tr.event("retry", rid=ent.req.rid,
                               step=self.steps + self.chunk_steps,
                               kind="admit", attempt=ent.admit_failures,
                               hold_steps=hold)
        return False

    # -- preempt-by-eviction --------------------------------------------------

    def _maybe_preempt(self) -> None:
        """Ask the policy for a victim (at most one per step) and evict
        it; re-run admission so the freed slot/blocks serve the at-risk
        request in the same step."""
        if not self.policy.preempts:
            return
        with self._cv:
            if not self._pending:
                return
            now = time.perf_counter()
            hint = (self.pool.evictable_cached()
                    if self.policy.needs_probes else None)
            pviews = [self._pending_view(i, e, now, hint)
                      for i, e in enumerate(self._pending)]
        sviews = [self._slot_view(i) for i in range(self.slots)]
        victim = self.policy.select_victim(pviews, sviews, now)
        if victim is None:
            return
        self._preempt(victim)
        self._admit()

    def _preempt(self, slot: int) -> None:
        """Evict a decoding slot: register its FULL sequence (prompt +
        produced tokens) so the resident KV blocks survive in the prefix
        cache (evictable under pressure, skip-prefilled on resume), drop
        the slot's refs, and re-queue the request with its produced
        tokens intact.  Greedy resume is token-identical: prefill writes
        the same KV decode would have, and the resumed prompt's last
        token is the victim's last produced token, whose logits seed the
        next decode step exactly where it left off."""
        st = self._slots[slot]
        assert st is not None and st.phase == "decode", (slot, st and
                                                         st.phase)
        full_seq = [int(t) for t in st.req.prompt] + [int(t)
                                                      for t in st.produced]
        # registration covers only FULL blocks among the resident
        # positions [0, pos) — the tail (incl. the not-yet-written last
        # produced token) is re-prefilled on resume.
        tr = self._tr
        with (tr.span("pool_release", rid=st.req.rid, slot=slot)
              if tr.enabled else NO_SPAN):
            self.pool.release_slot(slot, prompt=full_seq)
            self._bt = jnp.asarray(self.pool.tables)
        self._slots[slot] = None
        self._c_preemptions.inc()
        if self._tr.enabled:
            self._tr.event("preempt", rid=st.req.rid, slot=slot,
                           step=self.steps + self.chunk_steps,
                           produced=len(st.produced))
        ent = _Pending(
            req=st.req, t_submit=st.t_submit,
            full_prompt=np.asarray(full_seq, np.int32),
            resume_tokens=list(st.produced), t_first=st.t_first,
            ttft_steps=st.ttft_steps, preemptions=st.preemptions + 1,
            prefill_s=st.prefill_s_prev + (st.t_prefill_done - st.t_admit))
        with self._cv:
            # tail of the queue: the victim already holds its first
            # token, so at-risk TTFT requests go first (anti-thrash:
            # resumed entries never trigger further preemption).
            self._pending.append(ent)

    def _finish(self, slot: int) -> None:
        st = self._slots[slot]
        now = time.perf_counter()
        self._results.put(Result(
            rid=st.req.rid,
            tokens=np.asarray(st.produced, np.int32),
            prefill_s=st.prefill_s_prev + (st.t_prefill_done - st.t_admit),
            decode_s=now - st.t_prefill_done,
            latency_s=now - st.t_submit,
            ttft_s=st.t_first - st.t_submit,
            ttft_steps=max(st.ttft_steps, 0),
            preemptions=st.preemptions))
        self._c_finished.inc()
        self._c_tokens.inc(len(st.produced))
        self._h_ttft_steps.observe(max(st.ttft_steps, 0))
        self._h_ttft_s.observe(st.t_first - st.t_submit)
        self._h_latency.observe(now - st.t_submit)
        self._slots[slot] = None
        tr = self._tr
        if self.paged:
            # release refs; full prompt blocks (of the ADMISSION prompt —
            # original prompt + any resume tail) stay content-addressed
            # in the prefix cache until evicted, so an identical prompt
            # later skips their prefill entirely.
            with (tr.span("pool_release", rid=st.req.rid, slot=slot)
                  if tr.enabled else NO_SPAN):
                self.pool.release_slot(
                    slot, prompt=[int(t) for t in st.full_prompt])
                self._bt = jnp.asarray(self.pool.tables)
        if tr.enabled:
            # recorded after the release, so that a request's own events
            # end with its finish
            tr.event("finish", rid=st.req.rid, slot=slot,
                     step=self.steps + self.chunk_steps, ts=now,
                     tokens=len(st.produced), preemptions=st.preemptions)

    # -- resilience: lifecycle guards, step watchdog, warm restart ----------
    # (docs/RELIABILITY.md)

    def _note_fault(self, rec: dict) -> None:
        """FaultPlane.on_fire hook: count + trace every injection."""
        self._c_res_faults.inc()
        if self._tr.enabled:
            self._tr.event("fault_injected", rid=int(rec.get("rid", -1)),
                           step=self.steps + self.chunk_steps,
                           kind=rec.get("kind", "?"))

    def _emit_terminal(self, req: Request, *, t_submit: float, status: str,
                       error: str | None = None,
                       tokens: Sequence[int] = (), preemptions: int = 0,
                       ttft_steps: int = -1, t_first: float = 0.0,
                       prefill_s: float = 0.0) -> None:
        """Terminal Result for a request that never (re-)reached a slot:
        shed at submit, cancelled/timed out in the queue, or out of
        admission retries.  Any tokens from admissions before a
        preemption are still delivered."""
        now = time.perf_counter()
        self._results.put(Result(
            rid=req.rid, tokens=np.asarray(list(tokens), np.int32),
            prefill_s=prefill_s, decode_s=0.0,
            latency_s=now - t_submit,
            ttft_s=max(t_first - t_submit, 0.0) if t_first else 0.0,
            ttft_steps=max(ttft_steps, 0), preemptions=preemptions,
            status=status, error=error))
        self._c_finished.inc()
        self._c_tokens.inc(len(tokens))
        self._h_latency.observe(now - t_submit)

    def _finish_abnormal(self, slot: int, status: str,
                         error: str | None) -> None:
        """Terminal teardown of a RUNNING slot outside the happy path
        (cancel / timeout / quarantine): the same accounting as
        ``_finish`` with ``Result.status`` set, and the release
        generalized — a decode-phase slot prefix-registers its full
        sequence exactly like ``_preempt`` (the produced tokens' KV
        stays reusable), while a mid-prefill slot releases plainly (its
        tail blocks hold a partial prefill no other request may
        share)."""
        st = self._slots[slot]
        now = time.perf_counter()
        prefill_s = st.prefill_s_prev + (
            max(st.t_prefill_done - st.t_admit, 0.0)
            if st.t_prefill_done else 0.0)
        self._results.put(Result(
            rid=st.req.rid, tokens=np.asarray(st.produced, np.int32),
            prefill_s=prefill_s,
            decode_s=(now - st.t_prefill_done) if st.t_prefill_done
            else 0.0,
            latency_s=now - st.t_submit,
            ttft_s=max(st.t_first - st.t_submit, 0.0) if st.t_first
            else 0.0,
            ttft_steps=max(st.ttft_steps, 0), preemptions=st.preemptions,
            status=status, error=error))
        self._c_finished.inc()
        self._c_tokens.inc(len(st.produced))
        self._h_latency.observe(now - st.t_submit)
        self._slots[slot] = None
        tr = self._tr
        if self.paged:
            with (tr.span("pool_release", rid=st.req.rid, slot=slot)
                  if tr.enabled else NO_SPAN):
                if st.phase == "decode" and st.produced:
                    full_seq = ([int(t) for t in st.req.prompt]
                                + [int(t) for t in st.produced])
                    self.pool.release_slot(slot, prompt=full_seq)
                else:
                    self.pool.release_slot(slot)
                self._bt = jnp.asarray(self.pool.tables)
        if tr.enabled:
            tr.event("finish", rid=st.req.rid, slot=slot,
                     step=self.steps + self.chunk_steps, ts=now,
                     tokens=len(st.produced), status=status)

    def _service_guards(self) -> None:
        """Start-of-step lifecycle sweep: cancellations first, then hard
        deadlines — queue entries, then running slots.  Skipped entirely
        (one tuple check in ``step``) when no cancel is queued and no
        live request carries a deadline."""
        with self._cv:
            cancels, self._cancels = self._cancels, set()
        now = time.perf_counter()

        def verdict(req: Request, t_submit: float,
                    ttft_steps: int) -> tuple[str, str | None] | None:
            if req.rid in cancels:
                return "cancelled", None
            if (req.deadline_s is not None
                    and now - t_submit > req.deadline_s):
                return "timeout", "deadline_s exceeded"
            if (req.ttft_deadline_s is not None and ttft_steps < 0
                    and now - t_submit > req.ttft_deadline_s):
                return "timeout", "ttft_deadline_s exceeded"
            return None

        drop: list[tuple[_Pending, str, str | None]] = []
        with self._cv:
            keep: "collections.deque[_Pending]" = collections.deque()
            for ent in self._pending:
                v = verdict(ent.req, ent.t_submit, ent.ttft_steps)
                if v is None:
                    keep.append(ent)
                else:
                    drop.append((ent, *v))
            self._pending = keep
        for ent, status, error in drop:
            self._note_guard(status, ent.req.rid, -1)
            self._emit_terminal(ent.req, t_submit=ent.t_submit,
                                status=status, error=error,
                                tokens=ent.resume_tokens,
                                preemptions=ent.preemptions,
                                ttft_steps=ent.ttft_steps,
                                t_first=ent.t_first,
                                prefill_s=ent.prefill_s)
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            v = verdict(st.req, st.t_submit, st.ttft_steps)
            if v is not None:
                self._note_guard(v[0], st.req.rid, i)
                self._finish_abnormal(i, *v)

    def _note_guard(self, status: str, rid: int, slot: int) -> None:
        if status == "cancelled":
            self._c_res_cancelled.inc()
        else:
            self._c_res_timeouts.inc()
        if self._tr.enabled:
            self._tr.event("cancel" if status == "cancelled"
                           else "timeout", rid=rid, slot=slot,
                           step=self.steps + self.chunk_steps)

    def _quarantine(self, slot: int, exc: BaseException) -> None:
        """Fail ONE running request because its dispatch keeps raising;
        the engine stays alive for everyone else."""
        st = self._slots[slot]
        self._c_res_quarantined.inc()
        if self._tr.enabled:
            self._tr.event("quarantine", rid=st.req.rid, slot=slot,
                           step=self.steps + self.chunk_steps,
                           error=classify_error(exc))
        self._finish_abnormal(slot, "failed", classify_error(exc))

    def _dispatch_guarded(self, kind: str, slots: list[int],
                          fn) -> bool:
        """Run one dispatch under the step watchdog.

        Injection seams fire BEFORE the dispatch (no host state has
        mutated), so a retry is a pure re-run of the same engine step.
        A rid-targeted (poison) fault quarantines that request; an
        untargeted failure — injected or genuine — retries up to
        ``ResilienceConfig.dispatch_retries`` consecutive times, then
        the participating batch is quarantined (fail the requests, keep
        the engine).  :class:`EngineCrash` (the warm-restart drill) and
        :class:`PoolAuditError` (a real invariant break — never mask
        it) always propagate.  Returns True when the dispatch ran."""
        if self.faults is not None:
            try:
                self.faults.before_dispatch(
                    kind, self.steps + self.chunk_steps,
                    [self._slots[i].req.rid for i in slots])
            except InjectedFault as e:
                self._on_dispatch_error(kind, slots, e)
                return False
        try:
            fn(slots)
        except (EngineCrash, PoolAuditError, KeyboardInterrupt):
            raise
        except Exception as e:  # noqa: BLE001 — watchdog isolates the step
            self._on_dispatch_error(kind, slots, e)
            return False
        self._fail_streak.pop(kind, None)
        return True

    def _on_dispatch_error(self, kind: str, slots: list[int],
                           exc: BaseException) -> None:
        self.last_dispatch_error = exc
        rid = int(getattr(exc, "rid", -1))
        target = next((i for i in slots
                       if self._slots[i] is not None
                       and self._slots[i].req.rid == rid), None)
        if target is not None:
            # poison: exactly one request is at fault — drop it, let the
            # rest of the batch re-run next step
            self._resync_slots()
            self._quarantine(target, exc)
            self._fail_streak.pop(kind, None)
            return
        streak = self._fail_streak.get(kind, 0) + 1
        if streak > self.resilience.dispatch_retries:
            self._fail_streak.pop(kind, None)
            self._resync_slots()
            for i in list(slots):
                if self._slots[i] is not None:
                    self._quarantine(i, exc)
            return
        self._fail_streak[kind] = streak
        self._c_res_retries.inc()
        if self._tr.enabled:
            self._tr.event("retry", step=self.steps + self.chunk_steps,
                           kind=kind, attempt=streak,
                           error=classify_error(exc))
        self._resync_slots()

    def _resync_slots(self) -> None:
        """Restore cursor/pool agreement with ``self._pos`` after an
        interrupted dispatch.  The target caches are safe by
        construction — ``self.caches`` is only reassigned when a
        dispatch returns — but a spec step may have grown block tables
        (lazy extend) and advanced the draft provider's cursors
        (propose) before failing; roll both back to the authoritative
        host positions.  Non-spec slots reserve their whole span at
        admission, so there is nothing to undo."""
        if self.spec is None:
            return
        self.spec.on_rollback(self, self._pos)
        if self.paged:
            for i, st in enumerate(self._slots):
                if st is not None and st.phase == "decode":
                    self.pool.truncate(i, int(self._pos[i]))

    def _note_spec_pressure(self, pressure: bool) -> None:
        """Adaptive spec_k degradation (opt-in:
        ``ResilienceConfig.spec_degrade``): halve the live speculation
        depth when the pool denied an extend this step — shorter spans
        stop thrashing the allocator — and recover one step of depth
        after ``spec_recover_steps`` clean steps.  Output is unaffected:
        greedy accept-longest-prefix is depth-independent, and the
        verify dispatch keeps its fixed (slots, spec_k + 1) shape."""
        if not self.resilience.spec_degrade:
            return
        if pressure:
            self._spec_clean_steps = 0
            if self._spec_k_live > 1:
                self._spec_k_live = max(1, self._spec_k_live // 2)
                self._c_res_degrades.inc()
                if self._tr.enabled:
                    self._tr.event("degrade",
                                   step=self.steps + self.chunk_steps,
                                   spec_k=self._spec_k_live)
        else:
            self._spec_clean_steps += 1
            if (self._spec_k_live < self.spec_k
                    and self._spec_clean_steps
                    >= self.resilience.spec_recover_steps):
                self._spec_clean_steps = 0
                self._spec_k_live += 1
                if self._tr.enabled:
                    self._tr.event("degrade",
                                   step=self.steps + self.chunk_steps,
                                   spec_k=self._spec_k_live)

    def snapshot(self) -> dict:
        """Host-side warm-restart snapshot: every queued and in-flight
        request with its produced-token log, plus the pool's serialized
        state (``KVPool.snapshot_state``) for offline debugging.

        Device KV is deliberately NOT captured: :meth:`restore`
        re-admits each request through the prefix-cache skip-prefill
        path on a fresh engine, which reconstructs exactly the KV an
        uncrashed run holds (prefill writes the same KV decode would
        have, position for position) — so greedy outputs are
        token-identical across the crash.  Gated in
        ``tests/test_chaos.py`` and serve_bench's ``paged_chaos`` row."""

        def req_d(req: Request) -> dict:
            return {"rid": req.rid,
                    "prompt": [int(t) for t in req.prompt],
                    "max_new_tokens": req.max_new_tokens,
                    "temperature": req.temperature, "eos": req.eos,
                    "ttft_slo": req.ttft_slo, "priority": req.priority,
                    "ttft_deadline_s": req.ttft_deadline_s,
                    "deadline_s": req.deadline_s}

        entries = []
        for st in self._slots:
            if st is None:
                continue
            entries.append({
                "req": req_d(st.req),
                "full_prompt": [int(t) for t in st.full_prompt],
                "produced": [int(t) for t in st.produced],
                "phase": st.phase,
                "resume_len": st.resume_len,
                "preemptions": st.preemptions,
                "ttft_steps": st.ttft_steps})
        with self._cv:
            entries += [{
                "req": req_d(e.req),
                "full_prompt": [int(t) for t in e.full_prompt],
                "produced": [int(t) for t in e.resume_tokens],
                "phase": "queued",
                "resume_len": len(e.resume_tokens),
                "preemptions": e.preemptions,
                "ttft_steps": e.ttft_steps} for e in self._pending]
        return {"version": 1, "in_flight": entries,
                "pool": self.pool.snapshot_state() if self.paged
                else None}

    def restore(self, snap: dict) -> int:
        """Re-admit a crashed engine's :meth:`snapshot` on THIS (fresh,
        same cfg/params) engine.  A decode-phase entry re-queues with
        prompt + produced as its admission prompt — exactly the
        ``_preempt`` shape, so re-admission skip-prefills whatever KV
        survived in the restarted pool's prefix cache and re-prefills
        the rest; greedy decode then continues token-identically.
        Mid-prefill and queued entries restart from their recorded
        admission prompt.  Wall-clock deadlines restart from restore
        time (deadline budgets are per-process).  Returns the number of
        entries re-admitted."""
        if self._pending or any(s is not None for s in self._slots):
            raise RuntimeError("restore() requires a fresh engine")
        now = time.perf_counter()
        n = 0
        for d in snap["in_flight"]:
            r = d["req"]
            req = Request(
                rid=int(r["rid"]),
                prompt=np.asarray(r["prompt"], np.int32),
                max_new_tokens=int(r["max_new_tokens"]),
                temperature=float(r["temperature"]), eos=int(r["eos"]),
                ttft_slo=r["ttft_slo"], priority=int(r["priority"]),
                ttft_deadline_s=r["ttft_deadline_s"],
                deadline_s=r["deadline_s"])
            produced = [int(t) for t in d["produced"]]
            if d["phase"] == "decode" and produced:
                full = np.asarray([int(t) for t in r["prompt"]]
                                  + produced, np.int32)
            else:
                # queued / mid-prefill: produced == the entry's resume
                # tokens, already inside its recorded admission prompt
                full = np.asarray(d["full_prompt"], np.int32)
            if req.ttft_deadline_s is not None or req.deadline_s is not None:
                self._deadlines = True
            ent = _Pending(req=req, t_submit=now, full_prompt=full,
                           resume_tokens=produced,
                           ttft_steps=int(d["ttft_steps"]),
                           preemptions=int(d["preemptions"]))
            with self._cv:
                self._pending.append(ent)
                self._cv.notify()
            n += 1
        self._c_res_restores.inc(n)
        if self._tr.enabled:
            self._tr.event("restore", step=self.steps + self.chunk_steps,
                           entries=n)
        return n

    # -- the decode step ------------------------------------------------------

    def _apply_cow(self) -> None:
        """Execute any pending copy-on-write forks on the device pool and
        refresh the device block-table mirror."""
        copies = self.pool.take_copies()
        if copies:
            src = jnp.asarray([c[0] for c in copies], jnp.int32)
            dst = jnp.asarray([c[1] for c in copies], jnp.int32)
            self.caches = self._fns["copy_blocks"](self.caches, src, dst)
            if self.spec is not None:
                self.spec.on_apply_cow(self, src, dst)
            self._bt = jnp.asarray(self.pool.tables)

    def _prefill_chunk_step(self, pre: list[int]) -> None:
        """One decode-interleaved chunk for EVERY admitting slot (batched
        admission): a single jitted call advances them all; rows not mid-
        prefill ride along masked (len 0 — recurrent state untouched,
        stray writes land beyond their validity bound or in the trash
        block).  Traced, the ``chunk_batch`` span holds the dispatch's
        ``prep``/``launch``/``sync``; ``retire`` follows it."""
        tr = self._tr
        # the step index the chunk_batch event carries once it has run
        with (tr.span("chunk_batch", step=self.steps + self.chunk_steps + 1,
                      rows=len(pre)) if tr.enabled else NO_SPAN):
            with (tr.span("prep") if tr.enabled else NO_SPAN):
                L = self.prefill_chunk
                toks = np.zeros((self.slots, L), np.int32)
                lens = np.zeros(self.slots, np.int32)
                temps = np.zeros(self.slots, np.float32)
                for i in pre:
                    st = self._slots[i]
                    # peek, don't pop: the chunk is consumed only after
                    # the dispatch returns, so a watchdog-retried step
                    # re-runs it
                    chunk = st.chunks[0]
                    toks[i, :len(chunk)] = chunk
                    lens[i] = len(chunk)
                    temps[i] = st.req.temperature
                    self.pool.ensure_writable(i, int(self._pos[i]),
                                              int(self._pos[i]) + L - 1)
                self._apply_cow()
                self._register_gemms(self.slots * L, self.slots)
                last_idx = np.maximum(lens - 1, 0)

            t0 = time.perf_counter()
            with (tr.span("launch") if tr.enabled else NO_SPAN):
                tok, self.caches, self.key = self._fns["prefill_chunk"](
                    self.params, jnp.asarray(toks), self.caches,
                    self._slot_ids, self._bt, jnp.asarray(lens),
                    jnp.asarray(last_idx), self.key,
                    jnp.asarray(temps))
                if self.spec is not None:
                    # the draft model prefills the SAME chunk through the
                    # same tables, so its KV stays position-for-position
                    # resident with the target's (shared prefixes
                    # included — both models wrote the cached blocks when
                    # they were first prefilled).
                    self.spec.on_prefill_chunk(self, toks, lens, last_idx)
            self._c_chunk_steps.inc()
            if any(s is not None and s.phase == "decode"
                   for s in self._slots):
                self._chunks_since_decode += 1
                self.max_chunk_gap = max(self.max_chunk_gap,
                                         self._chunks_since_decode)
            with (tr.span("sync") if tr.enabled else NO_SPAN):
                tok_np = np.asarray(tok)
            now = time.perf_counter()
            self._s_chunk.append(now - t0)
            if tr.enabled:
                step = self.steps + self.chunk_steps
                for i in pre:
                    tr.event("prefill_chunk", rid=self._slots[i].req.rid,
                             slot=i, step=step, ts=t0, dur=now - t0,
                             tokens=int(lens[i]))
        with (tr.span("retire") if tr.enabled else NO_SPAN):
            for i in pre:
                st = self._slots[i]
                st.chunks.pop(0)
                self._pos[i] += int(lens[i])
                if st.chunks:
                    continue                   # more chunks next step
                self._c_prefills.inc()
                st.phase = "decode"
                st.t_prefill_done = now
                if st.t_first == 0.0:          # resumed slots keep theirs
                    st.t_first = now
                if st.ttft_steps < 0:
                    st.ttft_steps = self.steps + self.chunk_steps
                    if tr.enabled:
                        tr.event("first_token", rid=st.req.rid, slot=i,
                                 step=st.ttft_steps, ts=now)
                # prompt KV is now fully resident: content-address its
                # full blocks so even a CONCURRENT identical prompt shares
                # them (release re-registers, which is a no-op).
                with (tr.span("pool_register", rid=st.req.rid, slot=i)
                      if tr.enabled else NO_SPAN):
                    n = int(self.pool.n_slot_blocks[i])
                    self.pool.register_prefix(
                        [int(t) for t in st.full_prompt],
                        [int(b) for b in self.pool.tables[i, :n]])
                tok0 = int(tok_np[i])
                st.produced.append(tok0)
                st.cur_tok = tok0
                if (tok0 == st.req.eos
                        or len(st.produced) >= st.req.max_new_tokens
                        or self._pos[i] >= self.max_len):
                    self._finish(i)

    def _end_step(self) -> int:
        """Common step epilogue: pool-utilization sample + optional
        consistency audit; returns the active-slot count.  An audit
        failure raises :class:`~repro.serving.kv_pool.PoolAuditError`
        carrying the serialized pool state plus the slot states below —
        the same reproducer format ``analysis.pool_model``
        counterexamples use, so runtime failures replay offline."""
        active = sum(s is not None for s in self._slots)
        if self.paged:
            util = self.pool.used_blocks / (self.pool.num_blocks - 1)
            self._c_util_sum.inc(util)
            self._c_util_samples.inc()
            self._g_pool_util.set(util)
            if self._audit:
                self.pool.check(pending_op=self._audit_context())
        self._g_occupancy.set(active)
        if self._tr.enabled:
            step = self.steps + self.chunk_steps
            if self.paged:
                self._tr.counter("pool_util", util, step=step)
            self._tr.counter("batch_occupancy", active, step=step)
            self._tr.counter("pending_queue", len(self._pending), step=step)
        return active

    def _audit_context(self) -> dict:
        """Engine-side half of a :class:`PoolAuditError` reproducer:
        which requests occupy which slots, and where each one is."""
        slots = []
        for i, st in enumerate(self._slots):
            if st is None:
                slots.append(None)
            else:
                slots.append({"rid": st.req.rid, "phase": st.phase,
                              "pos": int(self._pos[i]),
                              "produced": len(st.produced),
                              "pool_blocks": int(self.pool.n_slot_blocks[i])
                              if self.paged else 0})
        return {"op": "end_step", "spec": self.spec is not None,
                "slots": slots}

    def step(self) -> int:
        """Admit what the policy picks, preempt if it names a victim, run
        at most one prefill-chunk batch (paged) and ONE batched decode
        step over the decoding slots, then finish/refill.  Returns the
        number of active slots after the step (0 = idle).

        Every jitted dispatch runs under the step watchdog
        (``_dispatch_guarded``): a failing dispatch never kills the
        engine — it is retried next step or its requests are
        quarantined — except :class:`EngineCrash` (warm-restart drill)
        and :class:`PoolAuditError`, which always propagate.

        Traced, a call that starts with a request queued or in a slot is
        one ``step`` span (the root of the spans inside it); idle calls
        record nothing."""
        self._ticks += 1
        tr = self._tr
        if tr.enabled:
            tr.tick = self._ticks
            if self._pending or any(s is not None for s in self._slots):
                with tr.span("step"):
                    return self._step()
        return self._step()

    def _step(self) -> int:
        if self._cancels or self._deadlines:
            self._service_guards()
        self._admit()
        if self.paged:
            self._maybe_preempt()
            pre = [i for i, s in enumerate(self._slots)
                   if s is not None and s.phase == "prefill"]
            if pre and not self._dispatch_guarded(
                    "chunk", pre, self._prefill_chunk_step):
                return self._end_step()     # failed batch retries next step
        active = [i for i, s in enumerate(self._slots)
                  if s is not None and s.phase == "decode"]
        if not active:
            return self._end_step()
        if self.spec is not None:
            self._dispatch_guarded("verify", active, self._spec_step)
        else:
            self._dispatch_guarded("decode", active, self._decode_step)
        self._admit()
        return self._end_step()

    def _decode_step(self, active: list[int]) -> None:
        """ONE batched single-token decode dispatch over ``active``.
        Traced, the ``decode`` span (its rows' request ids and attended
        lengths as ``rids``/``ctx``) holds ``prep``/``launch``/``sync``;
        its end is when the step's tokens reach the host.  ``retire``
        follows it."""
        tr = self._tr
        # the step index the decode event carries once it has run
        with (tr.span("decode", step=self.steps + self.chunk_steps + 1,
                      rows=len(active),
                      rids=[self._slots[i].req.rid for i in active],
                      ctx=[int(self._pos[i]) + 1 for i in active])
              if tr.enabled else NO_SPAN):
            with (tr.span("prep") if tr.enabled else NO_SPAN):
                self._register_gemms(self.slots, self.slots)
                toks = np.zeros((self.slots, 1), np.int32)
                temps = np.zeros(self.slots, np.float32)
                adv = np.zeros(self.slots, np.int32)
                for i in active:
                    toks[i, 0] = self._slots[i].cur_tok
                    temps[i] = self._slots[i].req.temperature
                    adv[i] = 1
                if self.paged:
                    for i in active:
                        self.pool.ensure_writable(i, int(self._pos[i]),
                                                  int(self._pos[i]))
                    self._apply_cow()

            if self.paged:
                with (tr.span("launch") if tr.enabled else NO_SPAN):
                    tok, self.caches, self.key = self._fns[
                        "decode_sample_paged"](
                        self.params, jnp.asarray(toks), self.caches,
                        jnp.asarray(self._pos), self._bt, jnp.asarray(adv),
                        self.key, jnp.asarray(temps))
                # the dispatch above IS the application of the gather
                # GEMMs; record it now so the applied log mirrors real
                # decode steps.
                PA.note_gather_applied(self.schedule, self.cfg,
                                       self.pool.block_size, self._prec)
                self._pos += adv        # only decoding slots advanced
                self._chunks_since_decode = 0
            else:
                with (tr.span("launch") if tr.enabled else NO_SPAN):
                    tok, self.caches, self.key = self._fns["decode_sample"](
                        self.params, jnp.asarray(toks), self.caches,
                        jnp.asarray(self._pos), self.key,
                        jnp.asarray(temps))
                # every slot's cache pos advanced by 1 (inactive slots
                # write masked garbage in place); mirror it so the next
                # step agrees.
                self._pos += 1
            self._c_steps.inc()
            self._s_decode.append(time.perf_counter())

            with (tr.span("sync") if tr.enabled else NO_SPAN):
                tok_np = np.asarray(tok)
        with (tr.span("retire") if tr.enabled else NO_SPAN):
            for i in active:
                st = self._slots[i]
                st.produced.append(int(tok_np[i]))
                st.cur_tok = int(tok_np[i])
                if (st.cur_tok == st.req.eos
                        or len(st.produced) >= st.req.max_new_tokens
                        or self._pos[i] >= self.max_len):
                    self._finish(i)

    # -- the speculative verify step ------------------------------------------

    def _spec_step(self, active: list[int]) -> None:
        """One DRAFT/VERIFY round over the decoding slots.

        Per slot: extend the block table one speculative span ahead
        (lazy reservation), COW-fork anything the span writes would
        touch, let the draft provider propose up to k tokens, then score
        ``[cur_tok, draft_1..draft_k]`` for every slot in ONE jitted
        ``verify_chunk`` dispatch (fixed (slots, k+1) shape — rows with
        shorter or no drafts ride along masked).  The host accepts the
        longest draft prefix matching the target's own argmax — between
        1 and k+1 tokens emitted per dispatch, token-identical to
        vanilla greedy decode by construction — and rolls the rejected
        tail back: cache cursors via ``set_pos``, pool blocks via
        ``KVPool.truncate``.  A slot whose span cannot be hosted even at
        k = 0 is preempted (re-queued with produced tokens; the freed
        blocks guarantee its lone re-admission succeeds).

        Traced: ``prep`` (reservation, drafts, inputs), then the
        ``verify`` span holding ``launch``/``sync``, then ``retire``."""
        L = self.spec_k + 1
        tr = self._tr
        with (tr.span("prep") if tr.enabled else NO_SPAN):
            ks: dict[int, int] = {}
            run: list[int] = []
            grew = False
            pressure = False
            for i in active:
                st = self._slots[i]
                remaining = st.req.max_new_tokens - len(st.produced)
                headroom = self.max_len - int(self._pos[i]) - 1
                # _spec_k_live <= spec_k: the adaptive-degradation cap
                # (_note_spec_pressure); the verify SHAPE stays spec_k + 1
                k_i = max(0, min(self._spec_k_live, remaining - 1, headroom))
                nblk = int(self.pool.n_slot_blocks[i])
                while not self.pool.extend(i, int(self._pos[i]) + k_i + 1):
                    pressure = True
                    if k_i == 0:
                        k_i = -1
                        break
                    k_i = 0
                if k_i < 0:
                    self._preempt(i)
                    continue
                grew |= int(self.pool.n_slot_blocks[i]) != nblk
                ks[i] = k_i
                run.append(i)
            # note pressure BEFORE the empty-run early-return: a step whose
            # every denied slot got preempted is maximal pressure, not none
            self._note_spec_pressure(pressure)
            if not run:
                return
            # writable span BEFORE the draft runs: tables are shared, so the
            # draft's speculative writes must land in forked blocks too.
            for i in run:
                self.pool.ensure_writable(i, int(self._pos[i]),
                                          int(self._pos[i]) + ks[i])
            self._apply_cow()
            if grew:
                # only re-upload the table mirror when extend actually grew a
                # row (most steps speculate within the blocks already mapped;
                # stale trailing entries from last step's truncate sit beyond
                # the validity bound, so reads through them are masked).
                self._bt = jnp.asarray(self.pool.tables)
            drafts = self.spec.propose(self, run, ks)
            if self.faults is not None:
                # draft-corruption seam: garbage drafts cost speculation
                # efficiency only — verify rejects them, output is unchanged
                drafts = {i: self.faults.corrupt_drafts(
                    self.steps + self.chunk_steps, d, self.cfg.vocab)
                    for i, d in drafts.items()}

            toks = np.zeros((self.slots, L), np.int32)
            lens = np.zeros(self.slots, np.int32)
            for i in run:
                d = [int(t) for t in drafts.get(i, [])][:ks[i]]
                drafts[i] = d
                toks[i, 0] = self._slots[i].cur_tok
                toks[i, 1:1 + len(d)] = d
                lens[i] = len(d) + 1
            self._register_gemms(self.slots * L, self.slots * L)
        # the step index the verify event carries once it has run
        with (tr.span("verify", step=self.steps + self.chunk_steps + 1,
                      rows=len(run)) if tr.enabled else NO_SPAN):
            with (tr.span("launch") if tr.enabled else NO_SPAN):
                tok, self.caches = self._fns["verify_chunk"](
                    self.params, jnp.asarray(toks), self.caches,
                    self._slot_ids, self._bt, jnp.asarray(lens))
            self._c_steps.inc()
            self._s_decode.append(time.perf_counter())
            self._chunks_since_decode = 0
            with (tr.span("sync") if tr.enabled else NO_SPAN):
                tok_np = np.asarray(tok)
        with (tr.span("retire") if tr.enabled else NO_SPAN):
            rejected = False
            for i in run:
                st = self._slots[i]
                d = drafts[i]
                emit: list[int] = []
                j = 0
                while True:
                    # emitting tok[j] is valid iff inputs 0..j were correct:
                    # input 0 is cur_tok (always), input j+1 is draft j —
                    # checked before advancing.  Budget/EOS stop emission.
                    t = int(tok_np[i, j])
                    emit.append(t)
                    if (t == st.req.eos or len(st.produced) + len(emit)
                            >= st.req.max_new_tokens):
                        break
                    if j < len(d) and d[j] == t:
                        j += 1
                        continue
                    break
                st.produced.extend(emit)
                st.cur_tok = emit[-1]
                self._pos[i] += len(emit)
                rejected |= len(emit) < int(lens[i])
                self._c_spec_emitted.inc(len(emit))
                self._c_spec_drafted.inc(len(d))
                self._c_spec_accepted.inc(len(emit) - 1)
                self._c_spec_verifies.inc()
            # KV rollback: cursors back to the accepted lengths, rejected
            # tail blocks back to the pool (ref-respecting truncate).  Full
            # acceptance everywhere means the cursors already sit at the
            # accepted lengths (verify advanced them by exactly ``lens``), so
            # the reset dispatches are skipped on that hot path.
            if rejected:
                self.caches = self._fns["set_pos"](self.caches,
                                                   jnp.asarray(self._pos))
                self.spec.on_rollback(self, self._pos)
            for i in run:
                self.pool.truncate(i, int(self._pos[i]))
                st = self._slots[i]
                if (st.cur_tok == st.req.eos
                        or len(st.produced) >= st.req.max_new_tokens
                        or self._pos[i] >= self.max_len):
                    self._finish(i)

    def avg_accept_len(self) -> float:
        """Mean tokens a slot emits per verify it takes part in (1.0 =
        nothing ever accepted, spec_k + 1 = every draft always accepted)
        — the deterministic speculation metric serve_bench gates on."""
        return self.spec_emitted / max(self.spec_slot_verifies, 1)

    def spec_stats(self) -> dict[str, Any]:
        """Speculation telemetry (zeros when spec is off)."""
        return {
            "provider": self.spec.name if self.spec else None,
            "k": self.spec_k if self.spec else 0,
            # steps counts ONLY verify dispatches in spec mode; without
            # spec it counts vanilla decode dispatches, which are not
            # verify steps — keep the zeros-when-off contract honest
            "verify_steps": self.steps if self.spec else 0,
            "tokens_emitted": self.spec_emitted,
            "drafted": self.spec_drafted,
            "accepted": self.spec_accepted,
            "avg_accept_len": round(self.avg_accept_len(), 4),
            "draft_steps": getattr(self.spec, "steps", 0),
            "draft_chunk_steps": getattr(self.spec, "chunk_steps", 0),
        }

    # -- synchronous convenience ----------------------------------------------

    def run(self, requests: Sequence[Request]) -> list[Result]:
        """Serve all requests; returns results in COMPLETION order (rid
        identifies the request — short requests admitted late legitimately
        finish before long early ones).  Mutually exclusive with the
        background loop: engine state is single-pumper."""
        if self._thread is not None:
            raise RuntimeError(
                "run() while the background serve loop is active; use "
                "submit()/get_result() instead (or stop() first)")
        for r in requests:
            self.submit(r)
        out: list[Result] = []
        while len(out) < len(requests):
            self.step()
            while True:
                try:
                    out.append(self._results.get_nowait())
                except _queue.Empty:
                    break
        return out


class WaveEngine:
    """Seed wave-level engine (kept as the benchmark baseline): each wave
    fills all slots, prefills once (right-padded prompts share one jitted
    prefill), then decodes in lockstep until the whole wave drains."""

    def __init__(self, cfg: ModelConfig, params: PyTree, *, slots: int = 8,
                 max_len: int = 2048, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.steps = 0
        fns = _engine_fns(cfg, max_len)
        self._prefill, self._decode = fns["prefill"], fns["decode"]

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> jax.Array:
        tok, self.key = _sample_traced(self.key, logits,
                                       jnp.asarray(temps, jnp.float32))
        return tok

    def run(self, requests: Sequence[Request]) -> list[Result]:
        """Serve all requests in waves of ``slots``."""
        out: list[Result] = []
        queue = list(requests)
        t_start = time.perf_counter()
        while queue:
            wave, queue = queue[:self.slots], queue[self.slots:]
            out.extend(self._run_wave(wave, t_start))
        return out

    def _run_wave(self, wave: Sequence[Request], t_start: float
                  ) -> list[Result]:
        B = len(wave)
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(wave):   # right-align so last token is real
            toks[i, plen - len(r.prompt):] = r.prompt
        temps = np.array([r.temperature for r in wave], np.float32)
        max_new = max(r.max_new_tokens for r in wave)

        caches = N.init_caches(self.cfg, B, self.max_len)
        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params,
                                       {"tokens": jnp.asarray(toks)}, caches)
        jax.block_until_ready(logits)
        t1 = time.perf_counter()

        done = np.zeros(B, bool)
        produced: list[list[int]] = [[] for _ in range(B)]
        tok = self._sample(logits, temps)
        for step in range(max_new):
            tok_np = np.asarray(tok)
            for i, r in enumerate(wave):
                if not done[i]:
                    produced[i].append(int(tok_np[i]))
                    if (tok_np[i] == r.eos
                            or len(produced[i]) >= r.max_new_tokens):
                        done[i] = True
            if done.all():
                break
            if plen + step >= self.max_len:
                # KV window exhausted: a further write would clamp onto the
                # last row and corrupt attention — truncate the wave.
                break
            pos = jnp.asarray(plen + step, jnp.int32)
            logits, caches = self._decode(self.params,
                                          tok[:, None].astype(jnp.int32),
                                          caches, pos)
            self.steps += 1
            tok = self._sample(logits, temps)
        jax.block_until_ready(logits)
        t2 = time.perf_counter()

        return [Result(r.rid, np.asarray(produced[i], np.int32),
                       t1 - t0, t2 - t1, latency_s=t2 - t_start,
                       ttft_s=t1 - t_start)
                for i, r in enumerate(wave)]


#: default engine: slot-level continuous batching
Engine = ContinuousEngine
