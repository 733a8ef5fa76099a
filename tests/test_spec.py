"""Speculative decoding: draft providers, the multi-token verify step,
and KV rollback.

The acceptance surface: greedy spec output (both providers, k in {2, 4})
is TOKEN-IDENTICAL to vanilla paged decode — and to the full-recompute
reference — with ``audit=True`` (``pool.check()`` after every step,
rollback steps included) and measurably fewer engine decode dispatches;
hybrids and sampled requests are rejected with clear errors."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs as CONFIGS
from repro.models import network as N
from repro.serving.engine import ContinuousEngine, Request
from repro.serving.spec import ModelDraft, NgramDraft, make_provider

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    params = N.init(cfg, KEY)
    return cfg, params


def _reqs(vocab, n=3, seed=7, max_new=12):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(3, vocab, 8 + 5 * i
                                        ).astype(np.int32),
                    max_new_tokens=max_new, eos=-1) for i in range(n)]


def _greedy_reference(params, cfg, req):
    seq = [int(t) for t in req.prompt]
    want = []
    for _ in range(req.max_new_tokens):
        logits, _ = N.forward(params, cfg, {"tokens": jnp.asarray(seq)[None]})
        nxt = int(jnp.argmax(logits[0, -1]))
        want.append(nxt)
        seq.append(nxt)
    return want


# ---------------------------------------------------------------------------
# ngram provider (pure host)
# ---------------------------------------------------------------------------

def test_ngram_lookup_proposes_repeat_continuation():
    d = NgramDraft(n=3)
    #       0  1  2  3  4  5  6  7
    hist = [5, 6, 7, 8, 9, 5, 6, 7]
    # tail [6, 7] recurs at idx 1: continuation [8, 9, 5]
    assert d.lookup(hist, 3) == [8, 9, 5]
    assert d.lookup(hist, 1) == [8]
    assert d.lookup([1, 2, 3], 2) == []          # no repeat, no proposal
    assert d.lookup(hist, 0) == []
    assert d.lookup([4], 2) == []                # history too short


def test_ngram_lookup_prefers_longest_gram():
    d = NgramDraft(n=3)
    # tail [2, 3]: 3-gram [9, 2, 3] matches idx 0 -> continuation [4];
    # a 1-gram match of [3] at idx 5 would wrongly propose [7]
    hist = [9, 2, 3, 4, 8, 3, 7, 9, 2, 3]
    assert d.lookup(hist, 2) == [4, 8]


def test_make_provider_rejects_unknown():
    assert isinstance(make_provider("ngram"), NgramDraft)
    with pytest.raises(ValueError, match="unknown spec provider"):
        make_provider("model")          # needs cfg + params: instance only


# ---------------------------------------------------------------------------
# token identity: spec == vanilla == reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4])
def test_spec_ngram_token_identical_to_vanilla(tiny, k):
    cfg, params = tiny
    reqs = _reqs(cfg.vocab)
    van = ContinuousEngine(cfg, params, slots=2, max_len=96)
    got_v = {r.rid: list(map(int, r.tokens)) for r in van.run(reqs)}
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                           spec="ngram", spec_k=k, audit=True)
    got_s = {r.rid: list(map(int, r.tokens))
             for r in eng.run([dataclasses.replace(r) for r in reqs])}
    assert got_s == got_v
    assert eng.steps < van.steps, (eng.steps, van.steps)
    assert eng.spec_accepted > 0          # drafting actually shortcut steps
    assert 1.0 <= eng.avg_accept_len() <= k + 1
    eng.pool.check()


@pytest.mark.parametrize("k", [2, 4])
def test_spec_model_self_draft_token_identical(tiny, k):
    """Self-drafting (draft config == target config, same params) accepts
    every proposal, so the verify step, rollback, and draft-cache
    mirroring are all exercised at full acceptance — and the output must
    still equal the vanilla run and the full-recompute reference."""
    cfg, params = tiny
    reqs = _reqs(cfg.vocab)
    van = ContinuousEngine(cfg, params, slots=2, max_len=96)
    got_v = {r.rid: list(map(int, r.tokens)) for r in van.run(reqs)}
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                           spec=ModelDraft(cfg, params), spec_k=k,
                           audit=True)
    got_s = {r.rid: list(map(int, r.tokens))
             for r in eng.run([dataclasses.replace(r) for r in reqs])}
    assert got_s == got_v
    # self-draft: every draft token matches the target argmax
    assert eng.spec_accepted == eng.spec_drafted > 0
    assert eng.steps * 2 <= van.steps, (eng.steps, van.steps)
    assert eng.spec.steps > 0             # draft dispatches ran
    eng.pool.check()
    for r in reqs[:1]:                    # reference-exact (spot check)
        assert got_s[r.rid] == _greedy_reference(params, cfg, r)


def test_spec_model_divergent_draft_rollback_exact(tiny):
    """A draft that genuinely disagrees with the target: rejected
    proposals fire the draft-cache rollback-then-repropose path (cursor
    reset, truncate, fresh drafts over the rolled-back state) — the path
    self-drafting never reaches — and output must still equal vanilla
    token-for-token.

    A freshly initialised tied-embedding model greedily repeats its last
    token whatever the seed, so a draft with other random weights would
    agree with the target everywhere.  Flipping the sign of the draft's
    final norm (which scales by ``1 + w``) turns its argmax away from the
    repeated token."""
    cfg, params = tiny
    draft_params = {**params, "final_norm": -2.0 - params["final_norm"]}
    reqs = _reqs(cfg.vocab)
    van = ContinuousEngine(cfg, params, slots=2, max_len=96)
    got_v = {r.rid: list(map(int, r.tokens)) for r in van.run(reqs)}
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                           spec=ModelDraft(cfg, draft_params), spec_k=4,
                           audit=True)
    got_s = {r.rid: list(map(int, r.tokens))
             for r in eng.run([dataclasses.replace(r) for r in reqs])}
    assert got_s == got_v
    # the draft really disagreed somewhere: rejections exercised rollback
    assert eng.spec_accepted < eng.spec_drafted, eng.spec_stats()
    eng.pool.check()


def test_spec_with_shared_prefixes_and_chunked_prefill(tiny):
    """Long shared-prefix prompts: admission skip-prefills cached blocks,
    chunked prefill interleaves, the draft mirrors both, and spec output
    still equals vanilla."""
    cfg, params = tiny
    rng = np.random.default_rng(99)
    prefix = rng.integers(3, cfg.vocab, 40).astype(np.int32)
    mk = lambda: [Request(rid=i,
                          prompt=np.concatenate(
                              [prefix, rng2.integers(3, cfg.vocab, 4 + 3 * i
                                                     ).astype(np.int32)]),
                          max_new_tokens=3 + i, eos=-1) for i in range(4)]
    rng2 = np.random.default_rng(1)
    van = ContinuousEngine(cfg, params, slots=2, max_len=96)
    got_v = {r.rid: list(map(int, r.tokens)) for r in van.run(mk())}
    for spec in ("ngram", ModelDraft(cfg, params)):
        rng2 = np.random.default_rng(1)
        eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                               spec=spec, spec_k=4, audit=True)
        got_s = {r.rid: list(map(int, r.tokens)) for r in eng.run(mk())}
        assert got_s == got_v
        assert eng.pool.stats()["shared_token_hits"] > 0
        assert eng.chunk_steps >= 2
        eng.pool.check()


def test_spec_tight_pool_backs_off_and_stays_exact(tiny):
    """Lazy reservation under a pool sized for barely more than one
    request: extends hit exhaustion, speculation degrades (and may
    preempt), truncate returns blocks every step — output must still be
    exact and the pool clean after every audited step."""
    cfg, params = tiny
    per_slot = -(-96 // 16)
    reqs = _reqs(cfg.vocab, n=3, max_new=8)
    van = ContinuousEngine(cfg, params, slots=2, max_len=96)
    got_v = {r.rid: list(map(int, r.tokens)) for r in van.run(reqs)}
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                           kv_blocks=per_slot + 2, share_prefixes=False,
                           spec="ngram", spec_k=4, audit=True)
    got_s = {r.rid: list(map(int, r.tokens))
             for r in eng.run([dataclasses.replace(r) for r in reqs])}
    assert got_s == got_v
    eng.pool.check()
    assert eng.pool.used_blocks == 0      # everything returned


def test_spec_full_window_and_eos_budget(tiny):
    """Budget/window clamps: a slot near max_len or out of budget
    speculates shorter (k trimmed), never writes past the window, and
    finishes exactly like vanilla."""
    cfg, params = tiny
    r = Request(rid=0, prompt=np.arange(3, 27, dtype=np.int32) % 20 + 3,
                max_new_tokens=8, eos=-1)
    van = ContinuousEngine(cfg, params, slots=2, max_len=32)
    got_v = list(map(int, van.run([dataclasses.replace(r)])[0].tokens))
    eng = ContinuousEngine(cfg, params, slots=2, max_len=32,
                           spec="ngram", spec_k=4, audit=True)
    got_s = list(map(int, eng.run([dataclasses.replace(r)])[0].tokens))
    assert got_s == got_v
    eng.pool.check()


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_spec_hybrid_arch_raises():
    cfg = CONFIGS.get("zamba2_7b").scaled_down()
    params = N.init(cfg, KEY)
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousEngine(cfg, params, slots=1, max_len=96, spec="ngram")


def test_spec_hybrid_draft_raises(tiny):
    cfg, params = tiny
    hy = CONFIGS.get("mamba2_2_7b").scaled_down()
    with pytest.raises(ValueError, match="hybrid"):
        ModelDraft(hy, None)


def test_spec_dense_engine_raises(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(cfg, params, slots=1, max_len=96, paged=False,
                         spec="ngram")


def test_spec_temperature_rejected_at_submit(tiny):
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, slots=1, max_len=96, spec="ngram")
    with pytest.raises(ValueError, match="greedy-only"):
        eng.submit(Request(rid=0, prompt=np.asarray([5, 6, 7], np.int32),
                           temperature=0.7))


def test_spec_k_and_vocab_validation(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousEngine(cfg, params, slots=1, max_len=96, spec="ngram",
                         spec_k=0)
    other = dataclasses.replace(cfg, vocab=cfg.vocab * 2).validate()
    with pytest.raises(ValueError, match="vocab"):
        ContinuousEngine(cfg, params, slots=1, max_len=96,
                         spec=ModelDraft(other, params))
