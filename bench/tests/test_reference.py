"""The float32 reference (bench/models/qwen2.py) against the served path
on the CPU, at a small size in float32: the program's chunked paged
prefill and paged decode give the reference's logits, and the engine's
greedy tokens, through the paged pool and shared prefix blocks, sit on
the reference's best logit."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import qwen2

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2**32 + 11


def _cfg():
    cj = json.loads((DATA / "configs" / "tiny-qwen2.json").read_text())
    cj["torch_dtype"] = "float32"
    cfg = qwen2.serving_config(cj, "float32")
    params = qwen2.to_program_tree(
        cfg, qwen2.make_weights(cj, SEED, jnp.float32))
    return cj, cfg, params


def _ref_logits(cj, seqs, rows):
    hs, table, _ = qwen2.reference_hidden(cj, SEED, jnp.float32, seqs)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(h[np.asarray(r)] @ table.astype(jnp.float32).T)
                for h, r in zip(hs, rows)]


def test_paged_prefill_and_decode_logits_match_the_reference():
    from repro.models import network as N
    cj, cfg, params = _cfg()
    slots, bs, max_len, chunk = 2, 16, 128, 16
    nbs = max_len // bs
    caches = N.expand_cache_pos(
        N.init_paged_caches(cfg, slots, slots * nbs + 1, bs), slots)
    bt = jnp.arange(1, slots * nbs + 1, dtype=jnp.int32).reshape(slots, nbs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cj["vocab_size"], n).astype(np.int32)
               for n in (37, 50)]
    seqs = [list(p) for p in prompts]
    got = [[] for _ in prompts]
    ids = jnp.arange(slots, dtype=jnp.int32)
    done = [0, 0]
    while any(d < len(p) for d, p in zip(done, prompts)):
        toks = np.zeros((slots, chunk), np.int32)
        lens = np.zeros(slots, np.int32)
        for i, p in enumerate(prompts):
            c = p[done[i]:done[i] + chunk]
            toks[i, :len(c)] = c
            lens[i] = len(c)
        logits, caches = N.prefill_paged_chunk(
            params, cfg, jnp.asarray(toks), caches, ids, bt,
            jnp.asarray(lens), jnp.asarray(np.maximum(lens - 1, 0)))
        for i in range(slots):
            done[i] += int(lens[i])
            if lens[i] and done[i] == len(prompts[i]):
                got[i].append(np.asarray(logits[i]))
    pos = np.array([len(p) for p in prompts], np.int32)
    for _ in range(6):
        nxt = np.array([int(np.argmax(g[-1])) for g in got], np.int32)
        for i in range(slots):
            seqs[i].append(int(nxt[i]))
        logits, caches = N.decode_step(
            params, cfg, jnp.asarray(nxt[:, None]), caches,
            jnp.asarray(pos), block_table=bt,
            pos_advance=jnp.ones(slots, jnp.int32))
        pos += 1
        for i in range(slots):
            got[i].append(np.asarray(logits[i]).reshape(-1))
    rows = [np.arange(len(p) - 1, len(s) - 1 + 1)[:len(g)]
            for p, s, g in zip(prompts, seqs, got)]
    ref = _ref_logits(cj, [np.asarray(s, np.int32) for s in seqs], rows)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.stack(g), r, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", [False, True])
def test_engine_tokens_sit_on_the_reference_best(monkeypatch, kernel):
    """Through the engine: chunked prefill, paged decode (the gather path,
    or the Pallas kernel in interpret mode), and a second request whose
    prompt shares full blocks with the first."""
    from repro.kernels import paged_attention as PA
    from repro.serving import ContinuousEngine, Request
    if kernel:
        monkeypatch.setattr(PA, "on_tpu", lambda: True)
    cj, cfg, params = _cfg()
    eng = ContinuousEngine(cfg, params, slots=2, max_len=128, block_size=16,
                           prefill_chunk=16)
    rng = np.random.default_rng(1)
    base = rng.integers(0, cj["vocab_size"], 40).astype(np.int32)
    prompts = [base, np.concatenate([base, rng.integers(
        0, cj["vocab_size"], 9).astype(np.int32)])]
    out = {}
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=8, eos=-1))
        while rid not in out:
            eng.step()
            for r in eng.drain_results():
                out[r.rid] = r
    assert eng.pool.shared_token_hits >= 32
    seqs = [np.concatenate([p, out[i].tokens]) for i, p in
            enumerate(prompts)]
    rows = [np.arange(len(p) - 1, len(p) + 7) for p in prompts]
    res = qwen2.reference_gaps(cj, SEED, jnp.float32, seqs, rows,
                               [out[i].tokens for i in range(2)])
    for gap, best in res:
        assert gap.max() < 1e-4
