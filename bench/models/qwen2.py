"""The Qwen2 / Qwen1.5 decoder: weights from a seed, the served
configuration, and a plain float32 reference forward.

One block (the Hugging Face ``Qwen2ForCausalLM`` equations, which
Qwen1.5 shares):

    h   = x + Wo · attn(RoPE(Wq·n1(x) + bq), RoPE(Wk·n1(x) + bk),
                        Wv·n1(x) + bv)          causal, GQA groups
    out = h + Wd · (silu(Wg·n2(h)) * (Wu·n2(h)))
    n(x) = x / sqrt(mean(x^2) + eps) * w        (RMSNorm)

RoPE is the split-half ("rotate_half") convention with base
``rope_theta``; logits are ``n_f(x) · E^T`` with the embedding table
``E`` when ``tie_word_embeddings``, else a separate ``lm_head``.

Weights are drawn here, from the seed, and never taken from the program:
``make_weights`` builds the served tree in one jitted call, and the
reference draws each layer again from the same keys.  Each leaf has a
fan-in standard deviation, so the residual stream is carried by the
blocks (not by the embedding), and the logits of the random model have
a spread of a few units: the served token is a real choice among close
candidates, which rounding can change.  The blocks' projection matrices
are heavy-tailed, as trained weights are (Student's t, 4 degrees of
freedom, scaled to that standard deviation): the median channel's
largest weight lies 6-10 standard deviations out over 896 and 4864
weights, against 3.4-3.8 for normal ones, so per-channel int8 steps are
coarser.  With normal matrices int8 adds a logit error only about three
times bfloat16's own, and the check could not tell the two apart
(PERF.md).  Embeddings, biases and norm weights are normal.

Departure noted: the program parametrises each RMSNorm weight as
``1 + g``; the weights drawn here are the ``g`` it serves, and the
reference uses ``w = 1 + g`` exactly (float32).

The reference imports nothing of the program.  ``serving_config`` and
``to_program_tree`` are the adapter to the system under test and are the
only functions here that touch ``repro``.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: standard deviations of the drawn leaves (see module docstring)
EMBED_STD = 0.05
BIAS_STD = 0.2
NORM_STD = 0.1


def dims(cj: dict) -> dict:
    d = cj["hidden_size"]
    h = cj["num_attention_heads"]
    return dict(d=d, h=h, kv=cj["num_key_value_heads"], hd=d // h,
                ff=cj["intermediate_size"], v=cj["vocab_size"],
                n=cj["num_hidden_layers"], eps=cj["rms_norm_eps"],
                theta=float(cj["rope_theta"]),
                tied=bool(cj["tie_word_embeddings"]))


def layer_leaves(cj: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """Per-layer leaves: name -> (shape, std)."""
    m = dims(cj)
    d, q, k, ff = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    return {
        "ln1": ((d,), NORM_STD), "ln2": ((d,), NORM_STD),
        "wq": ((d, q), d ** -0.5), "bq": ((q,), BIAS_STD),
        "wk": ((d, k), d ** -0.5), "bk": ((k,), BIAS_STD),
        "wv": ((d, k), d ** -0.5), "bv": ((k,), BIAS_STD),
        "wo": ((q, d), q ** -0.5),
        "w_gate": ((d, ff), d ** -0.5), "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
    }


def global_leaves(cj: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    m = dims(cj)
    out = {"embed": ((m["v"], m["d"]), EMBED_STD),
           "final_norm": ((m["d"],), NORM_STD)}
    if not m["tied"]:
        out["lm_head"] = ((m["v"], m["d"]), EMBED_STD)
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


#: degrees of freedom of the blocks' matrices (see module docstring)
MATRIX_DF = 4.0


def _normal(key: jax.Array, shape, std: float, dtype) -> jax.Array:
    """Normal with standard deviation ``std``."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _heavy(key: jax.Array, shape, std: float, dtype) -> jax.Array:
    """Student's t with ``MATRIX_DF`` degrees of freedom, scaled to the
    standard deviation ``std``."""
    z = jax.random.t(key, MATRIX_DF, shape, jnp.float32)
    return (z * (std * math.sqrt((MATRIX_DF - 2) / MATRIX_DF))
            ).astype(dtype)


def draw_layer(cj: dict, key: jax.Array, layer, dtype) -> dict:
    """One layer's leaves (traceable; ``layer`` may be a tracer)."""
    return {name: (_heavy if len(shape) == 2 else _normal)(
                jax.random.fold_in(_leaf_key(key, name), layer),
                shape, std, dtype)
            for name, (shape, std) in layer_leaves(cj).items()}


def draw_globals(cj: dict, key: jax.Array, dtype) -> dict:
    return {name: _normal(_leaf_key(key, name), shape, std, dtype)
            for name, (shape, std) in global_leaves(cj).items()}


def make_weights(cj: dict, seed: int, dtype) -> dict:
    """Every weight, layers stacked on a leading axis, in one jitted call
    on the default device."""
    n = dims(cj)["n"]

    def build(key):
        layers = jax.vmap(lambda i: draw_layer(cj, key, i, dtype))(
            jnp.arange(n, dtype=jnp.uint32))
        return {"globals": draw_globals(cj, key, dtype), "layers": layers}

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# Adapter to the system under test
# ---------------------------------------------------------------------------

def serving_config(cj: dict, dtype: str):
    """The program's ``ModelConfig`` for this configuration."""
    from repro.models.config import BlockKind, ModelConfig
    m = dims(cj)
    return ModelConfig(
        name=cj["name"], n_layers=m["n"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["kv"], d_ff=m["ff"], vocab=m["v"],
        pattern=(BlockKind.ATTN,), qkv_bias=True,
        tie_embeddings=m["tied"], rope_theta=m["theta"], norm_eps=m["eps"],
        param_dtype=dtype, compute_dtype=dtype).validate()


def to_program_tree(cfg, weights: dict) -> dict:
    """Arrange the drawn weights as the program's parameter tree, and
    check it against the tree the program itself declares."""
    from repro.models import network
    g, L = weights["globals"], weights["layers"]
    tree = {
        "final_norm": g["final_norm"],
        "embed": {"table": g["embed"]},
        "blocks": ({
            "ln1": L["ln1"], "ln2": L["ln2"],
            "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")},
            "mlp": {"wi_gate": L["w_gate"], "wi_up": L["w_up"],
                    "wo": L["w_down"]},
        },),
    }
    if "lm_head" in g:
        tree["lm_head"] = g["lm_head"]
    want = jax.eval_shape(lambda: network.init(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree no longer matches "
                           "the benchmark's qwen2 weights")
    return tree


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------

def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x (S, H, hd), positions 0..S-1, split-half convention."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def reference_layer(m: dict, w: dict, x: jax.Array) -> jax.Array:
    """One block over a whole sequence x (S, d), float32."""
    S = x.shape[0]
    h, kv, hd = m["h"], m["kv"], m["hd"]
    a = _norm(x, w["ln1"], m["eps"])
    q = _rope((a @ w["wq"] + w["bq"]).reshape(S, h, hd), m["theta"])
    k = _rope((a @ w["wk"] + w["bk"]).reshape(S, kv, hd), m["theta"])
    v = (a @ w["wv"] + w["bv"]).reshape(S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, h * hd)
    x = x + o @ w["wo"]
    b = _norm(x, w["ln2"], m["eps"])
    return x + (jax.nn.silu(b @ w["w_gate"]) * (b @ w["w_up"])) @ w["w_down"]


@jax.jit
def _embed(table, toks):
    return jnp.take(table.astype(jnp.float32), toks, axis=0)


@functools.partial(jax.jit, static_argnames=("cj", "dtype", "narrow"))
def _layer_weights(key, i, *, cj, dtype, narrow):
    return jax.tree.map(lambda t: _round(t.astype(jnp.float32), narrow),
                        draw_layer(dict(cj), key, i, dtype))


@functools.partial(jax.jit, static_argnames=("cj", "narrow"))
def _layer(w, x, *, cj, narrow):
    with jax.default_matmul_precision("highest"):
        return reference_layer(dims(dict(cj)), w, _round(x, narrow))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(gn, x, *, eps):
    return _norm(x, gn.astype(jnp.float32), eps)


def _round(t, narrow):
    """Round ``t`` through the dtype named ``narrow`` (None: leave it)."""
    return t if narrow is None else t.astype(narrow).astype(jnp.float32)


def _frozen(cj: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cj.items()
                        if not isinstance(v, (list, dict))))


def reference_hidden(cj: dict, seed: int, dtype, seqs: list[np.ndarray], *,
                     matmul_dtype=None):
    """Final-normed float32 hidden states of each sequence, and the head
    (the table the logits are taken against).

    Runs layer by layer: each layer's weights are drawn again from the
    seed (in the served ``dtype``, then widened to float32), applied to
    every sequence, and dropped.  Sequences are padded at the end to one
    length, which causal attention leaves without effect on the real
    positions.  ``matmul_dtype`` rounds the weights and every matmul
    input to a narrower type: a lower-precision control.  Every value
    that changes from run to run is an argument of the jitted functions,
    never a closure, so a program traced for one seed is never reused
    for another.
    """
    m = dims(cj)
    key = seed_key(seed)
    narrow = None if matmul_dtype is None else jnp.dtype(matmul_dtype).name
    dtype = jnp.dtype(dtype).name
    L = max(len(s) for s in seqs)
    L = -(-L // 128) * 128
    g = _globals(key, cj=_frozen(cj), dtype=dtype)
    xs = []
    for s in seqs:
        toks = np.zeros(L, np.int32)
        toks[:len(s)] = s
        xs.append(_embed(g["embed"], jnp.asarray(toks)))
    for i in range(m["n"]):
        w = _layer_weights(key, jnp.uint32(i), cj=_frozen(cj), dtype=dtype,
                           narrow=narrow)
        xs = [_layer(w, x, cj=_frozen(cj), narrow=narrow) for x in xs]
        del w
    table = g["lm_head"] if "lm_head" in g else g["embed"]
    return [_final(g["final_norm"], x, eps=m["eps"]) for x in xs], table, \
        narrow


@functools.partial(jax.jit, static_argnames=("cj", "dtype"))
def _globals(key, *, cj, dtype):
    return draw_globals(dict(cj), key, dtype)


@functools.partial(jax.jit, static_argnames=("narrow",))
def _head(table, h, idx, tok, *, narrow):
    with jax.default_matmul_precision("highest"):
        logits = _round(jnp.take(h, idx, axis=0), narrow) \
            @ _round(table.astype(jnp.float32), narrow).T
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return best - got, jnp.argmax(logits, -1)


def reference_gaps(cj: dict, seed: int, dtype, seqs: list[np.ndarray],
                   rows: list[np.ndarray], tokens: list[np.ndarray], *,
                   matmul_dtype=None) -> list[tuple[np.ndarray, np.ndarray]]:
    """For each sequence, at each position in ``rows``: how far the
    float32 reference's logit of the given token lies below its best
    logit, and the reference's own best token."""
    hs, table, narrow = reference_hidden(cj, seed, dtype, seqs,
                                         matmul_dtype=matmul_dtype)
    out = []
    for h, r, t in zip(hs, rows, tokens):
        gap, best = _head(table, h, jnp.asarray(r, jnp.int32),
                          jnp.asarray(t, jnp.int32), narrow=narrow)
        out.append((np.asarray(gap), np.asarray(best)))
    return out
