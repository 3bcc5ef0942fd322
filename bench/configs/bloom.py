"""BLOOM as this system serves it: the seeded int8 weights and a plain
float32 reference of the forward pass.

The reference imports nothing of the serving program.  It follows the
block of hf:bigscience/bloom-* with the departures the program makes,
each of which the reference copies so that the two compute one function:

* rotary position embedding (theta 1e4, halves rotated) in place of
  ALiBi;
* no LayerNorm after the word embedding, LayerNorm gains 1 and no
  biases anywhere (BLOOM initialises its biases to 0);
* a prompt is right-aligned into ``s_max`` positions padded with token 0,
  and attention does not mask the padding;
* every row of a cohort decodes at the shared position ``s_max + t``: a
  row admitted at cohort step ``t0`` sees ``t0`` zero keys and values
  between its prompt and its first generated token.

Weights: each matrix is drawn N(0, 1/fan_in) in float32 and quantized
per output column (absmax / 127, round to nearest) to int8; the tied
embedding is N(0, 0.02^2) in bfloat16.  Both are made on the device, one
layer at a time, from the seed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")
EMBED_CHUNKS = 16
LN_EPS = 1e-5


def dims(c: dict) -> dict:
    """Sizes the program and the reference need, from a configuration
    file in BLOOM's own key names."""
    D, H = c["hidden_size"], c["n_head"]
    return {"L": c["n_layer"], "D": D, "H": H, "dh": D // H, "F": 4 * D,
            "V": c["vocab_size"], "theta": c["rope_theta"]}


def matrix_shapes(d: dict) -> dict:
    D, F = d["D"], d["F"]
    return {"wq": (D, D), "wk": (D, D), "wv": (D, D), "wo": (D, D),
            "w1": (D, F), "w2": (F, D)}


@functools.partial(jax.jit, static_argnames=("L", "D", "F", "V"))
def _make(key, *, L, D, F, V):
    shapes = matrix_shapes({"D": D, "F": F})
    k_layers, k_embed = jax.random.split(key)

    def layer(l):
        ks = jax.random.split(jax.random.fold_in(k_layers, l), len(MATRICES))
        out = {}
        for kk, name in zip(ks, MATRICES):
            K, N = shapes[name]
            w = jax.random.normal(kk, (K, N), jnp.float32) / math.sqrt(K)
            absmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
            scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
            q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
            out[name] = (q, scale)
        return out

    layers = jax.lax.map(layer, jnp.arange(L))
    rows = V // EMBED_CHUNKS

    def chunk(i):
        e = jax.random.normal(jax.random.fold_in(k_embed, i), (rows, D),
                              jnp.float32) * 0.02
        return e.astype(jnp.bfloat16)

    embed = jax.lax.map(chunk, jnp.arange(EMBED_CHUNKS)).reshape(V, D)
    return {"layers": layers, "embed": embed}


def make_weights(seed: int, d: dict) -> dict:
    """``{"layers": {name: (q int8 (L, K, N), scale f32 (L, 1, N))},
    "embed": bf16 (V, D)}``, on the device, in one jitted call."""
    if d["V"] % EMBED_CHUNKS:
        raise ValueError(f"vocab {d['V']} is not a multiple of "
                         f"{EMBED_CHUNKS}")
    return _make(jax.random.key(seed), L=d["L"], D=d["D"], F=d["F"],
                 V=d["V"])


# ---------------------------------------------------------------------------
# Reference forward, float32 at "highest" matmul precision
# ---------------------------------------------------------------------------


def _ln(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)


def _rope(x, pos, theta):
    """x (R, S, H, dh), pos (R, S)."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rel(a, b):
    """Per position, |a - b| / |b| over the heads and head dims:
    (R, S, H, dh) -> (R, S), with the squared norms of both."""
    num = jnp.sum(jnp.square(a - b), (-2, -1))
    den = jnp.sum(jnp.square(b), (-2, -1))
    return num, den


def _hidden(layers, embed, tokens, pos, gap, kv, sel, valid, *, H, theta):
    """Final-LayerNorm hidden states (R, S, D) of a causal pass over
    ``tokens`` at positions ``pos``; query row j of row r also attends
    to ``gap[r, j]`` zero keys and values.

    Beside them, for each layer ``l`` with ``sel[l] >= 0``, how far the
    program's keys and values ``kv[leaf][:, sel[l]]`` (R, S, H, dh) lie
    from the pass's own at the ``valid`` positions: ``(L, R, 2, 2)`` of
    (largest per-position relative error, relative error of the whole
    row) for K and V (0 where a layer is not compared)."""
    R, S = tokens.shape
    x = embed[tokens].astype(jnp.float32)
    D = x.shape[-1]
    dh = D // H
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def err(ref, prog):
        num, den = _rel(prog.astype(jnp.float32), ref)
        worst = jnp.max(jnp.where(valid, jnp.sqrt(num / den), 0.0), -1)
        whole = jnp.sqrt(jnp.sum(jnp.where(valid, num, 0.0), -1)
                         / jnp.sum(jnp.where(valid, den, 1e-30), -1))
        return jnp.stack([worst, whole], -1)                # (R, 2)

    def layer(x, inputs):
        lw, l_sel = inputs
        w = {n: q.astype(jnp.float32) * s for n, (q, s) in lw.items()}
        h = _ln(x)
        q = _rope((h @ w["wq"]).reshape(R, S, H, dh), pos, theta)
        k = _rope((h @ w["wk"]).reshape(R, S, H, dh), pos, theta)
        v = (h @ w["wv"]).reshape(R, S, H, dh)
        i = jnp.maximum(l_sel, 0)
        e = jnp.stack([err(k, kv["k"][:, i]), err(v, kv["v"][:, i])], 1)
        e = jnp.where(l_sel >= 0, e, 0.0)                   # (R, 2, 2)
        s = jnp.einsum("rqhd,rkhd->rhqk", q, k) / math.sqrt(dh)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        g = gap[:, None, :, None]                           # (R,1,S,1)
        m = jnp.maximum(jnp.max(s, -1, keepdims=True),
                        jnp.where(g > 0, 0.0, -jnp.inf))
        p = jnp.exp(s - m)
        den = jnp.sum(p, -1, keepdims=True) + g * jnp.exp(-m)
        o = jnp.einsum("rhqk,rkhd->rqhd", p / den, v).reshape(R, S, D)
        x = x + o @ w["wo"]
        h = _ln(x)
        x = x + jax.nn.gelu(h @ w["w1"], approximate=True) @ w["w2"]
        return x, e

    x, errs = jax.lax.scan(layer, x, (layers, sel))
    return _ln(x), errs


@functools.partial(jax.jit, static_argnames=("H", "theta", "s_max"))
def _compare(layers, embed, tokens, pos, gap, served, n_served, kv, sel,
             valid, *, H, theta, s_max):
    with jax.default_matmul_precision("highest"):
        x, errs = _hidden(layers, embed, tokens, pos, gap, kv, sel, valid,
                          H=H, theta=theta)
        # row s_max - 1 predicts served token 0, row s_max + i token i + 1
        x = x[:, s_max - 1:-1]
        rows = embed.shape[0] // EMBED_CHUNKS

        def block(carry, i):
            best, got = carry
            e = jax.lax.dynamic_slice_in_dim(embed, i * rows, rows)
            lg = x @ e.astype(jnp.float32).T                # (R, T, rows)
            ids = served - i * rows
            hit = jnp.take_along_axis(
                lg, jnp.clip(ids, 0, rows - 1)[..., None], -1)[..., 0]
            got = jnp.where((ids >= 0) & (ids < rows), hit, got)
            return (jnp.maximum(best, jnp.max(lg, -1)), got), None

        neg = jnp.full(served.shape, -jnp.inf)
        (best, got), _ = jax.lax.scan(block, (neg, neg),
                                      jnp.arange(EMBED_CHUNKS))
    ok = jnp.arange(served.shape[1])[None] < n_served[:, None]
    return jnp.where(ok, best - got, 0.0), errs


def kv_layers(d: dict) -> tuple:
    """The layers whose keys and values the check compares: the first,
    the middle and the last."""
    return tuple(sorted({0, d["L"] // 2, d["L"] - 1}))


def compare(weights: dict, d: dict, s_max: int, n_max: int, rows):
    """The reference's reading of what the program served.

    ``rows`` is a list of ``(prompt, served, t0, kv)``: the prompt's
    token ids (at most ``s_max``), the served token ids (at most
    ``n_max``, at least 1), the cohort step the row was admitted at, and
    the program's keys and values of that row as its KV cache held them
    once it finished: ``{"k": ..., "v": ...}``, each ``(len(kv_layers),
    s_max + n_max, H, dh)`` by cache position.

    Returns ``(gaps, kv_err)``: per served token, how far its reference
    logit lies below the reference's best, ``(R, n_max)`` (0 past each
    row's end); and per row, compared layer and leaf (K, V), the largest
    relative error of a position's keys or values and that of the row's
    as a whole, ``(R, len(kv_layers), 2, 2)``, over the prompt's
    positions and those of every served token."""
    R = len(rows)
    S = s_max + n_max
    lay = kv_layers(d)
    H, dh = d["H"], d["dh"]
    tok = np.zeros((R, S), np.int32)
    pos = np.zeros((R, S), np.int32)
    gap = np.zeros((R, S), np.int32)
    valid = np.zeros((R, S), bool)
    served = np.zeros((R, n_max), np.int32)
    n_served = np.zeros((R,), np.int32)
    kv = {n: np.zeros((R, len(lay), S, H, dh), jnp.bfloat16)
          for n in ("k", "v")}
    for i, (prompt, out, t0, prog) in enumerate(rows):
        prompt = list(prompt)[-s_max:]
        out = list(out)[:n_max]
        n = len(out)
        if not n:
            raise ValueError("a compared row needs at least one token")
        if t0 + n > n_max:
            raise ValueError(f"row {i}: t0 {t0} + {n} tokens "
                             f"passes n_max {n_max}")
        tok[i, s_max - len(prompt):s_max] = prompt
        tok[i, s_max:s_max + n] = out
        pos[i, :s_max] = np.arange(s_max)
        pos[i, s_max:] = s_max + t0 + np.arange(n_max)
        gap[i, s_max:] = t0
        valid[i, s_max - len(prompt):s_max + n] = True
        served[i, :n] = out
        n_served[i] = n
        for name in kv:
            # cache position s_max + t0 + j holds served token j
            kv[name][i, :, :s_max] = prog[name][:, :s_max]
            kv[name][i, :, s_max:s_max + n] = \
                prog[name][:, s_max + t0:s_max + t0 + n]
    sel = np.full((d["L"],), -1, np.int32)
    sel[list(lay)] = np.arange(len(lay))
    gaps, errs = _compare(
        weights["layers"], weights["embed"], jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(gap), jnp.asarray(served),
        jnp.asarray(n_served), {n: jnp.asarray(a) for n, a in kv.items()},
        jnp.asarray(sel), jnp.asarray(valid), H=H, theta=d["theta"],
        s_max=s_max)
    return gaps, jnp.transpose(errs[np.asarray(lay)], (1, 0, 2, 3))
