"""The benchmark's weights and its plain reference of the Qwen3 decoder.

Nothing here imports the program.  Three parts:

* ``Dims``: the sizes, read from a configuration file of ``bench/configs``
  (the keys of the model's published ``config.json``).
* The weights: ``init_weights`` makes them from a seed on the device in one
  jitted call, in the configuration's dtype, as they are served, with the
  benchmark's Markov text planted in them (``Recipe``): the verifier puts
  its likeliest continuation first, a share of it is within the drafter's
  reach, and random attention makes the choice depend on the context.
  The same seed gives the same weights, so the reference rebuilds them
  itself rather than take them from the program.
* ``forward``: the published decoder in plain ``jax.numpy``: RMSNorm, GQA
  attention with per-head q/k RMSNorm and half-split RoPE, SwiGLU, tied
  unembedding.  Its ``mode`` sets the arithmetic: ``"f32"`` (float32 at
  ``highest`` precision: the reference) or ``"fp8"`` (the control, the
  step below the configuration's bfloat16: every matrix product's inputs
  rounded to float8 e4m3, with a scale per row of activations, per output
  channel of weights and per head vector of queries, keys, values and
  attention weights, and the residual stream between layers and the
  logits kept in float8 as the served model keeps them in bfloat16).

Weights are laid out as a list of layer stacks, each a dict of arrays with a
leading layer axis; the stacks together are the layers in order.  How the
layers are cut into stacks is a layout choice only (the harness cuts them
where the program's own parameter tree is cut, so no copy is needed).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if not c.get("tie_word_embeddings", False):
            raise ValueError("the reference covers tied embeddings only")
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ffn=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

# How strongly the planted units fire and write, the query/key norm gain,
# and the logit scale: one value serves every width (the readings in
# PERF.md were taken with them).
KEY_GAIN = 6.0
WRITE_GAIN = 1.0
QK_GAIN = 1.5
LOGIT_SCALE = 20.0


@dataclass(frozen=True)
class Recipe:
    """How the weights are made (a configuration's ``weights`` group).

    Every vector lives in the whole width ``d``.  A token's embedding
    ``e_t`` (entries drawn N(0, 1)) is its identity.  The final norm's gain
    is ``LOGIT_SCALE / d`` times a random sign per dimension, ``s``; so the
    logits read a residual ``x`` as ``x . (s * e_t')``, which a token's own
    embedding scarcely moves, and a *prediction* of token ``t'`` is a write
    of ``s * e_t'``.  Each transition of the Markov text (``span`` tokens a
    category) is planted as one unit of one layer's SwiGLU: the gate and up
    columns are ``KEY_GAIN * e_t / d`` (they fire when the current token is
    ``t``), the down row writes ``WRITE_GAIN * sum_k p_k s * e_succ_k`` (the
    successors, weighted by their probabilities), so the verifier puts the
    likeliest successor first with a margin set by the probabilities.  A
    share ``draft_share`` of the tokens keep their unit in the drafter's
    layers (below ``split_layer``); the rest spread over the deeper layers,
    where only the verifier reads them.  Attention draws its pattern at
    random (query/key norm gains ``QK_GAIN``) and adds, over all layers,
    ``attn_gain`` times a context-weighted mean of earlier positions: a term
    that decides near ties, so what is served depends on the whole context,
    not on the last token alone.
    """
    seed: int
    span: int
    draft_share: float
    attn_gain: float

    @classmethod
    def from_config(cls, c: dict) -> "Recipe":
        w = c["weights"]
        return cls(seed=int(w["seed"]), span=int(w["markov_span"]),
                   draft_share=float(w["draft_share"]),
                   attn_gain=float(w["attn_gain"]))


def plant_slots(dm: Dims, rc: Recipe, split: int, table) -> tuple:
    """Which unit of which layer holds each transition: (tokens (L, F),
    successors (L, F, branching), probabilities (L, F, branching)); an
    empty unit has token -1 and probabilities 0.  ``table`` is
    ``MarkovText.table()``."""
    toks, succ, prob = table
    n, L, F = len(toks), dm.layers, dm.ffn
    order = np.random.default_rng([rc.seed, 11]).permutation(n)
    shallow = int(round(rc.draft_share * n))
    s_tok = np.full((L, F), -1, np.int32)
    s_succ = np.zeros((L, F, succ.shape[1]), np.int32)
    s_prob = np.zeros((L, F, succ.shape[1]), np.float32)
    for group, (lo, hi) in ((order[:shallow], (0, split)),
                            (order[shallow:], (split, L))):
        if not len(group):
            continue
        if hi <= lo or len(group) > (hi - lo) * F:
            raise ValueError(f"{len(group)} transitions do not fit layers "
                             f"{lo}-{hi - 1} of {F} units")
        layer = lo + np.arange(len(group)) % (hi - lo)
        for li in range(lo, hi):
            idx = group[layer == li]
            s_tok[li, :len(idx)] = toks[idx]
            s_succ[li, :len(idx)] = succ[idx]
            s_prob[li, :len(idx)] = prob[idx]
    return s_tok, s_succ, s_prob


def _attn_stack(key, dm: Dims, rc: Recipe, n: int) -> dict:
    """Random queries and keys; values an orthonormal projection of the
    normed residual that the output projection maps back, scaled by
    ``attn_gain / layers``: each layer adds a context-weighted mean of what
    earlier positions hold (their tokens and their predictions), so that
    the layers together add ``attn_gain`` times such a mean at any depth."""
    d, H, KV, hd = dm.d, dm.heads, dm.kv_heads, dm.head_dim
    kq, kk, kv = jax.random.split(key, 3)
    dt = jnp.dtype(dm.dtype)

    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dt)

    if KV * hd > d:
        raise ValueError("values wider than the residual")
    wv = jnp.linalg.qr(jax.random.normal(kv, (n, d, KV * hd)))[0]
    # query head h reads value head h // (H / KV): average the group back
    wo = jnp.repeat(jnp.swapaxes(wv, 1, 2), H // KV, axis=1) * (
        rc.attn_gain / dm.layers * KV / H)
    one = jnp.ones
    return {"ln1": one((n, d), jnp.float32), "ln2": one((n, d), jnp.float32),
            "qn": QK_GAIN * one((n, hd), jnp.float32),
            "kn": QK_GAIN * one((n, hd), jnp.float32),
            "wq": w(kq, (n, d, H * hd)), "wk": w(kk, (n, d, KV * hd)),
            "wv": wv.astype(dt), "wo": wo.astype(dt)}


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init(dm: Dims, rc: Recipe, bounds: Tuple[Tuple[int, int], ...], key,
          s_tok, s_succ, s_prob) -> dict:
    ks = jax.random.split(key, len(bounds) + 2)
    dt = jnp.dtype(dm.dtype)
    e = jax.random.normal(ks[0], (dm.vocab, dm.d), jnp.float32).astype(dt)
    sign = jnp.where(jax.random.bernoulli(ks[1], 0.5, (dm.d,)), 1.0, -1.0)
    unit = jax.nn.silu(KEY_GAIN) * KEY_GAIN

    def ffn_layer(_, slot):
        tok, succ, prob = slot
        live = (tok >= 0)[:, None]
        keys = jnp.where(live, e[jnp.maximum(tok, 0)].astype(jnp.float32),
                         0.0) * (KEY_GAIN / dm.d)              # (F, d)
        pred = jnp.einsum("fb,fbd->fd", prob,
                          e[succ].astype(jnp.float32)) * sign     # (F, d)
        down = pred * (WRITE_GAIN / unit)
        return None, (keys.T.astype(dt), down.astype(dt))

    _, (gate, down) = jax.lax.scan(ffn_layer, None, (s_tok, s_succ, s_prob))
    stacks = []
    for k, (lo, hi) in zip(ks[2:], bounds):
        st = _attn_stack(k, dm, rc, hi - lo)
        st.update(w_gate=gate[lo:hi], w_up=gate[lo:hi], w_down=down[lo:hi])
        stacks.append(st)
    return {"embed": e,
            "norm": sign * (LOGIT_SCALE / dm.d),
            "stacks": stacks}


def init_weights(dm: Dims, rc: Recipe, bounds: Sequence[Tuple[int, int]],
                 split: int, table) -> dict:
    """The weights, made on the device in one jitted call from the recipe's
    seed and the Markov text's transition ``table``."""
    bounds = tuple((int(lo), int(hi)) for lo, hi in bounds)
    if bounds[0][0] != 0 or bounds[-1][1] != dm.layers or any(
            a[1] != b[0] for a, b in zip(bounds, bounds[1:])):
        raise ValueError(f"stacks {bounds} do not cover {dm.layers} layers")
    slots = plant_slots(dm, rc, split, table)
    return _init(dm, rc, bounds, jax.random.PRNGKey(rc.seed),
                 *map(jnp.asarray, slots))


def fingerprint(w: dict) -> float:
    """A float64 sum over every weight: equal weights give equal sums."""
    leaves = jax.tree.leaves(w)
    sums = jax.device_get([jnp.sum(jnp.abs(x.astype(jnp.float32)))
                           for x in leaves])
    return float(np.sum(np.asarray(sums, np.float64)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fake_fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def linear(x, w, mode: str):
    """``x @ w`` for ``x`` (..., k) and ``w`` (k, n) in the arithmetic of
    ``mode``."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """Half-split rotary embedding; x (B, T, H, hd), pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, dm: Dims, mode: str):
    B, T, _ = x.shape
    H, KV, hd = dm.heads, dm.kv_heads, dm.head_dim
    prec = jax.lax.Precision.HIGHEST
    pos = jnp.arange(T)
    h = rms_norm(x, p["ln1"], dm.eps)
    q = linear(h, p["wq"], mode).reshape(B, T, H, hd)
    k = linear(h, p["wk"], mode).reshape(B, T, KV, hd)
    v = linear(h, p["wv"], mode).reshape(B, T, KV, hd)
    q = rope(rms_norm(q, p["qn"], dm.eps), pos, dm.rope_theta)
    k = rope(rms_norm(k, p["kn"], dm.eps), pos, dm.rope_theta)
    # query head i reads key/value head i // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2).astype(jnp.float32)
    if mode == "fp8":
        q, k, v = _fake_fp8(q, -1), _fake_fp8(k, -1), _fake_fp8(v, 1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if mode == "fp8":
        a = _fake_fp8(a, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=prec)
    x = x + linear(o.reshape(B, T, H * hd), p["wo"], mode)
    h = rms_norm(x, p["ln2"], dm.eps)
    g = jax.nn.silu(linear(h, p["w_gate"], mode)) * linear(h, p["w_up"], mode)
    return x + linear(g, p["w_down"], mode)


def forward(w: dict, tokens, dm: Dims, mode: str = "f32"):
    """tokens (B, T) int -> logits (B, T, vocab) float32.  In ``"fp8"`` the
    tensors the served model keeps in its own dtype between operations are
    kept in float8 too: the residual stream after every layer, and the
    logits (one scale per row)."""
    keep = (lambda a: _fake_fp8(a, -1)) if mode == "fp8" else (lambda a: a)
    x = w["embed"][tokens].astype(jnp.float32)
    for stack in w["stacks"]:
        x, _ = jax.lax.scan(lambda c, p: (keep(_layer(c, p, dm, mode)), None),
                            x, stack)
    x = rms_norm(x, w["norm"], dm.eps)
    return keep(linear(x, w["embed"].T, mode))


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def to_float32(w: dict) -> dict:
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@partial(jax.jit, static_argnums=(0, 3))
def served_gaps(dm: Dims, w32: dict, seq, control: bool = False):
    """For one sequence ``seq`` (T,) of prompt then served tokens: at each
    position, how far the reference's logit of the next token lies below
    the reference's best.  With ``control`` the next token is not the
    served one but the one the fp8 control puts first.  Returns (T,);
    position ``t`` judges token ``t + 1`` (the last entry is meaningless)."""
    with jax.default_matmul_precision("highest"):
        ref = forward(w32, seq[None], dm, "f32")[0]
        if control:
            nxt = jnp.argmax(forward(w32, seq[None], dm, "fp8")[0], axis=-1)
        else:
            nxt = jnp.concatenate([seq[1:], seq[-1:]])
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]


def gaps(dm: Dims, w32: dict, samples: List[Tuple[list, list]],
         pad_to: int, control: bool = False) -> dict:
    """Over every served token of ``samples`` (pairs of prompt and served
    tokens): the widest gap, the mean gap and how many tokens were judged.
    Every sequence is padded to ``pad_to`` so one program serves them all;
    the padding lies after the judged positions and the attention is
    causal."""
    worst, total, n = 0.0, 0.0, 0
    for prompt, served in samples:
        seq = np.zeros((pad_to,), np.int32)
        full = list(prompt) + list(served)
        seq[:len(full)] = full
        g = np.asarray(served_gaps(dm, w32, jnp.asarray(seq), control),
                       np.float64)
        judged = g[len(prompt) - 1:len(full) - 1]
        if len(judged):
            worst = max(worst, float(judged.max()))
            total += float(judged.sum())
            n += len(judged)
    return {"widest_gap": worst, "mean_gap": total / n if n else float("nan"),
            "judged": n}
