"""Segmented decoder stack with a unified speculative-decoding cache.

The layer list is grouped into *segments* — maximal runs of layers with the
same (mixer kind, ffn kind) — and each segment's parameters are stacked on a
leading layer axis and executed with ``jax.lax.scan`` (keeps HLO size O(#
segments), not O(#layers): llama3-405b lowers as a single 126-deep scan).

Crucially for DVI, segment boundaries are also cut at ``cfg.dvi.split_layer``
so the *draft path* (layers [0, k)) and *target path* ([k, L)) are separate
segment runs over one shared parameter tree.

Two execution modes:

* ``forward_full`` — whole sequence, no cache reads (train / prefill).
  Optionally returns per-layer cache contributions so prefill can build the
  decode cache.
* ``forward_step`` — a block of T tokens (T = k_spec + 1 during speculation,
  1 for plain AR) against the cache.  Attention caches are written eagerly
  (rollback = length masking; sliding-window caches use a slack ring so
  speculative writes never clobber live slots).  Stateful mixers (SSD,
  RG-LRU) return per-step candidate states; ``commit_cache`` selects the
  state at the accepted length.

Two cache layouts share these entry points:

* **contiguous** (``init_cache``) — each lane reserves a worst-case
  ``(B, max_len)`` KV region; simple, but one long request strands memory.
* **paged** (``init_paged_cache``) — full-attention KV lives in a shared
  page pool ``(n, P, page_size, KV, hd)`` addressed through a per-lane
  block table ``cache["tbl"]`` (see ``repro.serving.kv_pool`` for the
  layout and rollback rules); ring/SSD/RG-LRU segments keep their
  per-slot constant-size state.  ``insert_slot`` becomes a block-table
  scatter and ``reset_slot`` just unmaps the lane's row — physical pages
  are recycled host-side by the serving engine's ``KVPool``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (MaskSpec, NEG_INF, apply_rope, attend,
                                 attend_full, causal_mask, dense_init,
                                 head_rms_norm, mlp, rms_norm, split_keys)

# Extra ring slots so speculative writes never evict live KV.  128 (not the
# minimal k_spec+1) keeps ring capacities mesh-divisible: W + 128 stays a
# multiple of 256 for the production windows (2048, 8192), so the cache's
# sequence dim shards cleanly over a 16-way mesh axis.
RING_SLACK = 128


@dataclass(frozen=True)
class Segment:
    idx: int
    kind: str          # attn | local | ssm | rglru
    ffn: str           # dense | moe | none
    start: int
    n: int
    d_ff: int
    cross: bool = False

    @property
    def name(self) -> str:
        return f"s{self.idx}"


def layer_kinds(cfg: ModelConfig):
    pat = cfg.layer_pattern
    kinds = []
    for layer in range(cfg.num_layers):
        kind = pat[layer % len(pat)]
        if cfg.ssm is not None:
            ffn = "none"
        elif cfg.moe is not None and layer >= cfg.moe.first_dense_layers:
            ffn = "moe"
        else:
            ffn = "dense"
        kinds.append((kind, ffn))
    return kinds


def build_segments(cfg: ModelConfig, boundaries=()):
    """Group layers into stacked-scan segments; force cuts at `boundaries`."""
    kinds = layer_kinds(cfg)
    cuts = set(boundaries) | {0, cfg.num_layers}
    segs, idx = [], 0
    start = 0
    for layer in range(1, cfg.num_layers + 1):
        if (layer in cuts or layer == cfg.num_layers
                or kinds[layer] != kinds[start]):
            kind, ffn = kinds[start]
            if ffn == "dense" and cfg.moe is not None and cfg.moe.first_dense_layers:
                d_ff = cfg.moe.d_ff_dense or cfg.d_ff
            else:
                d_ff = cfg.d_ff
            segs.append(Segment(idx, kind, ffn, start, layer - start, d_ff,
                                cross=(cfg.arch_type == "audio")))
            idx += 1
            start = layer
    return segs


def model_segments(cfg: ModelConfig):
    return build_segments(cfg, boundaries=(cfg.dvi.split_layer,))


def segments_in_range(cfg: ModelConfig, lo: int, hi: int):
    return [s for s in model_segments(cfg) if s.start >= lo and s.start + s.n <= hi]


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_segment(key, cfg: ModelConfig, seg: Segment, dtype) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    n = seg.n
    ks = split_keys(key, 20)
    if seg.kind == "ssm":
        return ssm_mod.init_ssm(ks[0], n, d, cfg.ssm, dtype)
    p = {"ln1": jnp.zeros((n, d), jnp.float32),
         "ln2": jnp.zeros((n, d), jnp.float32)}
    if seg.kind == "rglru":
        p.update(rglru_mod.init_rglru(ks[0], n, d, cfg.rglru, dtype))
    elif cfg.mla is not None:
        p.update(mla_mod.init_mla(ks[0], n, d, H, cfg.mla, dtype))
    else:
        p["wq"] = dense_init(ks[1], (n, d, H * hd), dtype)
        p["wk"] = dense_init(ks[2], (n, d, KV * hd), dtype)
        p["wv"] = dense_init(ks[3], (n, d, KV * hd), dtype)
        p["wo"] = dense_init(ks[4], (n, H * hd, d), dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((n, H * hd), dtype)
            p["bk"] = jnp.zeros((n, KV * hd), dtype)
            p["bv"] = jnp.zeros((n, KV * hd), dtype)
        if cfg.qk_norm:
            p["qn"] = jnp.zeros((n, hd), jnp.float32)
            p["kn"] = jnp.zeros((n, hd), jnp.float32)
    if seg.cross and seg.kind in ("attn", "local"):
        p["ln_x"] = jnp.zeros((n, d), jnp.float32)
        p["wq_x"] = dense_init(ks[5], (n, d, H * hd), dtype)
        p["wk_x"] = dense_init(ks[6], (n, d, H * hd), dtype)
        p["wv_x"] = dense_init(ks[7], (n, d, H * hd), dtype)
        p["wo_x"] = dense_init(ks[8], (n, H * hd, d), dtype)
    # FFN
    if seg.ffn == "dense":
        f = seg.d_ff
        p["wi"] = dense_init(ks[10], (n, d, f), dtype)
        if cfg.glu:
            p["wg"] = dense_init(ks[11], (n, d, f), dtype)
        p["wo_ff"] = dense_init(ks[12], (n, f, d), dtype)
    elif seg.ffn == "moe":
        p["moe"] = moe_mod.init_moe(ks[13], n, d, cfg.moe, cfg.glu, dtype)
    return p


# ---------------------------------------------------------------------------
# Single-layer bodies
# ---------------------------------------------------------------------------

def _qkv(p, xn, cfg):
    B, T = xn.shape[:2]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = xn @ p["wq"]
    k = xn @ p["wk"]
    v = xn @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, KV, hd)
    v = v.reshape(B, T, KV, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["qn"], cfg.norm_eps)
        k = head_rms_norm(k, p["kn"], cfg.norm_eps)
    return q, k, v


def _ffn(p, x, cfg, seg_ffn, aux, dropless=False):
    from repro.launch.hints import hint
    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    if dropless:
        # decode path: keep activations d-sharded over "data" so matmuls
        # against (d/data, f/model) weights run as weight-STATIONARY
        # partial sums + tiny activation all-reduces, instead of
        # all-gathering 2D-sharded weights every layer (51 GiB/step for
        # llama3-405b decode — see EXPERIMENTS.md §Perf H1)
        xn = hint(xn, None, None, "data")
    if seg_ffn == "moe":
        y, a = moe_mod.moe_ffn(p["moe"], xn, cfg.moe, cfg.act, cfg.glu, dropless)
        aux = aux + a
    else:
        # (H2 in EXPERIMENTS.md tried a batch-reduce-scatter GLU flow here;
        # it REGRESSED wire 7.1->8.1 GiB — GSPMD lowered the batch reshard
        # as all-gather+slice — so the plain flow stands.)
        y = mlp(p, xn, cfg.act, cfg.glu)
    return x + y, aux


def _cross_attn(p, x, cross_k, cross_v, cfg):
    """cross_k/v: (B, F, H, hd) — precomputed per layer from encoder output."""
    B, T = x.shape[:2]
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    xn = rms_norm(x, p["ln_x"], cfg.norm_eps)
    q = (xn @ p["wq_x"]).reshape(B, T, H, hd)
    out = attend_full(q, cross_k, cross_v, MaskSpec(bidirectional=True))
    return x + out.reshape(B, T, H * hd) @ p["wo_x"]


def attn_layer_full(p, x, cfg: ModelConfig, seg: Segment, positions, spec,
                    enc_out, aux, use_rope=True, collect=True):
    """Full-sequence attention layer.  Returns (x, cache_contrib, aux)."""
    from repro.launch.hints import hint
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    contrib = {}
    if cfg.mla is not None:
        out, lat = mla_mod.mla_full(p, xn, cfg.num_heads, cfg.mla, positions,
                                    spec, cfg.rope_theta)
        x = x + out
        if collect:
            # prefill cache contributions live sequence-sharded (they become
            # the decode cache; replicated they are 16x per-device memory)
            contrib = {"ckv": hint(lat["ckv"], "data", "model", None),
                       "krope": hint(lat["krope"], "data", "model", None)}
    else:
        q, k, v = _qkv(p, xn, cfg)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        out = attend_full(q, k, v, spec)
        B, T = x.shape[:2]
        x = x + out.reshape(B, T, -1) @ p["wo"]
        if collect:
            contrib = {"k": hint(k, "data", "model", None, None),
                       "v": hint(v, "data", "model", None, None)}
    if seg.cross:
        B, F = enc_out.shape[:2]
        H, hd = cfg.num_heads, cfg.resolved_head_dim
        ck = (enc_out @ p["wk_x"]).reshape(B, F, H, hd)
        cv = (enc_out @ p["wv_x"]).reshape(B, F, H, hd)
        x = _cross_attn(p, x, ck, cv, cfg)
        if collect:
            contrib.update({"xk": ck, "xv": cv})
    x, aux = _ffn(p, x, cfg, seg.ffn, aux)
    return x, contrib, aux


def kv_quantize(x):
    """(..., KV, hd) -> (int8 values, f32 per-(slot, kv-head) scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def spread_write(cache, blk, lengths, wrap: bool = True):
    """Write blk (B,T,...) into cache (B,C,...) at slots lengths + i via an
    elementwise select (sharding-preserving).  ``wrap=True`` (ring caches):
    slots are (lengths + i) mod C.  ``wrap=False`` (full / MLA caches, where
    slot index == absolute position): out-of-capacity writes are DROPPED —
    a position past C can only ever be an eager speculative / chunk-padding
    write that rollback would discard anyway, and wrapping it would clobber
    committed slots near 0.  Both clauses are depth-agnostic: with per-lane
    adaptive K a short lane's surplus draft writes (depth < batch width T)
    clip/wrap exactly like rejected full-depth drafts, and capacity is
    reserved for the worst-case k_max (engine ``_cap``), so committed slots
    are never displaced."""
    B, C = cache.shape[:2]
    T = blk.shape[1]
    rel = jnp.arange(C)[None, :] - lengths[:, None]           # (B,C)
    if wrap:
        rel = rel % C
    mask = (rel >= 0) & (rel < T)
    idx = jnp.clip(rel, 0, T - 1)
    idx = idx.reshape(idx.shape + (1,) * (cache.ndim - 2))
    src = jnp.take_along_axis(blk, idx, axis=1)
    mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
    return jnp.where(mask, src.astype(cache.dtype), cache)


def attn_layer_step(p, x, kcache, vcache, slot_pos, lengths, cfg: ModelConfig,
                    seg: Segment, aux, use_rope=True, kscale=None, vscale=None):
    """Block-decode attention layer against the cache.

    kcache/vcache: (B, C, KV, hd).  slot_pos: (B, C) int32 — absolute position
    stored in each slot (-1 = empty); for full caches slot_pos[b, j] = j when
    filled.  kscale/vscale: (B, C, KV) int8-cache scales when cfg.kv_quant.
    Returns (x, new_k, new_v, new_ks, new_vs, aux)."""
    B, T = x.shape[:2]
    C = kcache.shape[1]
    W = cfg.sliding_window if seg.kind == "local" else 0
    if seg.kind == "local" and cfg.rglru is not None:
        W = cfg.rglru.local_window
    from repro.launch.hints import hint
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    xn = hint(xn, None, None, "data")        # weight-stationary decode flow
    qpos = lengths[:, None] + jnp.arange(T)[None, :]          # (B,T)
    q, k, v = _qkv(p, xn, cfg)
    # cache I/O is batch-sharded: reshard the (tiny) q/k/v blocks, not the
    # (huge) cache or weights
    q = hint(q, "data", None, None, None)
    k = hint(k, "data", None, None, None)
    v = hint(v, "data", None, None, None)
    if use_rope:
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)

    # iota-select write: slot s <- blk[(s - lengths) mod C] where that index
    # falls in [0, T).  Pure elementwise select, so a sequence-sharded cache
    # stays sharded (a scatter at traced per-seq indices would force GSPMD
    # to regather the whole cache — 10x per-device memory at 32k decode).
    # Only rings wrap; full caches clip out-of-capacity eager writes.
    wrap = W > 0
    new_ks = new_vs = None
    if cfg.kv_quant:
        kq, ks_blk = kv_quantize(k)
        vq, vs_blk = kv_quantize(v)
        new_k = spread_write(kcache, kq, lengths, wrap)
        new_v = spread_write(vcache, vq, lengths, wrap)
        new_ks = spread_write(kscale, ks_blk, lengths, wrap)
        new_vs = spread_write(vscale, vs_blk, lengths, wrap)
        k_eff = kv_dequantize(new_k, new_ks, x.dtype)
        v_eff = kv_dequantize(new_v, new_vs, x.dtype)
    else:
        new_k = spread_write(kcache, k, lengths, wrap)
        new_v = spread_write(vcache, v, lengths, wrap)
        k_eff, v_eff = new_k, new_v

    mask = (slot_pos[:, None, :] <= qpos[:, :, None]) & (slot_pos[:, None, :] >= 0)
    if W:
        mask &= slot_pos[:, None, :] > qpos[:, :, None] - W
    # flash-decode layout: scores stay sequence-sharded over "model"
    # (the Pallas decode_attention kernel implements the same blocking)
    out = attend(q, hint(k_eff, "data", "model", None, None),
                 hint(v_eff, "data", "model", None, None), mask)
    x = x + out.reshape(B, T, -1) @ p["wo"]
    if seg.cross:
        x = _cross_attn(p, x, p["_xk"], p["_xv"], cfg)        # injected below
    x, aux = _ffn(p, x, cfg, seg.ffn, aux, dropless=True)
    return x, new_k, new_v, new_ks, new_vs, aux


def attn_layer_step_paged(p, x, pool, layer, tbl, lengths, cfg: ModelConfig,
                          seg: Segment, aux, use_rope=True):
    """Block-decode attention of layer `layer` against the POOLED paged cache.

    pool: the segment's whole pool, (kp, vp) or with int8 scales (kp, vp,
    ksp, vsp), each (n, P, page_size, ...): physical pages shared by every
    lane (page 0 = null page, never allocated), indexed by the traced
    `layer`.  The block is scattered into, and the lane views gathered out
    of, the whole pool, so a pool carried through the layer scan is
    updated in place and never copied.  tbl: (B, MPS) int32 block table —
    logical position t of lane b lives at physical slot
    ``tbl[b, t // ps] * ps + t % ps``; -1 entries clamp onto the null page
    so eager writes from dead lanes are harmless.  Speculative rollback is
    identical to the contiguous path: lengths simply don't advance past the
    accepted prefix and the stale slots are overwritten next block.
    Returns (x, new_pool, aux)."""
    B, T = x.shape[:2]
    n, Pp, ps = pool[0].shape[:3]
    MPS = tbl.shape[1]
    Lv = MPS * ps                                 # per-lane logical capacity
    from repro.launch.hints import hint
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    xn = hint(xn, None, None, "data")             # weight-stationary decode
    qpos = lengths[:, None] + jnp.arange(T)[None, :]              # (B, T)
    q, k, v = _qkv(p, xn, cfg)
    q = hint(q, "data", None, None, None)
    k = hint(k, "data", None, None, None)
    v = hint(v, "data", None, None, None)
    if use_rope:
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)

    def flat(c):
        return c.reshape((n, Pp * ps) + c.shape[3:])

    # eager paged write: scatter the T-token block through the block table.
    # Distinct lanes own disjoint pages, so indices never collide except on
    # the null page (garbage by construction, masked out of every read).
    from repro.serving.kv_pool import logical_to_physical
    _, wphys = logical_to_physical(tbl, qpos, ps)
    wphys = wphys.reshape(-1)

    def write(cache, blk):
        return flat(cache).at[layer, wphys].set(
            blk.reshape((B * T,) + blk.shape[2:]).astype(cache.dtype)
        ).reshape(cache.shape)

    if cfg.kv_quant:
        (kq, ks_blk), (vq, vs_blk) = kv_quantize(k), kv_quantize(v)
        blocks = (kq, vq, ks_blk, vs_blk)
    else:
        blocks = (k, v)
    new_pool = tuple(write(c, b) for c, b in zip(pool, blocks))

    # gather this lane's logical view back out of the pool (the Pallas
    # paged_decode_attention kernel fetches the same tiles page-by-page via
    # a scalar-prefetched block table instead of materializing the view)
    j = jnp.arange(Lv)
    rpage, rphys = logical_to_physical(
        tbl, jnp.broadcast_to(j[None, :], (B, Lv)), ps)           # (B, Lv)
    eff = [flat(c)[layer, rphys] for c in new_pool]               # (B,Lv,...)
    k_eff, v_eff = eff[0], eff[1]
    if cfg.kv_quant:
        k_eff = kv_dequantize(k_eff, eff[2], x.dtype)
        v_eff = kv_dequantize(v_eff, eff[3], x.dtype)
    slot_pos = jnp.where((rpage >= 0) & (j[None, :] < lengths[:, None] + T),
                         j[None, :], -1)
    mask = (slot_pos[:, None, :] <= qpos[:, :, None]) & (slot_pos[:, None, :] >= 0)
    out = attend(q, k_eff, v_eff, mask)
    x = x + out.reshape(B, T, -1) @ p["wo"]
    x, aux = _ffn(p, x, cfg, seg.ffn, aux, dropless=True)
    return x, new_pool, aux


def mla_layer_step(p, x, ckv_cache, krope_cache, lengths, cfg, seg, aux):
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    qpos = lengths[:, None] + jnp.arange(x.shape[1])[None, :]
    out, new_ckv, new_krope = mla_mod.mla_step(
        p, xn, ckv_cache, krope_cache, lengths, cfg.num_heads, cfg.mla,
        qpos, cfg.rope_theta)
    x = x + out
    x, aux = _ffn(p, x, cfg, seg.ffn, aux, dropless=True)
    return x, new_ckv, new_krope, aux


# ---------------------------------------------------------------------------
# Segment execution (scan over stacked layers)
# ---------------------------------------------------------------------------

def run_segment_full(sp, x, cfg: ModelConfig, seg: Segment, positions,
                     prefix_len, enc_out, collect, remat=False):
    """Returns (x, contribs stacked (n,...), aux)."""
    T = x.shape[1]
    if seg.kind == "local":
        W = cfg.rglru.local_window if cfg.rglru is not None else cfg.sliding_window
        spec = MaskSpec(window=W, prefix_len=prefix_len)
    elif cfg.arch_type == "audio" and seg.kind == "attn" and enc_out is None:
        spec = MaskSpec(bidirectional=True)   # encoder self-attention
    else:
        spec = MaskSpec(prefix_len=prefix_len)
    use_rope = cfg.arch_type != "audio"

    from repro.launch.hints import hint

    def body(carry, lp):
        x, aux = carry
        # pin batch-sharded activations: XLA must FSDP-gather the weights
        # rather than regather the (much larger) activations.  Batch takes
        # BOTH axes when it divides (pure-FSDP training layout, §Perf H4) —
        # the tuple falls back to "data" alone otherwise (decode/prefill).
        x = hint(x, ("data", "model"), None, None)
        if seg.kind == "ssm":
            x, contrib = ssm_mod.ssm_forward_full(lp, x, cfg.ssm, cfg.norm_eps)
        elif seg.kind == "rglru":
            x, contrib = rglru_mod.rglru_forward_full(lp, x, cfg.rglru, cfg.norm_eps)
            x, aux = _ffn(lp, x, cfg, seg.ffn, aux)
        else:
            x, contrib, aux = attn_layer_full(lp, x, cfg, seg, positions, spec,
                                              enc_out, aux, use_rope, collect)
        x = hint(x, "data", None, None)
        if not collect:
            contrib = {}
        return (x, aux), contrib

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), contribs = jax.lax.scan(body, (x, jnp.float32(0.0)), sp)
    return x, contribs, aux


def run_segment_step(sp, x, seg_cache, cross_cache, lengths, cfg: ModelConfig,
                     seg: Segment, tbl=None):
    """Returns (x, new_seg_cache, candidates, aux).  `tbl` is the paged
    block table (B, MPS) when the cache is paged (seg_cache holds pooled
    "kp"/"vp" pages instead of per-lane "k"/"v")."""
    T = x.shape[1]
    aux0 = jnp.float32(0.0)
    use_rope = cfg.arch_type != "audio"

    if "kp" in seg_cache:                  # pooled paged full attention
        # the pool rides the layer scan's CARRY, each layer writing and
        # reading its own index in place; scanned as xs/ys it would come
        # out as a fresh stacked buffer, a whole-pool copy per block
        keys = ("kp", "vp") + (("ksp", "vsp") if cfg.kv_quant else ())

        def body(carry, lp):
            x, aux, layer, pool = carry
            x, pool, aux = attn_layer_step_paged(
                lp, x, pool, layer, tbl, lengths, cfg, seg, aux, use_rope)
            return (x, aux, layer + 1, pool), None

        (x, aux, _, pool), _ = jax.lax.scan(
            body, (x, aux0, jnp.int32(0), tuple(seg_cache[k] for k in keys)),
            sp)
        return x, dict(zip(keys, pool)), {}, aux

    if seg.kind == "ssm":
        def body(carry, xs):
            x, aux = carry
            lp, conv, state = xs
            x, cand = ssm_mod.ssm_step(lp, x, {"conv": conv, "state": state},
                                       cfg.ssm, cfg.norm_eps)
            return (x, aux), cand
        (x, aux), cands = jax.lax.scan(
            body, (x, aux0), (sp, seg_cache["conv"], seg_cache["state"]))
        return x, seg_cache, cands, aux

    if seg.kind == "rglru":
        def body(carry, xs):
            x, aux = carry
            lp, conv, state = xs
            x, cand = rglru_mod.rglru_step(lp, x, {"conv": conv, "state": state},
                                           cfg.rglru, cfg.norm_eps)
            x, aux = _ffn(lp, x, cfg, seg.ffn, aux, dropless=True)
            return (x, aux), cand
        (x, aux), cands = jax.lax.scan(
            body, (x, aux0), (sp, seg_cache["conv"], seg_cache["state"]))
        return x, seg_cache, cands, aux

    if cfg.mla is not None:
        def body(carry, xs):
            x, aux = carry
            lp, ckv, krope = xs
            x, nckv, nkrope, aux = mla_layer_step(lp, x, ckv, krope, lengths,
                                                  cfg, seg, aux)
            return (x, aux), (nckv, nkrope)
        (x, aux), (nckv, nkrope) = jax.lax.scan(
            body, (x, aux0), (sp, seg_cache["ckv"], seg_cache["krope"]))
        return x, {"ckv": nckv, "krope": nkrope, "pos": seg_cache["pos"]}, {}, aux

    # attention (full or local ring)
    C = seg_cache["k"].shape[2]
    W = 0
    if seg.kind == "local":
        W = cfg.rglru.local_window if cfg.rglru is not None else cfg.sliding_window
    qpos = lengths[:, None] + jnp.arange(T)[None, :]
    rel = jnp.arange(C)[None, :] - lengths[:, None]
    if W:
        rel = rel % C
    new_pos = jnp.where((rel >= 0) & (rel < T), lengths[:, None] + rel,
                        seg_cache["pos"])

    quant = cfg.kv_quant

    def body(carry, xs):
        x, aux = carry
        ks = vs = None
        if seg.cross:
            lp, kc, vc, xk, xv = xs[:5]
            lp = dict(lp, _xk=xk, _xv=xv)
            if quant:
                ks, vs = xs[5], xs[6]
        else:
            lp, kc, vc = xs[:3]
            if quant:
                ks, vs = xs[3], xs[4]
        x, nk, nv, nks, nvs, aux = attn_layer_step(
            lp, x, kc, vc, new_pos, lengths, cfg, seg, aux, use_rope,
            kscale=ks, vscale=vs)
        ys = (nk, nv) + ((nks, nvs) if quant else ())
        return (x, aux), ys

    xs = (sp, seg_cache["k"], seg_cache["v"])
    if seg.cross:
        xs = xs + (cross_cache["xk"], cross_cache["xv"])
    if quant:
        xs = xs + (seg_cache["ks"], seg_cache["vs"])
    (x, aux), ys = jax.lax.scan(body, (x, aux0), xs)
    new_c = {"k": ys[0], "v": ys[1], "pos": new_pos}
    if quant:
        new_c["ks"], new_c["vs"] = ys[2], ys[3]
    return x, new_c, {}, aux


# ---------------------------------------------------------------------------
# Cache construction / commit
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, B: int, max_len: int, dtype=None) -> dict:
    """Cache pytree for the full stack (all segments, [0, L))."""
    dtype = dtype or cfg.jnp_dtype
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    segs = {}
    for seg in model_segments(cfg):
        n = seg.n
        if seg.kind == "ssm":
            c = ssm_mod.init_ssm_cache(n, B, cfg.d_model, cfg.ssm, dtype)
        elif seg.kind == "rglru":
            c = rglru_mod.init_rglru_cache(n, B, cfg.d_model, cfg.rglru, dtype)
        elif cfg.mla is not None:
            m = cfg.mla
            c = {"ckv": jnp.zeros((n, B, max_len, m.kv_lora_rank), dtype),
                 "krope": jnp.zeros((n, B, max_len, m.qk_rope_head_dim), dtype),
                 "pos": jnp.full((B, max_len), -1, jnp.int32)}
        else:
            if seg.kind == "local":
                W = cfg.rglru.local_window if cfg.rglru is not None else cfg.sliding_window
                C = W + RING_SLACK
            else:
                C = max_len
            kv_dtype = jnp.int8 if cfg.kv_quant else dtype
            c = {"k": jnp.zeros((n, B, C, KV, hd), kv_dtype),
                 "v": jnp.zeros((n, B, C, KV, hd), kv_dtype),
                 "pos": jnp.full((B, C), -1, jnp.int32)}
            if cfg.kv_quant:
                c["ks"] = jnp.zeros((n, B, C, KV), jnp.float32)
                c["vs"] = jnp.zeros((n, B, C, KV), jnp.float32)
        if seg.cross:
            F = cfg.encoder.num_frames
            c["xk"] = jnp.zeros((n, B, F, cfg.num_heads, hd), dtype)
            c["xv"] = jnp.zeros((n, B, F, cfg.num_heads, hd), dtype)
        segs[seg.name] = c
    return {"lengths": jnp.zeros((B,), jnp.int32), "segs": segs}


def init_paged_cache(cfg: ModelConfig, B: int, num_pages: int, page_size: int,
                     max_pages_per_slot: int, dtype=None) -> dict:
    """Paged cache pytree: full-attention KV pooled into `num_pages` shared
    fixed-size pages (+1 physical null page at index 0), addressed per lane
    through the block table ``cache["tbl"]`` (B, max_pages_per_slot) int32
    (-1 = unmapped).  Sliding-window rings and SSD/RG-LRU states stay
    per-slot — they are O(window)/O(1) per lane and gain nothing from
    paging.  Page ownership / recycling is host-side (``serving.kv_pool``).
    """
    dtype = dtype or cfg.jnp_dtype
    if cfg.mla is not None:
        raise NotImplementedError("paged KV: MLA latent caches not supported")
    if cfg.encoder is not None:
        raise NotImplementedError("paged KV: cross-attention caches not supported")
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    Pp = num_pages + 1                       # physical pages incl. null page
    segs = {}
    for seg in model_segments(cfg):
        n = seg.n
        if seg.kind == "ssm":
            c = ssm_mod.init_ssm_cache(n, B, cfg.d_model, cfg.ssm, dtype)
        elif seg.kind == "rglru":
            c = rglru_mod.init_rglru_cache(n, B, cfg.d_model, cfg.rglru, dtype)
        elif seg.kind == "local":
            W = cfg.rglru.local_window if cfg.rglru is not None else cfg.sliding_window
            C = W + RING_SLACK
            kv_dtype = jnp.int8 if cfg.kv_quant else dtype
            c = {"k": jnp.zeros((n, B, C, KV, hd), kv_dtype),
                 "v": jnp.zeros((n, B, C, KV, hd), kv_dtype),
                 "pos": jnp.full((B, C), -1, jnp.int32)}
            if cfg.kv_quant:
                c["ks"] = jnp.zeros((n, B, C, KV), jnp.float32)
                c["vs"] = jnp.zeros((n, B, C, KV), jnp.float32)
        else:                                # full attention -> page pool
            kv_dtype = jnp.int8 if cfg.kv_quant else dtype
            c = {"kp": jnp.zeros((n, Pp, page_size, KV, hd), kv_dtype),
                 "vp": jnp.zeros((n, Pp, page_size, KV, hd), kv_dtype)}
            if cfg.kv_quant:
                c["ksp"] = jnp.zeros((n, Pp, page_size, KV), jnp.float32)
                c["vsp"] = jnp.zeros((n, Pp, page_size, KV), jnp.float32)
        segs[seg.name] = c
    return {"lengths": jnp.zeros((B,), jnp.int32),
            "tbl": jnp.full((B, max_pages_per_slot), -1, jnp.int32),
            "segs": segs}


def map_slot_pages(cache: dict, slot, row: jax.Array) -> dict:
    """Point lane `slot`'s block-table row at physical pages `row`
    (MPS,) int32, -1-padded.  Pure table write — no KV moves."""
    tbl = jax.lax.dynamic_update_slice(cache["tbl"], row[None, :].astype(jnp.int32),
                                       (slot, 0))
    return dict(cache, tbl=tbl)


def set_block_tables(cache: dict, tbl: jax.Array) -> dict:
    """Replace the WHOLE block table (B, MPS) in one device op.  The serving
    engine keeps a host-side mirror of the table and batches every per-lane
    page-growth row update of a tick into this single push, instead of one
    ``map_slot_pages`` dispatch per lane per allocation.  No KV moves.  The
    table is written over the cache's own, so a donated cache is updated in
    place whole."""
    return dict(cache, tbl=jax.lax.dynamic_update_slice(
        cache["tbl"], tbl.astype(jnp.int32), (0, 0)))


def fill_cache_from_full(cfg: ModelConfig, cache: dict, contribs: dict,
                         T: int) -> dict:
    """Scatter prefill contributions (stacked (n,B,T,...)) into the cache.
    All sequences are assumed fully packed (length T)."""
    new_segs = dict(cache["segs"])
    for seg in model_segments(cfg):
        con = contribs.get(seg.name)
        if con is None or not con:
            continue
        c = dict(new_segs[seg.name])
        if seg.kind in ("ssm", "rglru"):
            c["conv"], c["state"] = con["conv"], con["state"]
        elif cfg.mla is not None and seg.kind in ("attn", "local"):
            S = c["ckv"].shape[2]
            c["ckv"] = jax.lax.dynamic_update_slice(
                c["ckv"], con["ckv"].astype(c["ckv"].dtype), (0, 0, 0, 0))
            c["krope"] = jax.lax.dynamic_update_slice(
                c["krope"], con["krope"].astype(c["krope"].dtype), (0, 0, 0, 0))
            c["pos"] = c["pos"].at[:, :T].set(jnp.arange(T)[None, :])
        else:
            Cap = c["k"].shape[2]
            kv_k, kv_v = con["k"], con["v"]
            if cfg.kv_quant:
                kv_k, ks_all = kv_quantize(kv_k)
                kv_v, vs_all = kv_quantize(kv_v)
            if seg.kind == "local" and T > Cap:
                keep = Cap
                pos = jnp.arange(T - keep, T)
                sl = pos % Cap
                c["k"] = c["k"].at[:, :, sl].set(kv_k[:, :, -keep:].astype(c["k"].dtype))
                c["v"] = c["v"].at[:, :, sl].set(kv_v[:, :, -keep:].astype(c["v"].dtype))
                c["pos"] = c["pos"].at[:, sl].set(pos[None, :])
                if cfg.kv_quant:
                    c["ks"] = c["ks"].at[:, :, sl].set(ks_all[:, :, -keep:])
                    c["vs"] = c["vs"].at[:, :, sl].set(vs_all[:, :, -keep:])
            else:
                c["k"] = jax.lax.dynamic_update_slice(
                    c["k"], kv_k.astype(c["k"].dtype), (0, 0, 0, 0, 0))
                c["v"] = jax.lax.dynamic_update_slice(
                    c["v"], kv_v.astype(c["v"].dtype), (0, 0, 0, 0, 0))
                c["pos"] = c["pos"].at[:, :T].set(jnp.arange(T)[None, :])
                if cfg.kv_quant:
                    c["ks"] = jax.lax.dynamic_update_slice(
                        c["ks"], ks_all, (0, 0, 0, 0))
                    c["vs"] = jax.lax.dynamic_update_slice(
                        c["vs"], vs_all, (0, 0, 0, 0))
        if seg.cross and "xk" in con:
            c["xk"], c["xv"] = (con["xk"].astype(c["xk"].dtype),
                                con["xv"].astype(c["xv"].dtype))
        new_segs[seg.name] = c
    B = cache["lengths"].shape[0]
    return {"lengths": jnp.full((B,), T, jnp.int32), "segs": new_segs}


def _slot_axis(leaf_name: str) -> int:
    """Batch axis of a per-segment cache leaf: `pos` maps (B, C); everything
    else is layer-stacked (n, B, ...)."""
    return 0 if leaf_name == "pos" else 1


def _insert_paged_seg(cfg: ModelConfig, seg_c: dict, src_c: dict,
                      tbl: jax.Array, slot, src_slot: int = 0) -> dict:
    """Splice a contiguous prefill lane into the slot's mapped pages: a
    block-table-indexed scatter of the source KV into the shared pool.
    Source positions past the mapped region clamp onto the null page."""
    from repro.serving.kv_pool import logical_to_physical
    Pp, ps = seg_c["kp"].shape[1:3]
    C_src = src_c["k"].shape[2]
    row = jax.lax.dynamic_slice_in_dim(tbl, slot, 1, 0)           # (1, MPS)
    _, phys = logical_to_physical(row, jnp.arange(C_src)[None, :], ps)
    phys = phys[0]

    def splice(pooled, src_leaf):
        piece = jax.lax.dynamic_slice_in_dim(src_leaf, src_slot, 1, 1)[:, 0]
        flatp = pooled.reshape((pooled.shape[0], Pp * ps) + pooled.shape[3:])
        return flatp.at[:, phys].set(piece.astype(pooled.dtype)
                                     ).reshape(pooled.shape)

    out = dict(seg_c, kp=splice(seg_c["kp"], src_c["k"]),
               vp=splice(seg_c["vp"], src_c["v"]))
    if cfg.kv_quant:
        out["ksp"] = splice(seg_c["ksp"], src_c["ks"])
        out["vsp"] = splice(seg_c["vsp"], src_c["vs"])
    return out


def copy_page(cache: dict, src_page, dst_page) -> dict:
    """Copy-on-write splice: duplicate physical page `src_page`'s KV (and
    int8 scales) onto page `dst_page` in every paged full-attention
    segment, in one device op per leaf.  Used by prefix-cache admission
    when a cached prompt prefix ends mid-page: the partially-matching
    cached page is copied into the lane's freshly allocated page, after
    which the lane appends through its own block table without ever
    touching the shared original.  Slots past the matched prefix carry
    donor garbage — overwritten by the consumer's tail prefill before any
    read, exactly like uninitialized pool slots.  `src_page`/`dst_page`
    may be traced scalars; pass 0 (the null page) for both to make the
    whole op a harmless no-op inside a jitted admission function."""
    segs = {}
    for name, seg_c in cache["segs"].items():
        if "kp" not in seg_c:
            segs[name] = seg_c
            continue
        out = dict(seg_c)
        for key in ("kp", "vp", "ksp", "vsp"):
            leaf = seg_c.get(key)
            if leaf is None:
                continue
            page = jax.lax.dynamic_slice_in_dim(leaf, src_page, 1, axis=1)
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                leaf, page, dst_page, axis=1)
        segs[name] = out
    return dict(cache, segs=segs)


def insert_slot(cfg: ModelConfig, cache: dict, src: Optional[dict], slot,
                src_slot: int = 0, shared_len=None) -> dict:
    """Continuous-batching cache surgery: copy sequence lane `src_slot` of
    cache `src` (e.g. a freshly prefilled B=1 contiguous cache) into lane
    `slot` of a live batched cache.  The source may be PARTIALLY BUILT: its
    per-slot sequence capacities (attention KV, quant scales, slot
    positions, MLA latents) only need to cover what was actually prefilled
    — e.g. a chunk-sized scratch holding the first prefill chunk — and are
    spliced into the lane's prefix; the destination lane must have been
    reset (``reset_slot``), so its tail is already inert (pos = -1, zero
    states).  Constant-size leaves (ring buffers, stateful-mixer
    conv/state, cross-attention KV) must share capacities exactly.  Paged
    full-attention segments instead scatter the source KV through the
    slot's block-table row (map the pages with ``map_slot_pages`` first).
    `slot` may be a traced scalar, so admission jits once per prompt (or
    chunk) shape.

    ``src=None`` (table-splice-without-copy): prefix-cache warm admission.
    The lane's first `shared_len` logical tokens already live in SHARED
    physical pages that ``map_slot_pages`` spliced into its block-table
    row, so the insert is pure bookkeeping — set the lane length to
    `shared_len` (traced ok) and move NO KV whatsoever; in particular
    nothing is ever scattered over the shared pages, which other lanes may
    be reading concurrently.  Only a pure paged full-attention cache
    qualifies (ring/SSM/RG-LRU segments hold per-lane state that cannot be
    shared by content)."""
    tbl = cache.get("tbl")
    if src is None:
        if shared_len is None:
            raise ValueError("insert_slot(src=None) needs shared_len")
        if tbl is None or any("kp" not in c for c in cache["segs"].values()):
            raise NotImplementedError(
                "table-splice admission (src=None) requires a pure paged "
                "full-attention cache — per-lane segment state cannot be "
                "prefix-shared")
        ln = jnp.asarray(shared_len, jnp.int32).reshape(1)
        lengths = jax.lax.dynamic_update_slice_in_dim(
            cache["lengths"], ln, slot, 0)
        return dict(cache, lengths=lengths)
    new_segs = {}
    for name, seg_c in cache["segs"].items():
        src_c = src["segs"][name]
        if "kp" in seg_c:
            new_segs[name] = _insert_paged_seg(cfg, seg_c, src_c, tbl, slot,
                                               src_slot)
            continue
        out = {}
        for kname, leaf in seg_c.items():
            ax = _slot_axis(kname)
            piece = jax.lax.dynamic_slice_in_dim(src_c[kname], src_slot, 1, ax)
            starts = [0] * leaf.ndim
            starts[ax] = slot
            out[kname] = jax.lax.dynamic_update_slice(
                leaf, piece.astype(leaf.dtype), tuple(starts))
        new_segs[name] = out
    ln = jax.lax.dynamic_slice_in_dim(src["lengths"], src_slot, 1, 0)
    lengths = jax.lax.dynamic_update_slice_in_dim(cache["lengths"], ln, slot, 0)
    out = {"lengths": lengths, "segs": new_segs}
    if tbl is not None:
        out["tbl"] = tbl
    return out


def reset_slot(cfg: ModelConfig, cache: dict, slot) -> dict:
    """Evict sequence lane `slot`: length 0, attention slots emptied
    (pos = -1), KV and stateful-mixer states zeroed — an inert lane that a
    later ``insert_slot`` can reuse.  Other lanes are untouched bit-for-bit.
    Paged segments need no KV work at all: the lane's block-table row is
    unmapped (-1) and the physical pages go back to the host-side pool —
    copy-free eviction."""
    new_segs = {}
    for name, seg_c in cache["segs"].items():
        if "kp" in seg_c:                    # pool pages are recycled, not zeroed
            new_segs[name] = seg_c
            continue
        out = {}
        for kname, leaf in seg_c.items():
            ax = _slot_axis(kname)
            shape = leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:]
            fill = -1 if kname == "pos" else 0
            piece = jnp.full(shape, fill, leaf.dtype)
            out[kname] = jax.lax.dynamic_update_slice_in_dim(leaf, piece,
                                                             slot, ax)
        new_segs[name] = out
    lengths = jax.lax.dynamic_update_slice_in_dim(
        cache["lengths"], jnp.zeros((1,), jnp.int32), slot, 0)
    out = {"lengths": lengths, "segs": new_segs}
    if "tbl" in cache:
        MPS = cache["tbl"].shape[1]
        out["tbl"] = jax.lax.dynamic_update_slice(
            cache["tbl"], jnp.full((1, MPS), -1, jnp.int32), (slot, 0))
    return out


def commit_cache(cfg: ModelConfig, cache: dict, cands: dict,
                 accept: jax.Array) -> dict:
    """Advance the cache by `accept` (B,) committed tokens; select stateful
    candidate states at index accept-1 (no-op rows where accept == 0).

    Ragged-depth audit (adaptive per-lane K): everything here is already
    per-lane — `accept` may be any value in [0, T] independently per batch
    row, the gather at accept-1 never reads past the candidate block, and
    rollback of the unaccepted tail is pure length truncation (the eager
    writes beyond ``lengths + accept`` are excluded from attention by the
    ``pos <= qpos`` mask and overwritten by the next block).  A lane whose
    depth k is below the batch draft width K commits at most k+1 tokens and
    its extra K-k eager writes are exactly the rejected-draft garbage this
    rollback rule already handles — no adaptive-depth special case."""
    new_segs = dict(cache["segs"])
    for seg in model_segments(cfg):
        cand = cands.get(seg.name)
        if not cand:
            continue
        c = dict(new_segs[seg.name])
        idx = jnp.maximum(accept - 1, 0)                    # (B,)
        keep_old = (accept == 0)

        def select(cand_arr, old):
            # cand_arr (n,B,T,...) -> per-batch gather at index `idx` on axis 2
            B = idx.shape[0]
            gidx = idx.reshape((1, B) + (1,) * (cand_arr.ndim - 2))
            sel = jnp.take_along_axis(cand_arr, gidx, axis=2).squeeze(2)
            mask_shape = (1, B) + (1,) * (sel.ndim - 2)
            return jnp.where(keep_old.reshape(mask_shape), old, sel.astype(old.dtype))

        c["conv"] = select(cand["conv"], c["conv"])
        c["state"] = select(cand["state"], c["state"])
        new_segs[seg.name] = c
    out = {"lengths": cache["lengths"] + accept, "segs": new_segs}
    if "tbl" in cache:
        out["tbl"] = cache["tbl"]
    return out


# ---------------------------------------------------------------------------
# Stack-level entry points
# ---------------------------------------------------------------------------

def forward_full(params_segs: dict, x: jax.Array, cfg: ModelConfig, lo: int,
                 hi: int, positions=None, prefix_len: int = 0, enc_out=None,
                 collect: bool = False, remat: bool = False):
    """Run layers [lo, hi) over a full sequence.  Returns (x, contribs, aux)."""
    B, T = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    contribs, aux = {}, jnp.float32(0.0)
    for seg in segments_in_range(cfg, lo, hi):
        x, con, a = run_segment_full(params_segs[seg.name], x, cfg, seg,
                                     positions, prefix_len, enc_out, collect,
                                     remat)
        contribs[seg.name] = con
        aux = aux + a
    return x, contribs, aux


def forward_step(params_segs: dict, x: jax.Array, cfg: ModelConfig, cache: dict,
                 lo: int, hi: int):
    """Run layers [lo, hi) on a T-token block against the cache.

    Returns (x, new_cache, cands, aux).  new_cache has attention caches
    updated eagerly; stateful segments updated only via `commit_cache`."""
    lengths = cache["lengths"]
    new_segs = dict(cache["segs"])
    cands, aux = {}, jnp.float32(0.0)
    for seg in segments_in_range(cfg, lo, hi):
        seg_cache = cache["segs"][seg.name]
        x, new_c, cand, a = run_segment_step(
            params_segs[seg.name], x, seg_cache, seg_cache, lengths, cfg, seg,
            tbl=cache.get("tbl"))
        new_segs[seg.name] = {**seg_cache, **new_c}
        if cand:
            cands[seg.name] = cand
        aux = aux + a
    out = {"lengths": lengths, "segs": new_segs}
    if "tbl" in cache:
        out["tbl"] = cache["tbl"]
    return x, out, cands, aux
