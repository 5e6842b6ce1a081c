"""Draft -> Verify -> commit: the self-speculative decoding engine (paper §3.2-3.3).

One speculative *block* at committed length t (all shapes static, batched,
runs inside ``jax.lax.while_loop``):

1. **Draft** — K+1 shallow feeds through layers [0, k).  Feed j embeds the
   pending token, produces ``h_k(t+j)``, and the LoRA draft head greedily
   proposes the next token.  Shallow caches advance eagerly; stateful
   mixers' per-feed states are stacked for later rollback-by-selection.
2. **Verify** — ONE deep pass of layers [k, L) over the h_k block (this is
   where self-speculation amortizes the deep compute), giving verifier
   greedy tokens ``y*(t+1 .. t+K+1)``.
3. **Commit** — the longest agreeing prefix m plus the verifier's
   correction/bonus token: m+1 tokens ∈ [1, K+1] per block.  The committed
   stream is *exactly* the target path's greedy decoding (tested as a
   property).  Accept/reject outcomes for drafted positions 1..K are logged
   to the replay buffer (r=1 accepted, r=0 first reject, counterfactuals
   excluded).

``k_spec=0`` degenerates to plain autoregressive decoding of the target
path through the same code path (the AR baseline).

``spec_block_step`` is the single owner of the block above; it is composed
two ways: ``speculative_generate`` loops it inside ``jax.lax.while_loop``
(batch decoding with tuple logging), and the continuous-batching
``ServingEngine`` interleaves it with per-slot cache surgery (admission /
retirement) so ragged traffic shares one persistent decode batch.

The cache may be contiguous (``init_cache``) or paged
(``init_paged_cache``): a paged cache carries its block table inside the
pytree (``cache["tbl"]``), so every draft feed and the deep verify pass
transparently read/write KV through the page indirection — the block-step
logic is layout-agnostic, and speculative rollback stays "truncate the
lane length" in both layouts (see repro.serving.kv_pool).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import buffer as buffer_mod
from repro.core import schedule as schedule_mod
from repro.core import scopes
from repro.core.lora import draft_logits
from repro.models import transformer as tfm
from repro.models.model import Model


class GenResult(NamedTuple):
    tokens: jax.Array          # (B, total) committed stream (prompt + gen)
    lengths: jax.Array         # (B,) valid token count
    blocks: jax.Array          # scalar: total verification steps
    committed: jax.Array       # scalar: total committed tokens (gen only)
    accepted_drafts: jax.Array # scalar: total accepted drafted tokens
    drafted: jax.Array         # scalar: total drafted tokens (valid blocks * K)
    buffer: Optional[dict]


class SuperstepResult(NamedTuple):
    """Result of a fused run of up to ``steps`` speculative blocks (one
    device dispatch, one host sync).  ``gen_buf[:, :gen_count]`` holds the
    tokens committed THIS superstep (per lane, already EOS/budget-capped);
    the per-lane counters summarize what the host would have accumulated
    block by block."""
    pending: jax.Array         # (B,) next pending token
    done: jax.Array            # (B,) bool — includes in-graph EOS/budget exits
    gen_buf: jax.Array         # (B, steps*(K+1)) committed tokens, capped
    gen_count: jax.Array       # (B,) valid prefix length of gen_buf
    lane_blocks: jax.Array     # (B,) blocks the lane was live for
    lane_committed: jax.Array  # (B,) cache advance (sum of accepts)
    lane_accepted: jax.Array   # (B,) accepted drafted tokens (sum of m)
    lane_drafted: jax.Array    # (B,) drafted tokens (sum of live-block depths)
    k_lane: jax.Array          # (B,) speculation depth after the last block
    accept_ema: jax.Array      # (B,) depth controller acceptance EMA
    k_cool: jax.Array          # (B,) depth controller cooldown counter
    accept_hist: jax.Array     # (K+1,) live blocks by accepted drafts m
    depth_hist: jax.Array      # (K+1,) live blocks by depth k they ran at
    cache: dict                # advanced decode cache
    buffer: Optional[dict]     # replay buffer with this superstep's tuples
    key: jax.Array             # threaded PRNG key (sampling path)


class BlockStep(NamedTuple):
    """Result of ONE speculative block (draft K+1, verify once, commit m+1)."""
    pending: jax.Array         # (B,) next pending token (unchanged where done)
    commit_vec: jax.Array      # (B, K+1) committed tokens (first `accept` valid)
    accept: jax.Array          # (B,) committed count: m+1 live, 0 where done
    m: jax.Array               # (B,) accepted drafted tokens this block
    cache: dict                # advanced decode cache
    hk_blk: jax.Array          # (B, K+1, d) draft-path hiddens (tuple logging)
    hL_blk: jax.Array          # (B, K+1, d) target-path hiddens
    d_blk: jax.Array           # (B, K+1) drafted tokens
    key: jax.Array             # threaded PRNG key (sampling path)


def _restack_cands(cand_stack):
    """scan-stacked shallow candidates (K+1, n, B, 1, ...) -> (n, B, K+1, ...)."""
    return jax.tree.map(lambda a: jnp.moveaxis(a.squeeze(3), 0, 2), cand_stack)


# ---------------------------------------------------------------------------
# Beyond-paper: temperature sampling with lossless rejection verification
# (Leviathan'23 speculative *sampling*; the paper evaluates greedy only)
# ---------------------------------------------------------------------------

def rejection_commit(key, d_blk, dprobs, vprobs, k_lane=None):
    """Speculative-sampling accept/reject (exact target distribution).

    d_blk (B, K+1) drafted tokens (position K is the bonus feed, unused for
    acceptance); dprobs/vprobs (B, K+1, V) drafter/verifier distributions.
    Accept drafted token i while u_i < p(d_i)/q(d_i); at the first reject
    emit a sample from norm(max(p - q, 0)); if all K accepted emit a bonus
    sample from p at position K.  Returns (m, correction (B,)).

    k_lane: optional (B,) per-lane speculation depth <= K.  Drafted
    positions at or beyond a lane's depth are forced-rejected (they were
    never really proposed), and the bonus branch fires at m == k_lane —
    exactness is per lane: each lane's stream is distributed as target
    sampling at ITS depth."""
    B, K1, V = dprobs.shape
    K = K1 - 1
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (B, K))
    p_at = jnp.take_along_axis(vprobs[:, :K], d_blk[:, :K, None], -1)[..., 0]
    q_at = jnp.take_along_axis(dprobs[:, :K], d_blk[:, :K, None], -1)[..., 0]
    ratio = p_at / jnp.maximum(q_at, 1e-20)
    ok = (u < ratio).astype(jnp.int32)
    if k_lane is not None:
        ok = ok * (jnp.arange(K)[None, :] < k_lane[:, None]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)                  # (B,)

    # correction distribution at position m: residual (reject) or p (bonus)
    pm = jnp.take_along_axis(vprobs, m[:, None, None], axis=1)[:, 0]   # (B,V)
    qm = jnp.take_along_axis(dprobs, m[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(pm - qm, 0.0)
    rsum = resid.sum(-1, keepdims=True)
    resid = jnp.where(rsum > 1e-20, resid / jnp.maximum(rsum, 1e-20), pm)
    k_eff = K if k_lane is None else k_lane
    dist = jnp.where((m == k_eff)[:, None], pm, resid)
    correction = jax.random.categorical(kr, jnp.log(jnp.maximum(dist, 1e-30)))
    return m, correction.astype(jnp.int32)


def spec_block_step(model: Model, params: dict, dvi_params: dict,
                    pending: jax.Array, cache: dict, *,
                    k_spec: Optional[int] = None,
                    done: Optional[jax.Array] = None,
                    temperature: float = 0.0,
                    key: Optional[jax.Array] = None,
                    k_lane: Optional[jax.Array] = None) -> BlockStep:
    """ONE speculative block-step against a live cache — the single owner of
    the draft -> verify -> commit logic.  Both ``speculative_generate`` (which
    loops it under ``jax.lax.while_loop``) and the continuous-batching serving
    engine (which interleaves it with per-slot admission/retirement) call this.

    pending: (B,) the last committed token per sequence.  done: (B,) bool —
    lanes marked done are masked out entirely (accept = 0, cache length and
    stateful-mixer states unchanged, pending passed through), which is how
    idle serving slots ride along in a fixed-size decode batch for free.

    k_lane: optional (B,) int32 per-lane speculation depth in [0, K].  The
    draft still runs K+1 feeds (static shapes, PRNG key schedule unchanged),
    but acceptance is masked so each lane commits at most ``k_lane + 1``
    tokens: positions at or beyond a lane's depth can never match (greedy)
    or be accepted (rejection sampling), and the correction/bonus token is
    drawn at position min(m, k_lane).  Rollback needs no new machinery — a
    short lane's extra eager writes are the same class of garbage as
    rejected full-depth drafts and roll back by length truncation.  With
    ``k_lane=None`` (or all lanes at K) the math is bit-identical to the
    fixed-depth path.

    temperature == 0: greedy drafting + longest-agreeing-prefix verification.
    temperature > 0: the drafter samples and the verifier runs Leviathan-style
    rejection sampling (lossless w.r.t. target-model sampling)."""
    cfg = model.cfg
    K = cfg.dvi.k_spec if k_spec is None else k_spec
    k, L = cfg.dvi.split_layer, cfg.num_layers
    B = pending.shape[0]
    sampling = temperature > 0.0
    key = key if key is not None else jax.random.PRNGKey(0)
    done = jnp.zeros((B,), bool) if done is None else done
    t0 = cache["lengths"]

    # done lanes must not advance draft state: a masked lane may be a lane
    # mid-chunked-prefill that will resume EXACTLY where it stopped, so its
    # stateful-mixer conv/state (which draft commits would otherwise evolve
    # on garbage pending tokens) and its draft lengths stay frozen.  Eager
    # attention writes still land but are rolled back by length masking.
    draft_accept = jnp.where(done, 0, 1).astype(jnp.int32)

    def draft_iter(carry, _):
        cache_c, pend, k_ = carry
        x = model.embed_block(params, pend[:, None], cache_c["lengths"])
        h_k, cache2, cands, _ = model.step(params, x, cache_c, 0, k)
        dlog = draft_logits(model, params, dvi_params, h_k[:, 0])
        if sampling:
            k_, sub = jax.random.split(k_)
            dprobs = jax.nn.softmax(dlog / temperature, axis=-1)
            d_tok = jax.random.categorical(sub, dlog / temperature).astype(jnp.int32)
        else:
            dprobs = jnp.zeros((B, 1), jnp.float32)     # unused placeholder
            d_tok = jnp.argmax(dlog, axis=-1).astype(jnp.int32)
        with jax.named_scope(scopes.COMMIT):
            cache3 = tfm.commit_cache(cfg, cache2, cands, draft_accept)
        return (cache3, d_tok, k_), (h_k[:, 0], d_tok, dprobs, cands)

    with jax.named_scope(scopes.DRAFT):
        (cache_d, _, key), (hk_s, d_s, dp_s, cand_stack) = jax.lax.scan(
            draft_iter, (cache, pending, key), None, length=K + 1)
        hk_blk = jnp.moveaxis(hk_s, 0, 1)               # (B, K+1, d)
        d_blk = jnp.moveaxis(d_s, 0, 1)                 # (B, K+1)

    # ---- verify: one deep pass over the h_k block ----
    with jax.named_scope(scopes.VERIFY):
        cache_v = dict(cache_d, lengths=t0)
        h_L_blk, cache_v2, deep_cands, _ = model.step(params, hk_blk,
                                                      cache_v, k, L)
        vlogits = model.logits(params, h_L_blk)
        y_star = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)   # (B, K+1)

        if sampling:
            key, sub = jax.random.split(key)
            vprobs = jax.nn.softmax(vlogits / temperature, axis=-1)
            dprobs = jnp.moveaxis(dp_s, 0, 1)           # (B, K+1, V)
            m, correction = rejection_commit(sub, d_blk, dprobs, vprobs,
                                             k_lane=k_lane)
        else:
            matches = (d_blk[:, :K] == y_star[:, :K])
            if k_lane is not None:
                matches = matches & (jnp.arange(K)[None, :]
                                     < k_lane[:, None])
            m = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1),
                        axis=1)
            correction = None
        accept = jnp.where(done, 0, m + 1)              # (B,)

    with jax.named_scope(scopes.COMMIT):
        all_cands = dict(_restack_cands(cand_stack), **deep_cands)
        cache_new = tfm.commit_cache(cfg, cache_v2, all_cands, accept)

        # ---- commit tokens ----
        ar = jnp.arange(K + 1)
        y_at_m = correction if sampling else \
            jnp.take_along_axis(y_star, m[:, None], axis=1)[:, 0]
        commit_vec = jnp.where(ar[None, :] < m[:, None], d_blk,
                               y_at_m[:, None])
        new_pending = jnp.where(done, pending, y_at_m)
    return BlockStep(new_pending, commit_vec, accept, m, cache_new,
                     hk_blk, h_L_blk, d_blk, key)


def log_block_tuples(cfg, buf: dict, step: BlockStep, prev_pending: jax.Array,
                     done: jax.Array, k_spec: Optional[int] = None,
                     k_lane: Optional[jax.Array] = None) -> dict:
    """Append one block's accept/reject tuples to the replay buffer: drafted
    positions 1..K up to and including the first reject; lanes marked `done`
    (finished sequences, idle serving slots, padded lanes) are excluded.
    With per-lane depths (`k_lane`), positions beyond a lane's depth were
    never proposed and are excluded too — a depth-k lane logs at most k
    tuples, so a throttled lane also stops flooding the replay buffer."""
    K = cfg.dvi.k_spec if k_spec is None else k_spec
    if K == 0:
        return buf
    B = step.d_blk.shape[0]
    d = cfg.d_model
    with jax.named_scope(scopes.LEARN_LOG):
        i_idx = jnp.arange(1, K + 1)                    # (K,)
        lim = jnp.minimum(step.m + 1, K if k_lane is None else k_lane)
        valid = (~done)[:, None] & (i_idx[None, :] <= lim[:, None])
        reward = (i_idx[None, :] <= step.m[:, None]).astype(jnp.float32)
        prev = jnp.concatenate([prev_pending[:, None], step.d_blk[:, :K - 1]],
                               axis=1) if K > 1 else prev_pending[:, None]
        return buffer_mod.add_block(
            buf,
            step.hk_blk[:, :K].reshape(B * K, d),
            step.hL_blk[:, :K].reshape(B * K, d),
            step.d_blk[:, :K].reshape(B * K),
            reward.reshape(B * K),
            jnp.broadcast_to(i_idx[None], (B, K)).reshape(B * K),
            prev.reshape(B * K),
            valid.reshape(B * K))


def spec_superstep(model: Model, params: dict, dvi_params: dict,
                   pending: jax.Array, cache: dict, *, steps: int,
                   done: Optional[jax.Array] = None,
                   budget: Optional[jax.Array] = None,
                   eos_id: int = 1,
                   buf: Optional[dict] = None,
                   collect: bool = False,
                   k_spec: Optional[int] = None,
                   temperature: float = 0.0,
                   key: Optional[jax.Array] = None,
                   k_lane: Optional[jax.Array] = None,
                   depth_cfg=None,
                   accept_ema: Optional[jax.Array] = None,
                   k_cool: Optional[jax.Array] = None,
                   k_cap: Optional[jax.Array] = None) -> SuperstepResult:
    """Fused multi-block tick: run up to ``steps`` speculative blocks inside
    one ``jax.lax.while_loop`` so the serving engine syncs with the device
    once per superstep instead of once per block.

    Everything the per-block host loop did between dispatches happens
    in-graph: committed tokens are appended to a per-lane buffer with the
    exact sequential semantics of the host loop (stop at the lane's
    remaining ``budget``; stop just after the first EOS), lanes flip their
    ``done`` flag the block they exhaust budget or emit EOS (masking them
    out of every later block: accept = 0, cache untouched, no tuples), and
    per-lane block/commit/accept counters accumulate so host stats need only
    the compact summary.  The loop exits early once every lane is done.

    ``budget``: (B,) int32 REMAINING generation budget per lane (max_new
    minus tokens already emitted in earlier supersteps).  The committed
    stream across supersteps is bit-identical to per-block ticking — the
    only behavioural difference is that retirement/admission happen at
    superstep boundaries (a finished lane rides along masked until the
    host next harvests).

    Adaptive depth: ``k_lane`` (B,) gives each lane its own speculation
    depth <= K; with ``depth_cfg`` (a ``schedule.DepthConfig``) the depth
    controller also runs IN-GRAPH after every block — the acceptance EMA
    (``accept_ema``) and cooldown (``k_cool``) ride the while-loop carry
    and the updated (k, ema, cool) come back in the result, so adapting
    depth per block costs zero extra host syncs.  ``k_cap`` (B,) is a hard
    per-lane ceiling the controller cannot raise k beyond — the serving
    engine passes the depth it provisioned KV pages for, decoupling pool
    soundness from controller behaviour.  Depth changes take effect at the
    NEXT block (boundaries only — the adaptive-depth contract).  All of
    this is inert by default: with ``k_lane=None`` and ``depth_cfg=None``
    the block math is bit-identical to the fixed-depth path."""
    cfg = model.cfg
    K = cfg.dvi.k_spec if k_spec is None else k_spec
    B = pending.shape[0]
    key = key if key is not None else jax.random.PRNGKey(0)
    done = jnp.zeros((B,), bool) if done is None else done
    budget = (jnp.full((B,), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
              if budget is None else budget.astype(jnp.int32))
    if collect and buf is None:
        buf = buffer_mod.init_buffer(cfg)
    ragged = k_lane is not None
    k0 = (jnp.full((B,), K, jnp.int32) if k_lane is None
          else k_lane.astype(jnp.int32))
    ema0 = (jnp.zeros((B,), jnp.float32) if accept_ema is None
            else accept_ema.astype(jnp.float32))
    cool0 = (jnp.zeros((B,), jnp.int32) if k_cool is None
             else k_cool.astype(jnp.int32))
    khi = None if k_cap is None else jnp.minimum(k_cap.astype(jnp.int32), K)
    cap = steps * (K + 1)
    ar = jnp.arange(K + 1)
    lane = jnp.arange(B)
    zeros = jnp.zeros((B,), jnp.int32)

    def body(carry):
        (i, pending, done, gen_buf, gen_count, blocks, committed, accepted,
         drafted, k, ema, cool, a_hist, d_hist, cache, buf, key) = carry
        live = (~done).astype(jnp.int32)
        blk = spec_block_step(model, params, dvi_params, pending, cache,
                              k_spec=K, done=done, temperature=temperature,
                              key=key, k_lane=k if ragged else None)
        if collect:
            buf = log_block_tuples(cfg, buf, blk, pending, done, k_spec=K,
                                   k_lane=k if ragged else None)
        with jax.named_scope(scopes.COMMIT):
            # sequential commit semantics, vectorized: candidate positions
            # are the accepted prefix that still fits the lane budget; an
            # EOS among them is written and stops everything after it
            can = ((ar[None, :] < blk.accept[:, None])
                   & (gen_count[:, None] + ar[None, :] < budget[:, None]))
            hit_eos = can & (blk.commit_vec == eos_id)
            eos_before = jnp.cumsum(hit_eos.astype(jnp.int32), axis=1) \
                - hit_eos.astype(jnp.int32)
            written = can & (eos_before == 0)
            dest = jnp.where(written,
                             lane[:, None] * cap + gen_count[:, None]
                             + ar[None, :],
                             B * cap)                       # OOB -> dropped
            gen_buf = gen_buf.reshape(-1).at[dest.reshape(-1)].set(
                blk.commit_vec.reshape(-1), mode="drop").reshape(B, cap)
            new_count = gen_count + written.sum(axis=1, dtype=jnp.int32)
            new_done = done | jnp.any(hit_eos, axis=1) | (new_count >= budget)
            drafted = drafted + k * live  # depth the block actually ran at
            # telemetry histograms, in-graph and UNCONDITIONAL (telemetry
            # on/off shares one compiled graph): per live block, bucket the
            # verifier's accepted-draft count m and the depth k the block
            # ran at.  Rides the superstep's existing host sync — zero
            # extra device round-trips
            a_hist = a_hist.at[blk.m].add(live, mode="drop")
            d_hist = d_hist.at[k].add(live, mode="drop")
            if depth_cfg is not None:
                # controller sees THIS block's outcome (depth k, accepted
                # m) and adjusts for the next block; masked lanes keep
                # frozen state
                k, ema, cool = schedule_mod.depth_update(
                    depth_cfg, k, ema, cool, blk.m, ~done, k_hi=khi)
        return (i + 1, blk.pending, new_done, gen_buf, new_count,
                blocks + live, committed + blk.accept,
                accepted + blk.m * live, drafted,
                k, ema, cool, a_hist, d_hist, blk.cache, buf, blk.key)

    def cond(carry):
        return (carry[0] < steps) & ~jnp.all(carry[2])

    hist0 = jnp.zeros((K + 1,), jnp.int32)
    carry = (jnp.int32(0), pending, done, jnp.zeros((B, cap), jnp.int32),
             zeros, zeros, zeros, zeros, zeros, k0, ema0, cool0,
             hist0, hist0, cache, buf, key)
    (_, pending, done, gen_buf, gen_count, blocks, committed, accepted,
     drafted, k_out, ema_out, cool_out, a_hist, d_hist, cache, buf, key) = \
        jax.lax.while_loop(cond, body, carry)
    return SuperstepResult(pending, done, gen_buf, gen_count, blocks,
                           committed, accepted, drafted, k_out, ema_out,
                           cool_out, a_hist, d_hist, cache, buf, key)


def speculative_generate(model: Model, params: dict, dvi_params: dict,
                         prompts: jax.Array, max_new: int,
                         k_spec: Optional[int] = None,
                         cache_len: Optional[int] = None,
                         eos_id: int = 1,
                         collect: bool = False,
                         buf: Optional[dict] = None,
                         aux_inputs: Optional[dict] = None,
                         temperature: float = 0.0,
                         key: Optional[jax.Array] = None,
                         live_mask: Optional[jax.Array] = None) -> GenResult:
    """Batched lossless speculative generation with optional tuple logging.

    prompts: (B, Tp) with Tp >= 2, all sequences the same length (serving
    buckets/pads upstream — required for exact stateful-mixer prefill).

    temperature == 0 (paper setting): greedy drafting + longest-prefix
    verification.  temperature > 0 (beyond-paper): the drafter *samples*
    and the verifier runs Leviathan-style rejection sampling — the emitted
    stream is distributed exactly as target-model sampling.

    live_mask: (B,) bool — lanes marked False (e.g. batch-padding duplicates
    in the sync serving path) generate nothing, log no tuples, and count in
    no statistics."""
    cfg = model.cfg
    K = cfg.dvi.k_spec if k_spec is None else k_spec
    B, Tp = prompts.shape
    key = key if key is not None else jax.random.PRNGKey(0)
    assert Tp >= 2, "need at least 2 prompt tokens (one prefill + one pending)"
    total = Tp + max_new + K + 2
    cache_cap = cache_len or (total + tfm.RING_SLACK)

    # ---- prefill all but the last prompt token; it becomes `pending` ----
    _, cache, _ = model.prefill(params, prompts[:, :Tp - 1], aux_inputs,
                                max_len=cache_cap)
    pending = prompts[:, Tp - 1]
    out = jnp.zeros((B, total), jnp.int32).at[:, :Tp].set(prompts)
    out_len = jnp.full((B,), Tp, jnp.int32)
    done = jnp.zeros((B,), bool) if live_mask is None else ~live_mask
    if collect and buf is None:
        buf = buffer_mod.init_buffer(cfg)
    stats = {k_: jnp.int32(0) for k_ in
             ("blocks", "committed", "accepted_drafts", "drafted")}

    def body(carry):
        out, out_len, pending, done, cache, buf, stats, key = carry
        blk = spec_block_step(model, params, dvi_params, pending, cache,
                              k_spec=K, done=done, temperature=temperature,
                              key=key)
        out = jax.vmap(lambda o, cv, s: jax.lax.dynamic_update_slice(o, cv, (s,)))(
            out, blk.commit_vec, out_len)
        ar = jnp.arange(K + 1)
        emitted_eos = jnp.any((ar[None, :] < blk.accept[:, None])
                              & (blk.commit_vec == eos_id), axis=1)
        out_len = out_len + blk.accept
        new_done = done | emitted_eos | (out_len >= Tp + max_new)

        if collect:
            buf = log_block_tuples(cfg, buf, blk, pending, done, k_spec=K)

        live = (~done).astype(jnp.int32)
        stats2 = {
            "blocks": stats["blocks"] + live.sum(),
            "committed": stats["committed"] + blk.accept.sum(),
            "accepted_drafts": stats["accepted_drafts"] + (blk.m * live).sum(),
            "drafted": stats["drafted"] + K * live.sum(),
        }
        return (out, out_len, blk.pending, new_done, blk.cache, buf, stats2,
                blk.key)

    def cond(carry):
        done = carry[3]
        return ~jnp.all(done)

    carry = (out, out_len, pending, done, cache, buf, stats, key)
    out, out_len, pending, done, cache, buf, stats, key = jax.lax.while_loop(
        cond, body, carry)
    return GenResult(out, out_len, stats["blocks"], stats["committed"],
                     stats["accepted_drafts"], stats["drafted"], buf)


def ar_generate(model: Model, params: dict, prompts, max_new, **kw):
    """Plain greedy autoregressive decoding of the target path (K = 0)."""
    dvi_dummy = {"A": jnp.zeros((model.cfg.d_model, 1), jnp.float32),
                 "B": jnp.zeros((1, model.cfg.vocab_size), jnp.float32)}
    return speculative_generate(model, params, dvi_dummy, prompts, max_new,
                                k_spec=0, collect=False, **kw)


def serve_step(model: Model, params: dict, dvi_params: dict, pending,
               cache, k_spec: Optional[int] = None):
    """ONE greedy speculative step against an existing cache — the unit the
    decode dry-run shapes lower (decode_32k / long_500k).  Thin compatibility
    wrapper over ``spec_block_step`` (the single draft/verify/commit owner).
    Returns (new_pending, commit_vec, accept, new_cache)."""
    blk = spec_block_step(model, params, dvi_params, pending, cache,
                          k_spec=k_spec)
    return blk.pending, blk.commit_vec, blk.accept, blk.cache
