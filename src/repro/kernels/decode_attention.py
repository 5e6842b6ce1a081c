"""Flash-decode GQA attention over a contiguous KV cache (single query).

Dispatch through ``repro.kernels.ops.decode_attention`` (the single entry
point choosing ref vs Pallas vs paged); this module only holds the
contiguous Pallas implementation.

TPU adaptation of flash-decoding: the KV sequence is blocked; each grid
step stages one (bs, KV*hd) K/V tile HBM->VMEM holding EVERY KV head, and
updates one online-softmax accumulator (m, l, acc) per KV head, held in
VMEM scratch for the whole q-head *group* sharing that head (GQA: G = H / KV
query heads per KV head).  The normalized output is written once on the
last block.  Length masking uses the per-sequence cache length (slots >=
length are dead speculative writes); the lengths ride in as a
scalar-prefetch operand (SMEM).

K/V are viewed as (B, S, KV*hd) — a free reshape of the cache layout — so
a tile's last two dimensions are (bs, KV*hd) and each head is a lane-aligned
``hd``-wide column slice (the TPU tiling wants the second-to-last block dim
a multiple of 8 or the whole axis, which a single-head ``(bs, 1, hd)`` block
is not).

Grid: (B, S/bs) — batch parallel, seq innermost sequential.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bs: int, hd: int, kv: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)
    nsb = pl.num_programs(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    for h in range(kv):
        q = q_ref[0, h]                            # (G, hd)
        k = k_ref[0, :, h * hd:(h + 1) * hd]       # (bs, hd)
        v = v_ref[0, :, h * hd:(h + 1) * hd]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (G, bs)
        slot = s * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(slot < length, scores, NEG)

        m_prev = m_ref[h]                          # (G, 1)
        m_cur = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(scores - m_cur)                # (G, bs)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = (acc_ref[h] * alpha
                      + jnp.dot(p, v.astype(jnp.float32),
                                preferred_element_type=jnp.float32))
        m_ref[h] = m_cur

    @pl.when(s == nsb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, *, block_s: int = 512,
                            interpret: bool = False):
    """q (B, H, hd); k/v (B, S, KV, hd); lengths (B,) -> out (B, H, hd)."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    Sp = -(-S // bs) * bs
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    k = k.reshape(B, Sp, KV * hd)
    v = v.reshape(B, Sp, KV * hd)
    qg = q.reshape(B, KV, G, hd)
    scale = 1.0 / math.sqrt(hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, s, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV * hd), lambda b, s, lens: (b, s, 0)),
            pl.BlockSpec((1, bs, KV * hd), lambda b, s, lens: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, s, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, hd=hd, kv=KV, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, H, hd)
