"""Paged flash-decode GQA attention: K/V tiles gathered via a block table.

Same online-softmax flash-decode loop as ``decode_attention``, but the KV
cache is the paged pool layout (``repro.serving.kv_pool``): physical pages
``(P, page_size, KV, hd)`` shared by every lane, addressed through a
per-lane block table ``(B, max_pages)`` (int32, -1 = unmapped, physical
page 0 = null page).

The block table, per-lane lengths, AND per-lane active page counts ride in
as **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``), so the
BlockSpec index map resolves the *physical* page to DMA before the kernel
body runs — the grid walks logical pages, the memory system fetches
``tbl[b, p]``.  Unmapped entries clamp onto the null page; their scores are
masked to -inf (the same rule the jnp model path applies), so null-page
garbage never reaches the accumulator.  A page holding slots past the
lane's length (the eager speculative tail) is masked per-slot by
``j < length``.

Each grid step stages one whole page ``(ps, KV*hd)`` — every KV head,
viewed through a free reshape so the tile's last two dimensions are
(page, KV*hd) as the TPU tiling requires — and runs one online-softmax
update per KV head on its lane-aligned ``hd``-wide column slice.

Grid: (B, max_pages) — batch parallel, logical pages innermost
sequential.  **Per-lane early-out**: pages at or beyond the
lane's active page count contribute nothing, so the index map clamps them
onto the lane's LAST active page (a repeated block index means Mosaic
skips the DMA — the tile is already resident) and the kernel body skips
the flash update entirely (``pl.when(p < page_count)``); the output is
written the moment the lane's last active page retires instead of at the
end of the sweep.  A lane holding 2 of 64 pages therefore pays 2 tiles of
DMA + compute, not 64 — the remaining grid steps are empty husks.
``page_counts`` defaults to ``ceil(lengths / page_size)`` and may be
passed explicitly (e.g. to force the full masked sweep for benchmarking).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(tbl_ref, len_ref, pc_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
            l_ref, acc_ref, *, ps: int, hd: int, kv: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    pc = pc_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p < pc)
    def _update():
        length = len_ref[b]
        mapped = tbl_ref[b, p] >= 0
        for h in range(kv):
            q = q_ref[0, h]                        # (G, hd)
            k = k_ref[0, :, h * hd:(h + 1) * hd]   # (ps, hd)
            v = v_ref[0, :, h * hd:(h + 1) * hd]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (G, ps)
            j = p * ps + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(mapped & (j < length), scores, NEG)

            m_prev = m_ref[h]                      # (G, 1)
            m_cur = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            pexp = jnp.exp(scores - m_cur)         # (G, ps)
            l_ref[h] = l_ref[h] * alpha + pexp.sum(axis=-1, keepdims=True)
            acc_ref[h] = (acc_ref[h] * alpha
                          + jnp.dot(pexp, v.astype(jnp.float32),
                                    preferred_element_type=jnp.float32))
            m_ref[h] = m_cur

    @pl.when(p == pc - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           block_tables: jax.Array, *,
                           page_counts: jax.Array | None = None,
                           interpret: bool = False):
    """q (B, H, hd); k_pages/v_pages (P, ps, KV, hd); lengths (B,);
    block_tables (B, MPS) int32; page_counts (B,) int32 active pages per
    lane (default ceil(lengths / ps)) -> out (B, H, hd)."""
    B, H, hd = q.shape
    P, ps, KV = k_pages.shape[:3]
    MPS = block_tables.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scale = 1.0 / math.sqrt(hd)
    if page_counts is None:
        page_counts = (lengths.astype(jnp.int32) + ps - 1) // ps
    page_counts = jnp.clip(page_counts.astype(jnp.int32), 1, MPS)

    # pages viewed as (P, ps, KV*hd): a free reshape whose tiles hold every
    # KV head, each a lane-aligned hd-wide column slice
    k_pages = k_pages.reshape(P, ps, KV * hd)
    v_pages = v_pages.reshape(P, ps, KV * hd)

    def kv_map(b, p, tbl, lens, pc):
        # beyond the lane's active pages: revisit the last active page so
        # the pipeline issues no new DMA for the skipped grid steps
        pe = jnp.minimum(p, pc[b] - 1)
        return (jnp.maximum(tbl[b, pe], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MPS),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd),
                         lambda b, p, tbl, lens, pc: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, KV * hd), kv_map),
            pl.BlockSpec((1, ps, KV * hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, p, tbl, lens, pc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, hd=hd, kv=KV, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), page_counts,
      qg, k_pages, v_pages)
    return out.reshape(B, H, hd)
