"""Fused LoRA draft-head logits: (W_S + gamma A B) h in one vocab-tiled pass.

The rank-r bottleneck u = h @ A is computed once per row-block (at the first
vocab tile) and parked in VMEM scratch; every vocab tile then fuses
``h @ W_blk + gamma * u @ B_blk`` on the MXU.  Compared to the unfused
``h@W + (h@A)@B`` this reads/writes the (T, V) logits exactly once and never
materializes the (T, r) intermediate in HBM.

Grid: (T/bt, V/bv), vocab innermost ('arbitrary' — scratch reuse).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(h_ref, w_ref, a_ref, b_ref, out_ref, u_ref, *, gamma: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _proj():
        u_ref[...] = jnp.dot(h_ref[...], a_ref[...],
                             preferred_element_type=jnp.float32)

    base = jnp.dot(h_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    lora = jnp.dot(u_ref[...], b_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = base + gamma * lora


def lora_logits(h: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                gamma: float, *, block_t: int = 128, block_v: int = 2048,
                interpret: bool = False):
    """h (T, d), w (d, V), a (d, r), b (r, V) -> logits (T, V) float32."""
    T, d = h.shape
    V = w.shape[1]
    r = a.shape[1]
    bt = min(block_t, max(8, T))
    bv = min(block_v, V)
    Tp = -(-T // bt) * bt
    Vp = -(-V // bv) * bv
    if Tp != T:
        h = jnp.pad(h, ((0, Tp - T), (0, 0)))
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
        b = jnp.pad(b, ((0, 0), (0, Vp - V)))

    out = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma),
        grid=(Tp // bt, Vp // bv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((d, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bv), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Vp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(h, w, a, b)
    return out[:T, :V]
