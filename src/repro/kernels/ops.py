"""Jit'd public wrappers for the Pallas kernels — the single dispatch point.

On a TPU backend the kernels compile to Mosaic.  On the CPU backend they
run in ``interpret=True`` mode — the kernel body executes in Python for
validation against the ref.py oracles.  Any other backend is an error: a
kernel never falls back silently.

``decode_attention`` dispatches across the three implementations by
argument/`impl`: the pure-jnp oracle (``impl="ref"``), the contiguous
flash-decode Pallas kernel (default), and the paged block-table kernel
(``paged_decode_attention`` / ``impl="paged"`` spelled as the dedicated
entry point, since the paged cache has different operands).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import \
    decode_attention_pallas as _decode_attention
from repro.kernels.lora_logits import lora_logits as _lora_logits
from repro.kernels.paged_decode_attention import \
    paged_decode_attention as _paged_decode_attention
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.verify_argmax import verify_argmax as _verify_argmax


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                           f"{backend!r}: they compile for 'tpu' and "
                           f"interpret on 'cpu' only")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("block_t", "block_v"))
def verify_argmax(h, w, block_t: int = 128, block_v: int = 2048):
    return _verify_argmax(h, w, block_t=block_t, block_v=block_v,
                          interpret=_interpret())


@partial(jax.jit, static_argnames=("gamma", "block_t", "block_v"))
def lora_logits(h, w, a, b, gamma: float, block_t: int = 128,
                block_v: int = 2048):
    return _lora_logits(h, w, a, b, gamma, block_t=block_t, block_v=block_v,
                        interpret=_interpret())


@partial(jax.jit, static_argnames=("block_s", "impl"))
def decode_attention(q, k, v, lengths, block_s: int = 512, impl: str = "pallas"):
    """Contiguous-cache flash decode.  impl: "pallas" (default; interpret
    mode on CPU) or "ref" (pure-jnp oracle)."""
    if impl == "ref":
        return ref.ref_decode_attention(q, k, v, lengths)
    return _decode_attention(q, k, v, lengths, block_s=block_s,
                             interpret=_interpret())


@partial(jax.jit, static_argnames=("impl",))
def paged_decode_attention(q, k_pages, v_pages, lengths, block_tables,
                           page_counts=None, impl: str = "pallas"):
    """Paged-cache flash decode: K/V tiles gathered through the per-lane
    block table (see repro.serving.kv_pool for the layout).  Lanes early-out
    of the page sweep after `page_counts` pages (default: just enough to
    cover `lengths`)."""
    if impl == "ref":
        return ref.ref_paged_decode_attention(q, k_pages, v_pages, lengths,
                                              block_tables,
                                              page_counts=page_counts)
    return _paged_decode_attention(q, k_pages, v_pages, lengths, block_tables,
                                   page_counts=page_counts,
                                   interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xh, Bc, Cc, dt, A, chunk: int = 128):
    return _ssd_scan(xh, Bc, Cc, dt, A, chunk, interpret=_interpret())


__all__ = ["verify_argmax", "lora_logits", "decode_attention",
           "paged_decode_attention", "ssd_scan", "ref"]
