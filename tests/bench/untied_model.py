"""A model module of another architecture, for the harness's CPU tests: the
Qwen3 decoder of ``model_ref`` with an untied unembedding, which the program
serves as its ``lm_head``.  The tests copy it into a copy of the benchmark
and name it in a configuration's ``reference``, as a change that adds an
architecture would add its module, and it meets ``model_ref``'s contract
with what it shares imported from there.

The weights are ``model_ref``'s with the final norm's gain (the random sign
and logit scale of ``model_ref.Recipe``) moved into the head:
``head = (norm * embed).T`` and the norm's gain is 1.  The logits read the
same predictions as the tied model's, through a matrix that is not the
embedding, so a program that reads the embedding in the head's place serves
other tokens.
"""
from functools import partial

import jax
import jax.numpy as jnp

import model_ref as base

fingerprint = base.fingerprint
to_float32 = base.to_float32
program_layout = base.program_layout


def program_config(conf: dict):
    if conf["tie_word_embeddings"]:
        raise ValueError("this module serves an untied unembedding")
    return base.program_config(conf)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init(dm, rc, bounds, key, *slots) -> dict:
    w = base._init(dm, rc, bounds, key, *slots)
    head = (w["norm"][None, :] * w["embed"].astype(jnp.float32)).T
    return dict(w, norm=jnp.ones_like(w["norm"]),
                head=head.astype(w["embed"].dtype))


def init_weights(conf: dict, bounds, table) -> dict:
    dm, rc = base.Dims.from_config(conf), base.Recipe.from_config(conf)
    bounds = base.check_bounds(bounds, dm.layers)
    slots = base.plant_slots(dm, rc, conf["dvi"]["split_layer"], table)
    return _init(dm, rc, bounds, jax.random.PRNGKey(rc.seed),
                 *map(jnp.asarray, slots))


def to_program(w: dict, layout) -> dict:
    return dict(base.to_program(w, layout), lm_head=w["head"])


def forward(w: dict, tokens, dm, mode: str = "f32"):
    """``model_ref.forward`` with the logits read through ``w["head"]``."""
    keep = (lambda a: base._fake_fp8(a, -1)) if mode == "fp8" else (
        lambda a: a)
    x = w["embed"][tokens].astype(jnp.float32)
    for stack in w["stacks"]:
        x, _ = jax.lax.scan(
            lambda c, p: (keep(base._layer(c, p, dm, mode)), None), x, stack)
    x = base.rms_norm(x, w["norm"], dm.eps)
    return keep(base.linear(x, w["head"], mode))


def gaps(conf: dict, w32: dict, samples, pad_to: int,
         control: bool = False) -> dict:
    return base.gaps_of(forward, base.Dims.from_config(conf), w32, samples,
                        pad_to, control)


def span_flops(conf: dict, lo: int, hi: int) -> int:
    """The untied head costs what the tied one does, ``d x vocab`` a
    token."""
    return base.span_flops(conf, lo, hi)
