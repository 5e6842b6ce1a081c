"""The serving programs take the KV cache donated: each compiled program
writes its output cache into the buffers it was handed (no whole-pool
copy per dispatch), the handle it was handed is dead afterwards, and the
engine counts every such dispatch in ``dvi_serving_kv_pool_donations_total``.
The supersteps take the cache undonated, but their layer scans carry the
paged pool in place, so no copy of it rides the block loop."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core import online
from repro.models.model import build_model
from repro.serving import Request, ServingEngine

SLOTS, CAP, PAGES, PAGE, CHUNK = 3, 40, 30, 4, 8
DONATING = ("_admit_fn", "_admit_paged_fn", "_admit_prefix_fn",
            "_admit_chunk_fn", "_chunk_fn", "_set_tbl_fn", "_reset_fn")


@pytest.fixture(scope="module")
def backbone():
    cfg = tiny_cfg("vicuna-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(backbone, paged, kv_pages=PAGES, **kw):
    _, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    paging = dict(kv_pages=kv_pages, kv_page_size=PAGE) if paged else {}
    return ServingEngine(model, params, state, scheduler="continuous",
                         num_slots=SLOTS, max_new=8, cache_len=CAP,
                         sync_every=2, **paging, **kw)


def _program_args(eng, program):
    """Arguments of the right shapes for one serving program: the cache
    first in each program that takes it, then what the engine passes."""
    n, i32 = SLOTS, jnp.int32
    cache = (eng.model.init_paged_cache(n, PAGES, PAGE, eng._mps)
             if eng.paged else eng.model.init_cache(n, CAP))
    pending = jnp.zeros((n,), i32)
    slot = jnp.int32(1)
    p = eng.params
    if program == "_admit_fn":
        return cache, (p, cache, pending, jnp.arange(2, 9, dtype=i32), slot)
    if program == "_admit_paged_fn":
        return cache, (p, cache, pending, jnp.arange(2, 9, dtype=i32), slot,
                       jnp.full((eng._mps,), -1, i32))
    if program == "_admit_prefix_fn":
        z = jnp.int32(0)
        return cache, (cache, pending, slot, jnp.full((eng._mps,), -1, i32),
                       jnp.int32(PAGE), z, z, jnp.int32(5), jnp.bool_(False))
    if program == "_admit_chunk_fn":
        return cache, (p, cache, jnp.arange(2, 2 + CHUNK, dtype=i32), slot)
    if program == "_chunk_fn":
        return cache, (p, cache, pending, jnp.zeros((n, CHUNK), i32),
                       jnp.zeros((n,), i32), jnp.zeros((n,), i32),
                       jnp.zeros((n,), bool))
    if program == "_set_tbl_fn":
        return cache, (cache, jnp.full((n, eng._mps), -1, i32))
    assert program == "_reset_fn"
    return cache, (cache, slot)


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


CASES = [("paged", p) for p in ("_admit_paged_fn", "_admit_prefix_fn",
                                "_chunk_fn", "_set_tbl_fn", "_reset_fn")]
CASES += [("contiguous", p) for p in ("_admit_fn", "_admit_chunk_fn",
                                      "_chunk_fn", "_reset_fn")]


@pytest.mark.parametrize("layout,program", CASES)
def test_program_writes_the_cache_in_place(backbone, layout, program):
    eng = _engine(backbone, layout == "paged", prefill_chunk=CHUNK)
    cache, args = _program_args(eng, program)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # "donated buffers were not
        compiled = getattr(eng, program).lower(*args).compile()  # usable"
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _nbytes(cache), (
        f"{program} ({layout}): {m.alias_size_in_bytes} of "
        f"{_nbytes(cache)} cache bytes aliased")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        getattr(eng, program)(*args)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))


def test_superstep_loop_carries_the_pool_in_place(backbone):
    """The paged superstep copies the pool once, into its output: its
    scratch does not grow with the pool, so no block of its loop writes a
    second one."""
    n, i32 = SLOTS, jnp.int32

    def scratch_and_pool(pages):
        eng = _engine(backbone, True, kv_pages=pages)
        cache = eng.model.init_paged_cache(n, pages, PAGE, eng._mps)
        m = eng._superstep_fn.lower(
            eng.params, eng.state.dvi_params, jnp.zeros((n,), i32), cache,
            eng.state.buf, jnp.zeros((n,), bool),
            jnp.ones((n,), i32)).compile().memory_analysis()
        return m.temp_size_in_bytes, _nbytes(cache)
    temp_small, pool_small = scratch_and_pool(PAGES)
    temp_big, pool_big = scratch_and_pool(400)
    assert temp_big - temp_small < pool_big - pool_small, (
        temp_small, temp_big, pool_small, pool_big)


def _requests(cfg, n):
    rng = np.random.default_rng(11)
    shared = rng.integers(2, cfg.vocab_size, 12).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(2, cfg.vocab_size,
                            int(rng.choice([3, 9, 17]))).astype(np.int32)
        prompt = np.concatenate([shared, tail]) if i % 2 else tail
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new=int(rng.choice([4, 8]))))
    return reqs


def test_continuous_run_donates_every_cache_it_hands_in(backbone):
    """A continuous run with the prefix cache and chunked prefill: every
    cache handed to a donating program is dead once the program returns,
    the last one included, and the counter counts each such dispatch."""
    cfg = backbone[0]
    eng = _engine(backbone, True, prefix_cache=True, prefill_chunk=CHUNK)
    handed = []

    def record(name):
        f = getattr(eng, name)

        def call(*a):
            cache = a[0] if name in ("_admit_prefix_fn", "_set_tbl_fn",
                                     "_reset_fn") else a[1]
            handed.append((name, cache))
            return f(*a)
        setattr(eng, name, call)
    for name in DONATING:
        record(name)
    for r in _requests(cfg, 8):
        eng.submit_request(r)
    outs = eng.run(max_steps=500)
    assert len(outs) == 8 and not eng.busy
    used = {name for name, _ in handed}
    assert {"_admit_paged_fn", "_admit_prefix_fn", "_chunk_fn",
            "_set_tbl_fn", "_reset_fn"} <= used, used
    assert eng.stats["prefix_hits"] > 0
    for name, cache in handed:
        assert all(a.is_deleted() for a in jax.tree.leaves(cache)), name
    assert jax.tree.leaves(handed[-1][1])[0].is_deleted()
    snap = eng.metrics_snapshot()
    assert snap["dvi_serving_kv_pool_donations_total"]["value"] == len(handed)
    assert eng.stats["kv_pool_donations"] == len(handed)
    assert eng.stats["dispatches"] > 0
