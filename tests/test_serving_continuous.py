"""Continuous-batching slot scheduler: per-request outputs are EXACTLY the
per-request greedy AR target stream, regardless of arrival order, mixed
prompt lengths, or mixed max_new; the sync path no longer pollutes training
signal with batch-padding duplicates."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core import lora, online, spec
from repro.models.model import build_model
from repro.serving import Completion, Request, ServingEngine


@pytest.fixture(scope="module")
def backbone():
    cfg = tiny_cfg("vicuna-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ragged_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        Tp = int(rng.choice([6, 9, 12]))
        mn = int(rng.choice([6, 10, 16]))
        p = np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i), (Tp,),
                                          2, cfg.vocab_size), np.int32)
        reqs.append(Request(uid=i, prompt=p, max_new=mn))
    return reqs


def _ar_reference(model, params, req, eos=1):
    r = spec.ar_generate(model, params, jnp.asarray(req.prompt)[None, :],
                         req.max_new)
    gen = np.asarray(r.tokens[0, len(req.prompt):int(r.lengths[0])]).tolist()
    out = []
    for t in gen[:req.max_new]:
        out.append(int(t))
        if t == eos:
            break
    return out


@pytest.mark.parametrize("order_seed", [0, 3])
def test_continuous_lossless_any_arrival_order(backbone, order_seed):
    cfg, model, params = backbone
    reqs = _ragged_requests(cfg, 7)
    order = np.random.default_rng(order_seed).permutation(len(reqs))
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        num_slots=3, max_new=16, buckets=(16,))
    for i in order:
        eng.submit(reqs[i])
    outs = eng.run(max_steps=500)
    assert len(outs) == len(reqs)
    assert not eng.busy
    by_uid = {o.uid: o for o in outs}
    for req in reqs:
        ref = _ar_reference(model, params, req)
        got = by_uid[req.uid].gen_tokens.tolist()
        assert got == ref, f"uid {req.uid}: {got} != AR {ref}"
        full = by_uid[req.uid].tokens
        np.testing.assert_array_equal(full[:len(req.prompt)], req.prompt)


def test_continuous_streams_and_tracks_latency(backbone):
    cfg, model, params = backbone
    reqs = _ragged_requests(cfg, 6, seed=5)
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        num_slots=2, max_new=16, update_every=2)
    for r in reqs:
        eng.submit(r)
    seen_partial = False
    done = []
    for _ in range(500):
        if not eng.busy:
            break
        out = eng.step()
        done.extend(out)
        # completions stream out while other requests are still in flight
        if out and eng.busy:
            seen_partial = True
    assert len(done) == len(reqs)
    assert seen_partial, "no streaming: all completions arrived at once"
    assert eng.stats["updates"] > 0          # cadence-driven drafter updates
    lat = eng.latency_percentiles()
    assert lat["p95_s"] >= lat["p50_s"] > 0.0
    assert len(eng.stats["latencies"]) == len(reqs)
    assert eng.slot_acceptance.shape == (2,)
    assert int(eng.stats["requests"]) == len(reqs)


def test_sync_padding_masked_out_of_collection(backbone):
    """A short sync batch is padded with duplicate requests; padded lanes
    must contribute no replay tuples and no draft/accept counters."""
    cfg, model, params = backbone
    dvi = lora.init_draft_params(jax.random.PRNGKey(5), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 2,
                                cfg.vocab_size)
    pp = jnp.concatenate([prompt, prompt], axis=0)
    full = spec.speculative_generate(model, params, dvi, pp, 12, collect=True)
    half = spec.speculative_generate(model, params, dvi, pp, 12, collect=True,
                                     live_mask=jnp.array([True, False]))
    # identical duplicated lanes: masking one must exactly halve everything
    assert int(full.buffer["count"]) == 2 * int(half.buffer["count"])
    assert int(full.drafted) == 2 * int(half.drafted)
    assert int(full.committed) == 2 * int(half.committed)
    # masked lane generated nothing
    assert int(half.lengths[1]) == 8


def test_sync_engine_short_batch_stats(backbone):
    """End-to-end: 3 requests into a batch of 4 must produce EXACTLY the same
    stats as the same 3 requests in a batch of 3 — the padded duplicate lane
    contributes nothing."""
    cfg, model, params = backbone

    def serve(batch_size):
        state = online.init_trainer(model, jax.random.PRNGKey(3))
        eng = ServingEngine(model, params, state, scheduler="sync",
                            batch_size=batch_size, max_new=8, buckets=(8,),
                            learn=False)
        for i in range(3):
            p = np.asarray(jax.random.randint(jax.random.PRNGKey(i), (8,), 2,
                                              cfg.vocab_size), np.int32)
            eng.submit(Request(uid=i, prompt=p, max_new=8))
        return eng, eng.run()

    eng4, outs4 = serve(4)
    eng3, outs3 = serve(3)
    assert len(outs4) == len(outs3) == 3
    assert eng4.stats["requests"] == 3
    for k in ("blocks", "committed", "accepted", "drafted"):
        assert eng4.stats[k] == eng3.stats[k], k
    assert int(eng4.state.buf["count"]) == int(eng3.state.buf["count"])
    assert all(isinstance(o, Completion) and o.latency_s > 0 for o in outs4)


@pytest.mark.parametrize("adaptive", [False, True])
def test_dispatch_copies_the_host_mirrors(backbone, adaptive):
    """The superstep and the block-table push take copies of the engine's
    host mirrors: admission rewrites them while that work is still queued,
    and on the CPU backend ``jnp.asarray`` would alias the numpy buffer."""
    cfg, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        num_slots=3, max_new=8, buckets=(16,), sync_every=2,
                        kv_pages=40, kv_page_size=4, cache_len=40,
                        adaptive_k=adaptive)
    seen = []
    attr = "_superstep_adaptive_fn" if adaptive else "_superstep_fn"
    tbl_fn, step_fn = eng._set_tbl_fn, getattr(eng, attr)

    def step(*a):
        seen.append([(x, np.asarray(x).copy()) for x in a[5:6] + a[7:10]])
        return step_fn(*a)

    def set_tbl(cache, tbl):
        seen.append([(tbl, np.asarray(tbl).copy())])
        return tbl_fn(cache, tbl)
    setattr(eng, attr, step)
    eng._set_tbl_fn = set_tbl
    for r in _ragged_requests(cfg, 5):
        eng.submit_request(r)
    eng.run(max_steps=500)
    assert len(seen) > 2
    for args in seen:                  # the mirrors moved on; the inputs
        for x, at_call in args:        # the device got did not
            np.testing.assert_array_equal(np.asarray(x), at_call)
