"""Unified telemetry subsystem: the metrics registry round-trips through
Prometheus text, the lifecycle tracer emits schema-valid Chrome traces,
and — the hard contract — turning telemetry ON adds ZERO host syncs and
leaves committed token streams bit-identical (the in-graph histograms are
computed unconditionally, so telemetry on/off shares one compiled graph,
and every host-side observation rides the harvest's single device_get)."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core import lora, online, spec
from repro.core import schedule as sched
from repro.models.model import build_model
from repro.serving import Request, ServingEngine
from repro.serving.telemetry import (
    Counter, Gauge, Histogram, MetricsRegistry, ServingTelemetry, Tracer,
    log_buckets, parse_prometheus_text, render_prometheus, snapshot_delta,
    validate_trace, LEGACY_STATS, DEQUE_STATS)

EOS = 1


@pytest.fixture(scope="module")
def backbone():
    cfg = tiny_cfg("vicuna-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, seed=0, max_new=16):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        Tp = int(rng.choice([6, 9, 12]))
        p = np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i), (Tp,),
                                          2, cfg.vocab_size), np.int32)
        reqs.append(Request(uid=i, prompt=p, max_new=max_new))
    return reqs


def _serve(model, params, reqs, **kw):
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        buckets=(16,), **kw)
    for r in reqs:
        eng.submit(r)
    outs = eng.run(max_steps=1000)
    return eng, outs


def _streams(outs):
    return {o.uid: o.gen_tokens.tolist() for o in outs}


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    c = Counter("c", "help")
    c.inc()
    c.inc(3)
    assert c.value == 4
    c.reset()
    assert c.value == 0
    g = Gauge("g", "help")
    g.set(2.5)
    g.set_max(1.0)
    assert g.value == 2.5
    g.set_max(7.0)
    assert g.value == 7.0


def test_log_buckets():
    bs = log_buckets(1e-4, 64.0)
    assert bs == sorted(bs) and len(set(bs)) == len(bs)
    assert bs[0] == pytest.approx(1e-4) and bs[-1] >= 64.0
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(2.0, 1.0)


def test_histogram_observe_add_snapshot():
    h = Histogram("h", "", [1, 2, 4])
    h.observe(0.5)
    h.observe(2)          # le-style: lands in the bucket with bound 2
    h.observe(100)        # overflow -> +Inf slot
    h.add(3, 5)           # exact integer fold keeps sum exact
    s = h.to_snapshot()
    assert s["count"] == 8
    assert s["sum"] == 0.5 + 2 + 100 + 15
    assert s["buckets"][-1][0] == "+Inf"
    cums = [c for _, c in s["buckets"]]
    assert cums == sorted(cums) and cums[-1] == s["count"]
    with pytest.raises(ValueError):
        Histogram("bad", "", [2, 1])


def test_registry_duplicate_name_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_prometheus_round_trip_unit():
    reg = MetricsRegistry()
    reg.counter("a_total", "a counter").inc(3)
    reg.gauge("b_gauge", "a gauge").set(-1.25)
    h = reg.histogram("c_hist", "a histogram", [1, 2])
    h.observe(0.5)
    h.observe(9)
    snap = reg.snapshot()
    back = parse_prometheus_text(render_prometheus(snap))
    assert set(back) == set(snap)
    for name, m in snap.items():
        assert back[name]["type"] == m["type"]
        if m["type"] == "histogram":
            assert back[name]["count"] == m["count"]
            assert back[name]["sum"] == m["sum"]
            assert back[name]["buckets"] == [[b, c] for b, c in m["buckets"]]
        else:
            assert back[name]["value"] == m["value"]


def test_snapshot_delta():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h", "", [1])
    c.inc(2)
    g.set(5)
    h.observe(0.5)
    prev = reg.snapshot()
    c.inc(3)
    g.set(1)
    h.observe(2)
    d = snapshot_delta(reg.snapshot(), prev)
    assert d["c_total"]["value"] == 3
    assert d["g"]["value"] == 1            # gauges keep the current value
    assert d["h"]["count"] == 1 and d["h"]["sum"] == 2
    assert d["h"]["buckets"] == [[1, 0], ["+Inf", 1]]


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def test_tracer_fake_clock_deterministic():
    fc = FakeClock(100.0)
    tr = Tracer(clock=fc, limit=100)
    tr.span(0, "tick", 100.0, 100.25)
    fc.t = 101.0
    tr.instant(1, "hit")
    ev_span, ev_inst = tr.events[-2], tr.events[-1]
    assert ev_span["ts"] == 0.0 and ev_span["dur"] == pytest.approx(0.25e6)
    assert ev_inst["ts"] == pytest.approx(1e6)
    d = tr.to_dict()
    assert d["otherData"]["dropped_events"] == 0
    validate_trace(d)


def test_tracer_event_cap_drops_not_grows():
    tr = Tracer(clock=FakeClock(), limit=3)
    for i in range(10):
        tr.instant(0, f"i{i}", t=100.0 + i)
    assert len(tr.events) == 3
    assert tr.to_dict()["otherData"]["dropped_events"] == 8


def test_validate_trace_catches_violations():
    def tr(*events):
        return {"traceEvents": list(events)}

    x = {"name": "a", "ph": "X", "pid": 0, "tid": 0, "ts": 0.0, "dur": 10.0}
    y = {"name": "b", "ph": "X", "pid": 0, "tid": 0, "ts": 5.0, "dur": 10.0}
    with pytest.raises(ValueError, match="half-overlap"):
        validate_trace(tr(x, y))
    validate_trace(tr(x, dict(y, ts=2.0, dur=3.0)))      # nested: fine
    validate_trace(tr(x, dict(y, ts=10.0)))              # disjoint: fine
    with pytest.raises(ValueError, match="missing"):
        validate_trace(tr({"name": "a", "ph": "X", "pid": 0}))
    b = {"name": "req", "ph": "b", "pid": 0, "tid": 0, "cat": "r", "id": 7,
         "ts": 0.0}
    e = dict(b, ph="e", ts=4.0)
    validate_trace(tr(b, e))
    with pytest.raises(ValueError, match="unclosed"):
        validate_trace(tr(b))
    with pytest.raises(ValueError, match="without begin"):
        validate_trace(tr(e))
    with pytest.raises(ValueError, match="ends before"):
        validate_trace(tr(dict(b, ts=9.0), e))


# ---------------------------------------------------------------------------
# schedule mirror + stats facade
# ---------------------------------------------------------------------------

def test_phase_info_matches_jnp_schedules():
    dvi = tiny_cfg("vicuna-7b").dvi
    probes = [0, 1, dvi.warmup_steps - 1, dvi.warmup_steps,
              dvi.warmup_steps + max(dvi.ramp_steps // 2, 1),
              dvi.warmup_steps + dvi.ramp_steps,
              dvi.warmup_steps + dvi.ramp_steps + 100, 10_000]
    for t in probes:
        info = sched.phase_info(t, dvi)
        lam_pg, lam_kl = sched.lambda_schedule(jnp.int32(t), dvi)
        assert info["lambda_pg"] == pytest.approx(float(lam_pg), abs=1e-6)
        assert info["lambda_kl"] == pytest.approx(float(lam_kl), abs=1e-6)
        assert info["beta"] == pytest.approx(
            float(sched.beta_schedule(jnp.int32(t), dvi)), rel=1e-5)
        assert info["gate"] == pytest.approx(
            float(sched.policy_gate(jnp.int32(t), dvi)), abs=1e-6)
        assert info["phase"] in (0, 1, 2)
        assert (info["phase"] == 0) == (t < dvi.warmup_steps)
        assert (info["phase"] == 2) == (t >= dvi.warmup_steps
                                        + dvi.ramp_steps)


def test_stats_view_facade():
    telem = ServingTelemetry(num_slots=2, k_max=4, latency_window=16,
                             clock=FakeClock())
    st = telem.stats
    st["requests"] += 2                       # read-modify-write idiom
    st["sync_wait_s"] += 0.5
    assert st["requests"] == 2
    assert st["sync_wait_s"] == 0.5
    st["latencies"].append(1.0)               # deque entries are live objects
    assert list(st["latencies"]) == [1.0]
    with pytest.raises(KeyError):
        st["made_up_key"] = 1
    assert set(LEGACY_STATS) | set(DEQUE_STATS) == set(st)
    st.reset()
    assert st["requests"] == 0 and len(st["latencies"]) == 0
    # the registry exposes exactly the keys LEGACY_STATS declares
    for name, _, _ in LEGACY_STATS.values():
        assert name in telem.registry.names()


# ---------------------------------------------------------------------------
# superstep in-graph histograms (greedy + rejection-sampled)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_superstep_hists_reconcile(backbone, temperature):
    """The in-graph per-block histograms are EXACT decompositions of the
    flat counters — greedy and rejection-sampled alike."""
    cfg, model, params = backbone
    dvi = lora.init_draft_params(jax.random.PRNGKey(5), cfg)
    B, Tp = 3, 8
    prompts = jax.random.randint(jax.random.PRNGKey(7), (B, Tp), 2,
                                 cfg.vocab_size)
    _, cache, _ = model.prefill(params, prompts[:, :-1], max_len=96)
    res = spec.spec_superstep(model, params, dvi, prompts[:, -1], cache,
                              steps=6, budget=jnp.array([4, 9, 30]),
                              eos_id=EOS, temperature=temperature,
                              key=jax.random.PRNGKey(99))
    K = cfg.dvi.k_spec
    ah = np.asarray(res.accept_hist)
    dh = np.asarray(res.depth_hist)
    assert ah.shape == dh.shape == (K + 1,)
    blocks = int(np.asarray(res.lane_blocks).sum())
    assert ah.sum() == blocks == dh.sum()
    assert (ah * np.arange(K + 1)).sum() == \
        int(np.asarray(res.lane_accepted).sum())
    assert (dh * np.arange(K + 1)).sum() == \
        int(np.asarray(res.lane_drafted).sum())


# ---------------------------------------------------------------------------
# engine: zero-host-sync bit-identity, trace validity, reconciliation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_pages,sync_every", [(0, 1), (0, 8), (40, 8)])
def test_telemetry_on_off_bit_identity(backbone, kv_pages, sync_every):
    """Telemetry ON vs OFF: identical committed streams, identical
    host_syncs/dispatches (the tracer rides the existing harvest), and
    the per-block histograms reconcile exactly with the flat counters."""
    cfg, model, params = backbone
    reqs = _requests(cfg, 5, seed=2, max_new=12)
    kw = dict(num_slots=3, max_new=12, sync_every=sync_every, learn=False)
    if kv_pages:
        kw.update(kv_pages=kv_pages, kv_page_size=4, cache_len=40)
    off_eng, off = _serve(model, params, reqs, telemetry=False, **kw)
    on_eng, on = _serve(model, params, reqs, telemetry=True, **kw)
    assert _streams(on) == _streams(off)
    for k in ("host_syncs", "dispatches", "blocks", "steps", "committed",
              "accepted", "drafted", "requests"):
        assert on_eng.stats[k] == off_eng.stats[k], k
    # one sync per superstep dispatch — telemetry added none
    assert on_eng.stats["host_syncs"] == on_eng.stats["dispatches"]

    snap = on_eng.metrics_snapshot()
    ah = snap["dvi_serving_block_accepted_drafts"]
    dh = snap["dvi_serving_block_depth"]
    assert ah["count"] == on_eng.stats["blocks"] == dh["count"]
    assert ah["sum"] == on_eng.stats["accepted"]
    assert dh["sum"] == on_eng.stats["drafted"]
    assert snap["dvi_serving_request_latency_seconds"]["count"] == len(reqs)

    trace = on_eng.trace_dict()
    validate_trace(trace)
    # the harvest's device wait and its fold are phases of their own (nested
    # in the harvest's, checked above): one of each per host sync
    phases = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert phases.count("sync_wait") == phases.count("fold") == \
        on_eng.stats["host_syncs"]
    assert off_eng.trace_dict() is None
    with pytest.raises(ValueError):
        off_eng.write_trace("/dev/null")


def test_trace_valid_with_preemption_replay(backbone, tmp_path):
    """A pool tight enough to force preemption/replay still yields a
    schema-valid trace covering every request lifecycle, with the
    preempt instants and replayed queued phases recorded."""
    cfg, model, params = backbone
    reqs = _requests(cfg, 7, seed=0, max_new=16)
    eng, outs = _serve(model, params, reqs, num_slots=3, max_new=16,
                       cache_len=40, kv_pages=14, kv_page_size=4,
                       sync_every=2, learn=False, telemetry=True)
    assert len(outs) == len(reqs)
    assert eng.stats["preemptions"] > 0, "tight pool should force preemption"
    trace = eng.trace_dict()
    tracks = validate_trace(trace)            # nesting + async pairing
    evs = trace["traceEvents"]
    # every request's lifecycle opens and closes
    begins = [e for e in evs if e["ph"] == "b" and e["name"] == "request"]
    ends = [e for e in evs if e["ph"] == "e" and e["name"] == "request"]
    assert {e["id"] for e in begins} == {r.uid for r in reqs}
    assert len(begins) == len(ends) == len(reqs)
    names = {e["name"] for e in evs}
    assert {"queued", "prefill", "decode", "superstep", "tick",
            "sync_wait", "preempt"} <= names
    replayed = [e for e in evs if e["ph"] == "b" and e["name"] == "queued"
                and e.get("args", {}).get("replay")]
    assert replayed, "preempted lanes must re-enter a queued phase"
    # lane tracks and the engine track both carry spans
    lane_spans = [e for t in range(eng.num_slots) for e in tracks.get(t, [])
                  if e["ph"] == "X"]
    assert lane_spans
    assert any(e["ph"] == "X" for e in tracks[eng.telem.tid_engine])

    out = tmp_path / "trace.json"
    eng.write_trace(str(out))
    validate_trace(json.loads(out.read_text()))
    mpath = tmp_path / "metrics.prom"
    eng.write_metrics(str(mpath))
    back = parse_prometheus_text(mpath.read_text())
    assert back["dvi_serving_preemptions_total"]["value"] == \
        eng.stats["preemptions"]


def test_train_telemetry_and_prometheus_exposure(backbone):
    """A learning run must surface all three DVI loss components and the
    acceptance EMA around updates — in train_telemetry(), in the bounded
    history, and in the Prometheus rendering."""
    cfg, model, params = backbone
    reqs = _requests(cfg, 6, seed=4, max_new=12)
    eng, outs = _serve(model, params, reqs, num_slots=3, max_new=12,
                       sync_every=2, learn=True, update_every=2,
                       telemetry=True)
    assert len(outs) == len(reqs)
    tt = eng.train_telemetry()
    assert tt["updates"] > 0
    assert tt["step"] == tt["updates"]
    assert tt["phase_name"] in ("warmup", "ramp", "rl")
    for k in ("loss", "loss_kl", "loss_ce", "loss_pg", "lambda_pg",
              "lambda_kl", "beta", "acceptance_batch",
              "acceptance_ema_before", "acceptance_ema_after"):
        assert np.isfinite(tt[k]), k
    assert tt["history"], "per-update history must accumulate"
    rec = tt["history"][-1]
    assert rec["step"] >= 1 and rec["span_s"] >= 0.0
    assert {"loss", "loss_kl", "loss_ce", "loss_pg", "ema_before",
            "ema_after", "phase"} <= set(rec)

    prom = eng.render_prometheus()
    for name in ("dvi_train_loss_kl", "dvi_train_loss_ce",
                 "dvi_train_loss_pg", "dvi_train_acceptance_ema_after",
                 "dvi_serving_block_accepted_drafts_bucket",
                 "dvi_serving_block_depth_bucket"):
        assert name in prom, name
    back = parse_prometheus_text(prom)
    assert back["dvi_train_updates_total"]["value"] == tt["updates"]
    assert back["dvi_train_loss_kl"]["value"] == \
        pytest.approx(tt["loss_kl"], rel=1e-6)

    # reset clears the registry, the deques, and the history
    eng.reset_stats()
    assert eng.stats["requests"] == 0
    assert eng.metrics_snapshot()["dvi_serving_blocks_total"]["value"] == 0
    assert eng.train_telemetry()["history"] == []


def test_frozen_clock_all_durations_zero(backbone):
    """With a frozen injected clock every recorded duration is EXACTLY
    zero — any residual time.time()/perf_counter() in a duration path
    would leak nonzero wall time into latencies/ticks/sync waits."""
    cfg, model, params = backbone
    reqs = _requests(cfg, 4, seed=6, max_new=8)
    eng, outs = _serve(model, params, reqs, num_slots=2, max_new=8,
                       sync_every=2, learn=False, clock=FakeClock(7.0))
    assert len(outs) == len(reqs)
    assert all(v == 0.0 for v in eng.stats["latencies"])
    assert all(v == 0.0 for v in eng.stats["tick_s"])
    assert eng.stats["sync_wait_s"] == 0.0
    assert all(o.latency_s == 0.0 for o in outs)
    lat = eng.latency_percentiles()
    assert lat["count"] == len(reqs) and lat["p50_s"] == 0.0
    snap = eng.metrics_snapshot()
    assert snap["dvi_serving_request_latency_seconds"]["sum"] == 0.0


def test_empty_percentiles_have_count_key(backbone):
    cfg, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        num_slots=2, buckets=(16,))
    lat = eng.latency_percentiles()
    tick = eng.tick_percentiles()
    assert lat == {"p50_s": 0.0, "p95_s": 0.0, "mean_s": 0.0, "count": 0}
    assert tick["count"] == 0 and tick["p50_s"] == 0.0 \
        and tick["max_s"] == 0.0


def test_train_history_keeps_every_update(backbone):
    """Every folded drafter update lands in the history, including the one
    folded while no superstep is in flight (the end of each burst), over
    two bursts with the engine idle in between."""
    cfg, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        buckets=(16,), num_slots=3, max_new=12, sync_every=2,
                        learn=True, update_every=2)
    for burst in range(2):
        for r in _requests(cfg, 4, seed=10 + burst, max_new=12):
            r.uid += 100 * burst
            eng.submit_request(r)
        eng.run(max_steps=1000)
    tt = eng.train_telemetry()
    assert tt["updates"] > 0
    assert len(tt["history"]) == tt["updates"]
    assert [h["step"] for h in tt["history"]] == list(range(tt["updates"]))
    assert eng.stats["host_syncs"] == eng.stats["dispatches"]


def test_failed_profile_capture_raises(backbone, monkeypatch, tmp_path):
    """A requested device-trace capture that cannot start fails the run
    instead of serving on without the trace."""
    cfg, model, params = backbone

    def refuse(*_a, **_k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        buckets=(16,), num_slots=2, max_new=4, learn=False,
                        profile_dir=str(tmp_path))
    eng.submit_request(_requests(cfg, 1)[0])
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        eng.run(max_steps=50)


# ---------------------------------------------------------------------------
# device scopes and host spans on the profiler's clock
# ---------------------------------------------------------------------------

def test_lowered_programs_carry_the_scopes(backbone):
    """The superstep, chunk-step, admission and drafter-update programs name
    their parts: the lowered text holds op names under every scope."""
    from repro.core import scopes
    cfg, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        buckets=(16,), num_slots=2, max_new=8, sync_every=2,
                        kv_pages=40, kv_page_size=4, cache_len=40,
                        prefill_chunk=4, learn=True, update_every=1)
    want = {"_superstep_fn": (scopes.DRAFT, scopes.VERIFY, scopes.COMMIT,
                              scopes.LEARN_LOG),
            "_chunk_fn": (scopes.PREFILL_CHUNK,),
            "_admit_paged_fn": (scopes.PREFILL_ADMIT,),
            "_update_fn": (scopes.LEARN_UPDATE,)}
    called = {}
    for attr in want:
        def record(*a, _f=getattr(eng, attr), _attr=attr):
            called.setdefault(_attr, (_f, a))
            return _f(*a)
        setattr(eng, attr, record)
    for r in _requests(cfg, 3, seed=1, max_new=8):
        eng.submit_request(r)
    eng.run(max_steps=500)
    assert set(called) == set(want)
    for attr, names in want.items():
        f, a = called[attr]
        text = f.lower(*a).as_text(debug_info=True)
        for name in names:
            assert f"/{name}/" in text, (attr, name)
    assert {n for v in want.values() for n in v} == set(scopes.ALL)


# the profiler annotation of each engine-track phase span
ANNOTATION = {"tick": "dvi.tick", "harvest": "dvi.tick.harvest",
              "sync_wait": "dvi.tick.harvest.sync_wait",
              "fold": "dvi.tick.harvest.fold",
              "dispatch": "dvi.tick.dispatch",
              "driver.inbox": "dvi.driver.inbox",
              "driver.idle": "dvi.driver.idle"}


def test_program_spans_on_the_profiler_clock(backbone, tmp_path):
    """A profiler capture holds the tick's and the driver's annotations,
    and each phase span of the tracer, placed on the wall clock through the
    tracer's anchor, starts within 1 ms of its annotation.  Its end is held
    to 1 ms or 5 % of its length: the profiler's clock interpolates between
    readings of the kernel's, and on a loaded machine it ran 4 ms short
    over a 226 ms tick."""
    from jax.profiler import ProfileData
    from repro.serving.http import EngineDriver
    cfg, model, params = backbone
    state = online.init_trainer(model, jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, state, scheduler="continuous",
                        buckets=(16,), num_slots=2, max_new=8, sync_every=2,
                        learn=False, telemetry=True)
    drv = EngineDriver(eng).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        hs = [drv.submit(r) for r in _requests(cfg, 3, seed=7, max_new=8)]
        for h in hs:
            h.result(timeout=120)
        time.sleep(0.1)                        # the driver idles
    finally:
        # the engine thread ends inside the capture, so that the profiler
        # collects its last annotations
        drv.stop(drain=True)
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    env = dict(data.find_plane_with_name("Task Environment").stats)
    lo, hi = int(env["profile_start_time"]), int(env["profile_stop_time"])
    ann = [(ev.name, lo + ev.start_ns, lo + ev.start_ns + ev.duration_ns)
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("dvi.")]
    assert set(ANNOTATION.values()) <= {n for n, _, _ in ann}
    tr = eng.telem.tracer
    checked = 0
    for e in tr.events:
        if e["ph"] != "X" or e["name"] not in ANNOTATION:
            continue
        w0 = tr.anchor[1] + e["ts"] * 1e3
        w1 = w0 + e["dur"] * 1e3
        if not lo + 2e6 < w0 < w1 < hi - 2e6:
            continue
        near = min((abs(a - w0) + abs(b - w1), a, b) for n, a, b in ann
                   if n == ANNOTATION[e["name"]])
        assert abs(near[1] - w0) < 1e6, e
        assert abs(near[2] - w1) < max(1e6, 0.05 * (w1 - w0)), e
        checked += 1
    assert checked >= 10
