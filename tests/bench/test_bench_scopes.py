"""The program's own account of a trace (``bench/scope_reduce.py``): self
time per device scope and idle gaps labelled by the program's host spans,
on constructed events and on a trace the profiler records here on the CPU;
the seven readers built on it and on the program's request spans; and the
split in a traced run of ``run.py`` end to end on the tiny copy of the
benchmark."""
import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import run as bench  # noqa: E402
import scope_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402
from test_bench_harness import PEAKS, tiny  # noqa: E402,F401

MS = 1_000_000   # ns
SHARES = {"draft_share": ("draft",), "verify_share": ("verify",),
          "commit_share": ("commit",),
          "learn_share": ("learn.log", "learn.update"),
          "prefill_share": ("prefill.chunk", "prefill.admit")}


def test_scope_names_are_the_programs():
    """Every device scope the program names is one the split reads: in the
    base set, or added by some configuration's model module."""
    from repro.core import scopes
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = set()
    for c in spec["configs"]:
        conf_file = ROOT / c["file"]
        model = bench.load_model(ROOT, conf_file,
                                 json.loads(conf_file.read_text()))
        added |= set(getattr(model, "SCOPES", ()))
    assert set(scopes.ALL) <= set(sr.SCOPES) | added


@pytest.mark.parametrize("path,want", [
    ("jit(superstep)/while/body/draft/while/body/closed_call/dot_general:",
     "draft"),
    ("jit(superstep)/while/body/draft/while/body/commit/add", "commit"),
    ("jit(update)/learn.update/transpose(jvp())/mul:", "learn.update"),
    ("jit(update)/transpose(jvp(learn.update))/mul", "learn.update"),
    ("jit(chunk_step)/prefill.chunk/while/body/sub:", "prefill.chunk"),
    ("jit(superstep)/while/body/verifying/add", None),
    ("jit(superstep)/while:", None),
    ("", None),
])
def test_scope_of(path, want):
    assert sr.scope_of(path) == want


def test_self_time_of_nested_loops():
    """A loop keeps only the time none of its body's operations runs, a
    loop nested in it likewise, and the self times add up to the busy
    time."""
    ops = [(0, 100, "outer"), (10, 30, "a"), (40, 80, "inner"),
           (45, 50, "b"), (60, 70, "c"), (90, 100, "d"), (120, 130, "e")]
    secs, count = sr.self_times(ops, 0, 200)
    assert secs == {"outer": 30, "a": 20, "inner": 25, "b": 5, "c": 10,
                    "d": 10, "e": 10}
    assert count["outer"] == 1 and sum(count.values()) == len(ops)
    busy = tr.union([(a, b) for a, b, _ in ops])
    assert sum(secs.values()) == sum(b - a for a, b in busy)


def test_self_time_clips_and_breaks_ties():
    """Operations are cut to the stretch; of two that begin together the
    shorter (nested) one takes the time; a half overlap goes to the one that
    began last."""
    ops = [(0, 50, "loop"), (0, 20, "first"), (40, 70, "late")]
    secs, _ = sr.self_times(ops, 10, 60)
    assert secs == {"first": 10, "loop": 20, "late": 20}


def test_reduce_constructed():
    """Scopes, a fusion under its root's scope, the program fallback and
    ``other`` add up to the busy time; gaps go to the innermost ``dvi.``
    span, or to ``NO_SPAN``."""
    paths = {
        (7, "%while.1 = (s32[]) while(...)"): "jit(superstep)/while:",
        (7, "%fusion.2 = bf16[4] fusion(...)"):
            "jit(superstep)/while/body/verify/dot_general:",
        (7, "%fusion.3 = bf16[4] fusion(...)"):
            "jit(superstep)/while/body/draft/while/body/commit/add:"}
    mods = [("jit_superstep(7)", 0, 60 * MS),
            ("jit_update(9)", 70 * MS, 80 * MS)]

    def lab(name, mod):
        return sr.label(paths, mod, name)
    ops = [(0, 60 * MS, lab("%while.1 = (s32[]) while(...)", mods[0][0])),
           (5 * MS, 25 * MS, lab("%fusion.2 = bf16[4] fusion(...)",
                                 mods[0][0])),
           (30 * MS, 40 * MS, lab("%fusion.3 = bf16[4] fusion(...)",
                                  mods[0][0])),
           (70 * MS, 80 * MS, lab("%fusion.9 = f32[2] fusion(...)",
                                  mods[1][0])),
           (85 * MS, 90 * MS, lab("%copy.1 = f32[2] copy(...)", None))]
    host = [(tr.STRETCH, 0, 100 * MS), ("dvi.tick", 55 * MS, 100 * MS),
            ("dvi.tick.harvest.sync_wait", 60 * MS, 68 * MS),
            ("bench.engine.step", 0, 100 * MS)]
    r = sr.reduce(ops, host, 0, 100 * MS)
    assert r["scopes"] == pytest.approx({
        "jit_superstep": 0.03, "verify": 0.02, "commit": 0.01,
        "jit_update": 0.01, "other": 0.005})
    assert list(r["scopes"])[0] == "jit_superstep"       # ranked
    assert r["scope_busy_s"] == pytest.approx(0.075)
    assert sum(r["scopes"].values()) == pytest.approx(r["scope_busy_s"])
    # gaps: [60,70) under sync_wait, [80,85) and [90,100) under dvi.tick
    assert r["program_gaps"] == pytest.approx({
        "dvi.tick": 0.015, "dvi.tick.harvest.sync_wait": 0.01})
    r = sr.reduce(ops, [(tr.STRETCH, 0, 100 * MS)], 0, 100 * MS)
    assert r["program_gaps"] == pytest.approx({sr.NO_SPAN: 0.025})
    # a gap between recorded spans that none covers, and gaps before the
    # first or after the last recorded span (the capture's edges)
    host = [(tr.STRETCH, -10 * MS, 100 * MS),
            ("dvi.tick", 62 * MS, 68 * MS), ("dvi.tick", 92 * MS, 99 * MS)]
    r = sr.reduce(ops, host, -10 * MS, 100 * MS)
    assert r["program_gaps"] == pytest.approx({
        "dvi.tick": 0.02, sr.EDGE: 0.01, sr.NO_SPAN: 0.005})
    with pytest.raises(ValueError):
        sr.reduce(ops, host, 5, 5)


def test_a_configuration_adds_a_scope():
    """A scope name that a model module adds takes its operations' time
    from the base scope around it; without the name they stay there."""
    inner = "jit(superstep)/while/body/verify/experts/dot_general:"
    assert sr.scope_of(inner) == "verify"
    scopes = sr.SCOPES + ("experts",)
    assert sr.scope_of(inner, scopes) == "experts"
    paths = {(7, "%fusion.1 = bf16[4] fusion(...)"): inner,
             (7, "%fusion.2 = bf16[4] fusion(...)"):
                 "jit(superstep)/while/body/verify/add:"}

    def split(names):
        ops = [(0, 10 * MS, sr.label(paths, "jit_superstep(7)",
                                     "%fusion.1 = bf16[4] fusion(...)",
                                     names)),
               (10 * MS, 30 * MS, sr.label(paths, "jit_superstep(7)",
                                           "%fusion.2 = bf16[4] fusion(...)",
                                           names))]
        return sr.reduce(ops, [(tr.STRETCH, 0, 40 * MS)], 0, 40 * MS)[
            "scopes"]
    assert split(sr.SCOPES) == pytest.approx({"verify": 0.03, "other": 0.0})
    assert split(scopes) == pytest.approx(
        {"verify": 0.02, "experts": 0.01, "other": 0.0})


def test_top_keeps_the_sum():
    """A breakdown list holds at most ten pairs, largest first; the smaller
    entries past the ninth are summed into ``rest``."""
    secs = {f"s{i}": float(i) for i in range(1, 13)}
    got = sr.top(secs)
    assert len(got) == 10 and got[0] == ["s12", 12.0]
    assert got[-1] == [sr.REST, 1.0 + 2.0 + 3.0]
    assert sum(v for _, v in got) == sum(secs.values())
    assert sr.top({"a": 1.0, "b": 2.0}) == [["b", 2.0], ["a", 1.0]]


def _message(*fields) -> bytes:
    """A protobuf message of (field, str | bytes | int) fields."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def test_op_paths_reads_event_metadata():
    """The ``tf_op`` stat of a device plane's event metadata, keyed by the
    program id stat and the event's name; a string held by reference, and
    planes that are not devices, too."""
    stat_meta = [(5, _message((1, s), (2, _message((1, s), (2, name)))))
                 for s, name in ((1, "tf_op"), (2, "program_id"),
                                 (3, "jit(f)/verify/dot:"))]
    fusion = _message((2, "%fusion.1 = f32[2] fusion()"),
                      (5, _message((1, 1), (5, "jit(f)/draft/add:"))),
                      (5, _message((1, 2), (3, 42))))
    ref = _message((2, "%dot.2 = f32[2] dot()"),
                   (5, _message((1, 1), (7, 3))),
                   (5, _message((1, 2), (3, 42))))
    plain = _message((2, "%copy.3 = f32[2] copy()"))
    device = _message((2, "/device:TPU:0"),
                      (4, _message((1, 1), (2, fusion))),
                      (4, _message((1, 2), (2, ref))),
                      (4, _message((1, 3), (2, plain))), *stat_meta)
    host = _message((2, "/host:CPU"), (4, _message((1, 1), (2, fusion))),
                    *stat_meta)
    raw = _message((1, device), (1, host))
    got = sr.op_paths(raw, lambda plane: plane.startswith("/device:"))
    assert got == {(42, "%fusion.1 = f32[2] fusion()"): "jit(f)/draft/add:",
                   (42, "%dot.2 = f32[2] dot()"): "jit(f)/verify/dot:"}
    assert sr.program_id("jit_superstep(8911407554505906894)") == \
        8911407554505906894
    assert sr.program_id("jit_f") == 0


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here runs through: the CPU's operations carry no
    scope path, so they fall to their program; the program's ``dvi.``
    spans label the gaps; the split adds up to the busy time."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("verify"):
            return jnp.tanh(x @ x).sum()
    g = jax.jit(f)
    x = jnp.ones((128, 128))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.STRETCH):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dvi.tick"):
                g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    r = sr.reduce_file(path, tr.is_cpu_ops)
    assert r["scope_busy_s"] > 0
    assert sum(r["scopes"].values()) == pytest.approx(r["scope_busy_s"])
    assert "jit_f" in r["scopes"]
    assert set(r["program_gaps"]) <= {"dvi.tick", sr.NO_SPAN}
    assert sum(r["program_gaps"].values()) > 0


def test_lost_annotations_come_from_the_tracer(tmp_path):
    """A phase that began before the capture has no annotation in it; the
    program tracer's event, placed through its clock anchor, labels the
    device's idle time in it all the same."""
    import time

    import jax
    import jax.numpy as jnp
    from repro.serving.telemetry import Tracer
    g = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    g(x).block_until_ready()
    tracer = Tracer()
    with tracer.phase(0, "sync_wait", "dvi.tick.harvest.sync_wait"):
        jax.profiler.start_trace(str(tmp_path))
        stretch = jax.profiler.TraceAnnotation(tr.STRETCH)
        stretch.__enter__()
        time.sleep(0.03)                # idle inside the lost annotation
    with tracer.phase(0, "fold", "dvi.tick.harvest.fold"):
        g(x).block_until_ready()
        time.sleep(0.01)
    stretch.__exit__(None, None, None)
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    alone = sr.reduce_file(path, tr.is_cpu_ops)["program_gaps"]
    assert alone.get(sr.EDGE, 0) > 0.02
    assert "dvi.tick.harvest.sync_wait" not in alone
    both = sr.reduce_file(path, tr.is_cpu_ops, tracer.to_dict())[
        "program_gaps"]
    assert both["dvi.tick.harvest.sync_wait"] > 0.02
    assert both.get(sr.EDGE, 0) < 0.005
    assert both["dvi.tick.harvest.fold"] > 0.005
    assert sr.tracer_spans({"traceEvents": [], "otherData": {}}, 0) == []


def _scoped_run(trace):
    return {"trace": trace, "samples": {}}


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_share_readers(metric):
    """Each share reads its scopes' seconds over the busy time, records the
    operations behind it, and reads None without the split."""
    secs = {"draft": 0.2, "verify": 0.4, "commit": 0.05, "learn.log": 0.01,
            "learn.update": 0.04, "prefill.chunk": 0.15,
            "prefill.admit": 0.05, "jit_superstep": 0.1, "other": 0.0}
    trace = {"scope_busy_s": 1.0, "scopes": secs,
             "scope_ops": {k: 10 for k in secs}}
    run = _scoped_run(trace)
    got = bench.reader(BENCH, metric)(run)
    want = 100.0 * sum(secs[s] for s in SHARES[metric])
    assert got == pytest.approx(want)
    assert run["samples"][metric] == 10 * len(SHARES[metric])
    assert bench.reader(BENCH, metric)(_scoped_run(None)) is None
    assert bench.reader(BENCH, metric)(
        _scoped_run({"busy_s": 1.0, "idle_share": 0.0})) is None


def _span(name, uid, b, e):
    return [{"name": name, "ph": "b", "cat": "request", "id": uid, "ts": b},
            {"name": name, "ph": "e", "cat": "request", "id": uid, "ts": e}]


@pytest.mark.parametrize("metric,span", [("submit_wait_p95_ms", "submit"),
                                         ("relay_p95_ms", "relay")])
def test_span_readers(metric, span):
    """p95 over the requests whose span began in the window (engine clock,
    µs after the tracer's t0), first span of a request only; None without
    the tracer; no value where the program records no such span."""
    events = (_span(span, 1, 2.0e6, 2.1e6) + _span(span, 2, 3.0e6, 3.3e6)
              + _span(span, 2, 4.0e6, 9.0e6)          # a second: ignored
              + _span(span, 3, 0.5e6, 0.6e6)          # before the window
              + _span(span, 4, 12.5e6, 12.6e6)        # after it
              + _span("queued", 5, 2.0e6, 8.0e6))
    run = {"tracer": {"events": events, "t0": 100.0}, "t0": 101.0,
           "seconds": 10.0}
    got = bench.reader(BENCH, metric)(run)
    assert got == pytest.approx(100.0 + 0.95 * 200.0)
    assert run["samples"][metric] == 2
    assert bench.reader(BENCH, metric)(
        {"tracer": None, "t0": 0.0, "seconds": 1.0}) is None
    assert bench.reader(BENCH, metric)(
        {"tracer": {"events": _span("queued", 1, 0, 1), "t0": 0.0},
         "t0": 0.0, "seconds": 1.0}) is None


def test_run_scopes_on_the_tiny_copy(tiny):  # noqa: F811
    """A traced run of ``run.py`` adds the scope split to the breakdown,
    reads the split's metrics and the request spans', and keys the compile
    cache with the programs' metadata for that run only."""
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench.main(
            ["--workload", "tiny.chat", "--seed", "21", "--seconds", "3",
             "--trace", "1"], require_chip=False, bench_dir=tiny / "bench",
            peaks=PEAKS)
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert {"submit_wait_p95_ms", "relay_p95_ms", "prefill_share",
            "queue_wait_p95_ms"} <= set(m)
    assert m["submit_wait_p95_ms"]["value"] >= 0
    assert m["relay_p95_ms"]["value"] >= 0
    bd = res["breakdown"]
    assert {"device_ops", "idle_gaps", "scopes", "program_gaps"} <= set(bd)
    assert sum(v for _, v in bd["scopes"]) == pytest.approx(
        res["device"]["busy_s"], rel=1e-6)
    assert any(k.startswith("dvi.") for k, _ in bd["program_gaps"])
    assert all(len(bd[k]) <= 10 for k in bd)
    import jax
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
