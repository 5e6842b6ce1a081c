"""The trace-to-metrics reduction (``bench/trace_reduce.py``): busy time,
idle share, top operations and labelled idle gaps, on constructed events
and on a trace the profiler records here on the CPU."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

MS = 1_000_000   # ns


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_reduce_constructed():
    ops = [("m:fusion.1", 10 * MS, 30 * MS), ("m:fusion.2", 25 * MS, 40 * MS),
           ("m:dot.3", 60 * MS, 90 * MS), ("m:fusion.1", 95 * MS, 120 * MS)]
    host = [(tr.STRETCH, 0, 100 * MS),
            ("bench.engine.step", 0, 100 * MS),
            ("bench.engine.harvest", 38 * MS, 62 * MS)]
    r = tr.reduce({"/device:TPU:0": ops}, host, 0, 100 * MS)
    # busy: [10, 40) and [60, 90) and [95, 100) clipped = 65 ms
    assert r["busy_s"] == pytest.approx(0.065)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_share"] == pytest.approx(0.35)
    # per op: fusion.1 = 20 + 5 (clipped), fusion.2 = 15, dot.3 = 30
    assert r["device_ops"] == [["m:dot.3", pytest.approx(0.03)],
                               ["m:fusion.1", pytest.approx(0.025)],
                               ["m:fusion.2", pytest.approx(0.015)]]
    # gaps [0,10) and [90,95) under step only; [40,60) inside harvest
    assert dict(r["idle_gaps"]) == {
        "bench.engine.harvest": pytest.approx(0.02),
        "bench.engine.step": pytest.approx(0.015)}
    assert r["gap_count"] == 3


def test_reduce_averages_devices_and_labels_uncovered_gaps():
    host = [(tr.STRETCH, 0, 10 * MS)]
    devs = {"/device:TPU:0": [("a", 0, 10 * MS)],
            "/device:TPU:1": [("a", 0, 4 * MS)]}
    r = tr.reduce(devs, host, 0, 10 * MS)
    assert r["busy_s"] == pytest.approx(0.007)
    r1 = tr.reduce({"/device:TPU:0": [("a", 2 * MS, 4 * MS)]}, host,
                   0, 10 * MS)
    assert r1["idle_gaps"] == [[tr.NO_SPAN, pytest.approx(0.008)]]


def test_reduce_refuses_empty():
    with pytest.raises(ValueError):
        tr.reduce({}, [], 0, 1)
    with pytest.raises(ValueError):
        tr.reduce({"d": [("a", 0, 1)]}, [], 5, 5)


def test_recorded_trace(tmp_path):
    """A trace recorded by the profiler reads back: the stretch span, the
    benchmark's host spans, and the XLA operations it ran."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.STRETCH):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.engine.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    devices, host = tr.load(path, tr.is_cpu_ops)
    names = {n for n, _, _ in host}
    assert {tr.STRETCH, "bench.engine.step"} <= names
    ops = [o for v in devices.values() for o in v]
    assert ops and all(b >= a for _, a, b in ops)
    r = tr.reduce_file(path, tr.is_cpu_ops)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    assert r["device_ops"] and len(r["device_ops"]) <= 10


def test_tpu_op_and_program_names():
    text = ("%fusion.12 = bf16[16,2048]{1,0:T(8,128)(2,1)} fusion(bf16[16,"
            "2048] %p0), kind=kLoop, calls=%fused_computation.12")
    assert tr.op_name(text) == "fusion.12"
    assert tr.module_name("jit_superstep(8911407554505906894)") == \
        "jit_superstep"
    mods = [(0, 10, "jit_a"), (20, 30, "jit_b")]
    assert tr._covering(mods, 5) == "jit_a"
    assert tr._covering(mods, 25) == "jit_b"
    assert tr._covering(mods, 15) is None
