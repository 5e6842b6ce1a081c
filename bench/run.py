#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  Everything is found by name from ``BENCHMARK.json``: the cell
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), and every metric is read by its own reader
(``bench/metrics/<metric>.py``, a function ``read(run) -> float | None``).

A run:

1. refuses to go on without the chips (exit 2, no result);
2. sets up: the weights are made from the configuration's seed on the
   device, with the benchmark's Markov text planted in them
   (``model_ref``); the
   program's model, drafter state and engine are built from the
   configuration file; an in-process ``ApiServer`` over an ``EngineDriver``
   serves ``POST /v1/completions`` on a loopback port; a fixed warm-up set
   of requests compiles every shape the window will use;
3. measures for ``--seconds``: a client process (``client.py``, no JAX)
   sends the seeded requests and times each from when it was due.  With
   ``--trace 1`` the engine's lifecycle tracer is on, the benchmark's host
   spans are set and the profiler traces a stretch in the middle;
4. checks what the window served against the plain float32 reference
   (``model_ref``), after the program's state is freed: the widest gap by
   which a served token's reference logit lies below the reference's best,
   over a seeded sample of finished requests that holds the longest one;
5. prints, on earlier lines, the compiles inside the window, peak device
   memory and request counts; on standard error, last, each number compared
   beside its limit; and as the last line of standard output one JSON
   object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
   with ``--trace 1`` ``breakdown``, and ``checks`` last.

``--control 1`` runs the check's control instead: the same run, but the
check judges, at every served position, the token that the reference in
fp8 arithmetic puts first, in place of the served one.  It has to come out
not correct.  Benchmark runs never set it.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import markov  # noqa: E402
import model_ref  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

GRACE_S = 60.0
# the reference's readings a configuration's ``check`` may hold a limit for
GAP_LIMITS = {"widest_gap": "gap_limit", "mean_gap": "mean_gap_limit"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOG = "[bench]"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"{LOG} {msg}", flush=True)


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench_dir: Path, workload: str) -> Cell:
    spec = json.loads((bench_dir.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=json.loads((bench_dir.parent / conf["file"]).read_text()),
        mix_name=w["traffic"],
        mix=traffic.load_mix(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if applies(m, workload)],
        bench_dir=bench_dir)


def reader(bench_dir: Path, metric: str):
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] == "cpu":
        raise NoChip(f"JAX found no accelerator ({info['kind']}); the "
                     f"benchmark has no CPU fallback")
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def load_peaks(bench_dir: Path, kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``.jax_cache/`` in the checkout), for every program however short
    its compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileLog:
    """Times of every XLA compile or persistent-cache load."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, fun_name: str = "?", **_):
        if name == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.monotonic(), fun_name))

    def between(self, a: float, b: float) -> List[str]:
        """Names of the programs compiled or loaded in ``[a, b)``."""
        with self._lock:
            return [n for t, n in self.events if a <= t < b]


# ---------------------------------------------------------------------------
# set-up: weights, program, server
# ---------------------------------------------------------------------------

PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}


def program_config(conf: dict):
    """The program's ModelConfig, set from the configuration file."""
    import dataclasses

    from repro.configs import get_config
    base = get_config(conf["registry_name"])
    kw = {PUBLISHED_TO_PROGRAM[k]: conf[k] for k in PUBLISHED_TO_PROGRAM}
    kw["rope_theta"] = float(kw["rope_theta"])
    kw["norm_eps"] = float(kw["norm_eps"])
    cfg = base.replace(**kw, qk_norm=True, qkv_bias=False, act="silu",
                       glu=True, sliding_window=0,
                       dvi=dataclasses.replace(base.dvi, **conf["dvi"]))
    cfg.validate()
    return cfg


def program_layout(cfg):
    """How the program stacks its layers: [(segment name, lo, hi)]."""
    from repro.models import transformer as tfm
    segs = tfm.model_segments(cfg)
    for s in segs:
        if s.kind != "attn" or s.ffn != "dense" or s.cross:
            raise ValueError(f"segment {s} is not a dense attention stack")
    return [(s.name, s.start, s.start + s.n) for s in segs]


def markov_text(conf: dict) -> markov.MarkovText:
    """The Markov text of a configuration: planted in its weights, and what
    its traffic's prompts are drawn from."""
    return markov.MarkovText(conf["vocab_size"], conf["weights"]["seed"],
                             span=conf["weights"]["markov_span"])


def bench_weights(conf: dict, bounds) -> dict:
    """The benchmark's weights, made from the configuration's recipe."""
    return model_ref.init_weights(
        model_ref.Dims.from_config(conf), model_ref.Recipe.from_config(conf),
        bounds, conf["dvi"]["split_layer"], markov_text(conf).table())


def to_program(w: dict, layout) -> dict:
    """The program's parameter tree over the same device arrays (norm gains
    become the program's offsets from 1)."""
    names = {"ln1": "ln1", "ln2": "ln2", "qn": "qn", "kn": "kn", "wq": "wq",
             "wk": "wk", "wv": "wv", "wo": "wo", "w_gate": "wi",
             "w_up": "wg", "w_down": "wo_ff"}
    offset = {"ln1", "ln2", "qn", "kn"}
    segs = {}
    for (name, _, _), st in zip(layout, w["stacks"]):
        segs[name] = {names[k]: (v - 1.0 if k in offset else v)
                      for k, v in st.items()}
    return {"embed": w["embed"], "final_norm": w["norm"] - 1.0,
            "segments": segs}


@dataclass
class Setup:
    layout: list
    engine: object
    driver: object
    server: object
    thread: object
    port: int
    fingerprint: float


def build(cell: Cell, trace: bool) -> Setup:
    import jax

    from repro.core import online as online_mod
    from repro.models.model import build_model
    from repro.serving.config import EngineConfig, build_engine
    from repro.serving.http import ApiServer, EngineDriver

    conf = cell.config
    cfg = program_config(conf)
    layout = program_layout(cfg)
    w = bench_weights(conf, [(lo, hi) for _, lo, hi in layout])
    fp = model_ref.fingerprint(w)
    log(f"weights: {conf['weights']}, fingerprint {fp!r}")
    params = to_program(w, layout)
    del w
    model = build_model(cfg)
    state = online_mod.init_trainer(
        model, jax.random.PRNGKey(conf["weights"]["seed"] + 7))
    econf = EngineConfig(**conf["engine"], telemetry=trace)
    engine = build_engine(econf, model, params, state)
    driver = EngineDriver(engine).start()
    server = ApiServer(("127.0.0.1", 0), driver, model_id=cell.config_name,
                       default_max_new=econf.max_new,
                       request_timeout_s=300.0)
    th = threading.Thread(target=server.serve_forever,
                          kwargs={"poll_interval": 0.05},
                          name="bench-http", daemon=True)
    th.start()
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}q/{cfg.num_kv_heads}kv x "
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype} params={n_params}; engine {conf['engine']}")
    return Setup(layout, engine, driver, server, th,
                 server.server_address[1], fp)


def stop(s: Setup) -> None:
    s.server.shutdown()
    s.server.server_close()
    s.driver.stop(drain=True)
    s.thread.join(timeout=30)


def annotate(engine) -> None:
    """Host spans of the benchmark's own, around the calls into the engine
    (the profiler labels idle gaps with them).  Methods a later version of
    the engine no longer has are skipped."""
    import jax

    def wrap(obj, attr, label):
        f = getattr(obj, attr, None)
        if f is None:
            return

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(label):
                return f(*a, **k)
        setattr(obj, attr, wrapped)

    for attr in ("step", "submit_request", "_harvest", "_admit_waiting",
                 "_advance_prefill", "_dispatch_superstep", "_grow_pages",
                 "_sweep_cancels"):
        wrap(engine, attr, f"bench.engine.{attr.strip('_')}")


# ---------------------------------------------------------------------------
# warm-up and the window
# ---------------------------------------------------------------------------

def sse(port: int, req: dict, timeout: float = 600.0) -> list:
    import http.client
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"], "stream": True})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"warm-up request: HTTP {resp.status}")
        toks = []
        for line in resp:
            line = line.strip()
            if line == b"data: [DONE]":
                return toks
            if line.startswith(b"data: "):
                obj = json.loads(line[6:])
                if "error" in obj:
                    raise RuntimeError(f"warm-up request: {obj['error']}")
                toks.extend(obj["choices"][0]["token_ids"])
        raise RuntimeError("warm-up stream ended without [DONE]")
    finally:
        conn.close()


def warm_up(port: int, waves: List[List[dict]], lanes: int) -> None:
    """Serve the warm-up waves in turn, each with twice as many requests at
    once as there are lanes."""
    for k, reqs in enumerate(waves):
        with ThreadPoolExecutor(max_workers=2 * lanes) as pool:
            outs = list(pool.map(lambda r: sse(port, r), reqs))
        log(f"warm-up wave {k}: {len(outs)} requests, "
            f"{sum(map(len, outs))} tokens")


def idle(driver, timeout: float = 120.0) -> None:
    """Wait until the engine has nothing left to do (its last drafter update
    folded), so that the window starts from an idle engine."""
    deadline = time.monotonic() + timeout
    while driver.call(lambda: driver.engine.busy):
        if time.monotonic() > deadline:
            raise RuntimeError("engine still busy after the warm-up")
        time.sleep(0.01)


def run_client(port: int, t0: float, seconds: float, plan, grace: float
               ) -> dict:
    doc = {"port": port, "t0": t0, "seconds": seconds, "loop": plan.loop,
           "clients": plan.clients, "grace_s": grace,
           "requests": [{k: r[k] for k in ("prompt", "max_tokens", "due")
                         if k in r} for r in plan.window]}
    proc = subprocess.Popen([sys.executable, str(BENCH / "client.py")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    out, _ = proc.communicate(json.dumps(doc).encode(),
                              timeout=seconds + grace + 120)
    if proc.returncode != 0:
        raise RuntimeError(f"client exited {proc.returncode}")
    return json.loads(out)


def snapshot(driver) -> dict:
    snap = driver.call(lambda: driver.engine.metrics_snapshot())
    return {k: v["value"] for k, v in snap.items() if "value" in v}


def traced_stretch(t0: float, seconds: float, out_dir: str,
                   box: dict) -> threading.Thread:
    """Trace a stretch in the middle of the window, in a thread of its
    own; the stretch is the ``bench.traced`` host span."""
    import jax
    length = min(2.0, seconds / 4)
    start = t0 + seconds / 2 - length / 2

    def body():
        try:
            time.sleep(max(0.0, start - time.monotonic()))
            jax.profiler.start_trace(out_dir)
            with jax.profiler.TraceAnnotation(trace_reduce.STRETCH):
                time.sleep(length)
            jax.profiler.stop_trace()
        except BaseException as e:              # reported by the caller
            box["error"] = e

    th = threading.Thread(target=body, name="bench-trace", daemon=True)
    th.start()
    return th


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def pick_sample(records: List[dict], window: List[dict], seed: int,
                n: int) -> List[int]:
    """Indices of finished window requests to check: the one that served the
    most tokens, then others drawn from the seed."""
    byi = records_by_i(records)
    ok = [r["i"] for r in records if r["ok"] and r["tokens"]]
    if not ok:
        return []
    longest = max(ok, key=lambda i: (len(byi[i]["tokens"]),
                                     len(window[i]["prompt"])))
    rest = [i for i in ok if i != longest]
    rng = np.random.default_rng([seed, 9])
    extra = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(j)] for j in sorted(extra)]


def records_by_i(records: List[dict]) -> dict:
    return {r["i"]: r for r in records}


def stream_faults(records: List[dict], window: List[dict], vocab: int,
                  eos: int) -> List[str]:
    """Served streams that cannot be right whatever the model: the wrong
    length, or a token outside the vocabulary."""
    bad = []
    for r in records:
        if not r["ok"]:
            continue
        toks, want = r["tokens"], window[r["i"]]["max_tokens"]
        ended = bool(toks) and toks[-1] == eos
        if not (len(toks) == want or (ended and len(toks) < want)):
            bad.append(f"request {r['i']}: {len(toks)} tokens of {want}")
        elif any(not 0 <= t < vocab for t in toks):
            bad.append(f"request {r['i']}: token outside the vocabulary")
    return bad


def reference_weights(conf: dict, layout, fingerprint: float) -> dict:
    """Rebuild the weights from the seed, check they are the served ones,
    and return them in float32."""
    w = bench_weights(conf, [(lo, hi) for _, lo, hi in layout])
    fp = model_ref.fingerprint(w)
    if fp != fingerprint:
        raise RuntimeError(f"rebuilt weights differ from the served ones: "
                           f"fingerprint {fp!r} != {fingerprint!r}")
    w32 = model_ref.to_float32(w)
    del w
    return w32


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def make_plan(cell: Cell, seed: int, seconds: float, mix=None):
    conf, eng = cell.config, cell.config["engine"]
    plan = traffic.make_plan(mix or cell.mix, markov_text(conf), seed,
                             seconds, oneshot_max=eng["prefill_chunk"] + 1)
    longest = max(len(r["prompt"]) + r["max_tokens"] for r in plan.window)
    limit = eng["cache_len"] - conf["dvi"]["k_spec"] - 2
    if longest > limit:
        raise ValueError(f"a request of {longest} tokens would be trimmed "
                         f"(lane limit {limit})")
    return plan


def window(setup: Setup, plan, t0: float, seconds: float,
           grace: float = GRACE_S) -> tuple:
    """Serve ``plan.window`` from ``t0``: (client result, counter changes
    over ``[t0, t0 + seconds)``)."""
    c0 = snapshot(setup.driver)
    box = {}
    timer = threading.Timer(max(0.0, t0 + seconds - time.monotonic()),
                            lambda: box.update(c=snapshot(setup.driver)))
    timer.start()
    client = run_client(setup.port, t0, seconds, plan, grace)
    timer.join()
    c1 = box["c"]
    return client, {k: c1[k] - c0.get(k, 0) for k in c1}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        device: dict, peaks: dict, compiles: CompileLog, fault=None,
        control: bool = False) -> dict:
    import jax
    conf = cell.config
    eng = conf["engine"]
    setup = build(cell, trace)
    if fault is not None:
        fault(setup.engine)
    if trace:
        annotate(setup.engine)
    plan = make_plan(cell, seed, seconds)
    warm_up(setup.port, plan.warmup, eng["num_slots"])
    idle(setup.driver)

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    box: dict = {}
    t0 = time.monotonic() + 1.0
    setup_s = t0 - T_START
    tracer_th = traced_stretch(t0, seconds, tmp, box) if trace else None
    client, counters = window(setup, plan, t0, seconds)
    in_window = compiles.between(t0, t0 + seconds)
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    if tracer_th is not None:
        tracer_th.join()
        if "error" in box:
            raise RuntimeError(f"profiler capture failed: {box['error']!r}")
    events = None
    tracer = setup.engine.telem.tracer
    if tracer is not None:
        events = {"events": list(tracer.events),
                  "t0": getattr(tracer, "_t0", None)}
    stop(setup)
    if setup.driver.crashed is not None:
        raise RuntimeError(f"engine thread crashed: {setup.driver.crashed!r}")

    red = None
    if trace:
        red = trace_reduce.reduce_file(
            trace_reduce.find_xplane(tmp),
            trace_reduce.DEVICE_LINES[device["platform"]])
        shutil.rmtree(tmp, ignore_errors=True)

    records = client["records"]
    attempted = len(records)
    failed = [r for r in records if not r["ok"]]
    log(f"window: {seconds:g} s, loop {plan.loop}, requests sent "
        f"{attempted}, completed {attempted - len(failed)}, failed "
        f"{len(failed)}" + (f" (first: {failed[0]['error']})"
                            if failed else ""))
    log(f"compiles inside the window: {len(in_window)}"
        + (f" ({', '.join(sorted(set(in_window)))})" if in_window else ""))
    log(f"device memory: peak_bytes_in_use {peak} of "
        f"{mem.get('bytes_limit')}")

    data = {"seconds": seconds, "setup_s": setup_s, "loop": plan.loop,
            "records": records, "window": plan.window, "counters": counters,
            "tracer": events, "t0": t0, "trace": red, "config": conf,
            "peaks": peaks,
            "grace_s": GRACE_S}
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        v = reader(cell.bench_dir, m["name"])(data)
        if v is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"metric {m['name']} = {v!r} {m['unit']}")
    for name, n in data.get("samples", {}).items():
        log(f"samples behind {name}: {n}")

    # the check, after the program's state is gone
    layout, fp = setup.layout, setup.fingerprint
    del setup
    gc.collect()
    log(f"device bytes in use before the reference: "
        f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}")
    check = conf["check"]
    byi = records_by_i(records)
    idx = pick_sample(records, plan.window, seed, check["sample_requests"])
    samples = [(plan.window[i]["prompt"], byi[i]["tokens"]) for i in idx]
    t_ref = time.monotonic()
    read = {"widest_gap": float("nan"), "mean_gap": float("nan"),
            "judged": 0}
    if samples:
        dm = model_ref.Dims.from_config(conf)
        w32 = reference_weights(conf, layout, fp)
        read = model_ref.gaps(dm, w32, samples, eng["cache_len"])
        log(f"reference: {len(samples)} requests, {read['judged']} served "
            f"tokens judged, widest gap {read['widest_gap']!r}, mean gap "
            f"{read['mean_gap']!r}, {time.monotonic() - t_ref:.1f} s")
        if control:
            read = model_ref.gaps(dm, w32, samples, eng["cache_len"],
                                  control=True)
            log(f"control: the fp8 reference's first token in place of the "
                f"served one at every position, widest gap "
                f"{read['widest_gap']!r}, mean gap {read['mean_gap']!r}")
        del w32
    if samples:
        log(f"longest checked request: prompt {len(samples[0][0])} tokens, "
            f"served {len(samples[0][1])}, first served "
            f"{samples[0][1][:12]}")
    bad = stream_faults(records, plan.window, conf["vocab_size"],
                        eng["eos_id"])
    for b in bad[:5]:
        log(f"bad stream: {b}")
    # the gaps a configuration compares, each with its limit
    checks = {name: {"value": read[name], "limit": check[key]}
              for name, key in GAP_LIMITS.items() if key in check}
    checks.update({
        "failed_requests": {"value": len(failed), "limit": 0},
        "bad_streams": {"value": len(bad), "limit": 0},
        "requests_checked": {"value": len(samples),
                             "limit": check["sample_requests"]},
    })
    if client.get("ran_out"):
        checks["callers_ran_out"] = {"value": 1, "limit": 0}
    # a NaN gap (nothing judged) compares false, so it is not correct
    correct = (read["judged"] > 0
               and all(c["value"] <= c["limit"] for k, c in checks.items()
                       if k != "requests_checked")
               and len(samples) == min(check["sample_requests"],
                                       attempted - len(failed)))
    dev = dict(device, count=device["count"], memory_peak_bytes=peak)
    if red is not None:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        log(f"trace: busy {red['busy_s']!r} s of {red['window_s']!r} s, "
            f"{red['gap_count']} idle gaps; longest "
            f"{red['longest_gaps'][:5]}")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": len(failed), "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, require_chip: bool = True, bench_dir: Path = BENCH,
         peaks: Optional[dict] = None, fault=None) -> int:
    """One run.  ``require_chip=False`` (tests only) skips the look for a
    chip and the compile cache, and takes ``peaks`` as given."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the fp8 control in place of the served "
                         "tokens (must come out not correct)")
    args = ap.parse_args(argv)
    cell = load_cell(Path(bench_dir), args.workload)
    import jax
    if require_chip:
        try:
            device = device_info(cell.chips)
        except NoChip as e:
            print(f"{LOG} FAIL: {e}", file=sys.stderr, flush=True)
            return 2
        peaks = load_peaks(Path(bench_dir), device["kind"])
        enable_compile_cache()
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    compiles = CompileLog()
    out = run(cell, args.seed, args.seconds, bool(args.trace), device,
              peaks, compiles, fault, bool(args.control))
    for name, c in out["checks"].items():
        print(f"{LOG} check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
