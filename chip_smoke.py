#!/usr/bin/env python3
"""Chip smoke: serve Qwen3-0.6B at its published widths on one TPU chip.

Run from the root of a checkout on a machine with one TPU chip:

    python3 chip_smoke.py

One process holds the chip for the whole run and drives the system's main
path through the entry points a user calls.  Phases, in order:

1. device    -- report what JAX sees; exit non-zero unless it is a TPU.
2. build     -- ``ModelSpec(qwen3-0.6b, full width)`` -> ``build_model_bundle``:
                random init from a seed in the config's bf16, plus a few
                synthetic pretrain steps so the backbone training step
                compiles and runs too.  Losses must be finite.
3. serve     -- ``build_engine`` (continuous scheduler, paged pool, prefix
                cache, chunked prefill, online drafter learning) behind
                ``EngineDriver`` + ``ApiServer`` on a loopback port in this
                process; about 16 ``/v1/completions`` requests, SSE
                included.  Checks every outcome, stream, SSE == blocking,
                no trimmed prompt, prefix hits, folded drafter updates,
                ``host_syncs == dispatches``, a drained pool and the
                metrics schema.
4. reference -- in float32 at ``highest`` matmul precision: plain greedy
                AR (``spec.ar_generate``) against greedy decoding through
                the engine, token for token.  A divergence passes only at a
                verifier top-2 logit margin below ``TIE_MARGIN``.
5. result    -- per-phase smoke timings (compile and run split; these are
                not benchmark numbers), then one JSON line:
                ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Any failure exits non-zero without that line.  The compile cache follows
``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` if set,
else ``.jax_cache/`` at the checkout root, so a second run compiles warm.
"""
from __future__ import annotations

import gc
import http.client
import importlib.util
import json
import math
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import closing
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import online as online_mod  # noqa: E402
from repro.core import spec as spec_mod  # noqa: E402
from repro.data import TASK_CATEGORIES  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving.config import (EngineConfig, ModelSpec,  # noqa: E402
                                  build_engine, build_model_bundle)
from repro.serving.engine import Request  # noqa: E402
from repro.serving.http import ApiServer, EngineDriver  # noqa: E402

ARCH = "qwen3-0.6b"
# Qwen3-0.6B's published widths (hf:Qwen/Qwen3-0.6B config.json); the
# registry config must match them and keep its bf16
PUBLISHED = dict(num_layers=28, d_model=1024, num_heads=16, num_kv_heads=8,
                 resolved_head_dim=128, d_ff=3072, vocab_size=151_936,
                 tie_embeddings=True, dtype="bfloat16")
# a verifier top-2 logit margin below this is a tie that float32 rounding
# may flip between two correct decoders
TIE_MARGIN = 1e-3


class NoChip(RuntimeError):
    """JAX found no TPU."""


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class SmokePlan:
    """Sizes of one smoke run.  ``FULL`` is what the script runs; the CPU
    test runs the same phases on a tiny plan."""
    tiny: bool
    engine: EngineConfig          # the served engine
    ref_engine: EngineConfig      # the float32 reference engine
    prompt_lens: tuple            # first wave: blocking requests
    shared_prefix: int            # leader (prompt 0) tokens the followers share
    follower_tails: tuple         # second wave: shared prefix + fresh tail
    sse_twins: tuple              # second wave: SSE repeats of these prompts
    ref_prompts: tuple            # equal-length prompts checked against AR
    max_tokens: int
    pretrain_steps: int = 8
    seed: int = 0
    timeout_s: float = 600.0


FULL = SmokePlan(
    tiny=False,
    # 1,024 pages of 16 tokens (1.75 GiB of bf16 KV) hold this traffic's
    # peak of about 560 live pages without preemption.  No engine program
    # donates its cache, so every program queued behind an in-flight
    # superstep holds a copy of the pool of its own: the pool is sized so
    # that several copies fit beside the weights
    engine=EngineConfig(scheduler="continuous", num_slots=16, max_new=128,
                        sync_every=4, cache_len=2048, kv_pages=1024,
                        kv_page_size=16, prefix_cache=True,
                        prefill_chunk=128),
    ref_engine=EngineConfig(scheduler="continuous", num_slots=2, max_new=128,
                            sync_every=4, learn=False, cache_len=1024,
                            kv_pages=160, kv_page_size=16, prefix_cache=True,
                            prefill_chunk=128),
    prompt_lens=(512, 1024, 384, 384, 160, 256, 640, 768, 896, 320, 448,
                 1000),
    shared_prefix=256,
    follower_tails=(200, 330),
    sse_twins=(1, 5),
    ref_prompts=(2, 3),
    max_tokens=128,
)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase() -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[smoke] device: platform={info['platform']} "
          f"kind={info['kind']} count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU chip: JAX found platform "
                     f"{info['platform']!r} ({info['kind']}); this smoke "
                     f"needs one TPU chip and has no CPU fallback")
    return info


def build_phase(plan: SmokePlan):
    bundle = build_model_bundle(ModelSpec(
        arch=ARCH, tiny=plan.tiny, seed=plan.seed,
        pretrain_steps=plan.pretrain_steps))
    cfg = bundle.cfg
    n_params = sum(int(a.size) for a in jax.tree.leaves(bundle.params))
    print(f"[smoke] model {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}q/"
          f"{cfg.num_kv_heads}kv x {cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} tied={cfg.tie_embeddings} "
          f"dtype={cfg.dtype} params={n_params}", flush=True)
    if not plan.tiny:
        for key, want in PUBLISHED.items():
            check(getattr(cfg, key) == want,
                  f"{key}={getattr(cfg, key)!r}, published {want!r}")
        check(bundle.params["embed"].dtype == jnp.bfloat16,
              f"params are {bundle.params['embed'].dtype}, config says bf16")
    losses = bundle.pretrain_losses
    print(f"[smoke] pretrain: {len(losses)} steps at batch 8 x 32, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)
    check(len(losses) == plan.pretrain_steps
          and all(math.isfinite(x) for x in losses),
          f"pretrain losses not finite: {losses}")
    return bundle


def make_prompts(plan: SmokePlan, tasks) -> tuple:
    """(first wave, followers): seeded synthetic prompts; the followers
    open with the leader's first ``shared_prefix`` tokens."""
    cats = TASK_CATEGORIES
    wave = [tasks.sample(cats[i % len(cats)], 1, n,
                         seed=plan.seed * 1000 + i)[0]
            for i, n in enumerate(plan.prompt_lens)]
    followers = [np.concatenate([
        wave[0][:plan.shared_prefix],
        tasks.sample(cats[(i + 3) % len(cats)], 1, n,
                     seed=plan.seed * 1000 + 100 + i)[0]])
        for i, n in enumerate(plan.follower_tails)]
    return wave, followers


def _post(port: int, prompt, max_tokens: int, stream: bool, timeout: float):
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": max_tokens, "stream": stream})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/completions", body,
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def complete(port: int, prompt, max_tokens: int, timeout: float) -> dict:
    """One blocking completion: tokens, finish reason, prompt tokens."""
    conn, resp = _post(port, prompt, max_tokens, False, timeout)
    with closing(conn):
        data = resp.read()
    check(resp.status == 200, f"HTTP {resp.status}: {data[:200]!r}")
    obj = json.loads(data)
    ch = obj["choices"][0]
    return {"tokens": ch["token_ids"], "finish": ch["finish_reason"],
            "prompt_tokens": obj["usage"]["prompt_tokens"]}


def stream(port: int, prompt, max_tokens: int, timeout: float) -> dict:
    """One SSE completion: the concatenated chunks and finish reason."""
    conn, resp = _post(port, prompt, max_tokens, True, timeout)
    toks, finish = [], None
    with closing(conn):
        check(resp.status == 200, f"SSE HTTP {resp.status}")
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                break
            obj = json.loads(payload)
            check("error" not in obj, f"SSE error: {obj.get('error')}")
            ch = obj["choices"][0]
            toks.extend(ch["token_ids"])
            finish = ch["finish_reason"] or finish
    return {"tokens": toks, "finish": finish}


def serve_phase(plan: SmokePlan, bundle) -> dict:
    econf = plan.engine
    cfg = bundle.cfg
    kv_bytes = (2 * cfg.num_layers * (econf.kv_pages + 1)
                * econf.kv_page_size * cfg.num_kv_heads
                * cfg.resolved_head_dim * jnp.dtype(cfg.dtype).itemsize)
    print(f"[smoke] engine: lanes={econf.num_slots} "
          f"sync_every={econf.sync_every} cache_len={econf.cache_len} "
          f"pool={econf.kv_pages} pages x {econf.kv_page_size} tokens "
          f"({kv_bytes} bytes of KV) prefix_cache={econf.prefix_cache} "
          f"prefill_chunk={econf.prefill_chunk} learn={econf.learn}",
          flush=True)
    wave, followers = make_prompts(plan, bundle.tasks)
    engine = build_engine(econf, bundle.model, bundle.params, bundle.state)
    driver = EngineDriver(engine).start()
    srv = ApiServer(("127.0.0.1", 0), driver, model_id=cfg.name,
                    default_max_new=econf.max_new,
                    request_timeout_s=plan.timeout_s)
    port = srv.server_address[1]
    http_thread = threading.Thread(target=srv.serve_forever,
                                   kwargs={"poll_interval": 0.1},
                                   name="smoke-http", daemon=True)
    http_thread.start()
    n_req = len(wave) + len(plan.sse_twins) + len(followers)
    print(f"[smoke] serving {n_req} requests on http://127.0.0.1:{port}",
          flush=True)
    mt, to = plan.max_tokens, plan.timeout_s
    try:
        with ThreadPoolExecutor(max_workers=len(wave)) as pool:
            first = list(pool.map(lambda p: complete(port, p, mt, to), wave))
            # second wave after the first has finished: every twin's
            # prompt and the leader's prefix are in the prefix cache
            sse_f = [pool.submit(stream, port, wave[i], mt, to)
                     for i in plan.sse_twins]
            fol_f = [pool.submit(complete, port, p, mt, to)
                     for p in followers]
            sse = [f.result() for f in sse_f]
            fol = [f.result() for f in fol_f]
    finally:
        srv.shutdown()
        srv.server_close()
        driver.stop(drain=True)
        http_thread.join(timeout=60)
    check(driver.crashed is None, f"engine thread crashed: {driver.crashed!r}")

    blocking = list(zip(wave, first)) + list(zip(followers, fol))
    vocab = cfg.vocab_size
    for i, (prompt, r) in enumerate(blocking + [(wave[j], s) for j, s in
                                                zip(plan.sse_twins, sse)]):
        toks = r["tokens"]
        check(r["finish"] in ("stop", "length"),
              f"request {i}: finish_reason {r['finish']!r}")
        check(0 < len(toks) <= mt, f"request {i}: {len(toks)} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"request {i}: token id outside the vocabulary")
    for prompt, r in blocking:
        check(r["prompt_tokens"] == len(prompt),
              f"prompt of {len(prompt)} tokens served as "
              f"{r['prompt_tokens']}: trimmed")
    for j, s in zip(plan.sse_twins, sse):
        check(s["tokens"] == first[j]["tokens"],
              f"SSE stream of prompt {j} differs from its blocking result")

    d = engine.dispatch_stats()
    kv = engine.kv_stats()
    tt = engine.train_telemetry()
    errs = _schema_errors(engine.metrics_snapshot())
    gen = sum(len(r["tokens"]) for _, r in blocking) + sum(
        len(s["tokens"]) for s in sse)
    print(f"[smoke] served {n_req} requests ({len(sse)} SSE), {gen} tokens; "
          f"prompts {min(len(p) for p, _ in blocking)}-"
          f"{max(len(p) for p, _ in blocking)} tokens, none trimmed; "
          f"acceptance={engine.acceptance:.3f}", flush=True)
    print(f"[smoke] prefix cache: hits={kv['prefix_hits']}/"
          f"{kv['prefix_lookups']} lookups, "
          f"tokens_spliced={kv['prefix_hit_tokens']}; pool used="
          f"{kv['used_pages']} (peak {kv['peak_used_pages']}) of "
          f"{kv['num_pages']} pages, preemptions={kv['preemptions']}",
          flush=True)
    print(f"[smoke] host_syncs={d['host_syncs']} "
          f"dispatches={d['dispatches']} drafter updates={tt['updates']} "
          f"(folded {len(tt['history'])}, last loss {tt['loss']:.4f}); "
          f"metrics schema errors={len(errs)}", flush=True)
    check(kv["prefix_hits"] > 0, "prefix cache recorded no hit")
    check(tt["updates"] > 0 and len(tt["history"]) == tt["updates"],
          f"drafter updates {tt['updates']}, folded {len(tt['history'])}")
    check(d["host_syncs"] == d["dispatches"],
          f"host_syncs {d['host_syncs']} != dispatches {d['dispatches']}")
    check(kv["used_pages"] == 0 and engine.active_slots == 0,
          f"pool not drained: {kv['used_pages']} pages in use")
    check(not errs, f"metrics schema: {errs}")
    return {"prompts": wave}


def _schema_errors(snapshot: dict) -> list:
    """``scripts/check_metrics_schema.py``'s checks on one snapshot."""
    spec = importlib.util.spec_from_file_location(
        "check_metrics_schema", ROOT / "scripts" / "check_metrics_schema.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.check_snapshot(snapshot, "chip_smoke")


def reference_phase(plan: SmokePlan, bundle, prompts: list) -> None:
    """Float32 greedy AR vs greedy decoding through the engine."""
    cfg32 = bundle.cfg.replace(dtype="float32")
    model = build_model(cfg32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), bundle.params)
    batch = np.stack([prompts[i] for i in plan.ref_prompts])
    Tp, mt = batch.shape[1], plan.max_tokens
    with jax.default_matmul_precision("highest"):
        ar = jax.jit(lambda p, x: spec_mod.ar_generate(model, p, x, mt))(
            params, jnp.asarray(batch))
        toks, lens = np.asarray(ar.tokens), np.asarray(ar.lengths)
        ar_streams = [toks[b, Tp:lens[b]].tolist() for b in range(len(batch))]
        state = online_mod.init_trainer(model,
                                        jax.random.PRNGKey(plan.seed + 7))
        engine = build_engine(plan.ref_engine, model, params, state)
        handles = [engine.submit_request(Request(uid=b, prompt=batch[b],
                                                 max_new=mt))
                   for b in range(len(batch))]
        engine.run()
        for b, (h, want) in enumerate(zip(handles, ar_streams)):
            got = [int(t) for t in h.tokens()]
            check(h.outcome == "completed", f"reference request {b}: "
                  f"{h.outcome}")
            if got == want:
                print(f"[smoke] reference: prompt {plan.ref_prompts[b]} "
                      f"({Tp} tokens): engine == AR for all {len(got)} "
                      f"tokens (float32, highest precision)", flush=True)
                continue
            j = next((i for i, (a, c) in enumerate(zip(got, want)) if a != c),
                     min(len(got), len(want)))
            seq = np.concatenate([batch[b], np.asarray(want[:j], np.int32)])
            logits, _ = model.forward_train(params, jnp.asarray(seq)[None])
            top2 = np.sort(np.asarray(logits[0, -1], np.float64))[-2:]
            margin = float(top2[1] - top2[0])
            print(f"[smoke] reference: prompt {plan.ref_prompts[b]}: engine "
                  f"and AR diverge at generated position {j}; verifier top-2 "
                  f"logit margin there {margin:.3e}", flush=True)
            check(margin < TIE_MARGIN,
                  f"engine != AR at position {j} with margin {margin:.3e}")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds XLA spends compiling, or fetching a compiled program from
    the persistent cache, summed from ``jax.monitoring`` events.  Tracing
    and lowering are left out: their events nest, so their sum can exceed
    the wall time."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            with self._lock:
                self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def main() -> int:
    cache_dir = enable_compile_cache()
    try:
        info = device_phase()
    except NoChip as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 2
    clock = CompileClock()
    timings = []

    def timed(name, fn, *args):
        c0, t0 = clock.seconds, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        timings.append((name, clock.seconds - c0, wall))
        mem = jax.devices()[0].memory_stats() or {}
        print(f"[smoke] after {name}: device bytes_in_use="
              f"{mem.get('bytes_in_use')} peak_bytes_in_use="
              f"{mem.get('peak_bytes_in_use')} of {mem.get('bytes_limit')}",
              flush=True)
        return out

    try:
        bundle = timed("build", build_phase, FULL)
        served = timed("serve", serve_phase, FULL, bundle)
        gc.collect()
        timed("reference", reference_phase, FULL, bundle, served["prompts"])
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    for name, comp, wall in timings:
        print(f"[smoke] smoke timing (not a benchmark number): {name}: "
              f"XLA compile or cache fetch {comp:.1f}s, rest (run, "
              f"tracing, host work) {wall - comp:.1f}s, wall {wall:.1f}s")
    print(f"[smoke] compile cache {cache_dir}: {clock.cache_hits} hits; "
          f"XLA compile or cache fetch {sum(t[1] for t in timings):.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
