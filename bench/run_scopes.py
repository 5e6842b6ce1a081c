#!/usr/bin/env python3
"""One run of one cell as ``run.py`` makes it, with the program's own
account of the traced stretch added:

    python3 bench/run_scopes.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

``scope_reduce`` reads the same trace as ``trace_reduce``, with the engine
tracer's phase spans; its seconds per device scope and per ``dvi.`` host
span of the idle gaps join the ``breakdown`` as ``scopes`` and
``program_gaps``, and the per-layer metrics
of ``METRICS`` are read beside those of ``BENCHMARK.json``.  ``METRICS`` are
written as ``BENCHMARK.json`` entries, to be moved there once the harness
reads them itself; until then this script is how they are measured.

The persistent compile cache keys a program without its debug metadata, so
a program cached before its scopes were named would come back with the old
``op_name``s; this run keys the cache with the metadata, at the price of
compiling each program once more.
"""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

CLOSED = "qwen3-1.7b.chat-closed16"
POISSON = "qwen3-0.6b.chat-poisson"


def _metric(name, unit, source, layer, moves, cells):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves, "workloads": cells}


METRICS = [
    _metric("draft_share", "%", "device_trace", "speculative core",
            "tpot_mean_ms", [CLOSED]),
    _metric("verify_share", "%", "device_trace", "model step",
            "tpot_mean_ms", [CLOSED]),
    _metric("commit_share", "%", "device_trace", "KV pool and prefix cache",
            "tpot_mean_ms", [CLOSED]),
    _metric("learn_share", "%", "device_trace", "speculative core",
            "output_tok_s", [CLOSED]),
    _metric("prefill_share", "%", "device_trace", "scheduler",
            "ttft_p95_ms", [POISSON, CLOSED]),
    _metric("submit_wait_p95_ms", "ms", "program_span", "HTTP front end",
            "ttft_p95_ms", [POISSON, CLOSED]),
    _metric("relay_p95_ms", "ms", "program_span", "HTTP front end",
            "ttft_p95_ms", [POISSON, CLOSED]),
]


def main(argv=None, **kw) -> int:
    """``run.main`` with the scope split and ``METRICS`` added (``kw`` as
    ``run.main`` takes them)."""
    split: dict = {}
    engines: list = []
    reduce_file, load_cell, run_cell, build = (
        trace_reduce.reduce_file, run.load_cell, run.run, run.build)

    def build_kept(*a, **k):
        setup = build(*a, **k)
        engines.append(setup.engine)
        return setup

    def reduce_both(path, device_lines=trace_reduce.is_tpu_ops):
        red = reduce_file(path, device_lines)
        split.update(scope_reduce.reduce_file(
            path, device_lines, engines[-1].trace_dict()))
        return dict(red, **split)

    def with_metrics(bench_dir, workload):
        cell = load_cell(bench_dir, workload)
        cell.per_layer = cell.per_layer + [
            m for m in METRICS if run.applies(m, workload)]
        return cell

    def with_breakdown(*a, **k):
        out = run_cell(*a, **k)
        if split:
            out["breakdown"].update(scopes=split["scopes"],
                                    program_gaps=split["program_gaps"])
        return out

    import jax
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    trace_reduce.reduce_file, run.load_cell, run.run, run.build = (
        reduce_both, with_metrics, with_breakdown, build_kept)
    try:
        return run.main(argv, **kw)
    finally:
        trace_reduce.reduce_file, run.load_cell, run.run, run.build = (
            reduce_file, load_cell, run_cell, build)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)


if __name__ == "__main__":
    sys.exit(main())
