#!/usr/bin/env python3
"""The readings a configuration's gap limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds 12

One set-up, then for each seed a window of the cell's own traffic at its
own load, and the same seeded sample of finished requests a benchmark run
checks (the longest among them).  Once every window is served the program's
state is freed and the float32 reference reads, for each seed:

* program: the widest gap by which a served token's reference logit lies
  below the reference's best, and the mean gap over every served token
  (what a run compares with ``gap_limit`` and ``mean_gap_limit``);
* control (``--control-seeds``): the same reading for the token that the
  reference computed with fp8 linear layers (``model_ref``, mode ``fp8``)
  puts first, at every position of the same prompts and served tokens.

A limit lies above every program reading and below every control reading
of its number, with more room above the first.  Benchmark runs never call this
script.
"""
from __future__ import annotations

import argparse
import gc
import sys
import time

import model_ref
import run as bench


def readings(cell, seeds, control_seeds, seconds: float) -> tuple:
    """(program readings, one per seed; control readings, one per control
    seed), all from one set-up."""
    conf = cell.config
    setup = bench.build(cell, trace=False)
    layout, fp = setup.layout, setup.fingerprint
    n = conf["check"]["sample_requests"]
    served = {}
    for k, seed in enumerate(seeds):
        plan = bench.make_plan(cell, seed, seconds)
        if k == 0:
            bench.warm_up(setup.port, plan.warmup,
                          conf["engine"]["num_slots"])
            bench.idle(setup.driver)
        client, counters = bench.window(setup, plan, time.monotonic() + 0.5,
                                        seconds)
        bench.idle(setup.driver, timeout=600)
        recs = client["records"]
        byi = bench.records_by_i(recs)
        idx = bench.pick_sample(recs, plan.window, seed, n)
        served[seed] = [(plan.window[i]["prompt"], byi[i]["tokens"])
                        for i in idx]
        bad = sum(not r["ok"] for r in recs)
        if idx:
            head = served[seed][0][1]
            print(f"[control] seed {seed}: longest served {len(head)} "
                  f"tokens, {len(set(head))} distinct, first {head[:16]}",
                  flush=True)
        print(f"[control] seed {seed}: {len(recs)} requests, {bad} failed, "
              f"{len(idx)} checked, tokens per block "
              f"{counters['dvi_serving_committed_tokens_total'] / max(1, counters['dvi_serving_blocks_total']):.3f}, "
              f"{sum(r['in_window'] for r in recs) / seconds:.1f} tokens/s, "
              f"{sum(len(t) for _, t in served[seed])} served tokens",
              flush=True)
    bench.stop(setup)
    del setup
    gc.collect()

    w32 = bench.reference_weights(conf, layout, fp)
    dm = model_ref.Dims.from_config(conf)
    pad = conf["engine"]["cache_len"]
    prog, cont = [], []
    for seed in seeds:
        g = model_ref.gaps(dm, w32, served[seed], pad)
        prog.append(g)
        line = (f"[control] seed {seed}: program widest {g['widest_gap']!r} "
                f"mean {g['mean_gap']!r} over {g['judged']} tokens")
        if seed in control_seeds:
            c = model_ref.gaps(dm, w32, served[seed], pad, control=True)
            cont.append(c)
            line += (f"; fp8 control widest {c['widest_gap']!r} mean "
                     f"{c['mean_gap']!r}")
        print(line, flush=True)
    return prog, cont


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(bench.BENCH, args.workload)
    try:
        bench.device_info(cell.chips)
    except bench.NoChip as e:
        print(f"[control] FAIL: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    prog, cont = readings(
        cell, [int(s) for s in args.seeds.split(",")],
        {int(s) for s in args.control_seeds.split(",") if s}, args.seconds)
    for name, key in bench.GAP_LIMITS.items():
        print(f"[control] {args.workload} {name}: program max "
              f"{max(g[name] for g in prog)!r} over {len(prog)} seeds; "
              f"control min {min((c[name] for c in cont), default=None)!r} "
              f"over {len(cont)} seeds; limit now "
              f"{cell.config['check'].get(key)!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
