#!/usr/bin/env python3
"""Find an open-loop cell's knee: its traffic at several fixed rates, one
set-up for all of them, one window each.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,6

For each rate it prints a row: requests sent and completed, output tokens
per second against the tokens per second offered, TTFT p50 and p95 over
all requests due, and TTFT p50 of the first and of the last third of the
window (a backlog that grows through the window shows as the second far
above the first).  The knee is the highest rate the system sustains: every
request completes, output keeps up with what is offered and TTFT does not
grow through the window.  The cell's rate is then fixed in its mix file at
about four fifths of that.  Benchmark runs never call this script.
"""
from __future__ import annotations

import argparse
import sys
import time

import run as bench
from stats import percentile

GRACE_S = 30.0


def row(rate: float, seconds: float, plan, client: dict) -> dict:
    recs = client["records"]
    ok = [r for r in recs if r["ok"]]
    wait = seconds + GRACE_S
    ttft = [((r["first"] if r["ok"] and r["first"] is not None else wait)
             - r["due"], r["due"]) for r in recs]
    third = seconds / 3
    early = [t for t, d in ttft if d < third]
    late = [t for t, d in ttft if d >= 2 * third]
    offered = sum(r["max_tokens"] for r in plan.window) / seconds
    return {"rate": rate, "sent": len(recs), "completed": len(ok),
            "out_tok_s": sum(r["in_window"] for r in recs) / seconds,
            "offered_tok_s": offered,
            "ttft_p50_ms": 1e3 * percentile([t for t, _ in ttft], 50),
            "ttft_p95_ms": 1e3 * percentile([t for t, _ in ttft], 95),
            "ttft_p50_first_third_ms": 1e3 * (percentile(early, 50) or 0),
            "ttft_p50_last_third_ms": 1e3 * (percentile(late, 50) or 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(bench.BENCH, args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("a sweep is for open-loop cells")
    try:
        bench.device_info(cell.chips)
    except bench.NoChip as e:
        print(f"[sweep] FAIL: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    setup = bench.build(cell, trace=False)
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        plan = bench.make_plan(cell, args.seed + k, args.seconds, mix)
        # each rate has its own set of sizes: warm up its shapes first
        bench.warm_up(setup.port, plan.warmup,
                      cell.config["engine"]["num_slots"])
        bench.idle(setup.driver)
        t0 = time.monotonic() + 0.5
        client, _ = bench.window(setup, plan, t0, args.seconds, GRACE_S)
        bench.idle(setup.driver, timeout=600)
        rows.append(row(rate, args.seconds, plan, client))
        print("[sweep] " + " ".join(f"{k}={v:.6g}" if isinstance(v, float)
                                    else f"{k}={v}"
                                    for k, v in rows[-1].items()),
              flush=True)
    bench.stop(setup)
    print("[sweep] table: rate req/s | sent | completed | out tok/s | "
          "offered tok/s | TTFT p50 ms | TTFT p95 ms | TTFT p50 first "
          "third | last third")
    for r in rows:
        print("[sweep] | " + " | ".join(
            f"{v:.1f}" if isinstance(v, float) else str(v)
            for v in r.values()) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
