"""The load generator's client: a process of its own that never imports JAX.

The engine thread of the server holds the GIL for long stretches; a client
in the server's process would be delayed by it and report the delay as the
server's.  This process reads its plan as one JSON document on standard
input and writes one JSON document of per-request records on standard
output.

Plan: ``port``, ``t0`` (a ``time.monotonic()`` reading, the same clock in
every process of the machine), ``seconds``, ``loop`` (``open`` or
``closed``), ``clients``, ``grace_s`` and ``requests`` (``prompt``,
``max_tokens`` and, open loop, ``due`` in seconds after ``t0``).

Open loop: request ``i`` is sent at ``t0 + due``, whatever the server does.
Closed loop: ``clients`` callers take the next request in order as soon as
their last one has ended, until the window closes; a request's due time is
when its caller sent it.  Every request streams (SSE).  Requests still open
when the window closes are waited for up to ``grace_s`` more; one that has
not ended by then is recorded as failed.

Record of each request sent (times in seconds after ``t0``): ``i``,
``due``, ``sent``, ``first`` (first token), ``last`` (last token), ``tokens``,
``chunks`` (``[time, tokens]`` of every streamed chunk that held tokens),
``in_window`` (tokens received before ``t0 + seconds``), ``ok``, ``error``.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

REQUEST_TIMEOUT_S = 300.0


def run_one(port: int, req: dict, rec: dict, t0: float, end: float,
            deadline: float) -> None:
    body = json.dumps({"prompt": req["prompt"], "max_tokens":
                       req["max_tokens"], "stream": True})
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    toks = rec["tokens"]
    try:
        rec["sent"] = time.monotonic() - t0
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}"
            return
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[6:]
            if payload == b"[DONE]":
                rec["ok"] = True
                break
            obj = json.loads(payload)
            if "error" in obj:
                rec["error"] = str(obj["error"])
                break
            got = obj["choices"][0]["token_ids"]
            if got:
                now = time.monotonic()
                if rec["first"] is None:
                    rec["first"] = now - t0
                rec["last"] = now - t0
                rec["chunks"].append([now - t0, len(got)])
                toks.extend(got)
                if now < end:
                    rec["in_window"] += len(got)
            if time.monotonic() > deadline:
                rec["error"] = "not ended within the grace period"
                break
    except Exception as e:                       # recorded, never raised
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
        rec["done"] = True


def new_record(i: int, due: float) -> dict:
    return {"i": i, "due": due, "sent": None, "first": None, "last": None,
            "tokens": [], "chunks": [], "in_window": 0, "ok": False, "error": None,
            "done": False}


def main() -> int:
    plan = json.loads(sys.stdin.read())
    port, t0, seconds = plan["port"], plan["t0"], float(plan["seconds"])
    reqs = plan["requests"]
    end = t0 + seconds
    deadline = end + float(plan["grace_s"])
    records, threads = [], []
    lock = threading.Lock()

    if plan["loop"] == "open":
        for i, r in enumerate(reqs):
            wait = t0 + r["due"] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            rec = new_record(i, r["due"])
            records.append(rec)
            th = threading.Thread(target=run_one, daemon=True,
                                  args=(port, r, rec, t0, end, deadline))
            th.start()
            threads.append(th)
    else:
        nxt = [0]
        ran_out = []

        def caller() -> None:
            while True:
                now = time.monotonic()
                if now >= end:
                    return
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                    if i >= len(reqs):
                        ran_out.append(i)
                        return
                    rec = new_record(i, now - t0)
                    records.append(rec)
                run_one(port, reqs[i], rec, t0, end, deadline)

        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        threads = [threading.Thread(target=caller, daemon=True)
                   for _ in range(int(plan["clients"]))]
        for th in threads:
            th.start()
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()) + 5.0)
    with lock:
        out = [dict(r) for r in records]
    for r in out:
        if not r.pop("done") and r["error"] is None:
            r["error"] = "not ended within the grace period"
            r["ok"] = False
    result = {"records": out}
    if plan["loop"] == "closed" and ran_out:
        result["ran_out"] = True
    sys.stdout.write(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
