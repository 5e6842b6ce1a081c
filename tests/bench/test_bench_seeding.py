"""Stable seeding: two processes with different ``PYTHONHASHSEED`` build the
same request lists and the same weights (the benchmark's Markov text, its
traffic and the transitions planted in its weights never go through the
salted ``hash()``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

PROBE = r"""
import hashlib, json, sys
sys.path.insert(0, "bench")
import run as bench
import model_ref, traffic
conf = json.load(open("bench/configs/qwen3-0.6b.json"))
conf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=300)
conf["weights"]["markov_span"] = 40
text = bench.markov_text(conf)
out = {}
for name in ("chat-poisson", "docqa-prefix", "chat-closed16"):
    mix = json.load(open(f"bench/traffic/{name}.json"))
    p = traffic.make_plan(mix, text, 2**31 + 3, 5.0, oneshot_max=17)
    blob = json.dumps([p.warmup, p.window], sort_keys=True).encode()
    out[name] = hashlib.sha256(blob).hexdigest()
w = bench.bench_weights(conf, [(0, 2), (2, 3)])
out["weights"] = model_ref.fingerprint(w)
print(json.dumps(out))
"""


def probe(hashseed: str) -> dict:
    env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", PROBE], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_two_processes_build_the_same_requests_and_weights():
    a, b = probe("1"), probe("2")
    assert a == b
    assert a["weights"] > 0
