"""The harness end to end on the CPU, at a tiny size.

The benchmark itself refuses a CPU (the first test shows it); the others
call ``run.main`` with the look for a chip skipped, on a temporary copy of
``bench/`` that adds tiny configurations, tiny mixes and a model module of
another architecture (``untied_model.py``) as files of their own, as a later
change would.  They drive the whole run: set-up, warm-up, the client
process, the window, the metric readers and the check against the float32
reference, and see ``correct`` come out false when the served stream is
broken underneath (a token altered, tokens sent to the wrong lane, a step
that returns its KV cache unchanged, the embedding served in the untied
head's place) and when the fp8 control stands in for the program.
"""
import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import run as bench  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12}
# the tiny configuration's limit, set from readings on the CPU over sixteen
# seeds (eight of each open and closed mix): program 0.0-0.0462, fp8
# control 0.2366-0.9851
TINY_LIMIT = 0.12
E2E = {"output_tok_s", "ttft_p95_ms", "tpot_mean_ms", "setup_s"}


def write_json(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory) -> Path:
    """A copy of the benchmark whose only additions are files: a tiny
    configuration, a tiny untied one with its own model module, three tiny
    mixes and one extra metric reader, and their entries in
    BENCHMARK.json."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    c.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64,
             vocab_size=512)
    c["weights"]["markov_span"] = 64
    c["dvi"] = {"split_layer": 2, "k_spec": 4, "lora_rank": 8,
                "buffer_slots": 512, "batch_size": 64}
    c["engine"].update(num_slots=4, max_new=64, cache_len=256, kv_pages=96,
                       prefill_chunk=16)
    c["check"] = {"gap_limit": TINY_LIMIT, "sample_requests": 4}
    write_json(root / "bench" / "configs" / "tiny.json", c)
    shutil.copy(Path(__file__).with_name("untied_model.py"),
                root / "bench" / "untied_ref.py")
    write_json(root / "bench" / "configs" / "tiny-untied.json",
               dict(c, tie_word_embeddings=False,
                    reference="bench/untied_ref.py"))
    chat = json.loads((BENCH / "traffic" / "chat-poisson.json").read_text())
    chat.update(rate_per_s=4.0, max_total_tokens=250,
                prompt_tokens={"median": 40, "sigma": 0.5, "min": 8,
                               "max": 120},
                output_tokens={"uniform": [8, 24]})
    write_json(root / "bench" / "traffic" / "tiny-chat.json", chat)
    doc = json.loads((BENCH / "traffic" / "docqa-prefix.json").read_text())
    doc.update(rate_per_s=4.0, max_total_tokens=250,
               documents={"count": 3, "tokens": 64, "categories": ["rag"],
                          "zipf_s": 1.0},
               question_tokens={"uniform": [8, 24]},
               output_tokens={"uniform": [8, 16]})
    write_json(root / "bench" / "traffic" / "tiny-doc.json", doc)
    closed = dict(chat, loop="closed", clients=2, strata=32,
                  output_tokens={"uniform": [48, 64]})
    closed.pop("rate_per_s")
    write_json(root / "bench" / "traffic" / "tiny-closed.json", closed)
    (root / "bench" / "metrics" / "prompt_tokens_sent.py").write_text(
        '"""Prompt tokens the client sent in the window."""\n\n\n'
        "def read(run):\n"
        "    return sum(len(run['window'][r['i']]['prompt'])\n"
        "               for r in run['records'] if r['sent'] is not None)\n")
    for name in ("tiny", "tiny-untied"):
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
    cells = {"tiny.chat": ("tiny", "tiny-chat"),
             "tiny.doc": ("tiny", "tiny-doc"),
             "tiny.closed": ("tiny", "tiny-closed"),
             "tiny-untied.chat": ("tiny-untied", "tiny-chat")}
    for cell, (conf, mix) in cells.items():
        spec["workloads"].append({"name": cell, "config": conf,
                                  "traffic": mix, "chips": 1,
                                  "why": "CPU test"})
    cells = list(cells)
    for m in spec["per_layer"]:
        m["workloads"] += [x for x in cells if x != "tiny.closed"] \
            if m["name"] == "gen_late_p95_ms" else cells
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += cells
    spec["per_layer"].append({
        "name": "prompt_tokens_sent", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "ttft_p95_ms", "workloads": ["tiny.chat"]})
    write_json(root / "BENCHMARK.json", spec)
    return root


def run_cell(root: Path, cell: str, seed: int, trace: int = 0,
             fault=None, control: int = 0) -> tuple:
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "3", "--trace", str(trace),
                         "--control", str(control)],
                        require_chip=False, bench_dir=root / "bench",
                        peaks=PEAKS, fault=fault)
    lines = out.getvalue().strip().splitlines()
    return rc, lines


def test_cpu_is_refused(capsys):
    rc = bench.main(["--workload", "qwen3-0.6b.chat-poisson", "--seed", "1",
                     "--seconds", "3", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc == 2
    assert "no CPU fallback" in cap.err
    assert not any(line.startswith("{") for line in cap.out.splitlines())


def test_copy_only_adds_files(tiny):
    for f in BENCH.rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            rel = f.relative_to(BENCH)
            assert (tiny / "bench" / rel).read_bytes() == f.read_bytes()


def test_run_end_to_end(tiny):
    rc, lines = run_cell(tiny, "tiny.chat", seed=2**31 + 11)
    assert rc == 0
    res = json.loads(lines[-1])
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["widest_gap"]["value"] <= TINY_LIMIT
    assert any("compiles inside the window: 0" in x for x in lines)


def test_traced_run_reads_the_layers_and_the_new_metric(tiny):
    """A new mix and a new metric run by their files alone; every per-layer
    metric of the cell is read, the scope split's among them."""
    rc, lines = run_cell(tiny, "tiny.chat", seed=7, trace=1)
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True
    want = {"gen_late_p95_ms", "queue_wait_p95_ms", "prefix_hit_share",
            "tokens_per_block", "sync_wait_share", "device_idle_share",
            "mfu", "prompt_tokens_sent", "draft_share", "verify_share",
            "commit_share", "learn_share", "prefill_share",
            "submit_wait_p95_ms", "relay_p95_ms"}
    assert want <= set(res["metrics"])
    assert res["metrics"]["prompt_tokens_sent"]["value"] > 0
    assert 1 <= res["metrics"]["tokens_per_block"]["value"] <= 5
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_prefix_and_closed_loop_mixes(tiny):
    rc, lines = run_cell(tiny, "tiny.doc", seed=3, trace=1)
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["prefix_hit_share"]["value"] > 50
    rc, lines = run_cell(tiny, "tiny.closed", seed=4)
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True
    assert "gen_late_p95_ms" not in res["metrics"]


def altered_tokens(engine):
    """A token altered where it is produced: the first token each lane
    commits in a superstep comes out one higher."""
    f = engine._superstep_fn

    def broken(*a):
        res = f(*a)
        buf = res.gen_buf.at[:, 0].add(1) % engine.model.cfg.vocab_size
        return res._replace(gen_buf=buf)

    engine._superstep_fn = broken


def test_altered_token_is_not_correct(tiny):
    rc, lines = run_cell(tiny, "tiny.chat", seed=5, fault=altered_tokens)
    res = json.loads(lines[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMIT


def swapped_lanes(engine):
    """Tokens delivered to the wrong lane: every lane's committed tokens of
    a superstep go to the next lane."""
    f = engine._superstep_fn

    def broken(*a):
        res = f(*a)
        return res._replace(gen_buf=jnp.roll(res.gen_buf, 1, axis=0))

    engine._superstep_fn = broken


def test_swapped_lanes_are_not_correct(tiny):
    rc, lines = run_cell(tiny, "tiny.chat", seed=6, fault=swapped_lanes)
    res = json.loads(lines[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMIT


def unchanged_cache(engine):
    """A step that returns its state unchanged: every superstep hands back
    a copy of the KV cache it was given, taken before the call (a superstep
    that takes its cache donated deletes it), so what it wrote is lost."""
    f = engine._superstep_fn

    def broken(*a):
        kept = jax.tree.map(jnp.copy, a[3])
        res = f(*a)
        return res._replace(cache=kept)

    engine._superstep_fn = broken


def test_unchanged_cache_is_not_correct(tiny):
    rc, lines = run_cell(tiny, "tiny.chat", seed=8, fault=unchanged_cache)
    res = json.loads(lines[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMIT


def test_control_run_is_not_correct(tiny):
    """``--control 1``: the run's own check, judging what the fp8 reference
    puts first in place of the served tokens, comes out false."""
    rc, lines = run_cell(tiny, "tiny.chat", seed=2, control=1)
    res = json.loads(lines[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMIT


def test_fp8_control_fails_where_the_program_passes(tiny):
    cell = bench.load_cell(tiny / "bench", "tiny.chat")
    prog, ctl = control.readings(cell, seeds=[1, 2, 3],
                                 control_seeds=[1, 2, 3], seconds=3.0)
    widest = [g["widest_gap"] for g in prog]
    assert max(widest) <= TINY_LIMIT < min(c["widest_gap"] for c in ctl)


def test_untied_configuration_comes_in_as_files(tiny):
    """A configuration whose model module is a file of its own (an untied
    unembedding, served as the program's ``lm_head``) runs end to end."""
    rc, lines = run_cell(tiny, "tiny-untied.chat", seed=2**31 + 13)
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == E2E
    assert res["checks"]["widest_gap"]["value"] <= TINY_LIMIT
    path = (tiny / "bench" / "untied_ref.py").resolve()
    assert sum(f"model module: {path}" in x for x in lines) == 1


def embedding_as_head(engine):
    """The untied model's head read from the wrong matrix: the program
    serves the embedding in its place."""
    p = engine.params
    engine.params = dict(p, lm_head=p["embed"].T)


def test_untied_head_fault_is_not_correct(tiny):
    rc, lines = run_cell(tiny, "tiny-untied.chat", seed=10,
                         fault=embedding_as_head)
    res = json.loads(lines[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMIT


PARTIAL_MODULE = (
    "from model_ref import (fingerprint, gaps, init_weights, program_config,"
    "\n    program_layout, to_float32, to_program)\n")


@pytest.mark.parametrize("fault,names", [
    ("no key", ("tiny.json", "'reference'")),
    ("no file", ("tiny.json", "absent_ref.py", "does not exist")),
    ("no function", ("tiny.json", "partial_ref.py", "lacks span_flops")),
])
def test_a_model_module_that_cannot_serve_fails_at_load(tiny, tmp_path,
                                                         fault, names):
    """A configuration with no model module, or one that lacks a function
    of the contract, fails when the cell is loaded, with a message that
    names the configuration and the module."""
    root = tmp_path / "copy"
    shutil.copytree(tiny, root)
    conf_file = root / "bench" / "configs" / "tiny.json"
    conf = json.loads(conf_file.read_text())
    if fault == "no key":
        del conf["reference"]
    elif fault == "no file":
        conf["reference"] = "bench/absent_ref.py"
    else:
        (root / "bench" / "partial_ref.py").write_text(PARTIAL_MODULE)
        conf["reference"] = "bench/partial_ref.py"
    write_json(conf_file, conf)
    with pytest.raises(ValueError) as err:
        bench.load_cell(root / "bench", "tiny.chat")
    for name in names:
        assert name in str(err.value)
