"""chip_smoke.py's phases on the tiny config, on the CPU.

The script itself refuses to run without a TPU; this test imports its
phase functions and runs build, HTTP serve and the float32 reference on a
tiny plan of the same shape (two waves, SSE twins, shared-prefix
followers), so tier-1 guards the script.  Only the device phase is
skipped: it is the one that must fail here.
"""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def tiny_plan(mod):
    E = mod.EngineConfig
    return mod.SmokePlan(
        tiny=True,
        engine=E(scheduler="continuous", num_slots=4, max_new=16,
                 sync_every=4, cache_len=256, kv_pages=96, kv_page_size=16,
                 prefix_cache=True, prefill_chunk=16),
        ref_engine=E(scheduler="continuous", num_slots=2, max_new=16,
                     sync_every=4, learn=False, cache_len=128, kv_pages=24,
                     kv_page_size=16, prefix_cache=True, prefill_chunk=16),
        prompt_lens=(48, 80, 40, 40, 36, 56),
        shared_prefix=32,
        follower_tails=(20, 28),
        sse_twins=(1, 5),
        ref_prompts=(2, 3),
        max_tokens=16,
        timeout_s=300.0)


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(smoke.NoChip, match="no TPU chip"):
        smoke.device_phase()


def test_full_plan_is_published_qwen3_0_6b(smoke):
    from repro.configs import get_config
    cfg = get_config(smoke.ARCH, tiny=False)
    for key, want in smoke.PUBLISHED.items():
        assert getattr(cfg, key) == want, key
    # the served lanes never trim a prompt of the full plan
    e = smoke.FULL.engine
    longest = max(smoke.FULL.prompt_lens + tuple(
        smoke.FULL.shared_prefix + t for t in smoke.FULL.follower_tails))
    assert longest <= e.cache_len - e.max_new - cfg.dvi.k_spec - 2
    assert smoke.FULL.shared_prefix % e.kv_page_size == 0
    assert len({smoke.FULL.prompt_lens[i] for i in smoke.FULL.ref_prompts}) == 1


def test_smoke_phases_tiny(smoke, capsys):
    plan = tiny_plan(smoke)
    bundle = smoke.build_phase(plan)
    served = smoke.serve_phase(plan, bundle)
    smoke.reference_phase(plan, bundle, served["prompts"])
    out = capsys.readouterr().out
    assert "none trimmed" in out
    assert out.count("engine == AR") + out.count("diverge at") == 2


def test_serve_phase_fails_on_trimmed_prompt(smoke):
    """The no-trim check bites: a lane too short for the longest prompt
    makes the served prompt shorter than the sent one."""
    plan = tiny_plan(smoke)
    short = dataclasses.replace(plan, engine=dataclasses.replace(
        plan.engine, cache_len=64))
    bundle = smoke.build_phase(short)
    with pytest.raises(smoke.SmokeFailure, match="trimmed"):
        smoke.serve_phase(short, bundle)
