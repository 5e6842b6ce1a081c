"""Compiles for a described TPU v5e: every Pallas kernel at Qwen3-0.6B
widths, and the full-width serving superstep at chip_smoke.py's pool size.

No chip is attached: the TPU compiler compiles for a topology it is told
about, which is what refuses a block the chip's tiling cannot take, a
kernel that overruns its fast memory, or a program that does not fit the
device.  The topology is described inside a fixture (never at import), so
every test worker collects the same tests and only the worker running
this file loads the TPU library; the persistent compilation cache is off
around these compiles, since an entry written without a chip cannot be
read back.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
GiB = 2 ** 30
HBM_BYTES = 16 * GiB            # one v5e chip

# Qwen3-0.6B (hf:Qwen/Qwen3-0.6B): 16 query / 8 KV heads of 128, d_model
# 1,024, vocab 151,936; 16 lanes, pages of 16, 2,048-token lanes
B, H, KV, HD, D, V, RANK = 16, 16, 8, 128, 1024, 151_936, 64
PAGES, PAGE, LANE_PAGES = 1025, 16, 128
# Mamba2-370M's SSD layer (configs/mamba2_370m.py): 32 heads of 64, state 128
SSD_T, SSD_H, SSD_HD, SSD_DS, SSD_Q = 512, 32, 64, 128, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _kernel_case(name, sd):
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.lora_logits import lora_logits
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.ssd_scan import ssd_scan
    from repro.kernels.verify_argmax import verify_argmax
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if name == "decode_attention":
        return decode_attention_pallas, (
            sd((B, H, HD), bf), sd((B, LANE_PAGES * PAGE, KV, HD), bf),
            sd((B, LANE_PAGES * PAGE, KV, HD), bf), sd((B,), i32))
    if name == "paged_decode_attention":
        return paged_decode_attention, (
            sd((B, H, HD), bf), sd((PAGES, PAGE, KV, HD), bf),
            sd((PAGES, PAGE, KV, HD), bf), sd((B,), i32),
            sd((B, LANE_PAGES), i32))
    if name == "verify_argmax":       # a K+1 = 5 token verify block per lane
        return verify_argmax, (sd((B * 5, D), bf), sd((D, V), bf))
    if name == "lora_logits":         # bf16 hidden + head, f32 adapters
        return (lambda h, w, a, b: lora_logits(h, w, a, b, 2.0),
                (sd((B, D), bf), sd((D, V), bf), sd((D, RANK), f32),
                 sd((RANK, V), f32)))
    assert name == "ssd_scan"
    return (lambda x, b, c, dt, a: ssd_scan(x, b, c, dt, a, SSD_Q),
            (sd((1, SSD_T, SSD_H, SSD_HD), f32),
             sd((1, SSD_T, 1, SSD_DS), f32), sd((1, SSD_T, 1, SSD_DS), f32),
             sd((1, SSD_T, SSD_H), f32), sd((SSD_H,), f32)))


@pytest.mark.parametrize("name", ["decode_attention",
                                  "paged_decode_attention", "verify_argmax",
                                  "lora_logits", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(
        name, lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_superstep_fits_v5e(one_chip):
    """The served superstep of chip_smoke.py's plan, at full width in bf16,
    fits one chip's HBM with its undonated second copy of the pool."""
    from repro.configs import get_config
    from repro.core import online
    from repro.models.model import build_model
    from repro.serving.config import build_engine

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = smoke
    try:
        spec.loader.exec_module(smoke)
    finally:
        sys.modules.pop("chip_smoke", None)
    econf = smoke.FULL.engine
    cfg = get_config(smoke.ARCH)
    assert cfg.dtype == "bfloat16"
    model = build_model(cfg)
    params = _shapes(one_chip, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    dvi, opt, buf = _shapes(one_chip, jax.eval_shape(
        lambda k: (lambda s: (s.dvi_params, s.opt_state, s.buf))(
            online.init_trainer(model, k)), jax.random.PRNGKey(7)))
    state = online.OnlineTrainerState(
        dvi_params=dvi, opt_state=opt, buf=buf,
        baseline=jnp.float32(0.0), step=jnp.int32(0))
    eng = build_engine(econf, model, params, state)
    n = econf.num_slots
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: model.init_paged_cache(n, econf.kv_pages,
                                       econf.kv_page_size, eng._mps)))
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                sharding=one_chip)
    compiled = eng._superstep_fn.lower(
        params, dvi, sd((n,), jnp.int32), cache, buf, sd((n,), bool),
        sd((n,), jnp.int32)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"superstep needs {total / GiB:.2f} GiB"
