"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

This is the proof that the distribution config is coherent without real
hardware: sharding mismatches, compile-time OOM, or unsupported collectives
all surface here as failures.  For each combination we record:

* memory_analysis()   — per-device argument/temp/output bytes (fits < 16 GB HBM?)
* cost_analysis()     — per-device HLO FLOPs / bytes accessed
* collective schedule — parsed from the post-SPMD HLO: per-kind op counts,
  payload bytes and estimated wire bytes per device (§Roofline inputs)

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \\
          --shape train_4k [--multipod] [--out experiments/dryrun]
"""
# The 512 placeholder devices MUST be forced before any jax import.
import os  # noqa: E402

_FLAG = "--xla_force_host_platform_device_count=512"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config          # noqa: E402
from repro.configs.base import InputShape, ModelConfig      # noqa: E402
from repro.core import losses as losses_mod                 # noqa: E402
from repro.core import spec as spec_mod                     # noqa: E402
from repro.core.lora import init_draft_params               # noqa: E402
from repro.launch import sharding as shd                    # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.models.model import build_model                  # noqa: E402
from repro.optim import adamw_init                          # noqa: E402

# ---------------------------------------------------------------------------
# Shape adaptation policy (DESIGN.md §6)
# ---------------------------------------------------------------------------

SWA_FALLBACK = {"llama3-405b", "qwen2.5-14b", "qwen3-0.6b", "qwen3-1.7b",
                "vicuna-7b"}
LONG_NATIVE = {"mamba2-370m", "recurrentgemma-9b", "llama4-scout-17b-a16e"}
LONG_SKIP = {"deepseek-v3-671b": "pure full-attention (MLA); no SWA variant claimed",
             "paligemma-3b": "pure full-attention (gemma-1); no SWA variant",
             "whisper-large-v3": "enc-dec with 448-token decoder context"}


def adapt_config(arch: str, shape: InputShape, kv_quant: bool = False):
    """Returns (cfg, note) or (None, skip_reason)."""
    cfg = get_config(arch)
    note = ""
    if kv_quant:
        cfg = cfg.replace(kv_quant=True)
        note = "int8 KV cache variant (§Perf H5)"
    if shape.name == "long_500k":
        if arch in LONG_SKIP:
            return None, LONG_SKIP[arch]
        if arch in SWA_FALLBACK:
            cfg = cfg.replace(sliding_window=8192, global_attn_every=0)
            note = "sliding-window 8192 variant (not the paper config)"
    return cfg, note


def make_aux_specs(cfg: ModelConfig, B: int):
    aux = {}
    if cfg.vision is not None:
        aux["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.vision.num_patches, cfg.vision.d_embed), jnp.float32)
    if cfg.encoder is not None:
        aux["frame_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder.num_frames, cfg.encoder.d_model or cfg.d_model),
            jnp.float32)
    return aux or None


# ---------------------------------------------------------------------------
# Step construction per shape kind
# ---------------------------------------------------------------------------

def build_case(cfg: ModelConfig, shape: InputShape, mesh):
    """Returns (fn, arg_shapes:list, in_shardings:list, out_spec_fn)."""
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(lambda: model.init(key))
    p_shard = shd.to_shardings(shd.param_specs(param_shapes, mesh), mesh)
    dvi_shapes = jax.eval_shape(lambda: init_draft_params(key, cfg))
    dvi_shard = shd.to_shardings(shd.param_specs(dvi_shapes, mesh), mesh)
    repl = shd.replicated(mesh)
    aux_specs = make_aux_specs(cfg, B)
    aux_shard = None if aux_specs is None else jax.tree.map(
        lambda _: NamedSharding(mesh, shd.tokens_spec(mesh, B)), aux_specs)

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(lambda: adamw_init(dvi_shapes))
        # Adam m/v mirror the dvi tree leaf-for-leaf: reuse the dvi specs
        opt_shard = {"m": dvi_shard, "v": dvi_shard,
                     "step": NamedSharding(mesh, P())}
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        tok_shard = NamedSharding(mesh, shd.tokens_spec(mesh, B,
                                                        include_model=True))
        from repro.optim import adamw_update

        def fn(params, dvi_params, opt_state, tokens, aux):
            def loss_fn(dp):
                return losses_mod.dense_train_losses(
                    model, params, dp, tokens, jnp.int32(100),
                    jnp.float32(0.5), "full", aux, remat=True)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(dvi_params)
            dvi2, opt2, _ = adamw_update(dvi_params, grads, opt_state, 1e-3)
            return dvi2, opt2, loss

        args = [param_shapes, dvi_shapes, opt_shapes, tokens, aux_specs]
        shards = [p_shard, dvi_shard, opt_shard, tok_shard, aux_shard]
        return fn, args, shards

    if shape.kind == "prefill":
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        tok_shard = NamedSharding(mesh, shd.tokens_spec(mesh, B))

        P_extra = cfg.vision.num_patches if cfg.vision is not None else 0

        cap = -(-(S + P_extra + cfg.dvi.k_spec + 8) // 256) * 256

        def fn(params, dvi_params, tokens, aux):
            h, cache, _ = model.prefill(params, tokens, aux, max_len=cap)
            cache = shd.constrain_cache_tree(cfg, cache)
            from repro.core.lora import draft_logits
            vlog = model.logits(params, h[:, -1])
            dlog = draft_logits(model, params, dvi_params, h[:, -1])
            return jnp.argmax(vlog, -1), jnp.argmax(dlog, -1), cache

        args = [param_shapes, dvi_shapes, tokens, aux_specs]
        shards = [p_shard, dvi_shard, tok_shard, aux_shard]
        return fn, args, shards

    # decode: one DVI speculative serve step against a seq_len cache
    # (capacity rounded to a mesh-divisible multiple so the sequence dim
    # shards: 32780 % 16 != 0 would silently replicate a 2 TB cache)
    cache_cap = -(-(S + cfg.dvi.k_spec + 8) // 256) * 256
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, cache_cap))
    c_shard = shd.to_shardings(shd.cache_specs(cfg, cache_shapes, mesh), mesh)
    pending = jax.ShapeDtypeStruct((B,), jnp.int32)
    pend_shard = NamedSharding(mesh, P(shd.batch_axes(mesh, B)))

    def fn(params, dvi_params, pending, cache):
        # mark the cache as "full": lengths = S (committed tokens)
        cache = dict(cache, lengths=jnp.full((B,), S, jnp.int32))
        y, commit_vec, accept, cache2 = spec_mod.serve_step(
            model, params, dvi_params, pending, cache)
        return y, commit_vec, accept, shd.constrain_cache_tree(cfg, cache2)

    # the cache updates in place, as the serving engine's admission, chunk,
    # block-table and reset programs take it (its supersteps copy it once)
    fn.donate = (3,)
    args = [param_shapes, dvi_shapes, pending, cache_shapes]
    shards = [p_shard, dvi_shard, pend_shard, c_shard]
    return fn, args, shards


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8,
                "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _type_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo: str):
    per_kind = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out_bytes = _type_bytes(m.group(1))
        kind = m.group(2)
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            gsize = len(gl.group(1).split(",")) if gl else 2
        gsize = max(gsize, 2)
        frac = (gsize - 1) / gsize
        if kind == "all-reduce":
            wire = 2 * out_bytes * frac
        elif kind == "all-gather":
            wire = out_bytes * frac        # received bytes per device
        elif kind == "reduce-scatter":
            wire = out_bytes * (gsize - 1) # input = out * gsize
        elif kind == "all-to-all":
            wire = out_bytes * frac
        else:                              # collective-permute
            wire = out_bytes
        d = per_kind.setdefault(kind, {"count": 0, "payload_bytes": 0.0,
                                       "wire_bytes": 0.0})
        d["count"] += 1
        d["payload_bytes"] += out_bytes
        d["wire_bytes"] += wire
    total = {"count": sum(d["count"] for d in per_kind.values()),
             "payload_bytes": sum(d["payload_bytes"] for d in per_kind.values()),
             "wire_bytes": sum(d["wire_bytes"] for d in per_kind.values())}
    return per_kind, total


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_case(arch: str, shape_name: str, multi_pod: bool,
             hlo_dir: str | None = None, kv_quant: bool = False) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg, note = adapt_config(arch, shape, kv_quant)
    rec = {"arch": arch + ("+kvq" if kv_quant else ""), "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "note": note}
    if cfg is None:
        rec["status"] = "skip"
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    from repro.launch.hints import set_hint_mesh
    set_hint_mesh(mesh)
    fn, args, shards = build_case(cfg, shape, mesh)
    donate = getattr(fn, "donate", ())
    with mesh:
        lowered = jax.jit(fn, in_shardings=shards,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    per_kind, total = parse_collectives(hlo)
    from repro.launch import hlo_analysis
    deep = hlo_analysis.analyze(hlo)
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "n_devices": mesh.size,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                           + ma.output_size_in_bytes - ma.alias_size_in_bytes),
        },
        "cost": {
            # raw XLA numbers (NOTE: while-loop bodies counted ONCE — see
            # hlo_analysis docstring; use the trip-weighted numbers below)
            "xla_flops_per_device": ca.get("flops", 0.0),
            "xla_bytes_accessed_per_device": ca.get("bytes accessed", 0.0),
            "transcendentals": ca.get("transcendentals", 0.0),
            # trip-count-weighted matmul flops (per device)
            "dot_flops_per_device": deep["dot_flops_per_device"],
        },
        "collectives": {
            "per_kind": deep["collectives_per_kind"],
            "total": deep["collectives_total"],
            "static_per_kind": per_kind,    # per-HLO-occurrence (un-weighted)
        },
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    })
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{rec['mesh']}"
        with open(os.path.join(hlo_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache variant (EXPERIMENTS.md §Perf H5)")
    args = ap.parse_args()
    try:
        rec = run_case(args.arch, args.shape, args.multipod,
                       hlo_dir=(args.out + "/hlo") if args.save_hlo else None,
                       kv_quant=args.kv_quant)
    except Exception as e:  # noqa: BLE001 — record the failure for the table
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multipod else "16x16",
               "status": "fail", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    os.makedirs(args.out, exist_ok=True)
    tag = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(args.out, tag), "w") as f:
        json.dump(rec, f, indent=2)
    status = rec["status"]
    mem = rec.get("memory", {}).get("peak_bytes", 0) / 2**30
    alias = rec.get("memory", {}).get("alias_bytes", 0) / 2**30
    print(f"[dryrun] {rec['arch']} x {rec['shape']} x {rec['mesh']}: {status}"
          + (f"  peak={mem:.2f} GiB/dev  alias={alias:.2f} GiB/dev  "
             f"dot_flops/dev={rec['cost']['dot_flops_per_device']:.3g}"
             if status == "ok" else "")
          + (f"  ({rec.get('note') or rec.get('error', '')})"
             if rec.get("note") or rec.get("error") else ""))
    if status == "fail":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
