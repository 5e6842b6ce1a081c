"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# interpret-mode Pallas is slow on CPU; CI runs these in their own
# kernels-interpret job (`-m kernels`) so the tier-1 matrix stays fast
pytestmark = pytest.mark.kernels

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas as decode_attention
from repro.kernels.lora_logits import lora_logits
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.verify_argmax import verify_argmax

I = dict(interpret=True)


@pytest.mark.parametrize("T,d,V,bt,bv", [
    (5, 64, 500, 16, 128), (128, 128, 2048, 64, 512), (33, 256, 1000, 8, 256),
    (1, 32, 128, 8, 128), (64, 64, 4096, 64, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_argmax(T, d, V, bt, bv, dtype):
    h = jax.random.normal(jax.random.PRNGKey(T + V), (T, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(V), (d, V), dtype)
    arg, mx = verify_argmax(h, w, block_t=bt, block_v=bv, **I)
    arg_ref, mx_ref = ref.ref_verify_argmax(h, w)
    np.testing.assert_array_equal(np.asarray(arg), np.asarray(arg_ref))
    np.testing.assert_allclose(np.asarray(mx), np.asarray(mx_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("T,d,V,r", [(5, 64, 500, 8), (64, 128, 1024, 16),
                                     (17, 64, 300, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_logits(T, d, V, r, dtype):
    h = jax.random.normal(jax.random.PRNGKey(0), (T, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V), dtype)
    a = jax.random.normal(jax.random.PRNGKey(2), (d, r), dtype)
    b = jax.random.normal(jax.random.PRNGKey(3), (r, V), dtype)
    out = lora_logits(h, w, a, b, 2.0, block_t=16, block_v=256, **I)
    expect = ref.ref_lora_logits(h, w, a, b, 2.0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("B,H,KV,hd,S,bs", [
    (2, 8, 2, 32, 100, 32), (3, 16, 16, 64, 64, 64), (1, 4, 1, 128, 300, 128),
    (2, 8, 8, 64, 33, 16),
])
def test_decode_attention(B, H, KV, hd, S, bs):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
    lens = jax.random.randint(jax.random.PRNGKey(3), (B,), 1, S + 1)
    out = decode_attention(q, k, v, lens, block_s=bs, **I)
    expect = ref.ref_decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


@pytest.mark.parametrize("B,T,H,hd,ds,Q", [
    (2, 64, 4, 16, 32, 16), (1, 128, 8, 64, 128, 64), (2, 32, 2, 8, 16, 32),
])
def test_ssd_scan(B, T, H, hd, ds, Q):
    xh = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, hd))
    Bc = jax.random.normal(jax.random.PRNGKey(1), (B, T, 1, ds)) * 0.5
    Cc = jax.random.normal(jax.random.PRNGKey(2), (B, T, 1, ds)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (B, T, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (H,)) * 0.3)
    y, h = ssd_scan(xh, Bc, Cc, dt, A, chunk=Q, **I)
    y_ref, h_ref = ref.ref_ssd_scan(xh, Bc, Cc, dt, A, Q)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4)


def _paged_setup(key, B, KV, hd, ps, pages_per_lane, holes=False):
    """Random pooled pages + block tables; returns (k_pages, v_pages,
    lengths, tbl).  Lanes own disjoint pages in shuffled physical order;
    `holes` leaves trailing table entries unmapped (-1)."""
    P = B * pages_per_lane + 1                     # + null page 0
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(ks[0], (P, ps, KV, hd))
    vp = jax.random.normal(ks[1], (P, ps, KV, hd))
    perm = np.random.default_rng(int(ks[2][0])).permutation(P - 1) + 1
    MPS = pages_per_lane + (2 if holes else 0)
    tbl = np.full((B, MPS), -1, np.int32)
    for b in range(B):
        tbl[b, :pages_per_lane] = perm[b * pages_per_lane:
                                       (b + 1) * pages_per_lane]
    cap = pages_per_lane * ps
    lens = jax.random.randint(ks[3], (B,), 1, cap + 1)
    return kp, vp, lens, jnp.asarray(tbl)


@pytest.mark.parametrize("B,H,KV,hd,ps,ppl", [
    (2, 8, 2, 32, 8, 4), (3, 16, 16, 64, 16, 2), (1, 4, 1, 128, 4, 7),
])
@pytest.mark.parametrize("holes", [False, True])
def test_paged_decode_attention(B, H, KV, hd, ps, ppl, holes):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kp, vp, lens, tbl = _paged_setup(jax.random.PRNGKey(B * H), B, KV, hd,
                                     ps, ppl, holes)
    out = paged_decode_attention(q, kp, vp, lens, tbl, **I)
    expect = ref.ref_paged_decode_attention(q, kp, vp, lens, tbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_paged_early_out_ragged_lengths():
    """Per-lane page-count early-out: lanes spanning 1 slot up to the full
    mapped capacity (ragged, incl. page-boundary lengths) must match the
    full-sweep oracle bit-for-bit — the skipped pages were all masked."""
    B, H, KV, hd, ps, ppl = 4, 8, 4, 16, 8, 6
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kp, vp, _, tbl = _paged_setup(jax.random.PRNGKey(2), B, KV, hd, ps, ppl)
    # 1 slot, page-boundary, mid-page, full capacity
    lens = jnp.array([1, ps, 2 * ps + 3, ppl * ps])
    out = paged_decode_attention(q, kp, vp, lens, tbl, **I)
    expect = ref.ref_paged_decode_attention(q, kp, vp, lens, tbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_paged_explicit_page_counts_matches_oracle():
    """An explicit page_counts SMALLER than the length coverage trims the
    attended window; kernel and oracle must agree on the trimmed result."""
    B, H, KV, hd, ps, ppl = 2, 8, 2, 32, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, hd))
    kp, vp, _, tbl = _paged_setup(jax.random.PRNGKey(3), B, KV, hd, ps, ppl)
    lens = jnp.full((B,), ppl * ps)                 # full lanes...
    pc = jnp.array([1, 3], jnp.int32)               # ...but trimmed sweeps
    out = paged_decode_attention(q, kp, vp, lens, tbl, page_counts=pc, **I)
    expect = ref.ref_paged_decode_attention(q, kp, vp, lens, tbl,
                                            page_counts=pc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)
    # and forcing the full sweep on short lanes changes nothing
    short = jnp.full((B,), ps // 2)
    full = paged_decode_attention(q, kp, vp, short, tbl,
                                  page_counts=jnp.full((B,), ppl, jnp.int32),
                                  **I)
    trim = paged_decode_attention(q, kp, vp, short, tbl, **I)
    np.testing.assert_allclose(np.asarray(trim), np.asarray(full), atol=2e-5)


def test_paged_matches_contiguous_ref():
    """A paged cache whose pages are laid out in logical order must attend
    identically to the same KV stored contiguously."""
    B, H, KV, hd, ps, ppl = 2, 8, 4, 32, 8, 3
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kp, vp, lens, tbl = _paged_setup(jax.random.PRNGKey(9), B, KV, hd, ps, ppl)
    # materialize each lane's logical view as a contiguous cache
    flat = lambda c: np.asarray(c).reshape(-1, KV, hd)
    tbl_np = np.asarray(tbl)
    idx = tbl_np[:, np.arange(ppl * ps) // ps] * ps + np.arange(ppl * ps) % ps
    k_c = jnp.asarray(flat(kp)[idx])                 # (B, S, KV, hd)
    v_c = jnp.asarray(flat(vp)[idx])
    out_p = paged_decode_attention(q, kp, vp, lens, tbl, **I)
    out_c = ref.ref_decode_attention(q, k_c, v_c, lens)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c), atol=2e-5)


def test_ops_wrappers_jit():
    """ops.py jit'd wrappers dispatch to interpret mode on CPU, and the
    decode dispatch point agrees across ref/pallas/paged implementations."""
    from repro.kernels import ops
    h = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 256))
    arg, mx = ops.verify_argmax(h, w, block_t=8, block_v=128)
    arg_ref, _ = ref.ref_verify_argmax(h, w)
    np.testing.assert_array_equal(np.asarray(arg), np.asarray(arg_ref))

    q = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 2, 32))
    lens = jnp.array([50, 3])
    np.testing.assert_allclose(
        np.asarray(ops.decode_attention(q, k, v, lens, block_s=16)),
        np.asarray(ops.decode_attention(q, k, v, lens, impl="ref")), atol=2e-5)
    kp, vp, plens, tbl = _paged_setup(jax.random.PRNGKey(5), 2, 2, 32, 8, 4)
    np.testing.assert_allclose(
        np.asarray(ops.paged_decode_attention(q, kp, vp, plens, tbl)),
        np.asarray(ops.paged_decode_attention(q, kp, vp, plens, tbl,
                                              impl="ref")), atol=2e-5)


def test_ops_refuse_backends_they_cannot_serve(monkeypatch):
    """Kernels compile on 'tpu', interpret on 'cpu', and refuse any other
    backend instead of silently interpreting there."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()
