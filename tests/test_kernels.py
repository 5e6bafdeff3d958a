"""Pallas kernels vs pure-jnp oracles (interpret mode), swept over shapes/dtypes.

The parametrized differential tests always run; the hypothesis fuzzers engage
wherever hypothesis is installed (CI via requirements-dev.txt)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
    settings.register_profile("kern", max_examples=12, deadline=None)
    settings.load_profile("kern")
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref


@pytest.mark.slow
@pytest.mark.parametrize("B,S,H,hd,bq,bk", [
    (1, 128, 1, 64, 64, 64),
    (2, 256, 4, 64, 128, 64),
    (1, 512, 2, 128, 128, 128),
    (2, 128, 3, 32, 128, 128),     # block == S edge
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, S, H, hd, bq, bk, dtype):
    rng = np.random.RandomState(B * S + H)
    q, k, v = [jnp.asarray(rng.randn(B, S, H, hd), dtype) for _ in range(3)]
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_non_causal():
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
               for _ in range(3)]
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("d,block,k,seed", [
    (10, 256, 1, 0), (1000, 256, 17, 3), (4096, 512, 32, 7),
    (2500, 1024, 9, 11),
])
def test_block_topk_kernel_matches_ref(d, block, k, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    out = ops.block_topk(x, block=block, k=k)
    expect = ref.block_topk_ref(x, block, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-7)


if HAVE_HYPOTHESIS:
    @given(st.integers(10, 5000), st.sampled_from([256, 512, 1024]),
           st.integers(1, 32), st.integers(0, 1000))
    def test_block_topk_kernel_property(d, block, k, seed):
        k = min(k, block)
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(d).astype(np.float32))
        out = ops.block_topk(x, block=block, k=k)
        expect = ref.block_topk_ref(x, block, k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_topk_dtypes(dtype):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4096), dtype)
    out = ops.block_topk(x, block=512, k=8)
    nz = int((np.asarray(out, np.float32) != 0).sum())
    assert nz == 8 * 8
    # kept values must be the originals
    mask = np.asarray(out, np.float32) != 0
    np.testing.assert_array_equal(np.asarray(out)[mask], np.asarray(x)[mask])


@pytest.mark.parametrize("d,eta,seed", [
    (100, 0.1, 0), (1000, 0.5, 3), (4000, 1.0, 7), (777, 0.01, 11),
])
def test_ef_update_kernel_matches_ref(d, eta, seed):
    rng = np.random.RandomState(seed)
    grad, v, g = [jnp.asarray(rng.randn(d).astype(np.float32))
                  for _ in range(3)]
    vn, gn, c = ops.ef21_sgdm_update(grad, v, g, eta=eta, block=512, k=16)
    vr, gr, cr = ref.ef21_sgdm_update_ref(grad, v, g, eta=eta, block=512, k=16)
    np.testing.assert_allclose(np.asarray(vn), np.asarray(vr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gn), np.asarray(gr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), atol=1e-6)


if HAVE_HYPOTHESIS:
    @given(st.integers(100, 4000), st.floats(0.01, 1.0), st.integers(0, 500))
    def test_ef_update_kernel_property(d, eta, seed):
        rng = np.random.RandomState(seed)
        grad, v, g = [jnp.asarray(rng.randn(d).astype(np.float32))
                      for _ in range(3)]
        vn, gn, c = ops.ef21_sgdm_update(grad, v, g, eta=eta, block=512, k=16)
        vr, gr, cr = ref.ef21_sgdm_update_ref(grad, v, g, eta=eta, block=512,
                                              k=16)
        np.testing.assert_allclose(np.asarray(vn), np.asarray(vr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(gn), np.asarray(gr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(c), np.asarray(cr), atol=1e-6)


def test_ef_update_kernel_matches_method():
    """The fused kernel computes exactly EF21SGDM.update with BlockTopK."""
    from repro.core import compressors as C, ef
    rng = np.random.RandomState(7)
    d, block, k, eta = 2048, 512, 16, 0.2
    grad = jnp.asarray(rng.randn(d).astype(np.float32))
    v0 = jnp.asarray(rng.randn(d).astype(np.float32))
    g0 = jnp.asarray(rng.randn(d).astype(np.float32))
    m = ef.EF21SGDM(compressor=C.BlockTopK(block=block, k_per_block=k), eta=eta)
    msg, st = m.update({"x": grad}, {"v": {"x": v0}, "g": {"x": g0}})
    vn, gn, c = ops.ef21_sgdm_update(grad, v0, g0, eta=eta, block=block, k=k)
    np.testing.assert_allclose(np.asarray(c), np.asarray(msg["x"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vn), np.asarray(st["v"]["x"]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gn), np.asarray(st["g"]["x"]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# quantize/dequantize (kernels/quantize.py) vs oracles (kernels/ref.py)
# ---------------------------------------------------------------------------

def _quant_ref(x, block, bits):
    """Oracle pipeline on the kernel's blocked layout."""
    d = x.size
    nb = -(-d // block)
    xb = jnp.pad(x.reshape(-1).astype(jnp.float32),
                 (0, nb * block - d)).reshape(nb, block)
    return ref.block_quantize_ref(xb, bits)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("d,block", [
    (50, 16), (257, 128), (1000, 256), (4096, 1024), (1, 256), (129, 64),
])
def test_quantize_kernel_matches_ref_odd_shapes(bits, d, block):
    """Pallas codec == jnp oracle on non-block-multiple and tiny shapes:
    mantissas bit-exact, scales/decodes to float-compilation tolerance."""
    rng = np.random.RandomState(d + bits)
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    q, s = ops.block_quantize(x, block=block, bits=bits)
    qr, sr = _quant_ref(x, block, bits)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = ops.block_dequantize(q, s, d=d, block=block, bits=bits)
    yr = ref.block_dequantize_ref(qr, sr, bits=bits,
                                  cols=block).reshape(-1)[:d]
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_kernel_zero_blocks(bits):
    """An all-zero block must get scale 0 and decode to exact zeros (no 0/0)."""
    x = jnp.concatenate([jnp.zeros(64), jnp.ones(64)])
    q, s = ops.block_quantize(x, block=64, bits=bits)
    assert float(s[0]) == 0.0 and float(s[1]) > 0.0
    y = np.asarray(ops.block_dequantize(q, s, d=128, block=64, bits=bits))
    assert (y[:64] == 0.0).all()
    np.testing.assert_allclose(y[64:], 1.0, rtol=2e-1 if bits == 4 else 2e-2)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_kernel_nonfinite_guard(bits):
    """inf/nan inputs quantize to exactly 0 with a finite scale (EF re-sends
    the lost mass as ordinary residual) — kernel and oracle agree."""
    x = jnp.asarray([1.0, jnp.inf, -jnp.inf, jnp.nan, -2.0, 0.5, 0.0, 3.0],
                    jnp.float32)
    q, s = ops.block_quantize(x, block=4, bits=bits)
    qr, sr = _quant_ref(x, 4, bits)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = np.asarray(ops.block_dequantize(q, s, d=8, block=4, bits=bits))
    assert np.isfinite(y).all()
    assert y[1] == 0.0 and y[2] == 0.0 and y[3] == 0.0


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_kernel_bf16(bits):
    """bf16 wires quantize through the same f32 arithmetic as the oracle.
    bf16's coarse grid puts inputs exactly on round-to-nearest boundaries,
    where the 1-ULP scale difference between the two compilations may flip a
    mantissa by one step — so kernel and oracle decodes must agree to one
    grid step, and BOTH must satisfy the round-trip bound vs the input."""
    rng = np.random.RandomState(bits)
    x = jnp.asarray(rng.randn(512), jnp.bfloat16)
    q, s = ops.block_quantize(x, block=128, bits=bits)
    qr, sr = _quant_ref(x, 128, bits)
    y = np.asarray(ops.block_dequantize(q, s, d=512, block=128, bits=bits))
    yr = np.asarray(ref.block_dequantize_ref(qr, sr, bits=bits,
                                             cols=128)).reshape(-1)
    step = np.repeat(np.asarray(s), 128)
    assert (np.abs(y - yr) <= step * (1 + 1e-6)).all()
    xf = np.asarray(x, np.float32)
    bound = np.abs(xf.reshape(4, 128)).max(1) / 2 ** (bits - 1)
    for dec in (y, yr):
        assert (np.abs(dec - xf).reshape(4, 128)
                <= bound[:, None] + 1e-6).all()


def test_bisection_threshold_exactness():
    """Bisection recovers the k-th largest magnitude to float precision."""
    from repro.kernels.topk_compress import _bisect_threshold
    rng = np.random.RandomState(0)
    ab = jnp.abs(jnp.asarray(rng.randn(4, 1024).astype(np.float32)))
    for k in (1, 16, 300, 1024):
        t = np.asarray(_bisect_threshold(ab, k))
        assert t.shape == (4, 1)
        t = t[:, 0]
        kth = np.sort(np.asarray(ab), axis=1)[:, -k]
        cnt = (np.asarray(ab) >= t[:, None]).sum(1)
        assert (cnt >= k).all()
        np.testing.assert_allclose(t, kth, rtol=2e-4)   # 26 bisection iters


def test_kernels_interpret_only_on_cpu(monkeypatch):
    """One switch decides interpreted vs compiled kernels: the CPU
    interprets, a TPU compiles, any other backend is an error — never a
    silent interpreter on an accelerator."""
    from repro.kernels import backend

    assert backend.interpret() is True              # tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        backend.interpret()
