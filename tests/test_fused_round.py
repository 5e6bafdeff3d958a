"""Fused uplink mega-kernel (kernels/fused_round.py) vs the composed oracle
``block_quantize_ref ∘ block_topk_ref ∘ ef21_sgdm_update_ref`` (kernels/ref.py
::ef21_sgdm_topk_quant_ref), plus the one-launch downlink ``dequant_add`` vs
the two-step decode — mirroring the differential structure of test_kernels.py.

Tolerance convention (same as the quantize tests): mantissas bit-exact,
float32 chains to float-compilation tolerance (the kernel and the oracle are
two XLA compilations of the same arithmetic — FMA fusion may differ by 1 ulp).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_round as fr
from repro.kernels import ops, ref


def _assert_fused_matches_oracle(grad, v, g, *, eta, block, k, bits,
                                 out=None):
    vn, gn, q, s = out if out is not None else ops.ef21_sgdm_topk_quant(
        grad, v, g, eta=eta, block=block, k=k, bits=bits)
    vr, gr, qr, sr = ref.ef21_sgdm_topk_quant_ref(
        grad, v, g, eta=eta, block=block, k=k, bits=bits)
    np.testing.assert_allclose(np.asarray(vn), np.asarray(vr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gn), np.asarray(gr),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("d,block,k", [
    (50, 16, 3), (257, 128, 9), (1000, 256, 17), (4096, 1024, 16),
    (1, 256, 1), (129, 64, 5),
])
def test_fused_uplink_matches_oracle_odd_shapes(bits, d, block, k):
    """One launch == the composed three-kernel chain on non-block-multiple
    and tiny shapes, both mantissa layouts."""
    rng = np.random.RandomState(d + bits)
    grad, v, g = [jnp.asarray(rng.randn(d).astype(np.float32))
                  for _ in range(3)]
    _assert_fused_matches_oracle(grad, v, g, eta=0.17, block=block, k=k,
                                 bits=bits)


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_uplink_zero_blocks(bits):
    """A block with zero residual (v' == g) must ship scale 0, decode to
    exact zeros, and leave g' unchanged there — no 0/0 anywhere."""
    d, block, k, eta = 256, 64, 7, 0.5
    rng = np.random.RandomState(bits)
    grad, v, g = [jnp.asarray(rng.randn(d).astype(np.float32))
                  for _ in range(3)]
    # force v' == g on block 0 EXACTLY: with η=0.5 and v = grad = g there,
    # v' = 0.5g + 0.5g = g bit-for-bit (0.5·g is exact, equal-magnitude add
    # is exact) — any other η leaves cancellation noise in v'−g whose tiny
    # survivors the two compilations may select differently
    g0 = np.asarray(g).copy()
    grad = grad.at[:block].set(g0[:block])
    v = v.at[:block].set(g0[:block])
    vn, gn, q, s = ops.ef21_sgdm_topk_quant(grad, v, g, eta=eta, block=block,
                                            k=k, bits=bits)
    assert float(s[0]) == 0.0
    np.testing.assert_array_equal(np.asarray(gn)[:block], g0[:block])
    _assert_fused_matches_oracle(grad, v, g, eta=eta, block=block, k=k,
                                 bits=bits, out=(vn, gn, q, s))


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_uplink_bf16(bits):
    """bf16 state runs the same f32 arithmetic as the oracle; like the
    quantize bf16 test, the 1-ulp scale difference between compilations may
    flip a mantissa one step, so decodes must agree to one grid step and g'
    to one step after the bf16 round."""
    d, block, k, eta = 512, 128, 9, 0.25
    rng = np.random.RandomState(bits + 7)
    grad, v, g = [jnp.asarray(rng.randn(d), jnp.bfloat16) for _ in range(3)]
    vn, gn, q, s = ops.ef21_sgdm_topk_quant(grad, v, g, eta=eta, block=block,
                                            k=k, bits=bits)
    vr, gr, qr, sr = ref.ef21_sgdm_topk_quant_ref(
        grad, v, g, eta=eta, block=block, k=k, bits=bits)
    assert vn.dtype == grad.dtype and gn.dtype == g.dtype
    # kernel accumulates v' in f32 then rounds once; the oracle's weak-typed
    # bf16 arithmetic rounds per op — they may differ by one bf16 ulp
    np.testing.assert_allclose(np.asarray(vn, np.float32),
                               np.asarray(vr, np.float32),
                               rtol=1e-2, atol=1e-2)
    step = np.repeat(np.asarray(sr, np.float32), block)
    dec = np.asarray(ref.block_dequantize_ref(q, s, bits=bits,
                                              cols=block)).reshape(-1)
    decr = np.asarray(ref.block_dequantize_ref(qr, sr, bits=bits,
                                               cols=block)).reshape(-1)
    assert (np.abs(dec - decr) <= step * (1 + 1e-6)).all()
    gdiff = np.abs(np.asarray(gn, np.float32) - np.asarray(gr, np.float32))
    assert (gdiff <= step[:d] + 1e-2).all()


def test_fused_uplink_interpret_flag_direct():
    """The kernels/fused_round.py entry point honors interpret=True
    explicitly (the path every off-TPU caller takes)."""
    rng = np.random.RandomState(3)
    grad, v, g = [jnp.asarray(rng.randn(300).astype(np.float32))
                  for _ in range(3)]
    out = fr.ef21_sgdm_topk_quant(grad, v, g, eta=0.1, block=128, k=5,
                                  bits=8, interpret=True)
    _assert_fused_matches_oracle(grad, v, g, eta=0.1, block=128, k=5, bits=8,
                                 out=out)


def test_fused_uplink_ef_invariant():
    """g' − g must equal dequantize(wire) exactly — what the client
    remembers is what the server reads (the EF21 contract, in-kernel)."""
    rng = np.random.RandomState(11)
    d, block, k, bits = 777, 256, 13, 8
    grad, v, g = [jnp.asarray(rng.randn(d).astype(np.float32))
                  for _ in range(3)]
    _, gn, q, s = ops.ef21_sgdm_topk_quant(grad, v, g, eta=0.4, block=block,
                                           k=k, bits=bits)
    dec = np.asarray(ref.block_dequantize_ref(q, s, bits=bits,
                                              cols=block)).reshape(-1)[:d]
    np.testing.assert_allclose(np.asarray(gn) - np.asarray(g), dec,
                               atol=1e-6)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("alpha", [1.0, 0.5])
def test_dequant_add_matches_two_step(bits, alpha):
    """One-launch downlink base + α·decode == the two-step decode-then-add
    chain (same f32 arithmetic, float-compilation tolerance)."""
    rng = np.random.RandomState(bits)
    d, block = 1000, 128
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    base = jnp.asarray(rng.randn(d).astype(np.float32))
    q, s = ops.block_quantize(x, block=block, bits=bits)
    out = ops.dequant_add(q, s, base, d=d, block=block, bits=bits,
                          alpha=alpha)
    two = base + alpha * ops.block_dequantize(q, s, d=d, block=block,
                                              bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(two), rtol=1e-6,
                               atol=1e-7)


def test_fused_carrier_round_matches_quant8_round():
    """The fused_quant8 one-launch round is bit-compatible with the unfused
    quant8 round through the production vmap runtime: zeros quantize to
    exact 0 and the per-block absmax equals the selected absmax, so the
    dense fused payload decodes to exactly the sparse quant8 decode."""
    from repro.core import compressors as C
    from repro.core import distributed as dist
    from repro.core import ef as ef_lib
    import jax

    rng = jax.random.PRNGKey(0)
    dp, d = 4, 700
    comp = C.BlockTopK(block=128, k_per_block=16)
    grads = jnp.asarray(
        np.random.RandomState(5).randn(dp, d).astype(np.float32))
    params = {"w": jnp.zeros(d)}
    results = {}
    for carrier in ("quant8", "fused_quant8"):
        method = ef_lib.make("ef21_sgdm", compressor=comp, eta=0.3)
        efc = dist.EFConfig(method=method, carrier=carrier)
        st = dist.init_ef_state(efc, params, dp,
                                init_grads={"w": grads})
        msg, st2 = dist.ef_round(efc, {"w": grads}, st, rng, eta=0.3)
        results[carrier] = (msg, st2)
    msg_q, st_q = results["quant8"]
    msg_f, st_f = results["fused_quant8"]
    np.testing.assert_allclose(np.asarray(msg_q["w"]),
                               np.asarray(msg_f["w"]), rtol=1e-6, atol=1e-7)
    for key in st_q["clients"]:
        np.testing.assert_allclose(
            np.asarray(st_q["clients"][key]["w"]),
            np.asarray(st_f["clients"][key]["w"]), rtol=1e-6, atol=1e-7)


def _padded_clients(x, clients, d, block):
    """``fused_wire_round``'s batched layout: each client's leaf padded to
    whole blocks, the clients stacked, so they become consecutive rows."""
    pad = -(-d // block) * block - d
    return jnp.pad(x.reshape(clients, d), ((0, 0), (0, pad)))


# (shape of each operand, block, k, clients): several 128-row grid steps;
# a row count that no multiple-of-8 tile divides, so padding rows run; two
# clients' partial-block leaves stacked along the rows
WIDE_TILE_CASES = {
    "steps": ((3 * 128 * 1024,), 1024, 51, 1),
    "pad_rows": ((389 * 1024 - 100,), 1024, 51, 1),
    "clients": ((2, 192 * 1024 - 5), 1024, 51, 2),
}


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("case", sorted(WIDE_TILE_CASES))
def test_fused_uplink_wide_tile_is_bit_identical(case, bits):
    """The uplink at its own (VMEM-sized) row tile gives the same bits as
    at 8 rows a tile: rows are independent and the bisection runs the
    same iterations on each."""
    from repro import telemetry

    shape, block, k, clients = WIDE_TILE_CASES[case]
    rng = np.random.RandomState(len(case) + bits)
    grad, v, g = [jnp.asarray(rng.randn(*shape).astype(np.float32))
                  for _ in range(3)]
    if clients > 1:
        d = shape[1]
        grad, v, g = [_padded_clients(x, clients, d, block)
                      for x in (grad, v, g)]
    seen = len(telemetry.tiles(fr.UPLINK))
    wide = fr.ef21_sgdm_topk_quant(grad, v, g, eta=0.1, block=block, k=k,
                                   bits=bits, interpret=True)
    rt, steps = (telemetry.tiles(fr.UPLINK)[seen][key]
                 for key in ("rt", "steps"))
    assert rt > 8 and steps > 1, (rt, steps)
    eight = fr.ef21_sgdm_topk_quant(grad, v, g, eta=0.1, block=block, k=k,
                                    bits=bits, rows_per_tile=8,
                                    interpret=True)
    assert telemetry.tiles(fr.UPLINK)[-1]["rt"] == 8
    for a, b in zip(wide, eight):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rows,rt", [
    (240000, 128), (76800, 128), (28800, 128), (7200, 120), (8, 8),
])
def test_uplink_row_tile_on_the_cell_leaves(rows, rt):
    """At block 1024 the VMEM budget allows 128 rows; a row count that 128
    does not divide takes the largest multiple of 8 that does (no pad
    copy), and a leaf of at most one tile runs as one step."""
    from repro.kernels.quantize import _row_tiles
    assert _row_tiles(rows, 1024, None) == (rt, rows)
