"""Counts from shapes: parameters, model FLOPs per token, the uplink
kernel's bytes, and the FLOP count against the compiled HLO's matrix
products (the program's ``launch/hlo_analysis``, sound on CPU HLO) on a
loop-free smoke model."""
import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
# SmolLM-360M at its published widths (HF HuggingFaceTB/SmolLM-360M), the
# configuration of a four-chip cell still to come
SMOLLM = {"d_model": 960, "num_heads": 15, "num_kv_heads": 5, "head_dim": 64,
          "d_ff": 2560, "vocab_size": 49152, "num_layers": 32}


def arch(name):
    if name == "smollm-360m":
        return SMOLLM
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["arch"]


def test_parameter_counts():
    assert flops.param_count(arch("smollm-360m")) == 361_821_120
    assert flops.param_count(arch("h2o-danube3-4b-l1")) == 277_720_320


@pytest.mark.parametrize("name,seq,per_token", [
    # 6 N + 6 S H hd L: the causal attention's average S/2 keys, fwd + bwd
    ("smollm-360m", 1024, 6 * 361_821_120 + 6 * 1024 * 15 * 64 * 32),
    ("h2o-danube3-4b-l1", 2048, 6 * 277_720_320 + 6 * 2048 * 32 * 120 * 1),
])
def test_model_flops_per_token(name, seq, per_token):
    assert flops.model_flops_per_token(arch(name), seq) == per_token


@pytest.mark.parametrize("name,clients,blocks", [
    # blocks of 1024 per leaf: embed, final_norm, wq, wk, wv, wo, attn norm,
    # w_gate, w_up, w_down, mlp norm (a 960-wide norm is one block of 960)
    ("smollm-360m", 1, 46080 + 1 + 28800 + 9600 + 9600 + 28800 + 30
     + 3 * 76800 + 30),
    ("h2o-danube3-4b-l1", 2, 120000 + 4 + 14400 + 3600 + 3600 + 14400 + 4
     + 3 * 38400 + 4),
])
def test_uplink_bytes(name, clients, blocks):
    a = arch(name)
    # read grad, v, g (f32); write v', g' (f32) and the int8 wire; one f32
    # scale per block
    want = clients * (flops.param_count(a) * (3 * 4 + 2 * 4 + 1)
                      + 4 * blocks)
    assert flops.uplink_bytes(a, clients, 1024, 0.05) == want


def test_block_geometry():
    assert flops.block_geom(960, 1024, 0.05) == (1, 960, 48)
    assert flops.block_geom(3840, 1024, 0.05) == (4, 1024, 51)
    assert flops.block_geom(5, 1024, 0.05) == (1, 5, 1)


def test_flops_against_compiled_matmuls():
    """A loop-free (unrolled, no remat) forward and backward of the
    reference model at smoke widths compiles to 3x the forward's matrix
    products: 6 N per token less the norms, plus the full (uncausal) S x S
    attention the reference computes, twice the causal count."""
    import jax
    import jax.numpy as jnp

    from bench.reference import model as ref_model

    a = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
             vocab_size=128, num_layers=2, norm_eps=1e-6, rope_theta=1e4,
             sliding_window=None)
    B, S = 2, 32
    params = jax.eval_shape(lambda: ref_model.init(a, jax.random.PRNGKey(0)))

    def loss(p, toks, labs):
        def one(t, y):
            h = p["embed"][t]
            for i in range(a["num_layers"]):
                layer = jax.tree_util.tree_map(lambda x: x[i], p["layers"])
                h = ref_model._layer(a, layer, h, None)
            h = ref_model._rms(h, p["final_norm"], a["norm_eps"])
            lg = jnp.einsum("sd,vd->sv", h, p["embed"])
            return jnp.mean(jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, y[:, None], -1)[:, 0])
        return jnp.mean(jax.vmap(one)(toks, labs))

    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    text = jax.jit(jax.grad(loss)).lower(params, tok, tok).compile().as_text()
    assert "while" not in text
    from bench import program
    program.import_program()
    from repro.launch import hlo_analysis
    got = hlo_analysis.analyze(text)["dot_flops"]
    L, d, H, hd = a["num_layers"], a["d_model"], a["num_heads"], a["head_dim"]
    norms = d * (2 * L + 1)
    causal = 6 * B * S * S * H * hd * L
    want = flops.model_flops_per_token(a, S) * B * S - 6 * B * S * norms \
        + causal
    assert got == pytest.approx(want, rel=1e-9)
