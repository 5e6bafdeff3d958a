"""The main-path Pallas kernels compile for a TPU v5e at smollm-360m leaf
sizes. Nothing runs: the TPU compiler in the installed libtpu compiles for
a described chip with none attached, so Mosaic refuses here what it would
refuse on the chip (unsupported casts, unaligned tiles, too much VMEM) —
faults the interpret-mode tests cannot see. Each compiled program must
hold the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under pytest-xdist
every worker imports this file."""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ef_update, fused_round, ops, quantize, topk_compress

# smollm-360m leaves: one MLP projection (d_model × d_ff) and the embedding
LEAVES = {"mlp": (2560, 960), "embed": (49152, 960)}
BLOCK, K, QBLOCK, ETA = 1024, 51, 256, 0.1   # Block-TopK ratio 0.05


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _kernel(name: str, shape):
    """(fn, [(shape, dtype), ...]) for one main-path kernel on a leaf."""
    d = shape[0] * shape[1]
    nb = -(-d // BLOCK)
    f32 = jnp.float32
    leaf = [(shape, f32)] * 3
    if name == "ef21_sgdm_update":
        return functools.partial(ef_update.ef21_sgdm_update, eta=ETA,
                                 block=BLOCK, k=K), leaf
    if name.startswith("ef21_sgdm_topk_quant"):
        bits = int(name[-1])
        return functools.partial(fused_round.ef21_sgdm_topk_quant, eta=ETA,
                                 block=BLOCK, k=K, bits=bits), leaf
    if name.startswith("dequant_add"):
        bits = int(name[-1])
        q = ((nb, BLOCK if bits == 8 else BLOCK // 2),
             jnp.int8 if bits == 8 else jnp.uint8)
        return functools.partial(fused_round.dequant_add, d=d, block=BLOCK,
                                 bits=bits), [q, ((nb,), f32), (shape, f32)]
    if name.startswith("block_quantize"):
        return functools.partial(quantize.block_quantize, block=QBLOCK,
                                 bits=int(name[-1])), [(shape, f32)]
    if name == "block_topk":
        return functools.partial(topk_compress.block_topk, block=BLOCK,
                                 k=K), [(shape, f32)]
    raise KeyError(name)


KERNELS = ["ef21_sgdm_update", "ef21_sgdm_topk_quant8",
           "ef21_sgdm_topk_quant4", "dequant_add8", "dequant_add4",
           "block_quantize8", "block_quantize4", "block_topk"]


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, leaf, one_chip):
    fn, args = _kernel(name, LEAVES[leaf])
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} at {LEAVES[leaf]} compiled without its Mosaic kernel"


# the uplink's rows at h2o-danube3-4b's widths, 2 clients stacked as the
# vmap round launches it: the embedding, an MLP projection and wk/wv
CELL_ROWS = {"embed": 240_000, "mlp": 76_800, "wk": 7_200}
ROOFLINE_READER = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                               "metrics", "kernel.uplink.roofline.py")


def _roofline_reader():
    spec = importlib.util.spec_from_file_location("uplink_roofline",
                                                  ROOFLINE_READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("leaf", sorted(CELL_ROWS))
@pytest.mark.parametrize("bits", [8, 4])
def test_uplink_compiles_at_the_cell_rows(bits, leaf, one_chip):
    """The uplink at its VMEM-sized row tile compiles for a v5e at the
    one-chip cell's leaf rows, as one Mosaic launch whose four outputs
    (v', g', wire, scales) are what the roofline reader looks for."""
    rows = CELL_ROWS[leaf]
    fn = functools.partial(fused_round.ef21_sgdm_topk_quant, eta=ETA,
                           block=BLOCK, k=K, bits=bits)
    spec = jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32,
                                sharding=one_chip)
    hlo = jax.jit(fn).lower(spec, spec, spec).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert len(_roofline_reader().launches(hlo)) == 1, \
        f"the uplink at {rows} rows lost its four-output signature"


# each cell's attention call as one chip traces it: (B, S, H, KV, hd)
ATTENTION_SHAPES = {"smollm-360m": (4, 1024, 15, 5, 64),
                    "h2o-danube3-4b": (8, 2048, 32, 8, 120)}


@pytest.mark.parametrize("cell", sorted(ATTENTION_SHAPES))
def test_causal_gqa_attention_compiles_for_v5e(cell, one_chip, monkeypatch):
    """The fused causal GQA kernel's forward and its dQ and dK/dV backward
    compile for a v5e at each cell's attention shape, head dim 120
    included, as Mosaic launches."""
    B, S, H, KV, hd = ATTENTION_SHAPES[cell]
    # the process's backend is the CPU's: build the kernel as the chip would
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def loss(q, k, v):
        return jnp.sum(ops.causal_gqa_attention(q, k, v).astype(jnp.float32))

    specs = [jax.ShapeDtypeStruct((B, S, n, hd), jnp.bfloat16,
                                  sharding=one_chip) for n in (H, KV, KV)]
    hlo = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        *specs).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3, \
        f"{cell}: forward, dQ and dK/dV did not all compile as Mosaic kernels"


def test_causal_gqa_attention_compiles_across_four_chips(topo, monkeypatch):
    """The four-chip cell's attention layer, forward and backward, compiles
    for a v5e 2x2 (mesh ``host4``: data 4, model 1) inside the clients'
    vmap as the train step runs it: batch split over ``data``, weights
    replicated. Mosaic cannot partition a kernel itself, so this holds only
    if each chip runs the kernel on its own shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist
    from repro.models import layers as L

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    B, S, H, KV, hd = ATTENTION_SHAPES["smollm-360m"]
    d, clients = 960, 4
    mesh = jax.sharding.Mesh(
        np.array(topo.devices).reshape(4, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = jax.eval_shape(lambda: L.attn_init(
        jax.random.PRNGKey(0), d, H, KV, hd, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), params)
    x = jax.ShapeDtypeStruct((clients * B, S, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        pos = jnp.broadcast_to(jnp.arange(S), x.shape[:2])
        out, _ = L.attn_apply(p, x, pos, rope_theta=1e4, eps=1e-6, chunk=512)
        return jnp.mean(out.astype(jnp.float32) ** 2), ()

    def step(p, x):
        return dist.per_client_value_and_grad(loss, p, x, clients)[2]

    with jax.set_mesh(mesh):
        hlo = jax.jit(step).lower(params, x).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3, \
        "forward, dQ and dK/dV did not all compile as Mosaic kernels"
