"""The program's own tracing (repro/telemetry.py): the train step's layer
scopes in the compiled HLO for each kind of carrier, the compile counter
per program phase with its recompiles, and the spans ``step_once`` opens."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.launch.session import Session
from repro.launch.spec import RunSpec

TINY = dict(arch="smollm-360m", smoke=True, clients=2, global_batch=4,
            seq_len=32, compressor="block_topk", ratio=0.1)
KERNELS = ("ef21_sgdm_uplink", "dequant_add", "block_quantize",
           "block_dequantize", "flash_attention", "ef_update",
           "topk_compress")
LAYERS = (telemetry.MODEL, telemetry.EF_CLIENT, telemetry.EF_AGGREGATE,
          telemetry.OPTIMIZER)


def _scopes(op_name: str):
    return [t for t in re.split(r"[/()]", op_name) if t in telemetry.SCOPES]


def _op_names(hlo: str):
    """(instruction line, op_name) of every instruction with metadata."""
    for line in hlo.splitlines():
        m = re.search(r'metadata=\{[^}]*op_name="([^"]*)"', line)
        if m and " = " in line:
            yield line, m.group(1)


def _step_hlo(sess):
    return sess.step_fn.lower(
        sess.params, sess.opt_state, sess.ef_state, sess.batch_for(0),
        jax.random.PRNGKey(0), 0).compile().as_text()


@pytest.mark.parametrize("carrier", ["dense", "fused", "fused_quant8",
                                     "quant8"])
def test_step_layers_are_scoped(carrier):
    """Each plan (dense, fused, fused_wire, wire) names the four layers of
    the step, and every matmul and every Pallas kernel lies under one."""
    sess = Session(RunSpec(**TINY, carrier=carrier))
    hlo = _step_hlo(sess)
    names = list(_op_names(hlo))
    seen = {s for _, op in names for s in _scopes(op)}
    assert set(LAYERS) <= seen, (carrier, seen)
    assert telemetry.DOWNLINK not in seen
    dots = [(line, op) for line, op in names
            if re.search(r" (dot|convolution)\(", line)]
    assert dots
    for line, op in dots:
        assert _scopes(op), line[:200]
    kernels = [op for _, op in names
               if any(k in op.split("/") for k in KERNELS)]
    if carrier.startswith("fused"):
        assert kernels, carrier
    for op in kernels:
        assert _scopes(op) == [telemetry.EF_CLIENT], op


def test_downlink_is_scoped():
    sess = Session(RunSpec(**TINY, carrier="quant8",
                           downlink_carrier="quant8", downlink_ratio=0.1))
    seen = {s for _, op in _op_names(_step_hlo(sess)) for s in _scopes(op)}
    assert set(LAYERS) | {telemetry.DOWNLINK} <= seen


def test_counter_counts_per_phase_and_recompiles():
    sess = Session(RunSpec(**TINY))
    c0 = telemetry.counters()
    sess.step_once()
    c1 = telemetry.counters()
    assert c1["init"]["compiles"] > c0["init"]["compiles"]
    assert c1["step"]["compiles"] > c0["step"]["compiles"]
    assert c1["step"]["seconds"] > c0["step"]["seconds"]
    assert c1["step"]["seconds"] <= (c1["step"]["trace_s"]
                                     + c1["step"]["lower_s"]
                                     + c1["step"]["compile_s"]) + 1e-9
    sess.step_once()
    c2 = telemetry.counters()
    assert c2["step"]["compiles"] == c1["step"]["compiles"]
    assert c2["recompiles"] == c1["recompiles"]

    # a batch of another shape reaches the step: one recompile, at its step
    inner = sess.batch_for
    sess.batch_for = lambda step: {k: v[:2] for k, v in inner(step).items()}
    sess.step_once()
    c3 = telemetry.counters()
    new = c3["recompiles"][len(c2["recompiles"]):]
    assert [r["step"] for r in new] == [2]
    assert c3["step"]["compiles"] == c2["step"]["compiles"] + 1


def test_compiles_outside_a_phase_are_not_counted():
    telemetry.install()
    c0 = telemetry.counters()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0))
    assert telemetry.counters() == c0


def test_train_verbose_prints_a_recompile(capsys):
    sess = Session(RunSpec(**TINY))
    sess.train(1, log_every=0, verbose=True)
    inner = sess.batch_for
    sess.batch_for = lambda step: {k: v[:2] for k, v in inner(step).items()}
    sess.train(2, log_every=0, verbose=True)
    out = capsys.readouterr().out
    assert re.search(r"recompiled \S+ at step 1 \(", out), out


def test_counter_by_hand():
    """Nested trace spans count once; a compile is a recompile only in a
    warm step phase; events outside a phase are dropped."""
    c = telemetry.CompileCounter()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    compile_ = "/jax/core/compile/backend_compile_duration"
    c.on_span(compile_, 0.0, 1.0, fun_name="f")
    with c.phase("init"):
        c.on_span(trace, 10.0, 14.0)
        c.on_span(trace, 11.0, 12.0)          # a jit traced inside another
        c.on_span(compile_, 14.0, 16.0, fun_name="init")
        c.on_event("/jax/compilation_cache/cache_hits")
    with c.phase("step", step=0, warm=False):
        c.on_span(compile_, 20.0, 21.0, fun_name="train_step")
    with c.phase("step", step=5, warm=True):
        c.on_span(compile_, 30.0, 30.5, fun_name="train_step")
        c.on_event("/jax/compilation_cache/cache_misses")
    snap = c.snapshot()
    assert snap["init"] == {"compiles": 1, "cache_hits": 1,
                            "cache_misses": 0, "trace_s": 4.0,
                            "lower_s": 0.0, "compile_s": 2.0,
                            "seconds": 6.0}
    assert snap["step"]["compiles"] == 2
    assert snap["step"]["cache_misses"] == 1
    assert snap["step"]["seconds"] == 1.5
    assert snap["recompiles"] == [{"step": 5, "seconds": 0.5,
                                   "fun": "train_step"}]
    with pytest.raises(ValueError):
        with c.phase("serve"):
            pass


@pytest.mark.parametrize("rows_per_tile,rt,rows", [(None, 128, 384),
                                                   (8, 8, 304)])
def test_uplink_launch_records_its_tile(rows_per_tile, rt, rows):
    """Tracing one uplink launch records its rows as launched, its row tile
    and its grid steps: 300 rows at block 1024 pad to whole tiles."""
    import functools

    from repro.kernels import fused_round

    seen = len(telemetry.tiles())
    x = jax.ShapeDtypeStruct((300 * 1024,), jnp.float32)
    jax.eval_shape(functools.partial(
        fused_round.ef21_sgdm_topk_quant, eta=0.1, block=1024, k=51,
        rows_per_tile=rows_per_tile, interpret=True), x, x, x)
    assert telemetry.tiles()[seen:] == [
        {"kernel": fused_round.UPLINK, "rows": rows, "block": 1024,
         "rt": rt, "steps": rows // rt}]
    assert telemetry.tiles(fused_round.UPLINK)[-1]["rt"] == rt
    assert telemetry.tiles("no_such_kernel") == []
