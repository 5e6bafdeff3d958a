"""The per-layer readers of the program's scopes (bench/scopes.py and the
six metrics that read them) on hand-made tables and HLO, and the program's
host spans in a real profiler trace, on the clock of the harness's own."""
import hashlib
import json
import os

import pytest

from bench import cell as cell_lib
from bench import scopes as S
from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_smoke_1chip.xplane.pb.gz")
ONE_CHIP = "h2o-danube3-4b-l1.train.b8x2k"

HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/ef_aggregate/mul"}
  %add.1 = f32[8]{0} add(%mul.1, %param_0), metadata={op_name="jit(train_step)/ef_aggregate/add"}
  ROOT %sub.1 = f32[8]{0} subtract(%add.1, %param_0), metadata={op_name="jit(train_step)/reduce_sum"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %neg.2 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(train_step)/ef_client/neg"}
  ROOT %abs.2 = f32[8]{0} abs(%neg.2), metadata={op_name="jit(train_step)/abs"}
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %inner.3 = f32[8]{0} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.2
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %dot.4 = f32[8,8]{1,0} dot(%gte, %gte), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/model_fwd_bwd/vmap(transpose(jvp()))/while/body/dot_general"}
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%gte, %dot.4)
}

ENTRY %main.9 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %while.2 = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(train_step)/model_fwd_bwd/vmap(jvp())/while"}
  %fusion.2 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %convolution.5 = f32[8,8]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/vmap(transpose(jvp(model_fwd_bwd)))/dot_general"}
  %ef21_sgdm_uplink.3 = (f32[8,1024]{1,0}, f32[8,1024]{1,0}, s8[8,1024]{1,0}, f32[8,1]{1,0}) custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/ef_client/ef21_sgdm_uplink/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/optimizer/sub"}
  %fusion.8 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.3
  %copy.7 = f32[8]{0} copy(%p0)
  ROOT %tuple = (f32[8]{0}) tuple(%copy.7)
}
"""


def table(ops, win=(0, 100)):
    return {"devices": {"/device:TPU:0": [tuple(o) for o in ops]},
            "host": [("bench.window",) + tuple(win)]}


def run_of(ops, hlo=HLO, steps=2):
    return {"trace": table(ops), "hlo": hlo, "window": {"steps": steps}}


# a loop 0..50 holding a matmul 5..15, then one op of each kind
OPS = [("while.2", "while", 0, 50), ("dot.4", "dot", 5, 15),
       ("fusion.2", "fusion", 50, 60), ("convolution.5", "convolution", 60, 80),
       ("ef21_sgdm_uplink.3", "custom-call", 80, 88),
       ("fusion.6", "fusion", 88, 93), ("fusion.8", "fusion", 93, 95),
       ("copy.7", "copy", 95, 100)]


def test_scope_of_bare_and_wrapped_names():
    assert S.scope_of("jit(train_step)/model_fwd_bwd/vmap(jvp())/mul") \
        == "model_fwd_bwd"
    assert S.scope_of("jit(train_step)/vmap(transpose(jvp(model_fwd_bwd)))"
                      "/dot_general") == "model_fwd_bwd"
    assert S.scope_of("jit(f)/ef_client/ef21_sgdm_uplink/pallas_call") \
        == "ef_client"
    # the innermost scope wins; a name that merely contains one does not
    assert S.scope_of("jit(f)/ef_client/ef_aggregate/psum") == "ef_aggregate"
    assert S.scope_of("jit(f)/my_optimizer_x/add") is None
    assert S.scope_of("params['w']") is None


def test_instruction_scopes_with_fusion_fallback():
    sc = S.instruction_scopes(HLO)
    assert sc["dot.4"] == "model_fwd_bwd"
    assert sc["while.2"] == "model_fwd_bwd"
    assert sc["convolution.5"] == "model_fwd_bwd"
    assert sc["ef21_sgdm_uplink.3"] == "ef_client"
    # no op_name: the majority of its computation (two of three)
    assert sc["fusion.2"] == "ef_aggregate"
    # its own op_name wins over its computation's
    assert sc["fusion.6"] == "optimizer"
    # a fusion calling a fusion: one scoped vote against one unscoped, and
    # a tie goes to the scope
    assert sc["fusion.8"] == "ef_client"
    assert sc["copy.7"] is None and sc["p0"] is None


def test_step_times_split_the_busy_time():
    run = run_of(OPS)
    t = S.step_times(run)
    ns = {k: v * 1e9 * 2 for k, v in t["scopes"].items()}
    # the loop's self time (40) counts once beside the matmul's (10)
    assert ns["model_fwd_bwd"] == pytest.approx(40 + 10 + 20)
    assert ns["ef_aggregate"] == pytest.approx(10)
    assert ns["ef_client"] == pytest.approx(8 + 2)
    assert ns["optimizer"] == pytest.approx(5)
    assert ns["downlink"] == 0
    assert t["unscoped"] * 1e9 * 2 == pytest.approx(5)
    busy = T.busy(run["trace"], "/device:TPU:0", T.window(run["trace"]))
    assert sum(t["scopes"].values()) + t["unscoped"] == pytest.approx(
        busy * 1e-9 / 2)
    assert t["present"] == {"model_fwd_bwd", "ef_aggregate", "ef_client",
                            "optimizer"}


def test_readers():
    run = run_of(OPS)
    read = {m: cell_lib.reader(m)(run) for m in (
        "model.ms_per_step", "ef_client.ms_per_step",
        "ef_aggregate.ms_per_step", "optimizer.ms_per_step",
        "step.unscoped_share")}
    assert read["model.ms_per_step"] == pytest.approx(70e-6 / 2)
    assert read["ef_client.ms_per_step"] == pytest.approx(10e-6 / 2)
    assert read["ef_aggregate.ms_per_step"] == pytest.approx(10e-6 / 2)
    assert read["optimizer.ms_per_step"] == pytest.approx(5e-6 / 2)
    assert read["step.unscoped_share"] == pytest.approx(5.0)
    assert S.ms_per_step(run, "downlink") is None


def test_readers_without_scopes_read_nothing():
    """A compiled step that carries no scope (a program without them), or
    a run without a trace, gives no number rather than a zero."""
    bare = "\n".join(l for l in HLO.splitlines()
                     if "op_name=\"jit" not in l)
    run = run_of(OPS, hlo=bare)
    for m in ("model.ms_per_step", "ef_client.ms_per_step",
              "ef_aggregate.ms_per_step", "optimizer.ms_per_step",
              "step.unscoped_share"):
        assert cell_lib.reader(m)(run) is None, m
        assert cell_lib.reader(m)({"trace": None, "hlo": HLO,
                                   "window": {"steps": 1}}) is None, m


def test_compile_reader_reads_the_program_counter():
    from bench import program
    program.import_program()
    from repro import telemetry

    c = telemetry.counters()
    got = cell_lib.reader("setup.compile_s")({})
    assert got == pytest.approx(c["init"]["seconds"] + c["step"]["seconds"])


def test_load_keeps_the_recorded_table():
    """The scopes and the program's spans leave the harness's loader as it
    was: the recorded trace still loads to the table it always did."""
    t = T.load(DATA)
    digest = hashlib.sha256(json.dumps(t, sort_keys=True).encode())
    assert digest.hexdigest() == ("2d1a847395684d28130c83c9b4344d53168a7ca4"
                                  "79212a0f7e5dabacb9405724")
    assert len(t["host"]) == 5


def program_spans(path):
    """The program's ``repro.*`` host spans in an xplane file, as
    (name, start_ns, end_ns) on the clock of ``trace.load``'s table."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns)
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("repro.")), key=lambda h: h[1])


def test_program_spans_in_a_trace(tmp_path):
    """Two window steps under the profiler: the program's spans sit inside
    the harness's, on the same clock, and name what the host was doing."""
    import jax

    from bench import program
    from bench.tools.smoke import smoke

    cell = smoke(cell_lib.load(ONE_CHIP))
    sess = program.session(cell, 2147483677)
    program.annotate(sess)
    sess.step_once()
    with jax.profiler.trace(str(tmp_path)):
        program.run_window(sess, 0.0, steps=2)
    path = T.find_xplane(str(tmp_path))
    outer = [h for h in T.load(path)["host"] if h[0] == "bench.step_once"]
    assert len(outer) == 2
    spans = program_spans(path)
    names = [h[0] for h in spans]
    for n in ("repro.step", "repro.batch", "repro.dispatch"):
        assert names.count(n) == 2, (n, names)
    for name, s, e in spans:
        assert any(os_ <= s and e <= oe for _, os_, oe in outer), name
    step = [h for h in spans if h[0] == "repro.step"]
    for name in ("repro.batch", "repro.dispatch"):
        for _, s, e in (h for h in spans if h[0] == name):
            assert any(ss <= s and e <= se for _, ss, se in step), name


def test_idle_gaps_named_by_program_spans():
    t = table([("a", "fusion", 0, 40), ("b", "fusion", 70, 100)])
    t["host"] += [("bench.step_once", 30, 100), ("repro.step", 31, 99),
                  ("repro.batch", 32, 38), ("bench.batch", 33, 37),
                  ("repro.dispatch", 38, 98)]
    gaps = T.idle_gaps(t, T.window(t))
    assert gaps == [["repro.dispatch", pytest.approx(30e-9)]]


def test_reference_compiles_are_not_counted():
    import jax

    from bench import program, verify
    from bench.tools.smoke import smoke
    program.import_program()
    from repro import telemetry

    telemetry.install()
    cell = smoke(cell_lib.load(ONE_CHIP))
    before = telemetry.counters()
    verify.readings(cell, 2147483681, jax.devices()[:1], 1)
    assert telemetry.counters() == before
