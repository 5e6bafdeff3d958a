"""The trace reduction: interval arithmetic on hand-made tables, and the
loader on a small trace recorded on a TPU v5e (two steps of the one-chip
cell at smoke widths, gzipped)."""
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_smoke_1chip.xplane.pb.gz")


def table(ops, host=(("bench.window", 0, 100),)):
    return {"devices": {"/device:TPU:0": [tuple(o) for o in ops]},
            "host": [tuple(h) for h in host]}


def test_union_subtract_length():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                      (7, 10)]
    assert T.subtract([(0, 4)], [(0, 4)]) == []


def test_idle_share_by_hand():
    # busy 10..30 and 25..50 (union 40) and 90..120 (10 inside the window)
    t = table([("a", "fusion", 10, 30), ("b", "fusion", 25, 50),
               ("c", "fusion", 90, 120)])
    w = T.window(t)
    assert T.busy(t, "/device:TPU:0", w) == 50
    from bench.cell import reader
    run = {"trace": t}
    assert reader("device.idle_share")(run) == pytest.approx(50.0)


def test_nested_ops_self_time_and_exposure():
    # a loop 0..50 holding a fusion 5..15 and an all-reduce 20..40: the
    # loop's self time is 20, and the busy time is the loop's 50
    t = table([("loop", "while", 0, 50), ("f", "fusion", 5, 15),
               ("ar", "all-reduce", 20, 40)])
    ops = t["devices"]["/device:TPU:0"]
    assert T.self_times(ops) == [20, 10, 20]
    w = T.window(t)
    assert T.busy(t, "/device:TPU:0", w) == 50
    tot = T.op_totals(t, w)
    assert tot["loop while"] == pytest.approx(20e-9)
    assert sum(tot.values()) == pytest.approx(
        T.busy(t, "/device:TPU:0", w) * 1e-9)


def test_idle_gaps_named_by_host_span():
    t = table([("a", "fusion", 0, 40), ("b", "fusion", 70, 100)],
              host=[("bench.window", 0, 100), ("bench.step_once", 35, 80),
                    ("bench.batch", 45, 60)])
    gaps = T.idle_gaps(t, T.window(t))
    assert [g[0] for g in gaps] == ["bench.step_once"]
    assert gaps[0][1] == pytest.approx(30e-9)
    t["host"].append(("bench.batch", 40, 41))
    assert T.idle_gaps(t, T.window(t))[0][0] == "bench.batch"


def test_breakdown_names_ops_by_their_jax_operation():
    t = table([("loop", "while", 0, 50), ("f", "fusion", 5, 15),
               ("k", "custom-call", 52, 100)])
    b = T.breakdown(t, T.window(t), {"k": "jit(train_step)/pallas_call"})
    assert b["device_ops"][0] == [
        "k custom-call jit(train_step)/pallas_call", pytest.approx(48e-9)]
    assert [x[0] for x in b["device_ops"][1:]] == ["loop while",
                                                   "f fusion"]
    assert b["idle_gaps"] == [["host:other", pytest.approx(2e-9)]]


def test_op_name_parsing():
    name = ("%train_step.19 = (f32[512,1024]{1,0:T(8,128)S(1)}, "
            "s8[512,1024]{1,0:T(8,128)(4,1)S(1)}) custom-call(f32[512,1024]"
            "{1,0:T(8,128)S(1)} %reshape.273), custom_call_target="
            "\"tpu_custom_call\"")
    assert T.op_name(name) == ("train_step.19", "custom-call")
    assert T.op_name("%all-reduce.3 = f32[960]{0} all-reduce(f32[960]{0} "
                     "%x)")[1] == "all-reduce"
    assert T.op_name("not an op") == ("not an op", "")


def test_op_names_from_compiled_hlo():
    hlo = (
        '  %fusion.7 = f32[8]{0:T(256)} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(train_step)/mul" '
        'source_file="x.py"}\n'
        '  ROOT %t = (f32[8]) tuple(%fusion.7)\n'
        '  %c.1 = f32[8] custom-call(%fusion.7), custom_call_target="x", '
        'metadata={op_type="pallas_call" op_name="jit(f)/pallas_call"}\n')
    assert T.op_names(hlo) == {"fusion.7": "jit(train_step)/mul",
                               "c.1": "jit(f)/pallas_call"}
    assert T.op_names("no instructions") == {}


def test_recorded_chip_trace():
    t = T.load(DATA)
    assert list(t["devices"]) == ["/device:TPU:0"]
    ops = t["devices"]["/device:TPU:0"]
    assert len(ops) == 1170
    w = T.window(t)
    assert w == (41674732.0, 51484432.0)
    # device ops sit on the host's clock: inside the traced window
    inside = T.device_ops(t, "/device:TPU:0", w)
    assert len(inside) > 1000
    busy = T.busy(t, "/device:TPU:0", w)
    assert 0 < busy < w[1] - w[0]
    assert busy == pytest.approx(2154038.0, rel=1e-9)
    # the 11 uplink kernel launches of each of the two steps
    kernels = [o for o in inside if o[1] == "custom-call"
               and o[0].startswith("train_step.")]
    assert len(kernels) == 22
    assert [s[0] for s in t["host"]].count("bench.step_once") == 2
    gaps = T.idle_gaps(t, w)
    assert gaps[0][0] == "bench.step_once"
    assert sum(g[1] for g in gaps) <= (w[1] - w[0] - busy) * 1e-9 + 1e-12
    # self times add up to the busy time: nested loop bodies count once
    assert sum(T.op_totals(t, w).values()) == pytest.approx(busy * 1e-9,
                                                            rel=1e-6)
