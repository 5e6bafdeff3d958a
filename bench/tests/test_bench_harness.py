"""The harness on the CPU: BENCHMARK.json against the files the harness
finds by name, the contract's name and unit rules, the entry's refusal
without a chip, and a run at smoke widths with the comparison passing and
failing where it must."""
import json
import os
import re
import subprocess
import sys
import uuid

import pytest

from bench import cell as cell_lib
from bench import check

ROOT = cell_lib.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = cell_lib.benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]
ONE_CHIP = "h2o-danube3-4b-l1.train.b8x2k"
# limits at smoke widths on the CPU, above what the bf16 program reads
# against the float32 reference there (up to 5e-4 loss, 7e-4 gradient and
# update, 1.7e-3 message, 3e-4 round mismatch at a resolution of 2e-2) and
# below the float8 control (from 9e-4 loss, 3.4e-3 gradient, 7e-3
# message) and the program's bfloat16 EF state (0.99 round mismatch)
SMOKE_LIMITS = {"loss_gap": 2e-3, "grad_gap": 1.5e-3, "update_gap": 1.5e-3,
                "msg_gap": 4e-3, "round_mismatch": 0.1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_benchmark_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_names_units_and_text_fields():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in SPEC["workloads"]] + [
            w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for c in SPEC["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    texts = [c["why"] for c in SPEC["configs"]] + [
        c["source"] for c in SPEC["configs"]] + [
        w["why"] for w in SPEC["workloads"]] + [
        m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for dirpath, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    for w in SPEC["workloads"]:
        cell = cell_lib.load(w["name"])
        work = cell["workload"]
        for k in ("config", "traffic", "chips", "why"):
            assert work[k] == w[k], (w["name"], k)
        assert w["config"] in configs
        c = configs[w["config"]]
        assert c["file"] == f"bench/configs/{w['config']}.json"
        assert cell["config"]["source"] == c["source"]
        assert cell["config"]["reduced"] == c["reduced"]
        assert cell["config"]["name"] == w["config"]
        assert set(work["limits"]) == set(check.NUMBERS)
        traffic = cell["traffic"]
        assert traffic["global_batch"] % cell["config"]["clients"] == 0
        used.add(w["config"])
    assert used == set(configs)


def test_every_metric_has_a_reader_and_a_layer():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(cell_lib.reader(m["name"])), m["name"]
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in SPEC["per_layer"]:
        assert m["layer"] in perf, m["layer"]
    e2e = {m["name"] for m in cell_lib.metrics(ONE_CHIP, trace=False)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for cell in CELLS:
        layer = {m["name"] for m in cell_lib.metrics(cell, trace=True)}
        assert layer == {m["name"] for m in SPEC["per_layer"]
                         if cell in m["workloads"]}
        chips = cell_lib.load(cell)["workload"]["chips"]
        assert chips > 1 or not any(n.startswith("collective.")
                                    for n in layer)


def test_unknown_device_has_no_peaks():
    assert cell_lib.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cell_lib.peaks("cpu")


def test_new_cell_and_metric_are_found_by_name():
    """A later PR adds a cell or a metric by adding files: nothing that
    exists is edited."""
    tag = "tmp-" + uuid.uuid4().hex[:8]
    work = os.path.join(ROOT, "bench", "workloads", tag + ".json")
    metric = os.path.join(ROOT, "bench", "metrics", tag + ".py")
    try:
        with open(work, "w") as f:
            json.dump({"config": "h2o-danube3-4b-l1", "traffic": "b8x2k",
                       "chips": 1, "why": "test", "limits": {}}, f)
        with open(metric, "w") as f:
            f.write("def read(run):\n    return run['x'] * 2\n")
        cell = cell_lib.load(tag)
        assert cell["config"]["name"] == "h2o-danube3-4b-l1"
        assert cell["traffic"]["seq_len"] == 2048
        assert cell_lib.reader(tag)({"x": 21}) == 42
    finally:
        for p in (work, metric):
            if os.path.exists(p):
                os.remove(p)


def _entry(*args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=e, capture_output=True, text=True, timeout=300)


def test_entry_refuses_without_a_tpu():
    r = _entry("--workload", ONE_CHIP, "--seed", "3000000001", "--seconds",
               "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
    r = _entry("--workload", "no-such-cell", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout.strip() == ""


def _smoke(name):
    from bench.tools.smoke import smoke
    cell = smoke(cell_lib.load(name))
    cell["workload"]["limits"] = dict(SMOKE_LIMITS)
    return cell


def _run(capsys, cell, seed, fault=None):
    import types

    import jax

    from bench import run
    from bench.tools import faults

    args = types.SimpleNamespace(seed=seed, seconds=0.5, trace=0)
    devs = jax.devices()[:cell["workload"]["chips"]]
    capsys.readouterr()
    if fault is None:
        run.measure(cell, args, devs, PEAKS)
    else:
        with faults.planted(fault):
            run.measure(cell, args, devs, PEAKS)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


def test_smoke_run_is_correct(capsys):
    res = _run(capsys, _smoke(ONE_CHIP), 2147483659)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["count"] == 1


def test_smoke_lower_precision_fails():
    """The control: the reference itself with its matrix products in
    float8, in the program's place, fails the comparison."""
    from bench import program, verify

    cell = _smoke(ONE_CHIP)
    ef = cell["config"]["ef"]
    import jax
    devs = jax.devices()[:1]
    ref = verify.readings(cell, 7, devs, program.CHECK_STEPS)
    sess = program.session(cell, 7)
    sound = program.first_steps(
        sess, program.round_sample(sess, 7, ef["block"]))
    low = verify.readings(cell, 7, devs, program.CHECK_STEPS,
                          matmul_dtype="float8_e4m3fn")
    assert check.verdict(check.compare(sound, ref, ef), SMOKE_LIMITS)
    numbers = check.compare(low, ref)
    assert not check.verdict(numbers, SMOKE_LIMITS)
    assert numbers["grad_gap"] > SMOKE_LIMITS["grad_gap"]


def test_smoke_state_bf16_fails(capsys, monkeypatch):
    """The program's own bfloat16 EF-state path, one precision below the
    configuration's float32 state: the harness refuses the session by its
    dtypes, and with that check passed over, the EF round repeated on its
    state fails ``round_mismatch``."""
    from bench import program

    cell = _smoke(ONE_CHIP)
    session = program.session
    monkeypatch.setattr(program, "session", lambda c, s, **kw: session(
        c, s, ef_state_dtype="bfloat16", **kw))
    with pytest.raises(ValueError, match="dtypes"):
        _run(capsys, cell, 17)
    monkeypatch.setattr(program, "check_precision", lambda sess, cfg: None)
    res = _run(capsys, cell, 17)
    assert res["correct"] is False
    mismatch = res["checks"]["round_mismatch"]
    assert mismatch["value"] > 5 * mismatch["limit"]
    assert res["checks"]["grad_gap"]["value"] <= SMOKE_LIMITS["grad_gap"]


def test_precision_matches_the_configuration():
    from bench import program

    cell = _smoke(ONE_CHIP)
    sess = program.session(cell, 19)
    program.check_precision(sess, cell["config"])
    wrong = dict(cell["config"], precision=dict(
        cell["config"]["precision"], parameters="bfloat16"))
    with pytest.raises(ValueError, match="parameters"):
        program.check_precision(sess, wrong)


def test_round_sample_is_whole_blocks_from_the_seed():
    import types

    import numpy as np

    from bench import program

    server = {"a": np.zeros((3, 1000)), "b": np.zeros((700,))}
    sess = types.SimpleNamespace(ef_state={"server": server})
    one = program.round_sample(sess, 2 ** 40 + 3, 256, blocks=4)
    again = program.round_sample(sess, 2 ** 40 + 3, 256, blocks=4)
    other = program.round_sample(sess, 5, 256, blocks=4)
    assert [s["leaf"] for s in one] == ["a", "b"]
    for s, t in zip(one, again):
        assert all((x == y).all() for x, y in zip(s["index"], t["index"]))
    assert any((x != y).any() for x, y in zip(one[0]["index"],
                                              other[0]["index"]))
    a, b = one
    assert a["block"] == 256 and b["block"] == 256
    flat = a["index"][0] * 1000 + a["index"][1]
    starts = flat.reshape(4, 256)[:, 0]
    assert (starts % 256 == 0).all() and len(set(starts)) == 4
    # 700 elements are 3 blocks; the last holds 188 of them
    assert b["inside"].sum() == 700 and len(b["inside"]) == 3 * 256


def test_round_mismatch_by_hand():
    """The reference's round repeated on the state it started from: a
    program that does the same reads 0, one whose g' is rounded to
    bfloat16 reads about 1, one whose server message is doubled about a
    half (its clients are right), and one element's flip one element's
    share."""
    import numpy as np

    from bench.reference import ef21

    rng = np.random.default_rng(0)
    n, blocks, b, kb = 2, 3, 128, 6
    v0 = rng.standard_normal((n, blocks * b)).astype(np.float32)
    g0 = v0 + 0.1 * rng.standard_normal((n, blocks * b)).astype(np.float32)
    s0 = g0.mean(0)
    v1 = v0 + np.float32(0.1) * (rng.standard_normal(v0.shape)
                                 .astype(np.float32) - v0)
    g1, s1, _, mean = ef21.round_blocks(
        v1.reshape(n, blocks, b), g0.reshape(n, blocks, b),
        s0.reshape(blocks, b), kb, 8)
    sample = [{"leaf": "w", "block": b, "inside": np.ones(blocks * b, bool)}]
    ef = {"ratio": kb / b, "bits": 8}

    def reads(g, s):
        rounds = [{"v": [v0], "g": [g0], "server": [s0]},
                  {"v": [v1], "g": [g.reshape(n, -1)],
                   "server": [s.reshape(-1)]}]
        return check.round_mismatch(rounds, sample, ef)["round_mismatch"]

    assert reads(g1, s1) == 0.0
    bf16 = (g1.view(np.uint32) + 0x8000 & 0xFFFF0000).view(np.float32)
    assert reads(bf16, s1) > 0.9
    assert 0.3 < reads(g1, s1 + mean) < 0.7
    flip = g1.copy()
    flip[0, 0, np.argmax(np.abs(g1 - g0.reshape(g1.shape))[0, 0])] *= 1.01
    changed = np.sum(g1 != g0.reshape(g1.shape)) + np.sum(
        s1 != s0.reshape(s1.shape))
    assert reads(flip, s1) == pytest.approx(1 / changed)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "msg_altered"])
def test_smoke_fault_is_not_correct(capsys, fault):
    res = _run(capsys, _smoke(ONE_CHIP), 11, fault=fault)
    assert res["correct"] is False, res["checks"]
