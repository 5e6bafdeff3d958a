"""Spec-layer tests (launch/spec.py): the jax-free mirrors stay in sync with
the jax-importing registries, JSON round-trips are identity across the
supported grid, validation fails at construction, and the golden fixtures
under results/specs/ fail loudly on any schema drift."""
import dataclasses
import glob
import os
import subprocess
import sys

import pytest

from repro.configs import base as cb
from repro.launch import spec as spec_lib
from repro.launch.spec import RunSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# mirror ↔ registry sync (the price of a jax-free spec layer)
# ---------------------------------------------------------------------------

def test_name_universes_match_registries():
    from repro.core import carriers as carrier_lib
    from repro.core import compressors as comp_lib
    from repro.core import ef as ef_lib
    from repro.optim import optimizer as opt_lib
    from repro.core import participation as part_lib
    assert spec_lib.METHODS == set(ef_lib.REGISTRY)
    assert spec_lib.COMPRESSORS == set(comp_lib.REGISTRY)
    assert spec_lib.CARRIERS == set(carrier_lib.REGISTRY)
    assert spec_lib.OPTIMIZERS == set(opt_lib.REGISTRY)
    assert spec_lib.PART_MODES == part_lib.PART_MODES


def test_mesh_geometry_matches_mesh_module():
    from repro.launch import mesh as mesh_lib
    assert spec_lib.MESH_GEOM["pod"] == {"data": mesh_lib.PROD_DATA,
                                         "model": mesh_lib.PROD_MODEL}
    assert spec_lib.MESH_GEOM["multi_pod"] == {
        "pod": mesh_lib.PROD_PODS, "data": mesh_lib.PROD_DATA,
        "model": mesh_lib.PROD_MODEL}
    assert spec_lib.MESH_GEOM["host4"] == dict(zip(("data", "model"),
                                                   mesh_lib.HOST4))


def test_host4_is_four_clients_and_refuses_fewer_devices(monkeypatch):
    """Mesh host4 is one four-chip host, one client per chip: the spec
    checks its batch against 4 clients, and the mesh is built from exactly
    four devices, never shrunk onto fewer."""
    import jax

    from repro.launch import mesh as mesh_lib
    from repro.launch.session import Session

    assert mesh_lib.HOST4 == (4, 1)
    spec = RunSpec(mesh="host4", global_batch=4, seq_len=32)
    assert spec.n_clients_preview() == 4
    with pytest.raises(ValueError, match="not divisible by the 4 EF"):
        RunSpec(mesh="host4", global_batch=6)
    with pytest.raises(ValueError, match="mesh='host4' has no pod axis"):
        RunSpec(mesh="host4", global_batch=4, hops={"pods": 2})
    two = jax.devices()[:1] * 2
    monkeypatch.setattr(jax, "devices", lambda *a: two)
    with pytest.raises(ValueError, match="needs 4 devices, JAX found 2"):
        mesh_lib.make_host4_mesh()
    with pytest.raises(ValueError, match="needs 4 devices, JAX found 2"):
        Session(spec)


def test_attribute_mirrors_match_method_and_compressor_classes():
    import dataclasses as dc

    from repro.core import compressors as comp_lib
    from repro.core import ef as ef_lib
    for name, cls in ef_lib.REGISTRY.items():
        assert (name in spec_lib.WIRE_IS_NOT_MSG) == (not cls().wire_is_msg), name
        has_eta = "eta" in {f.name for f in dc.fields(cls)}
        assert (name in spec_lib.ETA_METHODS) == has_eta, name
    for name, cls in comp_lib.REGISTRY.items():
        assert (name in spec_lib.NEEDS_RNG) == cls().needs_rng, name


def test_spec_eta_drives_every_eta_bearing_method():
    """A spec that records η must never run a class default instead — incl.
    the abs/ideal variants whose defaults differ from the spec default."""
    from repro.launch import session as session_lib
    for m in sorted(spec_lib.ETA_METHODS):
        method = session_lib.make_method(
            RunSpec(method=m, compressor="identity", eta=0.33))
        assert method.eta == 0.33, m
    # method_kw still overrides
    method = session_lib.make_method(RunSpec(
        method="ef21_sgdm", eta=0.33, method_kw={"eta": 0.5}))
    assert method.eta == 0.5


def test_downlink_plan_preview_matches_real_carriers_over_grid():
    """The jax-free downlink_plan_preview must agree with
    Carrier.plan_down_with_reason for every (compressor × carrier) cell —
    same plan, degradation reasons non-empty in the same cells (the fused
    cell is reason-ful in BOTH: the spec turns it into a construction
    error)."""
    from repro.core import carriers as carrier_lib
    from repro.launch import session as session_lib
    for c in sorted(spec_lib.COMPRESSORS):
        comp = session_lib.make_compressor(RunSpec(compressor=c))
        for ca in sorted(spec_lib.CARRIERS):
            real = carrier_lib.make(ca).plan_down_with_reason(comp)
            mirror = spec_lib.downlink_plan_preview(c, ca)
            assert mirror[0] == real[0], (c, ca, mirror, real)
            assert bool(mirror[1]) == bool(real[1]), (c, ca)
    assert spec_lib.DOWN_CARRIERS == spec_lib.CARRIERS - {"fused"}


def test_downlink_spec_construction_and_factory():
    from repro.core import compressors as comp_lib
    from repro.launch import session as session_lib
    with pytest.raises(ValueError, match="invalid RunSpec"):
        RunSpec(downlink_carrier="fused")
    with pytest.raises(ValueError, match="downlink_ratio"):
        RunSpec(downlink_carrier="quant4", downlink_ratio=0.0)
    # 'dense' downlink = NO downlink machinery: factory returns None
    assert session_lib.make_down_compressor(RunSpec()) is None
    # otherwise the uplink class re-budgeted to downlink_ratio — geometry
    # kw carries over, absolute-budget kw must not shadow the ratio
    spec = RunSpec(downlink_carrier="quant4", downlink_ratio=0.02,
                   compressor_kw={"block": 64, "k_per_block": 9})
    down = session_lib.make_down_compressor(spec)
    assert isinstance(down, comp_lib.BlockTopK)
    assert down.block == 64 and down.ratio == 0.02
    assert down.k_per_block is None


def test_plan_preview_matches_real_carriers_over_grid():
    """The jax-free plan_preview must agree with Carrier.plan_with_reason for
    every (method × compressor × carrier) cell: same plan, and degradation
    reasons are non-empty in exactly the same cells."""
    from repro.core import carriers as carrier_lib
    from repro.launch import session as session_lib
    for m in sorted(spec_lib.METHODS):
        for c in sorted(spec_lib.COMPRESSORS):
            spec = RunSpec(method=m, compressor=c, carrier="dense")
            method = session_lib.make_method(spec)
            for ca in sorted(spec_lib.CARRIERS):
                real = carrier_lib.make(ca).plan_with_reason(method, spec.eta)
                mirror = spec_lib.plan_preview(m, c, ca)
                assert mirror[0] == real[0], (m, c, ca, mirror, real)
                assert bool(mirror[1]) == bool(real[1]), (m, c, ca)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def _supported_grid():
    for m in sorted(spec_lib.METHODS):
        for c in sorted(spec_lib.COMPRESSORS):
            for ca in sorted(spec_lib.CARRIERS):
                if ca == "fused" and spec_lib.plan_preview(m, c, ca)[0] != "fused":
                    continue        # fused misconfig is a construction error
                if ca in ("fused_quant8", "fused_quant4") \
                        and spec_lib.plan_preview(m, c, ca)[0] != "fused_wire":
                    continue        # degraded fused_quant, same hard error
                yield m, c, ca


def test_json_roundtrip_identity_across_grid():
    n = 0
    for m, c, ca in _supported_grid():
        spec = RunSpec(method=m, compressor=c, carrier=ca)
        assert RunSpec.from_json(spec.to_json()) == spec
        n += 1
    assert n > 100      # the grid is real, not vacuously skipped


@pytest.mark.parametrize("arch", cb.ARCH_IDS)
def test_json_roundtrip_every_config_zoo_arch(arch):
    spec = RunSpec(arch=arch, smoke=True, carrier="sparse",
                   compressor="topk", compressor_kw={"k": 7},
                   method_kw={}, ef_state_dtype="bfloat16",
                   mesh="multi_pod", client_granularity="pod",
                   shape="train_4k")
    back = RunSpec.from_json(spec.to_json())
    assert back == spec
    assert back.spec_hash() == spec.spec_hash()


def test_flag_spec_flag_stability():
    cases = [
        RunSpec(),
        RunSpec(arch="gemma2-9b", smoke=True, carrier="quant4",
                compressor="block_topk", ratio=0.01, eta=0.3, lr=0.1,
                clients=4, global_batch=8, seq_len=64, seed=3,
                ckpt_dir="/tmp/x", ckpt_every=50),
        RunSpec(method="ef21_sgdm_abs", compressor="hard_threshold",
                compressor_kw={"lam": 0.05}, method_kw={"gamma": 0.01}),
        RunSpec(shape="prefill_32k", mesh="pod", state_sharding="zero",
                ef_state_dtype="bfloat16", tp_pad_heads=4,
                moe_impl="dense", optimizer="adamw"),
        RunSpec(carrier="sparse", downlink_carrier="quant4",
                downlink_ratio=0.02),
        # --schedule grammar round-trip (compact form)
        RunSpec(groups=spec_lib.parse_schedule_flag(
            "norm|bias=dense,embed=quant4:0.05,*=sparse:0.02")),
        # --schedule JSON round-trip (per-group knobs the grammar can't say)
        RunSpec(groups=[
            {"pattern": "norm|bias", "carrier": "dense"},
            {"pattern": "*", "carrier": "quant4", "ratio": 0.02,
             "downlink_carrier": "quant4", "downlink_ratio": 0.05,
             "ef_state_dtype": "bfloat16"}]),
        # --participation grammar round-trip (mode[:fraction[:seed]])
        RunSpec(participation={"mode": "sampled", "fraction": 0.25,
                               "seed": 7}),
        RunSpec(participation={"mode": "sampled", "fraction": 0.5}),
        # --participation JSON fallback (non-prefix keyset)
        RunSpec(participation={"mode": "sampled", "seed": 3}),
    ]
    for spec in cases:
        assert RunSpec.from_flags(spec.to_flags()) == spec, spec.to_flags()


def test_schedule_flag_grammar_roundtrip():
    """grammar → groups → grammar is identity for grammar-expressible
    schedules, and the parser rejects malformed entries."""
    s = "embed=dense,norm|bias=dense,attn=quant4:0.05@topk,*=sparse:0.02"
    groups = spec_lib.parse_schedule_flag(s)
    assert groups[2] == {"pattern": "attn", "carrier": "quant4",
                         "ratio": 0.05, "compressor": "topk"}
    assert spec_lib.format_schedule_flag(groups) == s
    # JSON fallback for non-grammar keys
    rich = [{"pattern": "*", "carrier": "quant4",
             "downlink_carrier": "quant4"}]
    out = spec_lib.format_schedule_flag(rich)
    assert spec_lib.parse_schedule_flag(out) == rich
    for bad in ("embed", "=dense", "embed=", ""):
        with pytest.raises(ValueError):
            spec_lib.parse_schedule_flag(bad)


def test_groups_validation_fails_at_construction():
    ok = [{"pattern": "norm", "carrier": "dense"}, {"pattern": "*"}]
    RunSpec(groups=ok)
    cases = [
        ([{"pattern": "norm"}], "catch-all"),          # no '*' last
        ([{"pattern": "*"}, {"pattern": "norm"}], "LAST"),
        ([{"pattern": "a"}, {"pattern": "a"}, {"pattern": "*"}],
         "duplicate"),
        ([{"pattern": "a=b"}, {"pattern": "*"}], "reserved"),
        ([{"pattern": "norm|"}, {"pattern": "*"}], "empty"),
        ([{"pattern": "embed|*"}, {"pattern": "*"}], "standalone"),
        ([{"pattern": "*", "carrier": "laser"}], "unknown carrier"),
        ([{"pattern": "*", "compressor": "gzip"}], "unknown compressor"),
        ([{"pattern": "*", "ratio": 0.0}], "ratio"),
        ([{"pattern": "*", "ef_state_dtype": "fp8"}], "ef_state_dtype"),
        ([{"pattern": "*", "downlink_carrier": "fused"}], "downlink"),
        ([{"pattern": "*", "bogus_key": 1}], "unknown keys"),
        # per-group fused misconfig is a construction error
        ([{"pattern": "*", "carrier": "fused", "compressor": "topk"}],
         "UNFUSED"),
    ]
    for groups, match in cases:
        with pytest.raises(ValueError, match=match):
            RunSpec(groups=groups)
    # a valid fused group constructs
    RunSpec(groups=[{"pattern": "*", "carrier": "fused",
                     "compressor": "block_topk"}])


def test_regen_goldens_reproduces_checked_in_fixtures(tmp_path):
    """`python -m repro.launch.spec --regen-goldens` must reproduce the
    checked-in results/specs/*.json byte-for-byte — goldens are generated
    mechanically from spec.GOLDEN_SPECS, never hand-edited."""
    golden_dir = os.path.join(os.path.dirname(__file__), "..", "results",
                              "specs")
    spec_lib.regen_goldens(str(tmp_path))
    disk = sorted(os.path.basename(p) for p in glob.glob(
        os.path.join(golden_dir, "*.json")))
    regen = sorted(os.path.basename(p) for p in glob.glob(
        str(tmp_path / "*.json")))
    assert disk == regen, "GOLDEN_SPECS and results/specs/ disagree on names"
    for name in disk:
        with open(os.path.join(golden_dir, name)) as f:
            want = f.read()
        with open(tmp_path / name) as f:
            got = f.read()
        assert got == want, f"{name} drifted from its GOLDEN_SPECS recipe"


def test_spec_hash_ignores_checkpoint_policy_only():
    a = RunSpec()
    assert dataclasses.replace(a, ckpt_dir="/x", ckpt_every=9).spec_hash() \
        == a.spec_hash()
    assert dataclasses.replace(a, eta=0.4).spec_hash() != a.spec_hash()


def test_spec_hash_survives_additive_schema_evolution():
    """The hash is over the SPARSE form (fields ≠ default), so a spec dict
    written BEFORE a new defaulted field existed hashes identically to one
    written after — additive evolution never invalidates checkpoints."""
    now = RunSpec(arch="gemma2-9b", eta=0.3, ckpt_dir="/x")
    old_dict = {k: v for k, v in now.to_dict().items()
                if k != "heterogeneity"}        # pretend the field is new
    assert RunSpec.from_dict(old_dict).spec_hash() == now.spec_hash()


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

def test_fused_misconfig_fails_at_construction():
    with pytest.raises(ValueError, match="UNFUSED dense plan"):
        RunSpec(carrier="fused", method="ef14_sgd")
    with pytest.raises(ValueError, match="UNFUSED dense plan"):
        RunSpec(carrier="fused", compressor="topk")
    # and the valid fused cell constructs
    assert RunSpec(carrier="fused", method="ef21_sgdm",
                   compressor="block_topk").plan() == ("fused", "")


def test_unknown_names_fail_at_construction():
    for kw in [{"carrier": "laser"}, {"method": "adam"},
               {"compressor": "gzip"}, {"optimizer": "lion"},
               {"arch": "gpt5"}, {"mesh": "torus"}, {"shape": "train_8k"},
               {"heterogeneity": 5.0}, {"eta": 0.0}, {"ratio": 1.5}]:
        with pytest.raises(ValueError, match="invalid RunSpec"):
            RunSpec(**kw)


def test_non_divisible_batch_fails_at_construction():
    with pytest.raises(ValueError, match="not divisible"):
        RunSpec(global_batch=10, clients=8)
    with pytest.raises(ValueError, match="not divisible"):
        # train_4k ships batch 256; 7 does not divide it on the smoke mesh
        RunSpec(shape="train_4k", clients=7)
    with pytest.raises(ValueError, match="not divisible"):
        # the INTERACTIVE train geometry is validated even when a named
        # shape is also set (Session.train would crash mid-step otherwise)
        RunSpec(shape="train_4k", clients=4, global_batch=6)
    RunSpec(global_batch=16, clients=8)     # divisible constructs fine


def test_from_json_rejects_unknown_keys_and_bad_version():
    good = RunSpec().to_dict()
    with pytest.raises(ValueError, match="unknown RunSpec keys"):
        RunSpec.from_dict({**good, "carier": "dense"})
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict({**good, "version": 99})
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict({k: v for k, v in good.items() if k != "version"})
    # the v2 schema bump (downlink fields change what a spec EXECUTES):
    # pre-downlink v1 specs are rejected loudly, never silently upgraded
    assert spec_lib.SCHEMA_VERSION == 5
    v1 = {k: v for k, v in good.items()
          if k not in ("downlink_carrier", "downlink_ratio", "groups",
                       "participation", "hops")}
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict({**v1, "version": 1})


def test_old_specs_auto_upgrade_and_roundtrip():
    """v3 is purely additive over v2 (``groups`` defaults to the uniform
    one-group schedule), v4 over v3 (``participation`` defaults to mode
    'full'), and v5 over v4 (``hops`` defaults to the flat topology) —
    exactly what every older spec always meant — so old dicts upgrade
    mechanically (chaining through the intermediate versions), round-trip
    at the current schema, and hash identically: every old checkpoint
    stays resumable."""
    now = RunSpec(arch="gemma2-9b", carrier="quant4", eta=0.3)
    v4 = {k: v for k, v in now.to_dict().items() if k != "hops"}
    v4["version"] = 4
    up4 = RunSpec.from_dict(v4)
    assert up4 == now and up4.version == 5 and up4.hops == {}
    assert up4.spec_hash() == now.spec_hash()
    v3 = {k: v for k, v in now.to_dict().items()
          if k not in ("participation", "hops")}
    v3["version"] = 3
    up = RunSpec.from_dict(v3)
    assert up == now and up.version == 5 and up.participation == {}
    assert RunSpec.from_json(up.to_json()) == up
    assert up.spec_hash() == now.spec_hash()
    # v2 chains v2 → v3 → v4 → v5
    v2 = {k: v for k, v in now.to_dict().items()
          if k not in ("groups", "participation", "hops")}
    v2["version"] = 2
    up2 = RunSpec.from_dict(v2)
    assert up2 == now and up2.version == 5 and up2.groups == []
    assert up2.spec_hash() == now.spec_hash()
    # an old dict that somehow carries the newer field is NOT silently
    # upgraded (it was written by something claiming an impossible schema)
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict({**now.to_dict(), "version": 2})
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict(
            {**v3, "version": 3,
             "participation": {"mode": "sampled", "fraction": 0.5}})
    with pytest.raises(ValueError, match="version"):
        RunSpec.from_dict({**v4, "version": 4, "hops": {"pods": 2}})


# ---------------------------------------------------------------------------
# golden fixtures: schema drift must be loud
# ---------------------------------------------------------------------------

def test_golden_spec_fixtures_roundtrip_bytewise():
    """Every results/specs/*.json must parse as a valid RunSpec AND
    re-serialize to exactly the bytes on disk. Adding/renaming/removing a
    RunSpec field changes the canonical JSON and fails here — regenerate the
    fixtures deliberately (python -m repro.launch.spec --out ...) and bump
    SCHEMA_VERSION when the change is not purely additive."""
    fixtures = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "results", "specs", "*.json")))
    assert fixtures, "golden spec fixtures missing (results/specs/*.json)"
    for path in fixtures:
        with open(path) as f:
            text = f.read()
        spec = RunSpec.from_json(text)
        assert spec.to_json(indent=1) + "\n" == text, \
            f"schema drift against golden fixture {os.path.basename(path)}"


# ---------------------------------------------------------------------------
# the jax-free guarantee + CLI
# ---------------------------------------------------------------------------

def test_spec_module_importable_without_jax():
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    code = ("import sys; import repro.launch.spec as S; "
            "s = S.RunSpec(arch='gemma2-9b'); s.to_json(); "
            "assert 'jax' not in sys.modules, 'spec import dragged in jax'")
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_no_smoke_negation_overrides_spec_file(tmp_path):
    """Truthy bools in a --spec file must be revocable from the CLI."""
    import argparse
    path = tmp_path / "cell.json"
    path.write_text(RunSpec(smoke=True, arch="gemma2-9b").to_json())
    ap = argparse.ArgumentParser()
    spec_lib.add_flags(ap)
    spec = RunSpec.from_args(ap.parse_args(["--spec", str(path),
                                            "--no-smoke"]))
    assert spec.smoke is False and spec.arch == "gemma2-9b"
    # without the negation, the file's value wins
    spec = RunSpec.from_args(ap.parse_args(["--spec", str(path)]))
    assert spec.smoke is True


def test_explicit_fields_detects_flags_equal_to_defaults():
    import argparse
    ap = argparse.ArgumentParser()
    spec_lib.add_flags(ap)
    # --lr 0.5 equals the default VALUE but was explicitly passed: it must
    # count, so a --resume enforces it against the checkpoint spec
    args = ap.parse_args(["--lr", "0.5", "--ckpt-dir", "/tmp/x"])
    assert spec_lib.explicit_fields(args, ignore=("ckpt_dir",)) == ["lr"]
    assert spec_lib.explicit_fields(ap.parse_args(["--ckpt-dir", "/tmp/x"]),
                                    ignore=("ckpt_dir",)) == []


def test_spec_cli_print_emits_valid_json():
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.spec", "--print",
         "--arch", "olmoe-1b-7b", "--carrier", "quant8", "--eta", "0.25"],
        check=True, env=env, capture_output=True, text=True).stdout
    spec = RunSpec.from_json(out)
    assert spec.arch == "olmoe-1b-7b" and spec.carrier == "quant8"
    assert spec.eta == 0.25
