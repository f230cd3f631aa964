"""Static contract analyzer (DESIGN.md §14): the analyzer must catch
every known-bad fixture with its stable rule id, stay silent on the live
repo, and the engine's ``donate_argnums`` contracts must hold end to end
through the HLO parser (the donation tier-1 test — a silently dropped
donation doubles pool HBM with no error)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.analysis import findings as flib
from repro.analysis import hlo as hlo_lib
from repro.analysis import jitlint, style, vmem
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving.engine import ContinuousServingEngine

ROOT = flib.repo_root()
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "analysis")


def _fixture_src(name: str) -> tuple[str, str]:
    path = os.path.join(FIXTURES, name)
    with open(path) as fh:
        return f"tests/fixtures/analysis/{name}", fh.read()


def _scan_fixture(name: str, opts=None) -> list:
    """jitlint over ONE fixture injected as an extra source (fixtures are
    excluded from disk scans so the repo itself stays clean)."""
    rel, src = _fixture_src(name)
    return jitlint.scan(ROOT, subdirs=(), opts=opts,
                        extra_sources=[(rel, src)])


def _load_fixture_module(name: str):
    path = os.path.join(FIXTURES, name)
    spec = importlib.util.spec_from_file_location(name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Known-bad fixtures: exactly one finding each, with the right rule id.
# ---------------------------------------------------------------------------


def test_fixture_hidden_host_sync():
    got = _scan_fixture("bad_host_sync.py")
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "SYNC001"
    assert got[0].symbol == "_leaf"          # one call deep from the scan
    assert ".item()" in got[0].message


def test_fixture_unseeded_rng():
    got = _scan_fixture("bad_unseeded_rng.py")
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "RNG001"
    assert "default_rng" in got[0].message


def test_fixture_tag_collision():
    got = _scan_fixture("bad_tag_collision.py")
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "TAG001"
    assert got[0].symbol == "SPEC_TAG_BETA"
    assert "SPEC_TAG_ALPHA" in got[0].message


def test_fixture_wall_clock():
    opts = jitlint.Options(clock_paths=("tests/fixtures/analysis/",),
                           exclude_parts=("__pycache__",))
    got = _scan_fixture("bad_wall_clock.py", opts=opts)
    # Exactly one: the time.time() *call*. The clock=time.perf_counter
    # default is a reference — the injectable surface — and must pass.
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "CLK001"
    assert got[0].symbol == "time.time"


def test_fixture_vmem_over_budget():
    mod = _load_fixture_module("bad_vmem_kernel.py")
    records = []
    with vmem.record_pallas_calls(records, "bad_vmem_kernel"):
        jax.eval_shape(mod.oversized_copy,
                       jax.ShapeDtypeStruct((4096, 4096), jnp.float32))
    assert len(records) == 1
    fp = records[0]
    assert fp.name == "bad_vmem_kernel._kernel"
    # 2 × (64 MiB in + 64 MiB out): way over the 16 MiB budget.
    assert fp.total_bytes == 4 * 4096 * 4096 * 4
    got = vmem.check({fp.name: fp}, baseline={fp.name: fp.total_bytes})
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "VMEM001"


def test_fixture_hlo_collective():
    with open(os.path.join(FIXTURES, "bad_collective.hlo")) as fh:
        module = hlo_lib.parse_hlo(fh.read())
    got = hlo_lib.check_no_collectives(module, "bad_collective")
    # Exactly one: the async all-gather-start (a substring grep keyed on
    # "all-gather" alone used to miss renamed/async forms; one keyed on
    # "all-reduce" would false-positive on the decoy *fusion name* here).
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "HLO001"
    assert got[0].symbol == "all-gather"
    assert "all-reduce" not in {i.opcode for i in module.instructions}


def test_fixture_hlo_host_callback():
    with open(os.path.join(FIXTURES, "bad_callback.hlo")) as fh:
        module = hlo_lib.parse_hlo(fh.read())
    got = hlo_lib.check_no_host_ops(module, "bad_callback")
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "HLO002"
    assert got[0].symbol == "xla_python_cpu_callback"


def test_dropped_donation_is_caught():
    """DON001 end to end on real compiled output: donate an input that
    cannot alias any output (an (8,) f32 in, a scalar out: no output has
    its byte size) — XLA silently drops the donation, and the analyzer
    must say so. (Same-size outputs do not work as a control: XLA aliases
    an f32[8] input to an s32[8] output.)"""
    def bad(x):
        return jnp.sum(x)

    lowered = jax.jit(bad, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    module = hlo_lib.parse_hlo(lowered.compile().as_text())
    assert module.donated_params() == set()
    got = hlo_lib.check_donation(module, 1, "bad_donation")
    assert len(got) == 1, [f.render() for f in got]
    assert got[0].rule == "DON001"

    # Positive control: a donatable same-shape/dtype update aliases.
    lowered = jax.jit(lambda x: x + 1, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    module = hlo_lib.parse_hlo(lowered.compile().as_text())
    assert module.donated_params() == {(0, ())}
    assert hlo_lib.check_donation(module, 1, "good_donation") == []


# ---------------------------------------------------------------------------
# HLO parser unit coverage.
# ---------------------------------------------------------------------------


def test_parse_hlo_table_and_alias_map():
    text = "\n".join([
        "HloModule jit_step, input_output_alias={ {0}: (1, {}, "
        "must-alias), {1,0}: (2, {0}, may-alias) }",
        "",
        "ENTRY %main (p0: s32[4], p1: f32[8], p2: (f32[2], f32[2]))"
        " -> (f32[8], (f32[2], f32[2])) {",
        "  %p0 = s32[4]{0} parameter(0)",
        "  %p1 = f32[8]{0} parameter(1), sharding={devices=[4]0,1,2,3}",
        "  %p2 = (f32[2]{0}, f32[2]{0}) parameter(2)",
        "  %add.1 = f32[8]{0} add(f32[8]{0} %p1, f32[8]{0} %p1)",
        "  ROOT %tup = (f32[8]{0}, (f32[2]{0}, f32[2]{0})) "
        "tuple(f32[8]{0} %add.1, (f32[2]{0}, f32[2]{0}) %p2)",
        "}",
    ])
    module = hlo_lib.parse_hlo(text)
    assert module.name == "jit_step"
    assert {"parameter", "add", "tuple"} <= module.opcodes()
    assert module.input_output_alias == {
        (0,): (1, (), "must-alias"),
        (1, 0): (2, (0,), "may-alias"),
    }
    assert module.donated_params() == {(1, ()), (2, (0,))}
    (p1,) = [i for i in module.instructions if i.name == "p1"]
    assert p1.sharding == "{devices=[4]0,1,2,3}"
    (tup,) = [i for i in module.instructions if i.name == "tup"]
    assert tup.shape.startswith("(f32[8]")      # tuple shape survives


def test_base_opcode_normalization():
    assert hlo_lib.base_opcode("all-gather-start") == "all-gather"
    assert hlo_lib.base_opcode("all-reduce-done") == "all-reduce"
    assert hlo_lib.base_opcode("collective-permute-start") == \
        "collective-permute"
    assert hlo_lib.base_opcode("dynamic-update-slice") == \
        "dynamic-update-slice"
    assert hlo_lib.is_collective("reduce-scatter-start")
    assert not hlo_lib.is_collective("reduce")
    assert not hlo_lib.is_collective("fusion")


def test_baseline_roundtrip_and_staleness():
    sups = [flib.Suppression(rule="SYNC001", path="a.py", reason="r"),
            flib.Suppression(rule="RNG001", path="b.py", reason="r",
                             symbol="f")]
    f1 = flib.Finding(rule="SYNC001", path="a.py", line=3, message="m")
    f2 = flib.Finding(rule="RNG001", path="b.py", line=9, message="m",
                      symbol="g")          # symbol mismatch -> unsuppressed
    un, sup, stale = flib.apply_baseline([f1, f2], sups)
    assert un == [f2]
    assert sup == [f1]
    assert stale == [sups[1]]


# ---------------------------------------------------------------------------
# Clean-repo gates: the live tree has zero unsuppressed findings.
# ---------------------------------------------------------------------------


def test_repo_jitlint_clean():
    findings = jitlint.scan(ROOT)
    sups = flib.load_baseline(flib.DEFAULT_BASELINE)
    unsuppressed, _sup, _stale = flib.apply_baseline(findings, sups)
    assert not unsuppressed, "\n".join(f.render() for f in unsuppressed)


def test_repo_style_clean():
    files = jitlint.iter_python_files(
        ROOT, ("src", "benchmarks", "tests", "tools"), jitlint.Options())
    findings = style.scan_files(files)
    assert not findings, "\n".join(f.render() for f in findings)


def test_repo_vmem_within_budget_and_baseline():
    findings = vmem.check()
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# Donation tier-1 contract: every donate_argnums leaf actually aliases.
# ---------------------------------------------------------------------------


@pytest.mark.serving
@pytest.mark.parametrize("kind", ["slay", "softmax"])
def test_engine_donation_contract(kind):
    """Compile macro_decode / write_slot / reset_slot at engine shapes and
    assert via ``input_output_alias`` that *every* donated pool leaf is
    honoured — plus the no-host-op contract on the same modules."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousServingEngine(
        cfg, params, make_host_mesh(),
        serving=ServingConfig(num_slots=2, max_len=32, prefill_chunk=4,
                              macro_ticks=2))
    lowerings = eng.contract_lowerings()
    assert set(lowerings) == {"macro_decode", "write_slot", "reset_slot"}
    for name, (text, expected) in lowerings.items():
        module = hlo_lib.parse_hlo(text)
        assert expected > 0
        bad = (hlo_lib.check_donation(module, expected, name)
               + hlo_lib.check_no_host_ops(module, name))
        assert not bad, "\n".join(f.render() for f in bad)
        assert len(module.donated_params()) == expected, name
