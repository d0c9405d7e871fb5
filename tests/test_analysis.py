"""repro.analysis: AST rules on seeded fixtures, baseline machinery, the
CLI, and the trace-level (jaxpr) checks.

Fast tier: every rule catches exactly its fixture's ``# VIOLATION`` lines
and nothing else; the repo itself lints clean modulo the baseline; the
jaxpr walkers flag a seeded bf16 accumulation; a warm-started 4-point
C-grid on the engine compiles the ADMM run exactly once.

Slow tier (8 emulated devices, subprocess like tests/test_engine.py): the
mesh-placement check passes — factors land per fac_shardings, the matmat /
solve graphs carry node_partition_spec-conformant pins.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.analysis import baseline as baseline_mod
from repro.analysis import jaxpr_check
from repro.analysis.__main__ import main as cli_main
from repro.analysis.findings import Finding
from repro.analysis.lint import lint_file, lint_paths, repo_root
from repro.analysis.rules import ALL_RULES

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")


def _fixture(name: str):
    """(findings, expected ``# VIOLATION`` line numbers) for one fixture."""
    path = os.path.join(FIXTURES, name)
    findings = lint_file(path, f"tests/analysis_fixtures/{name}",
                         explicit=True)
    with open(path, encoding="utf-8") as fh:
        expected = {i for i, line in enumerate(fh, 1) if "# VIOLATION" in line}
    return findings, expected


# --------------------------------------------------------------------- #
# layer 1: each rule catches its seeded fixture, exactly                 #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name,rule", [
    ("viol_precision.py", "precision-accumulate"),
    ("viol_host_sync.py", "host-sync-in-traced"),
    ("viol_retrace.py", "retrace-knob"),
    ("viol_prng.py", "prng-key-reuse"),
    ("viol_tracer_branch.py", "python-branch-on-tracer"),
])
def test_rule_catches_seeded_fixture(name, rule):
    findings, expected = _fixture(name)
    assert expected, f"{name} has no # VIOLATION markers"
    assert {f.line for f in findings} == expected, \
        [f.render() for f in findings]
    assert all(f.rule == rule for f in findings), \
        [f.rule for f in findings]


def test_clean_fixture_has_no_findings():
    findings, _ = _fixture("clean.py")
    assert findings == [], [f.render() for f in findings]


def test_inline_disable_suppresses():
    findings, _ = _fixture("suppressed.py")
    assert findings == [], [f.render() for f in findings]
    # the same line WITHOUT the comment is caught (the disable is load-bearing)
    src_path = os.path.join(FIXTURES, "suppressed.py")
    with open(src_path, encoding="utf-8") as fh:
        assert "lint: disable=precision-accumulate" in fh.read()


def test_rule_registry_is_complete():
    names = {r.NAME for r in ALL_RULES}
    assert names == {"precision-accumulate", "host-sync-in-traced",
                     "retrace-knob", "prng-key-reuse",
                     "python-branch-on-tracer"}
    for r in ALL_RULES:
        assert r.DESCRIPTION and r.SCOPE


def test_repo_lints_clean_modulo_baseline():
    """The whole source tree is clean after this change; the baseline
    carries any justified exceptions (none today)."""
    findings = lint_paths(base=repo_root())
    entries = baseline_mod.load()
    new, _suppressed, stale = baseline_mod.partition(findings, entries)
    assert new == [], [f.render() for f in new]
    assert stale == [], stale


# --------------------------------------------------------------------- #
# baseline file machinery                                               #
# --------------------------------------------------------------------- #
def test_baseline_roundtrip_and_partition(tmp_path):
    f1 = Finding("precision-accumulate", "src/repro/x.py", 3, "m",
                 'c = jnp.einsum("ij,jk->ik", a, b)')
    f2 = Finding("prng-key-reuse", "src/repro/y.py", 9, "m",
                 "b = jax.random.normal(key, (4,))")
    path = str(tmp_path / "baseline.toml")
    entries = baseline_mod.from_findings([f1], reason="bench-only path")
    baseline_mod.dump(entries, path)
    loaded = baseline_mod.load(path)
    assert loaded == entries
    new, suppressed, stale = baseline_mod.partition([f1, f2], loaded)
    assert new == [f2] and suppressed == [f1] and stale == []
    # a stale entry (nothing matches it any more) is reported
    _, _, stale = baseline_mod.partition([f2], loaded)
    assert len(stale) == 1


def test_baseline_requires_reason(tmp_path):
    path = str(tmp_path / "baseline.toml")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write('[[suppress]]\nrule = "r"\npath = "p"\n'
                 'line_content = "x = 1"\n')
    with pytest.raises(ValueError, match="reason"):
        baseline_mod.load(path)


# --------------------------------------------------------------------- #
# CLI                                                                   #
# --------------------------------------------------------------------- #
def test_cli_exit_codes(capsys):
    bad = os.path.join(FIXTURES, "viol_precision.py")
    clean = os.path.join(FIXTURES, "clean.py")
    assert cli_main([clean]) == 0
    assert "clean" in capsys.readouterr().out
    assert cli_main([bad]) == 1
    out = capsys.readouterr().out
    assert "precision-accumulate" in out and "2 finding(s)" in out
    assert cli_main(["--rules"]) == 0
    assert "prng-key-reuse" in capsys.readouterr().out


def test_cli_write_baseline(tmp_path, capsys):
    bad = os.path.join(FIXTURES, "viol_precision.py")
    path = str(tmp_path / "baseline.toml")
    assert cli_main([bad, "--write-baseline", "--baseline", path]) == 0
    capsys.readouterr()
    # the generated baseline suppresses exactly those findings
    assert cli_main([bad, "--baseline", path]) == 0
    assert "2 suppressed" in capsys.readouterr().out


# --------------------------------------------------------------------- #
# layer 2: jaxpr walkers + the recompile guard                          #
# --------------------------------------------------------------------- #
def test_dtype_downcast_walker_flags_bf16_accumulation():
    a = jnp.zeros((8, 8), jnp.bfloat16)

    def unprotected(x, y):
        return x @ y                      # bf16 accumulator

    def protected(x, y):
        return jax.lax.dot(x, y, preferred_element_type=jnp.float32)

    assert jaxpr_check.dtype_downcasts(jax.make_jaxpr(unprotected)(a, a))
    assert not jaxpr_check.dtype_downcasts(jax.make_jaxpr(protected)(a, a))


def test_dtype_downcast_walker_recurses_into_scan():
    a = jnp.zeros((4, 8, 8), jnp.bfloat16)

    def run(xs):
        def body(c, x):
            return c @ x, ()              # bf16 accumulation inside scan
        return jax.lax.scan(body, xs[0], xs[1:])

    assert jaxpr_check.dtype_downcasts(jax.make_jaxpr(run)(a))


def test_host_callback_walker():
    def with_cb(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jaxpr = jax.make_jaxpr(with_cb)(jnp.zeros(3))
    assert jaxpr_check.host_callbacks(jaxpr)
    assert not jaxpr_check.host_callbacks(
        jax.make_jaxpr(lambda x: x * 2)(jnp.zeros(3)))


def test_abstract_signature_scalar_semantics():
    sig = jaxpr_check.abstract_signature
    # traced-scalar convention: identical signatures across the sweep
    assert (sig(jnp.asarray(0.5, jnp.float32))
            == sig(jnp.asarray(4.0, jnp.float32)))
    # a mixed int/float Python grid changes the weak dtype => retrace
    assert sig(1) != sig(1.0)


def test_engine_c_sweep_compiles_once():
    """The recompile-count guard: 4 grid points, ONE compile (PR 5)."""
    findings = jaxpr_check.check_recompile_engine()
    assert findings == [], [f.render() for f in findings]


def test_two_same_shape_engines_c_sweeps_trace_once():
    """Two fresh engines of one shape, one sweep each: one trace of the
    shared ADMM program in all, the first engine's."""
    before = obs.total("hss.admm.traces")
    findings = jaxpr_check.check_recompile_engine(engines=2, rows=512)
    assert findings == [], [f.render() for f in findings]
    assert obs.total("hss.admm.traces") - before == 1


# --------------------------------------------------------------------- #
# slow tier: mesh placement under 8 emulated devices                    #
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_mesh_placement_check_passes_on_8_devices():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        from repro.analysis import jaxpr_check
        findings = jaxpr_check.check_mesh_placement()
        for f in findings:
            print(f.render())
        assert not findings
        print("MESH_PLACEMENT_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "MESH_PLACEMENT_OK" in r.stdout, r.stdout + r.stderr
