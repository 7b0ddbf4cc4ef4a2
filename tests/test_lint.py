"""The jit/Pallas/allocator lint: planted-hazard fixtures + repo gate.

Three layers of pinning:

* the fixture files in ``tests/fixtures/lint/`` plant one violation per
  rule family (plus deliberate look-alikes that must NOT fire: an unwind
  path that releases, a ``list.extend`` inside a try) — each expected
  finding is asserted by rule and file,
* the pragma file suppresses every planted hazard and must come back
  clean,
* ``src/repro`` itself must lint clean — this is the same gate
  ``scripts/ci.sh`` runs, kept here so a plain pytest run catches a
  violation before CI does — while the kernels under ``src/repro``
  prove the checks resolve real call sites rather than skipping them
  (``_probe`` counts resolved jit/pallas sites).
"""
import ast
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import lint as L

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "lint"
SRC = REPO / "src" / "repro"


def _rules(findings):
    return sorted(f.rule for f in findings)


def test_fixture_retrace_findings():
    got = _rules(L.lint_file(FIXTURES / "bad_retrace.py"))
    assert got == ["jit-static-missing", "jit-static-mutable-default",
                   "jit-traced-str-default"]


def test_fixture_pallas_findings():
    findings = L.lint_file(FIXTURES / "bad_pallas.py")
    assert _rules(findings) == [
        "pallas-index-map-arity", "pallas-kernel-arity",
        "pallas-operand-arity", "pallas-vmem-scratch"]
    sev = {f.rule: f.severity for f in findings}
    assert sev["pallas-vmem-scratch"] == "warning"
    assert all(s == "error" for r, s in sev.items()
               if r != "pallas-vmem-scratch")


def test_fixture_alloc_findings():
    findings = L.lint_file(FIXTURES / "bad_alloc.py")
    assert _rules(findings) == ["alloc-try-no-release"]
    # the leak is in leaky(); disciplined() and untried() are clean
    assert findings[0].line < 18


def test_fixture_mesh_findings():
    findings = L.lint_file(FIXTURES / "bad_mesh.py")
    assert _rules(findings) == ["constrain-unknown-axis",
                                "jit-mesh-closure"]
    by_rule = {f.rule: f for f in findings}
    # the closure finding names the offending global; the axis finding
    # names the typo'd axis — and the known/non-literal calls are silent
    assert "'SHARDING'" in by_rule["jit-mesh-closure"].message
    assert "'heds'" in by_rule["constrain-unknown-axis"].message


def test_known_axes_registry_is_live():
    """The lint's axis registry is the real RULE_PRESETS vocabulary,
    not a drifting copy: every axis the serve presets map is known."""
    from repro.dist.sharding import KNOWN_LOGICAL_AXES, RULE_PRESETS
    assert L.KNOWN_LOGICAL_AXES == KNOWN_LOGICAL_AXES
    for rules in RULE_PRESETS.values():
        for axis, _ in rules.items():
            assert axis in L.KNOWN_LOGICAL_AXES


def test_pragma_suppresses_everything():
    assert L.lint_file(FIXTURES / "pragma_ok.py") == []


def test_src_repro_is_clean():
    findings, n_files = L.lint_paths([str(SRC)])
    assert n_files > 50  # the walk actually covered the package
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule}: {f.message}" for f in findings)


def test_checks_resolve_real_sites():
    """Zero findings must mean 'checked and clean', not 'skipped':
    the repo's jit wrappers and every pallas_call kernel resolve."""
    n_jit = n_pallas = n_index_maps = 0
    for path in sorted(SRC.rglob("*.py")):
        src = path.read_text(encoding="utf-8")
        fl = L._FileLinter(str(path), ast.parse(src), src)
        n_jit += sum(1 for _ in fl._jit_sites())
        for node in ast.walk(fl.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                n_pallas += 1
                assert fl._resolve_kernel(node.args[0]) is not None, path
                _, _, in_specs, out_specs, _, _ = \
                    fl._grid_spec_fields(node)
                n_index_maps += len(fl._index_maps(in_specs)
                                    + fl._index_maps(out_specs))
    assert n_jit >= 6        # ops.py wrappers + dryrun prefill_step
    assert n_pallas == 5     # one per kernel module
    assert n_index_maps >= 19  # the paged kernel copies K/V itself


def test_cli_json_and_exit_codes(tmp_path):
    env_src = str(REPO / "src")
    clean = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--check",
         str(SRC / "analysis")],
        capture_output=True, text=True, cwd=str(REPO),
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"})
    assert clean.returncode == 0, clean.stderr
    doc = json.loads(clean.stdout)
    assert doc["findings"] == [] and doc["files_checked"] >= 3

    dirty = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--check",
         "--compact", str(FIXTURES)],
        capture_output=True, text=True, cwd=str(REPO),
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"})
    assert dirty.returncode == 1
    doc = json.loads(dirty.stdout)
    assert doc["n_errors"] > 0 and doc["n_warnings"] > 0
    # machine-readable contract: every finding carries the full schema
    for f in doc["findings"]:
        assert set(f) == {"rule", "severity", "path", "line", "col",
                          "message"}
        assert f["rule"] in L.RULES


def test_syntax_error_is_reported(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def oops(:\n", encoding="utf-8")
    findings = L.lint_file(bad)
    assert [f.rule for f in findings] == ["syntax-error"]


# ---------------------------------------------------------------------------
# PR 10: interprocedural dataflow rule families
# ---------------------------------------------------------------------------
def test_fixture_taint_findings():
    """Every planted leak fires determinism-taint at its line, and the
    OK blocks (timer→metric, seeded rng, sorted() laundering, the PR 8
    step-counted retune) stay silent."""
    findings = L.lint_file(FIXTURES / "bad_taint.py")
    assert {f.rule for f in findings} == {"determinism-taint"}
    assert sorted(f.line for f in findings) == [23, 29, 42, 43, 53, 61, 70]
    # the OK section starts at the timed_metrics block — nothing after it
    assert max(f.line for f in findings) < 73


def test_fixture_taint_messages_name_source_and_sink():
    by_line = {f.line: f.message for f in
               L.lint_file(FIXTURES / "bad_taint.py")}
    assert "select_victim" in by_line[23]
    assert "time.time" in by_line[23]
    assert "PRNGKey" in by_line[29]
    # the interprocedural chain names the intermediate helpers
    assert "default_rng" in by_line[42] or "_derive" in by_line[42]
    assert "set" in by_line[61].lower()  # set-iteration-order source


def test_fixture_trace_capture_findings():
    findings = L.lint_file(FIXTURES / "bad_trace_capture.py")
    got = sorted((f.line, f.rule) for f in findings)
    assert got == [(27, "jit-trace-capture"),
                   (33, "jit-host-effect"),
                   (34, "jit-host-effect"),
                   (34, "jit-trace-capture"),
                   (58, "jit-trace-capture")]
    # line 58 is the PR 9 regression shape: a bound method of a shared
    # model jitted under an ambient mesh — the message must point at it
    pr9 = next(f for f in findings if f.line == 58)
    assert "bound method" in pr9.message
    assert "decode_step" in pr9.message


def test_fixture_cache_lock_findings():
    findings = L.lint_file(FIXTURES / "bad_cache_lock.py")
    assert {f.rule for f in findings} == {"cache-lock-discipline"}
    assert sorted(f.line for f in findings) == [24, 26, 29]
    # the interprocedural part: _write's findings name the unlocked
    # entry point that reaches them
    assert all("put()" in f.message for f in findings)


def test_output_is_byte_identical_across_runs():
    """Determinism contract: two independent lints of the same tree
    produce byte-identical JSON (sorted findings, sorted keys)."""
    def run_once():
        findings, n = L.lint_paths([str(FIXTURES)])
        return json.dumps(
            {"files_checked": n,
             "findings": [f.to_dict() for f in findings]},
            sort_keys=True)

    assert run_once() == run_once()


def test_cli_github_format(tmp_path):
    env_src = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--check",
         "--format", "github", str(FIXTURES)],
        capture_output=True, text=True, cwd=str(REPO),
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 1  # exit codes unchanged by the format
    lines = proc.stdout.strip().splitlines()
    ann = [ln for ln in lines if ln.startswith("::error")
           or ln.startswith("::warning")]
    assert ann, proc.stdout
    for ln in ann:
        assert re.match(
            r"^::(error|warning) file=[^,]+,line=\d+,col=\d+,"
            r"title=[a-z-]+::", ln), ln
    assert lines[-1].startswith("::notice title=lint::checked ")
    # byte-identical across runs, like the JSON format
    again = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--check",
         "--format", "github", str(FIXTURES)],
        capture_output=True, text=True, cwd=str(REPO),
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"})
    assert again.stdout == proc.stdout
