"""Tests for the determinism-contract static analyzer.

Two families:
* seeded-violation fixtures under ``tests/analysis_fixtures/`` — the
  checker MUST flag every one of them (a checker that stops firing is
  worse than no checker);
* the real repo sources MUST come out clean modulo the justified
  allowlist (the full jaxpr-tracing prover run is ``slow``; the default
  tier exercises the source passes and the comparison machinery).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.analysis import check, hazards, kernel_lint, taint
from repro.analysis.jaxpr_utils import compare_canonical, dce
from repro.analysis.report import (
    AllowEntry,
    AllowlistError,
    Finding,
    Report,
    _parse_toml_allow,
    load_allowlist,
)

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "analysis_fixtures"


def _fixture(name: str) -> Path:
    p = FIXTURES / name
    assert p.exists(), p
    return p


def _rules(findings) -> set:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# seeded violations: every fixture must be flagged


def test_fixture_adaptive_block_flagged():
    fs = kernel_lint.run_pass(REPO, files=[_fixture("fixture_adaptive_block.py")])
    assert "adaptive-block-size" in _rules(fs)
    assert "grid-reduction-extent" in _rules(fs)
    assert all(f.where.startswith("tests/analysis_fixtures/") for f in fs)


def test_fixture_bf16_accum_flagged():
    fs = kernel_lint.run_pass(REPO, files=[_fixture("fixture_bf16_accum.py")])
    accum = [f for f in fs if f.rule == "accum-dtype"]
    # both the VMEM scratch and the in-kernel preferred_element_type
    assert len(accum) == 2, fs
    assert {f.where.split("::")[1] for f in accum} == {"gemm_bf16_accum", "_kernel"}


def test_fixture_prefetch_grid_spec_flagged():
    """Specs inside ``grid_spec=`` are linted; a DMA-only staging buffer
    keeps its input dtype without an accum-dtype finding."""
    fs = kernel_lint.run_pass(
        REPO, files=[_fixture("fixture_prefetch_grid_spec.py")]
    )
    rules = sorted(f.rule for f in fs)
    assert rules == ["accum-dtype", "grid-reduction-extent"], fs


def test_fixture_splitk_commit_flagged():
    fs = taint.scan_files(
        [_fixture("fixture_splitk_commit.py")], REPO, expected_roots=frozenset()
    )
    assert "fast-schedule-on-commit-path" in _rules(fs)
    assert "unresolved-schedule" in _rules(fs)
    # the threaded-parameter helper is fine: its binding is checked upstream
    assert not any("_project" == f.where.split("::")[-1] for f in fs)


def test_fixture_scatter_hazard_flagged():
    path = _fixture("fixture_scatter_hazard.py")
    spec = importlib.util.spec_from_file_location("fx_scatter", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    closed, batch = mod.analysis_trace()
    fs = hazards.scan_trace(dce(closed), batch, arch="fixture", kind="scatter")
    assert "scatter-add-overlap" in _rules(fs), fs
    flagged = [f for f in fs if f.rule == "scatter-add-overlap"]
    assert any("fixture_scatter_hazard" in f.where for f in flagged)


def test_fixture_mode_cli_exits_nonzero():
    rc = check.main(["--paths", str(_fixture("fixture_splitk_commit.py"))])
    assert rc == 1


def test_fixture_paged_runtime_extent_flagged():
    """A block-table walk run as a GRID axis: the reduction extent is the
    runtime table length (``tables.shape[1]``), not a literal — the
    shape-adaptive schedule the real paged kernel's fori_loop avoids."""
    path = _fixture("fixture_paged_runtime_extent.py")
    fs = kernel_lint.run_pass(REPO, files=[path])
    extent = [f for f in fs if f.rule == "grid-reduction-extent"]
    assert extent, fs
    assert all("fixture_paged_runtime_extent" in f.where for f in extent)
    # and the CLI treats it as a blocking finding
    assert check.main(["--paths", str(path)]) == 1


# ---------------------------------------------------------------------------
# the real repo must be clean (source passes; trace passes are slow-tier)


def test_repo_taint_clean():
    assert taint.run_pass(REPO) == []


def test_repo_kernel_lint_clean_modulo_allowlist():
    report = Report(
        allowlist=load_allowlist(REPO / "src/repro/analysis/allowlist.toml")
    )
    report.extend(kernel_lint.run_pass(REPO))
    assert report.ok, report.format()
    # the rmsnorm row-tile clamp is the one expected suppression
    assert [f.rule for f in report.suppressed] == ["adaptive-block-size"]


def test_commit_roots_annotated():
    # deleting a '# det: commit-path' annotation must be a finding, so
    # sabotage one root in a copied tree and re-run
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        root = Path(td)
        for rel in ("src/repro/core", "src/repro/serving", "src/repro/models"):
            shutil.copytree(REPO / rel, root / rel)
        vf = root / "src/repro/core/verifier.py"
        vf.write_text(vf.read_text().replace("# det: commit-path\n", "", 1))
        fs = taint.run_pass(root)
        assert "unannotated-commit-root" in _rules(fs)


# ---------------------------------------------------------------------------
# allowlist mechanics


def test_allowlist_requires_justification():
    with pytest.raises(AllowlistError, match="justification"):
        _parse_toml_allow(
            '[[allow]]\npass = "hazards"\nrule = "x"\nwhere = "y"\n', "t"
        )
    with pytest.raises(AllowlistError, match="empty justification"):
        _parse_toml_allow(
            '[[allow]]\npass = "hazards"\nrule = "x"\nwhere = "y"\n'
            'justification = "  "\n',
            "t",
        )


def test_allowlist_stale_entry_flagged():
    report = Report(
        allowlist=[
            AllowEntry(
                pass_name="hazards", rule="gone", where="nowhere.py::f",
                justification="used to matter",
            )
        ]
    )
    report.finish(check_stale=True)
    assert [f.rule for f in report.findings] == ["stale-entry"]


def test_allowlist_suppression_is_exact_key_match():
    entry = AllowEntry(
        pass_name="kernel_lint", rule="accum-dtype", where="a.py::f",
        justification="j",
    )
    report = Report(allowlist=[entry])
    report.add(Finding("kernel_lint", "accum-dtype", "a.py::f", "m"))
    report.add(Finding("kernel_lint", "accum-dtype", "a.py::g", "m"))
    assert len(report.suppressed) == 1 and len(report.findings) == 1


def test_repo_allowlist_loads_and_is_justified():
    entries = load_allowlist(REPO / "src/repro/analysis/allowlist.toml")
    assert len(entries) >= 5
    assert all(len(e.justification) > 40 for e in entries)


# ---------------------------------------------------------------------------
# canonical-form comparison machinery (fast unit coverage of the prover)


def test_compare_affine_batch_dims_match():
    a = "x = foo[dim=104] (13, 8) out\ny = bar 1.5"
    b = "x = foo[dim=136] (17, 8) out\ny = bar 1.5"
    # 104 = 8*13, 136 = 8*17 (k=8, c=0); 8 = const (same both sides)
    assert compare_canonical(a, b, 13, 17) is None


def test_compare_affine_with_offset():
    # mamba conv-pad style: C + 3
    assert compare_canonical("pad 16", "pad 20", 13, 17) is None
    # rwkv shift style: C - 1
    assert compare_canonical("slice 12", "slice 16", 13, 17) is None


def test_compare_rejects_schedule_change():
    # split-K chunk 64 -> 128 would need c = -144, far beyond the affine
    # tolerance: a schedule difference cannot masquerade as a batch dim
    assert compare_canonical("chunk 64", "chunk 128", 13, 17) is not None


def test_compare_rejects_negative_slope():
    # integers that shrink as batch grows are never batch dims
    assert compare_canonical("v 17", "v 13", 13, 17) is not None


def test_compare_rejects_float_drift():
    # float literals must be bit-identical (e.g. 1/T scaling constants)
    assert compare_canonical("scale 0.0048", "scale 0.0036", 13, 17) is not None


def test_compare_reports_first_divergence():
    a = "same\nleft only line\nsame2"
    b = "same\nright only words\nsame2"
    idx, la, lb = compare_canonical(a, b, 13, 17)
    assert idx == 1 and "left" in la and "right" in lb


# ---------------------------------------------------------------------------
# the full prover (traces every arch class; minutes of work -> slow tier)


@pytest.mark.slow
def test_prover_certifies_all_arch_classes():
    from repro.analysis import invariance

    findings, certs, _ = invariance.run_pass()
    assert findings == [], [f.format() for f in findings]
    assert set(certs) == set(invariance.ARCH_CLASSES)
    for cert in certs.values():
        for kind_cert in cert["kinds"].values():
            assert kind_cert["invariant"] is True
            assert len(kind_cert["batches"]) >= 3
        assert cert["negative_control"]["schedules_differ"] is True
