"""Cross-layer contract checks (analysis/contracts.py, MUR101-103) and the
repo-wide cleanliness gate (`python -m murmura_tpu check murmura_tpu/` as a
tier-1 step — ISSUE 1 acceptance)."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np

import murmura_tpu
from murmura_tpu.analysis import run_check
from murmura_tpu.analysis.contracts import (
    _TOPOLOGY_CASES,
    _coverage_findings,
    _sync_findings,
    check_contracts,
)

PKG = Path(murmura_tpu.__file__).resolve().parent


class TestRepoIsClean:
    """The tier-1 CI gate: every future PR must keep the package clean."""

    def test_full_check_runs_clean(self):
        # ir=True: the jaxpr/HLO contracts and cost budgets (MUR200-206)
        # are part of the gate (ISSUE 2 acceptance) — explicit because
        # passing paths would otherwise default the IR pass off.
        findings = run_check([PKG], ir=True)
        assert findings == [], "\n".join(
            f"{f.path}:{f.line}: {f.rule} {f.message}" for f in findings
        )

    def test_contracts_hold(self):
        assert check_contracts() == []


class TestMUR100ImportFailure:
    def test_broken_registry_import_is_a_finding(self, monkeypatch):
        # A package broken below the contract layer must surface as a
        # greppable finding, not crash the check run with a traceback.
        import sys
        import types

        monkeypatch.setitem(
            sys.modules, "murmura_tpu.attacks",
            types.ModuleType("murmura_tpu.attacks"),
        )
        fs = check_contracts()
        assert [f.rule for f in fs] == ["MUR100"]
        assert "ImportError" in fs[0].message


class TestMUR101RegistrySchemaSync:
    def test_registry_only_name_flagged(self):
        fs = list(_sync_findings(
            "aggregation rule", {"fedavg", "newrule"}, {"fedavg"},
            "reg.py", "schema.py",
        ))
        assert [f.rule for f in fs] == ["MUR101"]
        assert "newrule" in fs[0].message and fs[0].path == "reg.py"

    def test_schema_only_name_flagged(self):
        fs = list(_sync_findings(
            "attack", {"gaussian"}, {"gaussian", "phantom"},
            "reg.py", "schema.py",
        ))
        assert [f.rule for f in fs] == ["MUR101"]
        assert "phantom" in fs[0].message and fs[0].path == "schema.py"

    def test_bijection_is_clean(self):
        assert list(_sync_findings(
            "topology", {"ring", "fully"}, {"ring", "fully"}, "a", "b"
        )) == []


class TestMUR102TestCoverage:
    def test_uncovered_name_flagged(self):
        src = 'agg = build_aggregator("fedavg", {})\n'
        fs = list(_coverage_findings(
            "aggregation rule", {"fedavg", "krum"}, src, "reg.py"
        ))
        assert [f.rule for f in fs] == ["MUR102"]
        assert "krum" in fs[0].message

    def test_single_quotes_count(self):
        src = "agg = build_aggregator('krum', {})\n"
        assert list(_coverage_findings(
            "aggregation rule", {"krum"}, src, "reg.py"
        )) == []

    def test_missing_tests_dir_skips(self):
        # Installed-package mode: no tests/ checkout, no false findings.
        assert list(_coverage_findings("attack", {"gaussian"}, "", "r")) == []

    def test_missing_tests_dir_end_to_end(self, tmp_path):
        fs = check_contracts(tests_dir=tmp_path / "definitely-missing")
        # tests_dir that doesn't exist -> rglob finds nothing -> no MUR102;
        # MUR101/103 still run and must hold on the real repo.
        assert fs == [] or all(f.rule != "MUR102" for f in fs)


class TestMUR103ZeroDiagonal:
    def test_every_topology_type_has_cases(self):
        from murmura_tpu.topology.generators import TOPOLOGY_TYPES

        assert set(_TOPOLOGY_CASES) == set(TOPOLOGY_TYPES)

    def test_uncased_topology_type_flagged(self, monkeypatch):
        # A registered type with no _TOPOLOGY_CASES entry must be a
        # finding from check_contracts itself, not only a test assert —
        # a user runs `murmura check`, not the test suite.
        from murmura_tpu.topology import generators

        monkeypatch.setattr(
            generators, "TOPOLOGY_TYPES",
            generators.TOPOLOGY_TYPES + ("phantom-grid",),
        )
        fs = [f for f in check_contracts() if f.rule == "MUR103"]
        assert any(
            "phantom-grid" in f.message and "_TOPOLOGY_CASES" in f.message
            for f in fs
        )

    def test_self_edges_detected(self, monkeypatch):
        from murmura_tpu.topology import generators

        def bad_topology(topology_type, **kwargs):
            n = kwargs["num_nodes"]
            return SimpleNamespace(adjacency=np.eye(n, dtype=bool))

        monkeypatch.setattr(generators, "create_topology", bad_topology)
        fs = check_contracts()
        assert any(f.rule == "MUR103" for f in fs)
        assert all(
            "self-" in f.message for f in fs if f.rule == "MUR103"
        )

    def test_generator_crash_is_a_finding(self, monkeypatch):
        from murmura_tpu.topology import generators

        def boom(topology_type, **kwargs):
            raise RuntimeError("kaboom")

        monkeypatch.setattr(generators, "create_topology", boom)
        fs = [f for f in check_contracts() if f.rule == "MUR103"]
        assert fs and "kaboom" in fs[0].message
