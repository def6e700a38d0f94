"""IR-level contracts (analysis/ir.py, MUR200-205) and AOT cost budgets
(analysis/budgets.py, MUR206) — ISSUE 2.

The repo-wide "everything is clean" assertion lives in
test_analysis_contracts.py::TestRepoIsClean (run_check with ir=True); this
file pins the *mechanisms*: jaxpr snapshots for the flagship rules,
negative cases for every MUR2xx rule, and the budget-drift gate.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from murmura_tpu.analysis import budgets, ir
from murmura_tpu.analysis.lint import Finding


def _custom_prog(fn, n=8, dim=32, dtype=jnp.float32, name="custom"):
    """Wrap a bare aggregate-shaped function as a CanonicalProgram."""
    from murmura_tpu.aggregation.base import AggregatorDef

    own = jnp.zeros((n, dim), dtype)
    args = (own, own, jnp.ones((n, n), jnp.float32),
            jnp.asarray(0.0, jnp.float32), {})
    return ir.CanonicalProgram(
        name=name, n=n, dim=dim, circulant=False, fn=fn, args=args,
        arg_shardings=lambda node_s, repl: (node_s, node_s, node_s, repl, {}),
        agg=AggregatorDef(name=name, aggregate=fn),
    )


class TestJaxprSnapshots:
    """MUR200 pinned on the flagship rules: their canonical jaxprs are
    host-callback-free in both exchange modes."""

    @pytest.mark.parametrize("name", ["krum", "fedavg", "ubar"])
    @pytest.mark.parametrize("circulant", [False, True])
    def test_no_host_callbacks(self, name, circulant):
        prog = ir.build_canonical(name, 8, "float32", circulant)
        jaxpr = ir.trace_jaxpr(prog)
        callbacks = [
            e.primitive.name
            for e in ir.iter_eqns(jaxpr)
            if ir.is_host_callback(e.primitive.name)
        ]
        assert callbacks == []
        assert ir._check_callbacks(name, prog, jaxpr) == []

    def test_debug_print_is_a_finding(self):
        def chatty(own, bcast, adj, ridx, state):
            jax.debug.print("round {r}", r=ridx)
            return own, state, {}

        prog = _custom_prog(chatty)
        jaxpr = jax.make_jaxpr(prog.fn)(*prog.args)
        fs = ir._check_callbacks("custom", prog, jaxpr)
        assert [f.rule for f in fs] == ["MUR200"]
        assert "debug_print" in fs[0].message

    def test_pure_callback_is_a_finding(self):
        def hosty(own, bcast, adj, ridx, state):
            out = jax.pure_callback(
                np.asarray, jax.ShapeDtypeStruct(own.shape, own.dtype), own
            )
            return out, state, {}

        prog = _custom_prog(hosty)
        jaxpr = jax.make_jaxpr(prog.fn)(*prog.args)
        fs = ir._check_callbacks("custom", prog, jaxpr)
        assert [f.rule for f in fs] == ["MUR200"]


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a multi-device host")
class TestCollectiveInventory:
    """MUR202 pinned on the flagship rules: the circulant programs lower to
    boundary ppermutes ONLY (the north-star invariant — no all_gather on
    the masked-exchange path), and stray/undeclared collectives fail."""

    @pytest.mark.parametrize("name", ["krum", "fedavg", "ubar"])
    def test_circulant_is_ppermute_only(self, name):
        prog = ir.build_canonical(
            name, 8, "float32", circulant=True, node_axis_sharded=True
        )
        assert ir.collective_inventory(prog) == {"ppermute"}

    def test_dense_krum_inventory_is_declared(self):
        prog = ir.build_canonical(
            "krum", 8, "float32", circulant=False, node_axis_sharded=True
        )
        found = ir.collective_inventory(prog)
        assert found <= {"all_gather", "all_reduce"}
        assert ir._check_collectives("krum", prog) == []

    def test_undeclared_collective_is_a_finding(self):
        # A dense program whose declaration claims circulant-only traffic:
        # the real all_gather must surface as a stray-collective finding
        # (ISSUE 2 acceptance: an undeclared collective fails the check).
        prog = ir.build_canonical(
            "krum", 8, "float32", circulant=False, node_axis_sharded=True
        )
        prog.agg = dataclasses.replace(
            prog.agg, collectives={"dense": {"ppermute"}}
        )
        fs = ir._check_collectives("krum", prog)
        assert [f.rule for f in fs] == ["MUR202"]
        assert "all_gather" in fs[0].message

    def test_missing_declaration_is_a_finding(self):
        prog = ir.build_canonical(
            "fedavg", 8, "float32", circulant=False, node_axis_sharded=True
        )
        prog.agg = dataclasses.replace(prog.agg, collectives=None)
        fs = ir._check_collectives("fedavg", prog)
        assert [f.rule for f in fs] == ["MUR202"]
        assert "declares no collective inventory" in fs[0].message


class TestDtypeDiscipline:
    def test_upcasting_output_is_a_finding(self):
        # The dataflow truth behind MUR006: a rule returning the exchanged
        # [N, P] tensor promoted to f32 under bf16 resident params.
        def upcasting(own, bcast, adj, ridx, state):
            return own.astype(jnp.float32) * 1.0, state, {}

        f32 = _custom_prog(upcasting, dtype=jnp.float32)
        bf16 = _custom_prog(upcasting, dtype=jnp.bfloat16)
        fs = ir._check_dtypes("custom", f32, bf16)
        assert any(
            f.rule == "MUR201" and "bfloat16 params" in f.message for f in fs
        )

    def test_full_size_f32_matmul_operand_is_a_finding(self):
        # f32 *operands* double the memory-bound matmul's HBM reads; f32
        # belongs in accumulation (preferred_element_type).
        def promoting(own, bcast, adj, ridx, state):
            mixed = jnp.dot(adj, bcast.astype(jnp.float32))
            return mixed.astype(own.dtype), state, {}

        f32 = _custom_prog(promoting, dtype=jnp.float32)
        bf16 = _custom_prog(promoting, dtype=jnp.bfloat16)
        fs = ir._check_dtypes("custom", f32, bf16)
        assert any(
            f.rule == "MUR201" and "full-size float32 operand" in f.message
            for f in fs
        )

    def test_state_dtype_drift_is_a_finding(self):
        def drifting(own, bcast, adj, ridx, state):
            return own, {"w": state["w"].astype(jnp.float16)}, {}

        def prog(dtype):
            p = _custom_prog(drifting, dtype=dtype)
            state = {"w": jnp.zeros((8,), jnp.float32)}
            return dataclasses.replace(p, args=p.args[:4] + (state,))

        fs = ir._check_dtypes("custom", prog(jnp.float32), prog(jnp.bfloat16))
        assert any(f.rule == "MUR201" and "'w'" in f.message for f in fs)

    def test_clean_rule_passes(self):
        f32 = ir.build_canonical("krum", 8, "float32")
        bf16 = ir.build_canonical("krum", 8, "bfloat16")
        assert ir._check_dtypes("krum", f32, bf16) == []


class TestShapePolymorphism:
    def test_n_dependent_program_is_a_finding(self):
        # A rule whose *program* (not just its shapes) changes with n —
        # the recompile hazard MUR203 exists for.
        def shapeshifter(own, bcast, adj, ridx, state):
            out = own + bcast
            if own.shape[0] > 8:  # legal Python branch on a static shape
                out = jnp.tanh(out)
            return out, state, {}

        a = _custom_prog(shapeshifter, n=8)
        b = _custom_prog(shapeshifter, n=12)
        fs = ir._check_structure("custom", a, b)
        assert [f.rule for f in fs] == ["MUR203"]
        assert "structurally different" in fs[0].message

    def test_signature_is_stable_across_n(self):
        a = ir.trace_jaxpr(ir.build_canonical("geometric_median", 8, "float32"))
        b = ir.trace_jaxpr(ir.build_canonical("geometric_median", 12, "float32"))
        assert ir.jaxpr_signature(a) == ir.jaxpr_signature(b)


class TestCoverage:
    def test_unregistered_case_and_uncased_rule_flagged(self, monkeypatch):
        from murmura_tpu import aggregation

        monkeypatch.setitem(
            aggregation.AGGREGATORS, "phantom_rule", lambda **kw: None
        )
        monkeypatch.setitem(ir.AGG_CASES, "stale_case", {})
        fs = ir.check_coverage()
        msgs = [f.message for f in fs]
        assert all(f.rule == "MUR205" for f in fs)
        assert any("phantom_rule" in m and "AGG_CASES" in m for m in msgs)
        assert any("stale_case" in m for m in msgs)

    def test_registry_fully_covered(self):
        assert ir.check_coverage() == []


class TestDonation:
    def test_round_step_donation_holds(self):
        # The compiled round step actually aliases every donated buffer
        # (params + carried aggregation state) — MUR204 clean on the repo.
        assert ir.check_donation() == []


class TestBudgets:
    """MUR206: the committed FLOPs/bytes envelope is a perf gate."""

    def test_committed_budgets_hold(self):
        fs, deltas = budgets.check_budgets()
        assert fs == [], "\n".join(f.message for f in fs)
        assert deltas and all(d["within_tolerance"] for d in deltas)

    def test_perturbed_budget_fails(self, tmp_path):
        # ISSUE 2 acceptance: a deliberate +20% FLOPs change to any
        # aggregator fails the check.  Equivalent formulation: the measured
        # program against a budget 20% lower trips the ±10% tolerance.
        committed = budgets.load_budgets()
        key = sorted(committed)[0]
        perturbed = {k: dict(v) for k, v in committed.items()}
        perturbed[key]["flops"] = perturbed[key]["flops"] / 1.20
        p = tmp_path / "BUDGETS.json"
        p.write_text(json.dumps({"budgets": perturbed}))
        fs, deltas = budgets.check_budgets(p)
        drifted = [f for f in fs if f.rule == "MUR206"]
        assert drifted and any(key in f.message for f in drifted)
        assert any(
            f.data and f.data.get("key") == key and f.data["delta"] > 0.10
            for f in drifted
        )

    def test_missing_budget_entry_fails(self, tmp_path):
        committed = budgets.load_budgets()
        trimmed = dict(committed)
        missing = sorted(trimmed)[0]
        del trimmed[missing]
        p = tmp_path / "BUDGETS.json"
        p.write_text(json.dumps({"budgets": trimmed}))
        fs, _ = budgets.check_budgets(p)
        assert any(
            f.rule == "MUR206" and missing in f.message
            and "--update-budgets" in f.message
            for f in fs
        )

    def test_stale_budget_entry_fails(self, tmp_path):
        committed = dict(budgets.load_budgets())
        committed["ghost_rule/n8/d256/float32/dense"] = {
            "flops": 1.0, "bytes": 1.0,
        }
        p = tmp_path / "BUDGETS.json"
        p.write_text(json.dumps({"budgets": committed}))
        fs, _ = budgets.check_budgets(p)
        assert any(
            f.rule == "MUR206" and "ghost_rule" in f.message and "stale" in f.message
            for f in fs
        )

    @pytest.mark.slow  # regen sweep; the committed-budget gate stays tier-1
    def test_update_budgets_roundtrip(self, tmp_path):
        p = budgets.update_budgets(tmp_path / "BUDGETS.json")
        fs, deltas = budgets.check_budgets(p)
        assert fs == []
        assert all(
            d["flops_delta"] == 0.0 and d["bytes_delta"] == 0.0 for d in deltas
        )

    def test_file_tolerance_governs(self, tmp_path):
        # The committed file's "tolerance" field is the knob the file
        # advertises — a widened tolerance must absorb drift the module
        # default would flag.
        committed = budgets.load_budgets()
        key = sorted(committed)[0]
        perturbed = {k: dict(v) for k, v in committed.items()}
        perturbed[key]["flops"] = perturbed[key]["flops"] / 1.20
        p = tmp_path / "BUDGETS.json"
        p.write_text(json.dumps({"tolerance": 0.5, "budgets": perturbed}))
        fs, deltas = budgets.check_budgets(p)
        assert fs == []
        assert all(d["within_tolerance"] for d in deltas)

    def test_update_budgets_refuses_error_cells(self, tmp_path, monkeypatch):
        # A cell that failed to compile must never be committed as a
        # budget — it would later read as an infinite-drift finding.
        monkeypatch.setattr(
            budgets, "measure_all",
            lambda force=False: {"x/n8/d256/float32/dense": {"error": "boom"}},
        )
        with pytest.raises(RuntimeError, match="refusing to rewrite"):
            budgets.update_budgets(tmp_path / "BUDGETS.json")

    def test_factory_line_suppression_applies_to_mur206(
        self, tmp_path, monkeypatch
    ):
        # docs/ANALYSIS.md: `# murmura: ignore[MUR206]` on the factory def
        # line exempts that rule's cells — budget findings must pass
        # through the same suppression filter as the other IR rules.
        fake = tmp_path / "fake_rule.py"
        fake.write_text("def make_fake():  # murmura: ignore[MUR206]\n    pass\n")
        monkeypatch.setattr(ir, "_rule_anchor", lambda name: (str(fake), 1))
        committed = budgets.load_budgets()
        key = sorted(committed)[0]
        perturbed = {k: dict(v) for k, v in committed.items()}
        perturbed[key]["flops"] = perturbed[key]["flops"] / 1.5
        p = tmp_path / "BUDGETS.json"
        p.write_text(json.dumps({"budgets": perturbed}))
        fs, _ = budgets.check_budgets(p)
        assert fs == []


class TestCrashIsolation:
    def test_broken_rule_is_a_finding_not_a_crash(self, monkeypatch):
        # One rule whose aggregate() raises on the canonical shapes must
        # surface as a MUR205 finding; it must not take down the sweep.
        from murmura_tpu import aggregation
        from murmura_tpu.aggregation.base import AggregatorDef

        def make_broken(**kw):
            def aggregate(own, bcast, adj, ridx, state, ctx):
                raise ValueError("needs n >= 1024")

            return AggregatorDef(name="broken", aggregate=aggregate)

        monkeypatch.setattr(aggregation, "AGGREGATORS", {"broken": make_broken})
        monkeypatch.setitem(ir.AGG_CASES, "broken", {})
        monkeypatch.setattr(ir, "_IR_MEMO", None)
        fs = ir.check_ir(force=True)
        assert any(
            f.rule == "MUR205" and "crashed the canonical IR sweep" in f.message
            and "needs n >= 1024" in f.message
            for f in fs
        )


class TestGangContracts:
    """MUR500/MUR501 (ISSUE 5): gang batching is IR-inert — vmapping the
    round program over the seed axis adds no collectives and growing the
    member count within a power-of-two bucket causes no recompile."""

    def test_gang_contracts_hold(self):
        assert ir.check_gang_round() == []

    def test_broken_bucket_mapping_is_a_finding(self, monkeypatch):
        # next_bucket degraded to identity: S=3 and S=4 gangs present
        # different stacked shapes and the growth recompiles — exactly the
        # drift MUR501 exists to catch.
        from murmura_tpu.core import gang as gang_mod

        monkeypatch.setattr(gang_mod, "next_bucket", lambda s: s)
        fs = ir.check_gang_round()
        assert any(
            f.rule == "MUR501" and "recompiled the gang round step" in f.message
            for f in fs
        )

    @pytest.mark.skipif(
        len(jax.devices()) < 2, reason="needs a multi-device host"
    )
    def test_cross_member_communication_is_a_finding(self, monkeypatch):
        # A gang program that mixes members — a roll along the sharded seed
        # axis lowers to a collective-permute absent from the single run —
        # must surface as a stray-collective MUR500 finding.
        from murmura_tpu.parallel import mesh as mesh_mod

        real = mesh_mod.shard_gang_step

        def leaky(vstep, prog, batch, mesh, donate=True):
            def leaky_step(params, agg, keys, adj, comp, ridx, data):
                new_params, new_agg, metrics = vstep(
                    params, agg, keys, adj, comp, ridx, data
                )
                mixed = jax.tree_util.tree_map(
                    lambda l: (0.5 * l + 0.5 * jnp.roll(l, 1, axis=0)).astype(
                        l.dtype
                    ),
                    new_params,
                )
                return mixed, new_agg, metrics

            return real(leaky_step, prog, batch, mesh, donate=donate)

        monkeypatch.setattr(mesh_mod, "shard_gang_step", leaky)
        fs = ir.check_gang_round()
        assert any(
            f.rule == "MUR500" and "seed axis" in f.message for f in fs
        )


class TestJsonOutput:
    """Satellite: `check --json` emits machine-readable JSON lines."""

    def test_format_findings_json_parses(self):
        from murmura_tpu.analysis import format_findings_json

        fs = [
            Finding("MUR206", "a.py", 3, "drift", data={"key": "k", "delta": 0.2}),
            Finding("MUR001", "b.py", 7, "branch"),
        ]
        deltas = [{"key": "k", "flops": 1.0, "within_tolerance": True}]
        lines = format_findings_json(fs, deltas).splitlines()
        recs = [json.loads(line) for line in lines]
        assert [r["kind"] for r in recs] == [
            "finding", "finding", "budget_delta",
        ]
        assert recs[0]["rule"] == "MUR206" and recs[0]["data"]["delta"] == 0.2
        assert recs[0]["name"] == "cost-budget-drift"
        assert recs[2]["key"] == "k"

    def test_cli_json_findings(self, tmp_path):
        from click.testing import CliRunner

        from murmura_tpu.cli import app

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
        )
        result = CliRunner().invoke(
            app, ["check", "--json", "--no-contracts", str(bad)]
        )
        assert result.exit_code == 1
        recs = [json.loads(line) for line in result.output.splitlines() if line]
        assert any(
            r["kind"] == "finding" and r["rule"] == "MUR001" for r in recs
        )

    def test_cli_json_clean_file_exits_zero(self, tmp_path):
        from click.testing import CliRunner

        from murmura_tpu.cli import app

        good = tmp_path / "good.py"
        good.write_text("def f(x):\n    return x\n")
        result = CliRunner().invoke(
            app, ["check", "--json", "--no-contracts", str(good)]
        )
        assert result.exit_code == 0


class TestFaultRoundInventory:
    """MUR303's inventory (ir.collective_names): by op name, with the two
    scalar fault-metric sums licensed by exact count, dtype and shape —
    pinned on synthetic HLO in the installed XLA's spelling."""

    METRICS = (
        "  %all-reduce.2 = (f32[], f32[]) all-reduce(%bitcast.75, %fusion), "
        "channel_id=4, replica_groups=[1,4]<=[4], to_apply=%region\n"
    )
    GATHER = (
        "  %all-gather = f32[4,244]{1,0} all-gather(%concatenate.15), "
        "channel_id=1, dimensions={0}\n"
    )

    def test_unlicensed_inventory_is_by_op_name(self):
        from murmura_tpu.analysis.ir import collective_names

        assert collective_names(self.GATHER + self.METRICS) == {
            "all_gather", "all_reduce"
        }

    def test_exactly_the_two_metric_sums_are_licensed(self):
        from murmura_tpu.analysis.ir import (
            FAULT_METRIC_ALL_REDUCES, all_reduce_results, collective_names,
        )

        txt = self.GATHER + self.METRICS
        assert all_reduce_results(txt) == ("f32[]", "f32[]")
        assert collective_names(txt, FAULT_METRIC_ALL_REDUCES) == {"all_gather"}
        # The same two sums spelled as separate statements.
        split = (
            "  %all-reduce.3 = f32[] all-reduce(%a), channel_id=4\n"
            "  %all-reduce.4 = f32[] all-reduce-start(%b), channel_id=5\n"
        )
        assert collective_names(
            self.GATHER + split, FAULT_METRIC_ALL_REDUCES
        ) == {"all_gather"}

    @pytest.mark.parametrize(
        "extra",
        [
            # a global any-non-finite sentinel sync
            "  %all-reduce.5 = pred[] all-reduce(%any), channel_id=7\n",
            # a third scalar sum, separate or fused into the tuple
            "  %all-reduce.5 = f32[] all-reduce(%x), channel_id=7\n",
            # rows
            "  %all-reduce.7 = f32[4,244]{1,0} all-reduce(%f), channel_id=5\n",
            "  %all-reduce.8 = f32[4]{0} all-reduce-start(%x), channel_id=6\n",
        ],
    )
    def test_any_further_all_reduce_is_a_finding(self, extra):
        from murmura_tpu.analysis.ir import (
            FAULT_METRIC_ALL_REDUCES, collective_names,
        )

        txt = self.GATHER + self.METRICS + extra
        assert "all_reduce" in collective_names(txt, FAULT_METRIC_ALL_REDUCES)

    def test_a_fused_third_scalar_is_a_finding(self):
        from murmura_tpu.analysis.ir import (
            FAULT_METRIC_ALL_REDUCES, collective_names,
        )

        fused = self.METRICS.replace("(f32[], f32[])", "(f32[], f32[], f32[])")
        assert "all_reduce" in collective_names(
            self.GATHER + fused, FAULT_METRIC_ALL_REDUCES
        )


class TestMUR700CompressedPayload:
    """The MUR700 HLO scan (ir.float_exchange_operands): the compressed
    payload — not a dequantized float tensor — is what crosses the
    collectives.  The positive sweep itself runs in check_ir (tier-1 via
    test_analysis_contracts); here the scan's negatives are pinned on
    synthetic HLO so a regression in the regexes cannot go vacuous."""

    def test_flags_full_width_float_collective(self):
        from murmura_tpu.analysis.ir import float_exchange_operands

        txt = (
            "%collective-permute.1 = f32[3,256]{1,0} "
            "collective-permute(f32[3,256]{1,0} %fusion.2), channel_id=1\n"
        )
        offending, lines = float_exchange_operands(txt, 256)
        assert offending == ["f32[3,256]"]
        assert len(lines) == 1

    def test_int8_payload_and_scales_are_clean(self):
        from murmura_tpu.analysis.ir import float_exchange_operands

        txt = (
            "%collective-permute = s8[3,256]{1,0} "
            "collective-permute(s8[3,256]{1,0} %slice.1), channel_id=1\n"
            "%collective-permute.1 = f32[3,4]{1,0} "
            "collective-permute(f32[3,4]{1,0} %slice.2), channel_id=2\n"
        )
        offending, lines = float_exchange_operands(txt, 256)
        assert offending == []
        assert len(lines) == 2
        assert any("s8[" in ln for ln in lines)

    def test_operands_printed_by_name_are_read_from_the_result(self):
        # The installed XLA prints `collective-permute(%slice.1)`: no
        # operand shape inside the parens, so the result shape carries the
        # moved dtype — a scan of the parens alone went vacuous ("no int8
        # collective at all") on a program that does move int8.
        from murmura_tpu.analysis.ir import float_exchange_operands

        txt = (
            "  %collective-permute = s8[1,256]{1,0} collective-permute("
            "%wrapped_slice), channel_id=2, source_target_pairs={{0,1}}\n"
            "  %all-gather.3 = f32[8,256]{1,0} all-gather(%fusion.9), "
            "channel_id=3, dimensions={0}\n"
        )
        offending, lines = float_exchange_operands(txt, 256)
        assert offending == ["f32[8,256]"]
        assert len(lines) == 2
        assert any("s8[" in ln for ln in lines)

    def test_fusion_lines_referencing_collectives_are_ignored(self):
        # The bug the opcode-anchored regex exists for: a fusion CONSUMING
        # %collective-permute.7 as an operand carries full-width float
        # shapes but moves nothing.
        from murmura_tpu.analysis.ir import float_exchange_operands

        txt = (
            "%collective-permute.7 = s8[1,256]{1,0} "
            "collective-permute(s8[1,256]{1,0} %slice.1), channel_id=1\n"
            "%broadcast_divide_fusion = f32[3,256]{1,0} fusion(f32[3,256]"
            "{1,0} %param, f32[1,4]{1,0} %collective-permute.7)\n"
        )
        offending, _ = float_exchange_operands(txt, 256)
        assert offending == []

    def test_quantized_exchange_rules_declare_the_flag(self):
        # The MUR700 sweep's rule set must match what the factories
        # actually build: every QUANTIZED_EXCHANGE_RULES circulant build
        # sets AggregatorDef.quantized_exchange, and the probe/sketch
        # rules do not (they receive the dequantized tensor).
        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.analysis.ir import QUANTIZED_EXCHANGE_RULES

        for name in QUANTIZED_EXCHANGE_RULES:
            agg = build_aggregator(
                name, {"exchange_offsets": [1, 2]}, model_dim=64,
                total_rounds=5,
            )
            assert agg.quantized_exchange, name
            dense = build_aggregator(name, {}, model_dim=64, total_rounds=5)
            assert not dense.quantized_exchange, f"{name} (dense)"
        for name in ("ubar", "sketchguard", "evidential_trust"):
            agg = build_aggregator(
                name, {"exchange_offsets": [1, 2]}, model_dim=64,
                total_rounds=5,
            )
            assert not agg.quantized_exchange, name
