"""The readers of the routed experts' parts and of the round's counters
(``leaf_chain_self_ms``, ``round_counter``): against a hand-made reduction
and tables, against the program's own tables, and through a traced run of
the tiny Moonlight cell of ``test_bench_moonlight.py``."""

import json
import types

import jax
import pytest

from benchmark import harness
from benchmark.cells import Cell
from benchmark.readers import leaf_chain_self_ms, leaf_scope_ms, round_counter
from benchmark.trace_reduce import Reduction

from bench_tiny import BENCH
from test_bench_moonlight import make_root

EXPERTS = "murmura.train/murmura.experts"
PARTS = ("train_experts_self_ops_ms", "train_experts_pairs_ops_ms",
         "train_experts_rows_ops_ms")


def _trace():
    """Two rounds: 8 ms under the loop's label alone, 20 under the experts'
    alone, 6 / 2 under ``murmura.pairs`` / ``murmura.rows``, 10 of
    attention, and eval's experts, which no training metric reads."""
    return Reduction(devices=1, leaf_s={
        "murmura.train": 0.008, EXPERTS: 0.020,
        EXPERTS + "/murmura.pairs": 0.006, EXPERTS + "/murmura.rows": 0.002,
        "murmura.train/murmura.attention": 0.010, "murmura.eval/murmura.experts": 1.0,
    })


def _spec(name):
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


@pytest.mark.parametrize("chain,want", [
    ("murmura.train", 4.0), (EXPERTS, 10.0), (EXPERTS + "/murmura.pairs", 3.0),
    ("murmura.train/murmura.ffn", None),
])
def test_the_exact_chain_and_none_below_it(chain, want):
    context = {"trace": _trace(), "traced_rounds": 2}
    got = leaf_chain_self_ms.read(context, chain=chain)
    assert got == (None if want is None else pytest.approx(want))
    assert leaf_chain_self_ms.read({"trace": _trace(), "traced_rounds": 0}, chain=chain) is None
    assert leaf_chain_self_ms.read({"trace": Reduction(), "traced_rounds": 2},
                                   chain=chain) is None


def test_the_parts_are_the_experts_metric():
    """By their files: products + pairs + rows = what
    ``train_experts_ops_ms`` reads (10 + 3 + 1 ms a round)."""
    context = {"trace": _trace(), "traced_rounds": 2}
    readers = {"leaf_scope_ms": leaf_scope_ms, "leaf_chain_self_ms": leaf_chain_self_ms}
    parts = [readers[_spec(n)["reader"]].read(context, **_spec(n)["args"]) for n in PARTS]
    whole = _spec("train_experts_ops_ms")
    assert parts == pytest.approx([10.0, 3.0, 1.0])
    assert sum(parts) == pytest.approx(leaf_scope_ms.read(context, **whole["args"]))
    assert _spec("train_unlabelled_ops_ms")["args"] == {"chain": "murmura.train"}


def _program(counters=None, before=None):
    tables = {"spans": {}, "first_dispatch": {}, "spans_before_session": {}}
    if counters is not None:
        tables.update(counters=counters, counters_before_session=before or {})
    return types.SimpleNamespace(totals=lambda: tables)


def test_the_rise_since_the_session_over_its_rounds(monkeypatch):
    """Five rounds before the session at 0.9, then four at 0.6, 0.6, 0.65
    and 0.65: 62.5 %."""
    counters = {"agg_moe.padding_share": [9, 4.5 + 2.5], "agg_other": [9, 1.0]}
    before = {"agg_moe.padding_share": [5, 4.5]}
    monkeypatch.setattr(round_counter, "host_spans", lambda: _program(counters, before))
    assert round_counter.read({}, counter="agg_moe.padding_share",
                              scale=100) == pytest.approx(62.5)
    assert round_counter.read({}, counter="agg_other") == pytest.approx(1.0 / 9)
    assert round_counter.read({}, counter="agg_absent") is None


def test_no_round_since_the_session_reads_nothing(monkeypatch):
    counters = {"agg_moe.padding_share": [5, 4.5]}
    monkeypatch.setattr(round_counter, "host_spans", lambda: _program(counters, counters))
    assert round_counter.read({}, counter="agg_moe.padding_share") is None


@pytest.mark.parametrize("program", [None, _program()], ids=["no_module", "no_table"])
def test_a_program_without_the_table_reads_nothing(monkeypatch, program):
    monkeypatch.setattr(round_counter, "host_spans", lambda: program)
    assert round_counter.read({}, counter="agg_moe.padding_share", scale=100) is None


def test_the_programs_own_table(tmp_path):
    from murmura_tpu.telemetry import host_spans

    host_spans.add_counters({"agg_test.share": 0.1})
    with jax.profiler.trace(str(tmp_path)):
        with host_spans.span("test.session"):
            pass
        host_spans.add_counters({"agg_test.share": 0.5})
    host_spans.add_counters({"agg_test.share": 0.7})
    assert round_counter.read({}, counter="agg_test.share", scale=100) == pytest.approx(60.0)


def test_a_traced_tiny_decoder_run_reports_its_padding(tmp_path, monkeypatch):
    """The tiny cell traced on the CPU: the counter is the program's, so it
    is read; the trace has no device plane, so the device metrics are not."""
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    root = make_root(tmp_path)
    r = harness.run_cell(Cell("tiny_moonlight_fedavg", root=root), seed=2**31 + 39,
                         seconds=0.5, trace=True)
    assert r["correct"] is True, r["checks"]
    padding = r["metrics"]["experts_padding_pct"]
    # 16 positions x 2 of 8 experts, 4 held, in tiles of 512 over a floor of
    # 1,536 rows: nearly every row multiplied is padding.
    assert padding["unit"] == "%" and 97.0 < padding["value"] < 100.0
    for name in ("train_unlabelled_ops_ms",) + PARTS:
        assert name not in r["metrics"]
