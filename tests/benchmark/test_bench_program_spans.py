"""The readers of the program's own host spans and of the device time under
no scope: against a hand-made ring and context, and through a traced run of
the tiny CPU cell."""

import types

import pytest

from benchmark import harness
from benchmark.cells import Cell
from benchmark.readers import program_span, unscoped_device_ms
from benchmark.trace_reduce import Reduction

from bench_tiny import make_root

MS = 1_000_000  # nanoseconds


def _record(id, name, start_ms, end_ms, parent=None, round=0, **args):
    return {"id": id, "name": name, "start_ns": start_ms * MS,
            "end_ns": end_ms * MS, "parent": parent, "round": round, "args": args}


def _ring():
    """Two rounds of 10 ms: stage 1, dispatch 2 + 1, fetch 4, record 0.5."""
    out = []
    for r, t0 in ((0, 0), (1, 20)):
        top = 10 * r + 1
        out += [
            _record(top + 1, "murmura.host.stage", t0, t0 + 1, top, r),
            _record(top + 2, "murmura.host.dispatch", t0 + 1, t0 + 3, top, r,
                    program="step"),
            _record(top + 3, "murmura.host.dispatch", t0 + 3, t0 + 4, top, r,
                    program="eval"),
            _record(top + 4, "murmura.host.fetch", t0 + 4, t0 + 8, top, r),
            _record(top + 5, "murmura.host.record", t0 + 8, t0 + 8.5, top, r),
            _record(top, "murmura.round", t0, t0 + 10, None, r),
        ]
    return out


def _program(records, first_dispatch=None, spans=None, before=None):
    return types.SimpleNamespace(
        records=lambda: records,
        totals=lambda: {"spans": spans or {},
                        "first_dispatch": first_dispatch or {},
                        "spans_before_session": before or {}},
    )


def _tables():
    """The always-on table around ``_ring()``'s session: five warm-up rounds
    before it, the ring's two, then four untraced rounds of 8 ms: stage 0.5,
    dispatch 1.5, fetch 5, record 0.25 (0.75 the loop's own)."""
    per_round = {"murmura.round": (1, 8.0), "murmura.host.stage": (1, 0.5),
                 "murmura.host.dispatch": (2, 1.5), "murmura.host.fetch": (1, 5.0),
                 "murmura.host.record": (1, 0.25)}
    before = {name: [5 * n, 5 * 30e-3] for name, (n, _) in per_round.items()}
    spans = {}
    for name, (n, ms) in per_round.items():
        in_ring = [r for r in _ring() if r["name"] == name]
        spans[name] = [
            before[name][0] + len(in_ring) + 4 * n,
            before[name][1] + sum(program_span.seconds(r) for r in in_ring)
            + 4 * ms * 1e-3,
        ]
    return spans, before


@pytest.mark.parametrize("args,want", [
    ({"spans": ["murmura.host.stage"]}, 1.0),
    ({"spans": ["murmura.host.dispatch"]}, 3.0),
    ({"spans": ["murmura.host.record"]}, 0.5),
    ({"spans": ["murmura.host.stage", "murmura.host.fetch"]}, 5.0),
    ({"self_of_span": "murmura.round"}, 1.5),
])
def test_per_round_from_the_ring(monkeypatch, capsys, args, want):
    monkeypatch.setattr(program_span, "host_spans", lambda: _program(_ring()))
    assert program_span.read({"traced_rounds": 2}, **args) == pytest.approx(want)
    assert "2 rounds in the ring, 2 traced" in capsys.readouterr().out


@pytest.mark.parametrize("args,want", [
    ({"spans": ["murmura.host.stage"]}, 0.5),
    ({"spans": ["murmura.host.dispatch"]}, 1.5),
    ({"spans": ["murmura.host.record"]}, 0.25),
    ({"self_of_span": "murmura.round",
      "less": ["murmura.host.stage", "murmura.host.dispatch",
               "murmura.host.fetch", "murmura.host.record"]}, 0.75),
])
def test_per_round_after_the_session_from_the_table(monkeypatch, capsys, args, want):
    spans, before = _tables()
    monkeypatch.setattr(program_span, "host_spans",
                        lambda: _program(_ring(), spans=spans, before=before))
    got = program_span.read({"traced_rounds": 2}, untraced=True, **args)
    assert got == pytest.approx(want)
    assert "4 rounds after the session" in capsys.readouterr().out


def test_spans_outside_the_round_are_not_taken_from_its_self_time(monkeypatch):
    """Rounds dispatched ahead: the fetch and the record come after the
    round spans have closed, so the round's own time is its seconds less
    the stage and the dispatches alone."""
    ring = []
    for r in _ring():
        if r["name"] in ("murmura.host.fetch", "murmura.host.record"):
            r = {**r, "parent": None}
        elif r["name"] == "murmura.round":  # 4 ms: stage 1, dispatch 3
            r = {**r, "end_ns": r["start_ns"] + 4 * MS}
        ring.append(r)
    spans, before = _tables()
    spans["murmura.round"][1] -= 2 * 6e-3 + 4 * 5.25e-3  # rounds of 4 and 2.75 ms
    monkeypatch.setattr(program_span, "host_spans",
                        lambda: _program(ring, spans=spans, before=before))
    args = {"self_of_span": "murmura.round",
            "less": ["murmura.host.stage", "murmura.host.dispatch",
                     "murmura.host.fetch", "murmura.host.record"]}
    assert program_span.read({"traced_rounds": 2}, **{"self_of_span": "murmura.round"}) \
        == pytest.approx(0.0, abs=1e-9)
    got = program_span.read({"traced_rounds": 2}, untraced=True, **args)
    assert got == pytest.approx(2.75 - 0.5 - 1.5)


def test_a_window_that_ends_with_the_session_has_no_untraced_round(monkeypatch):
    spans, before = _tables()
    for name, row in spans.items():  # take the four rounds after it away
        n = 2 if name == "murmura.host.dispatch" else 1
        row[0] -= 4 * n
    monkeypatch.setattr(program_span, "host_spans",
                        lambda: _program(_ring(), spans=spans, before=before))
    assert program_span.read({"traced_rounds": 2}, untraced=True,
                             spans=["murmura.host.stage"]) is None
    # Nor has a ring of another round count anything to scale by.
    assert program_span.read({"traced_rounds": 3}, untraced=True,
                             spans=["murmura.host.stage"]) is None


def test_untraced_rounds_of_fused_chunks_count_their_rounds(monkeypatch):
    ring = [_record(1, "murmura.round", 0, 30, None, 0, rounds=3)]
    spans = {"murmura.round": [4, 30e-3 + 2 * 24e-3],
             "murmura.host.stage": [3, 2 * 3e-3]}
    before = {"murmura.round": [1, 0.0], "murmura.host.stage": [1, 0.0]}
    monkeypatch.setattr(program_span, "host_spans",
                        lambda: _program(ring, spans=spans, before=before))
    assert program_span.read(
        {"traced_rounds": 3}, untraced=True, spans=["murmura.host.stage"]
    ) == pytest.approx(1.0)  # 6 ms over two chunks of three rounds


def test_self_time_is_the_duration_less_the_children():
    ring = _ring()
    top = next(r for r in ring if r["name"] == "murmura.round")
    assert program_span.self_of(top, ring) == pytest.approx(1.5e-3)
    leaf = next(r for r in ring if r["name"] == "murmura.host.fetch")
    assert program_span.self_of(leaf, ring) == pytest.approx(4e-3)


@pytest.mark.parametrize("traced", [0, 1, 3])
def test_another_round_count_reads_nothing(monkeypatch, capsys, traced):
    monkeypatch.setattr(program_span, "host_spans", lambda: _program(_ring()))
    got = program_span.read({"traced_rounds": traced},
                            spans=["murmura.host.stage"])
    assert got is None
    assert f"2 rounds in the ring, {traced} traced" in capsys.readouterr().out


def test_a_fused_chunk_counts_its_rounds(monkeypatch):
    ring = [_record(1, "murmura.round", 0, 30, None, 0, rounds=3),
            _record(2, "murmura.host.stage", 0, 3, 1, 0)]
    monkeypatch.setattr(program_span, "host_spans", lambda: _program(ring))
    assert program_span.ring_rounds(ring) == 3
    assert program_span.read(
        {"traced_rounds": 3}, spans=["murmura.host.stage"]
    ) == pytest.approx(1.0)


def test_per_run_from_a_table(monkeypatch):
    table = {"murmura.host.dispatch": [2, 4.5], "murmura.round": [2, 6.0]}
    monkeypatch.setattr(program_span, "host_spans",
                        lambda: _program([], first_dispatch=table))
    read = lambda spans: program_span.read(  # noqa: E731
        {"traced_rounds": 8}, table="first_dispatch", spans=spans)
    assert read(["murmura.host.dispatch"]) == 4.5
    assert read(["murmura.host.stage"]) is None  # nothing compiled there


def test_a_program_without_the_module_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_span, "host_spans", lambda: None)
    assert program_span.read({"traced_rounds": 2}, spans=["murmura.round"]) is None
    assert program_span.read({}, table="first_dispatch", spans=["x"]) is None


def test_the_module_is_found_in_this_program():
    from murmura_tpu.telemetry import host_spans

    assert program_span.host_spans() is host_spans


def test_unscoped_device_time_per_round():
    trace = Reduction(devices=1, unscoped_s=0.016)
    assert unscoped_device_ms.read(
        {"trace": trace, "traced_rounds": 8}
    ) == pytest.approx(2.0)
    assert unscoped_device_ms.read({"trace": trace, "traced_rounds": 0}) is None
    assert unscoped_device_ms.read(
        {"trace": Reduction(), "traced_rounds": 8}
    ) is None  # no device plane: nothing to read, never 0


def test_traced_run_reports_the_programs_host_spans(tmp_path, monkeypatch):
    monkeypatch.setattr(
        harness, "load_peaks",
        lambda kind: {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    )
    root = make_root(tmp_path)
    r = harness.run_cell(Cell("tiny_sketchguard", root=root), seed=2**31 + 26,
                         seconds=8.0, trace=True)  # a traced call of two rounds, then one more
    assert r["correct"] is True
    metrics = r["metrics"]
    hosts = ("host_stage_ms", "host_dispatch_ms", "host_record_ms",
             "host_round_self_ms", "host_stage_untraced_ms",
             "host_dispatch_untraced_ms", "host_record_untraced_ms",
             "host_round_self_untraced_ms")
    for name in hosts + ("setup_first_dispatch_s",):
        assert metrics[name]["value"] > 0, name
    assert all(metrics[name]["unit"] == "ms" for name in hosts)
    assert metrics["setup_first_dispatch_s"]["unit"] == "s"
    # No device plane in a CPU trace: the device readers find nothing.
    assert "flatten_scope_ms" not in metrics
    assert "unscoped_device_ms" not in metrics
