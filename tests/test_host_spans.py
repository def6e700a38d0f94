"""Host spans of the orchestrator (telemetry/host_spans.py) and the
``murmura.flatten`` device scope (docs/OBSERVABILITY.md "Host spans and
device scopes").

The tables are process-wide and only grow, so every test reads them before
and after what it drives; the ring holds the newest profiler session's
spans, so a test that wants it empty empties it first.
"""

import glob
import json
import re

import jax
import pytest

from murmura_tpu.config import Config
from murmura_tpu.telemetry import host_spans
from murmura_tpu.telemetry.host_spans import span
from murmura_tpu.telemetry.writer import events_of_type
from murmura_tpu.utils.factories import build_network_from_config

ROUND = "murmura.round"
STAGE = "murmura.host.stage"
DISPATCH = "murmura.host.dispatch"
FETCH = "murmura.host.fetch"
RECORD = "murmura.host.record"
CHECKPOINT = "murmura.host.checkpoint"


def _net(**overrides):
    cfg = {
        "experiment": {"name": "spans", "seed": 5, "rounds": 6},
        "topology": {"type": "ring", "num_nodes": 4},
        "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {
            "adapter": "synthetic",
            "params": {"num_samples": 320, "input_dim": 8, "num_classes": 3},
        },
        "model": {
            "factory": "mlp",
            "params": {"input_dim": 8, "hidden_dims": [16], "num_classes": 3},
        },
        "backend": "simulation",
    }
    cfg.update(overrides)
    return build_network_from_config(Config.model_validate(cfg))


def _rise(before, after, table="spans"):
    """name -> (count, seconds) gained between two ``totals()``."""
    return {
        name: (row[0] - before[table].get(name, [0, 0.0])[0],
               row[1] - before[table].get(name, [0, 0.0])[1])
        for name, row in after[table].items()
        if row[0] != before[table].get(name, [0, 0.0])[0]
    }


def _seconds(record):
    return (record["end_ns"] - record["start_ns"]) / 1e9


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A fresh network's first three rounds under a profiler session."""
    trace_dir = tmp_path_factory.mktemp("trace")
    before = host_spans.totals()
    net = _net()
    with jax.profiler.trace(str(trace_dir)):
        net.train(rounds=3)
    return {"net": net, "dir": trace_dir, "records": host_spans.records(),
            "before": before, "after": host_spans.totals()}


def test_without_a_session_the_tables_count_and_the_ring_stays_empty():
    host_spans._ring.clear()
    net = _net()
    before = host_spans.totals()
    net.train(rounds=3)
    counts = {k: v[0] for k, v in _rise(before, host_spans.totals()).items()}
    assert counts == {ROUND: 3, STAGE: 3, DISPATCH: 6, FETCH: 3, RECORD: 3}
    assert host_spans.records() == []
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_under_a_session_the_ring_holds_the_rounds_and_their_children(traced):
    records = traced["records"]
    rounds = [r for r in records if r["name"] == ROUND]
    assert [r["round"] for r in rounds] == [0, 1, 2]
    assert all(r["parent"] is None for r in rounds)
    for parent in rounds:
        children = sorted(
            (r for r in records if r["parent"] == parent["id"]),
            key=lambda r: r["start_ns"],
        )
        assert [c["name"] for c in children] == [
            STAGE, DISPATCH, DISPATCH, FETCH, RECORD
        ]
        assert [c["args"].get("program") for c in children] == [
            None, "step", "eval", None, None
        ]
        assert all(c["round"] == parent["round"] for c in children)
        assert parent["start_ns"] <= children[0]["start_ns"]
        assert children[-1]["end_ns"] <= parent["end_ns"]
        for a, b in zip(children, children[1:]):
            assert a["end_ns"] <= b["start_ns"]  # one thread: no overlap
        self_time = _seconds(parent) - sum(_seconds(c) for c in children)
        assert 0 <= self_time < _seconds(parent)
    assert len(records) == 18  # nothing else was recorded


def test_first_dispatch_table_names_the_dispatches_that_compiled(traced):
    first = _rise(traced["before"], traced["after"], "first_dispatch")
    # The step's and the eval's first dispatch; only a dispatch is given
    # the compile counter (the jitted fold of the key compiles under
    # staging, once in a process, and is not a program of the round's).
    assert first[DISPATCH][0] == 2 and set(first) == {DISPATCH}
    compiled = {
        (r["round"], r["args"].get("program")): r["args"].get("compiled", 0)
        for r in traced["records"] if r["name"] == DISPATCH
    }
    assert compiled[(0, "step")] > 0 and compiled[(0, "eval")] > 0
    assert all(n == 0 for (rnd, _), n in compiled.items() if rnd > 0)
    spans = _rise(traced["before"], traced["after"])
    assert 0 < first[DISPATCH][1] <= spans[DISPATCH][1]
    # Once a call has compiled nothing, a later call adds nothing.
    net = traced["net"]
    settled = host_spans.totals()
    net.train(rounds=2)
    assert _rise(settled, host_spans.totals(), "first_dispatch") == {}
    assert _rise(settled, host_spans.totals())[DISPATCH][0] == 4


def test_the_spans_lie_in_the_host_plane_of_the_profilers_trace(traced):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(traced["dir"] / "plugins/profile/*/*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    events = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("murmura."):
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                )
    assert {ROUND, STAGE, DISPATCH, FETCH, RECORD} <= set(events)
    assert sorted(e[2]["step_num"] for e in events[ROUND]) == [0, 1, 2]
    assert {e[2]["program"] for e in events[DISPATCH]} == {"step", "eval"}
    assert any(e[2].get("compiled", 0) > 0 for e in events[DISPATCH])
    # One clock: every stage event lies inside the round event of its round.
    by_round = {e[2]["step_num"]: e for e in events[ROUND]}
    for start, end, stats in events[STAGE]:
        assert by_round[stats["round"]][0] <= start <= end <= by_round[stats["round"]][1]


def test_round_times_are_the_round_spans_durations(traced):
    rounds = [r for r in traced["records"] if r["name"] == ROUND]
    assert traced["net"].round_times[:3] == [_seconds(r) for r in rounds]


def test_fused_dispatch_is_one_round_span_of_k_rounds(tmp_path):
    net = _net()
    with jax.profiler.trace(str(tmp_path)):
        net.train(rounds=3, rounds_per_dispatch=3)
    records = host_spans.records()
    (chunk,) = [r for r in records if r["name"] == ROUND]
    assert chunk["round"] == 0 and chunk["args"]["rounds"] == 3
    children = [r for r in records if r["parent"] == chunk["id"]]
    assert [c["name"] for c in children] == [STAGE, DISPATCH, FETCH]
    assert children[1]["args"]["program"] == "fused"
    assert children[1]["args"]["compiled"] > 0
    # The chunk's bookkeeping follows its span: round_times excludes it.
    (record,) = [r for r in records if r["name"] == RECORD]
    assert record["parent"] is None and record["start_ns"] >= chunk["end_ns"]
    assert net.round_times == [_seconds(chunk) / 3] * 3


def test_a_raise_in_the_fused_bookkeeping_comes_after_the_times(tmp_path):
    """The chunk's params have advanced by then: its round_times and
    phase_times must already be there for whoever catches and checkpoints."""
    net = _net(telemetry={"enabled": True, "dir": str(tmp_path / "run")})

    def broken(*_args):
        raise RuntimeError("in _record")

    net._record = broken
    with pytest.raises(RuntimeError, match="in _record"):
        net.train(rounds=3, rounds_per_dispatch=3)
    assert net.current_round == 3 and len(net.round_times) == 3
    walls = [e["wall_s"] for e in events_of_type(tmp_path / "run", "phase_times")]
    assert walls == net.round_times


def test_deferred_metrics_fetch_and_record_when_they_are_drained():
    net = _net()
    before = host_spans.totals()
    net.train(rounds=3, defer_metrics=True)
    counts = {k: v[0] for k, v in _rise(before, host_spans.totals()).items()}
    # Three drained fetches and the quiesce of the last round's state.
    assert counts == {ROUND: 3, STAGE: 3, DISPATCH: 6, FETCH: 4, RECORD: 3}
    assert net.history["round"] == [1, 2, 3]


def test_telemetry_and_checkpoint_take_their_times_from_the_spans(tmp_path):
    net = _net(telemetry={"enabled": True, "dir": str(tmp_path / "run")})
    before = host_spans.totals()
    with jax.profiler.trace(str(tmp_path / "trace")):
        net.train(rounds=2, checkpoint_dir=str(tmp_path / "ckpt"))
    rise = _rise(before, host_spans.totals())
    # The phase_times of a round are recorded after its span has closed
    # (they carry its duration): a record span of the round's, under none.
    assert rise[RECORD][0] == 4 and rise[CHECKPOINT][0] == 1
    records = host_spans.records()
    after_round = [r for r in records if r["name"] == RECORD and r["parent"] is None]
    assert [r["round"] for r in after_round] == [0, 1]
    walls = [e["wall_s"] for e in events_of_type(tmp_path / "run", "phase_times")]
    assert walls == net.round_times == [
        _seconds(r) for r in records if r["name"] == ROUND
    ]
    (saved,) = events_of_type(tmp_path / "run", "checkpoint")
    assert saved["duration_s"] == pytest.approx(rise[CHECKPOINT][1])
    restored = _net(telemetry={"enabled": True, "dir": str(tmp_path / "run2")})
    assert restored.restore_checkpoint(str(tmp_path / "ckpt")) == 2
    assert _rise(before, host_spans.totals())[CHECKPOINT][0] == 2


def test_a_new_session_empties_the_ring_and_a_raise_still_closes_the_span(tmp_path):
    with jax.profiler.trace(str(tmp_path / "a")):
        with span("test.outer", round=7, why="first"):
            pass
    assert [r["name"] for r in host_spans.records()][-1] == "test.outer"
    with span("test.untraced"):  # the span that sees the session gone
        pass
    before = host_spans.totals()
    with jax.profiler.trace(str(tmp_path / "b")):
        with pytest.raises(RuntimeError):
            with span("test.outer", round=8) as outer:
                with span("test.inner"):
                    raise RuntimeError("inside")
    records = host_spans.records()
    assert [(r["name"], r["round"]) for r in records] == [
        ("test.inner", None), ("test.outer", 8)
    ]
    assert records[0]["parent"] == records[1]["id"]
    assert outer.seconds == _seconds(records[1]) > 0
    assert _rise(before, host_spans.totals()) == {
        "test.outer": (1, pytest.approx(outer.seconds)),
        "test.inner": (1, pytest.approx(_seconds(records[0]))),
    }
    # Nothing is left open on this thread: the next span has no parent.
    with jax.profiler.trace(str(tmp_path / "c")):
        with span("test.next"):
            pass
    after = host_spans.records()[-1]
    assert after["name"] == "test.next" and after["parent"] is None
    json.dumps(host_spans.records())  # plain data


def test_the_table_as_the_session_began_gives_the_spans_after_it(tmp_path):
    with span("test.loop"):
        pass
    before = host_spans.totals()["spans"]["test.loop"]
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with span("test.loop"):
                pass
    for _ in range(3):
        with span("test.loop"):
            pass
    totals = host_spans.totals()
    assert totals["spans_before_session"]["test.loop"] == before
    ring = [r for r in host_spans.records() if r["name"] == "test.loop"]
    after = totals["spans"]["test.loop"][0] - before[0] - len(ring)
    assert (len(ring), after) == (2, 3)


def _without_source_lines(text):
    """A compiled program's text less its tables of files, functions and
    stack frames (they hold the caller's line too) and the references into
    them; the operations and their ``op_name`` scopes stay."""
    head, _, rest = text.partition("\nFileNames\n")
    body = rest[rest.index("\n\n", rest.index("\nStackFrames\n")):]
    return head + re.sub(r" stack_frame_id=\d+", "", body)


def test_flatten_has_a_scope_and_telemetry_does_not_change_the_program(tmp_path):
    from benchmark import trace_reduce

    off = _net()._step_compiled().as_text()
    on = _net(
        telemetry={"enabled": True, "dir": str(tmp_path / "run")}
    )._step_compiled().as_text()
    assert _without_source_lines(on) == _without_source_lines(off)
    assert 'op_name="' in _without_source_lines(off)
    (ops,) = trace_reduce.scope_map_from_hlo([off]).values()
    scopes = set(ops.values())
    assert {"murmura.flatten", "murmura.train", "murmura.aggregate"} <= scopes
