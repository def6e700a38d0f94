"""Host time the program's own spans recorded
(``murmura_tpu/telemetry/host_spans.py``; the orchestrator opens them in
``core/network.py``), four ways:

- ``spans``: milliseconds a round of the traced window under the named
  spans, from the ring of full records, which fills only while a profiler
  session is active and so holds exactly the traced rounds;
- ``self_of``: the same for one span's self time, its duration less the
  part its child spans cover (children do not overlap: one thread);
- ``untraced``: either of the two, per round of the window's rounds that
  ran after the profiler session had closed, from the always-on table
  ``spans``: its rise since the session began less the ring.  The same
  loop with no Python tracer on it: what the traced reading costs shows as
  the difference.  A span's self time is there its seconds less those of
  the spans named in ``less`` as far as the ring shows them under it: all
  of a fetch under a fetch every round, none of it where the rounds are
  dispatched ahead and fetched after their round spans have closed;
- ``table`` with ``spans``: seconds of the whole run from an always-on
  table (``first_dispatch``: the dispatches during which a program was
  compiled or loaded).

A program without that module (an older commit) has nothing to read: None.
The ring's round count (``murmura.round`` records, a fused chunk counting
its ``rounds``) must equal the harness's ``traced_rounds``; both are
printed, and a difference reads None.  A window that ended with the session
has no untraced round: None as well.
"""


def host_spans():
    try:
        from murmura_tpu.telemetry import host_spans as module
    except ImportError:
        return None
    return module


def seconds(record):
    return (record["end_ns"] - record["start_ns"]) / 1e9


def self_of(record, records):
    """A span's duration less its children's."""
    return seconds(record) - sum(
        seconds(r) for r in records if r["parent"] == record["id"]
    )


def share_under(records, name, parent_name):
    """The share of the ring's seconds under spans ``name`` that lies in
    children of a ``parent_name`` span."""
    parents = {r["id"] for r in records if r["name"] == parent_name}
    rows = [r for r in records if r["name"] == name]
    total = sum(seconds(r) for r in rows)
    under = sum(seconds(r) for r in rows if r["parent"] in parents)
    return under / total if total > 0 else 0.0


def ring_rounds(records):
    return sum(
        int(r["args"].get("rounds", 1)) for r in records
        if r["name"] == "murmura.round"
    )


def after_session(totals, records):
    """name -> [count, seconds] of the spans that closed after the newest
    profiler session: the rise of ``spans`` since it began, less the ring."""
    before = totals["spans_before_session"]
    out = {name: [row[0] - before.get(name, [0, 0.0])[0],
                  row[1] - before.get(name, [0, 0.0])[1]]
           for name, row in totals["spans"].items()}
    for r in records:
        out[r["name"]][0] -= 1
        out[r["name"]][1] -= seconds(r)
    return out


def read(context, spans=(), self_of_span=None, table=None, untraced=False,
         less=()):
    program = host_spans()
    if program is None:
        return None
    if table is not None:
        rows = program.totals()[table]
        found = [rows[name][1] for name in spans if name in rows]
        return sum(found) if found else None
    records, traced = program.records(), context["traced_rounds"]
    in_ring = ring_rounds(records)
    print(f"[bench] program spans: {in_ring} rounds in the ring, {traced} traced",
          flush=True)
    if not traced or in_ring != traced:
        return None
    if not untraced:
        total = sum(seconds(r) for r in records if r["name"] in spans) + sum(
            self_of(r, records) for r in records if r["name"] == self_of_span
        )
        return total / traced * 1e3
    after = after_session(program.totals(), records)
    none = [0, 0.0]
    # A round span is as many rounds as in the ring (a fused chunk: several).
    round_spans = sum(1 for r in records if r["name"] == "murmura.round")
    rounds = after.get("murmura.round", none)[0] * traced // round_spans
    print(f"[bench] program spans: {rounds} rounds after the session", flush=True)
    if rounds <= 0:
        return None
    total = sum(after.get(name, none)[1] for name in spans)
    if self_of_span is not None:
        total += after.get(self_of_span, none)[1] - sum(
            after.get(name, none)[1] * share_under(records, name, self_of_span)
            for name in less
        )
    return total / rounds * 1e3
