"""The mean of one of the round's counters over the rounds recorded since
the newest profiler session began, scaled: the always-on table
``counters`` of ``murmura_tpu/telemetry/host_spans.py`` (``core/network.py``
adds every recorded round's ``agg_*`` means to it), its rise since
``counters_before_session``.  A program without that table (an older
commit), or a window in which no round recorded the counter, reads None."""

from benchmark.readers.program_span import host_spans


def read(context, counter: str, scale: float = 1.0):
    program = host_spans()
    totals = program.totals() if program is not None else {}
    if "counters" not in totals:
        return None
    now = totals["counters"].get(counter, [0, 0.0])
    before = totals["counters_before_session"].get(counter, [0, 0.0])
    rounds = now[0] - before[0]
    return (now[1] - before[1]) / rounds * scale if rounds > 0 else None
