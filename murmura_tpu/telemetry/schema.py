"""The telemetry run schema: one manifest, one event stream.

Every producer in the framework — the simulation/tpu orchestrator
(core/network.py), the ZMQ Monitor (distributed/monitor.py) and the serve
daemon (serve/daemon.py) — writes observability data through this one
schema instead of private JSON shapes:

    <run_dir>/manifest.json   versioned envelope: schema_version, kind,
                              run_id, config snapshot, summary, counters,
                              history — finalized ATOMICALLY via
                              utils.checkpoint.durable_replace, so a crash
                              mid-run leaves either the previous manifest
                              or the new complete one.
    <run_dir>/events.jsonl    append-only event stream, one JSON object per
                              line.  A crash leaves a valid prefix (each
                              line is flushed whole); readers must tolerate
                              a truncated final line.

Event types (the ``type`` field of each line):

=============== ==========================================================
type            meaning
=============== ==========================================================
``run``         run lifecycle marker (started / resumed / finalized)
``round``       one recorded round: per-node metric arrays (accuracy,
                agg_* rule statistics, ``agg_tap_*`` audit taps) plus the
                host-side ``in_degree`` of the round's effective adjacency
``phase_times`` where a round's wall time went.  ``mode`` records the
                dispatch semantics: ``per_round`` entries are wall round
                times; ``fused`` entries are ``elapsed/k`` amortized over
                the chunk (per-round wall times inside a single device
                dispatch are not observable — core/network.py round_times)
``memory``      per-round device ``memory_stats()`` sample
``checkpoint``  checkpoint write (``duration_s``) or restore
``profile``     profiler trace window started/stopped (``trace_dir``)
``run_resumed`` a durability restore continued this run from a snapshot
                (``round``, ``path``, ``run_id``) — the event stream it
                appends to is the SAME stream the interrupted run wrote
                (durability/snapshot.py; a resumed run never rotates its
                own events to ``*.prev``)
``backend_degraded``
                the dispatch envelope observed a degradation: a
                transient device/transport failure being retried with
                backoff (``reason``, ``retry``, ``delay_s``), or a
                frozen gang member lane
                (``member``, ``reason`` — core/gang.py freeze_member)
``counter``     distributed-backend node counters folded by the Monitor
                (reconnects, send retries/failures, skipped frames,
                checkpoint durations)
``serve``       (v2) one serve-daemon lifecycle transition of this
                tenant: ``event`` in submitted / admitted /
                generation_start / generation_done / evicted / frozen /
                resumed, with ``bucket``/``gen``/``lane`` context — the
                stream-side twin of the durable ledger record, so
                ``murmura report`` and the trace export see the
                lifecycle without reading daemon internals
``extra``       forward-compat: metric keys this version does not know,
                preserved verbatim under ``extra.*`` instead of dropped
=============== ==========================================================

Since v2 every event line also carries ``t``, the host wall-clock unix
timestamp at emit — the anchor the trace-span builder
(telemetry/spans.py) and the offline metrics fold need.  v1 streams
(no ``t``) still render everywhere: readers synthesize a timeline from
the manifest's ``created_unix`` plus cumulative wall time (MUR1703).

Versioning: ``MANIFEST_SCHEMA_VERSION`` bumps on any breaking change to the
manifest envelope or an event's required fields, and every version must
have a migration note in docs/OBSERVABILITY.md ("Schema versions") —
enforced by ``murmura check`` rule MUR401 (analysis/contracts.py).
"""

MANIFEST_SCHEMA_VERSION = 2

MANIFEST_FILE = "manifest.json"
EVENTS_FILE = "events.jsonl"

# The manifest's ``kind``: a training run (CLI / Network / Monitor / serve).
# One value; committed manifests and ``murmura report`` read the key.
KIND_RUN = "run"

# Metric keys the Monitor understands natively; anything else a node
# reports is forwarded under ``extra.*`` (never silently dropped — the
# forward-compat contract an old monitor owes new node events).
MONITOR_KNOWN_KEYS = frozenset({
    "round", "node", "skipped", "compromised",
    "accuracy", "loss", "vacuity", "entropy", "strength",
    "stats", "counters",
})
