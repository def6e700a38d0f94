"""Telemetry writer/reader: the one path every producer emits through.

``TelemetryWriter`` owns a run directory holding the versioned manifest and
the append-only JSONL event stream (schema.py).  Design constraints:

- **Crash-safe**: events append line-at-a-time (a crash loses at most the
  in-flight line); the manifest is only ever replaced atomically via
  :func:`murmura_tpu.utils.checkpoint.durable_replace` — the same fsync'd
  temp-file + rename + directory-fsync path the checkpoints use, so a
  half-written manifest is impossible.
- **Resumable**: reopening an existing run directory appends to the event
  stream (the checkpoint/restore path keeps one stream per run) and marks
  the manifest ``resumed``.
- **jax-free at import**: a producer may construct a writer before it
  decides which backend it runs on; only :meth:`memory_event` touches
  jax, lazily.
"""

import json
import os
import time
import uuid
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from murmura_tpu.telemetry.schema import (
    EVENTS_FILE,
    KIND_RUN,
    MANIFEST_FILE,
    MANIFEST_SCHEMA_VERSION,
)
from murmura_tpu.utils.checkpoint import durable_replace


def _jsonable(value: Any) -> Any:
    """Recursively convert numpy/jax leaves to plain JSON types."""
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if isinstance(value, np.ndarray):
        return _jsonable(value.tolist())
    if isinstance(value, (np.floating, np.integer, np.bool_)):
        return value.item()
    if hasattr(value, "tolist") and not isinstance(value, (str, bytes)):
        # jax arrays (and anything array-like) without importing jax here.
        return _jsonable(np.asarray(value).tolist())
    # Non-finite floats stay floats: Python's json emits/accepts NaN and
    # Infinity literals, so manifest histories round-trip with full
    # fidelity (a partial-flush NaN row must not come back as a string).
    return value


class TelemetryWriter:
    """Manifest + event-stream writer for one run directory.

    Args:
        run_dir: directory to create/append; one run per directory.
        run_id: stable id across resumes; generated when omitted.
        config: optional validated Config — snapshotted (``model_dump``)
            into the manifest so a report is self-describing.
        record_taps: host-side toggle for per-node ``agg_tap_*`` arrays in
            round events.  Purely a recording decision — the compiled round
            program is identical either way (MUR402, analysis/ir.py).
        resume: the caller is CONTINUING a prior run in this directory
            (checkpoint restore, crash recovery): append to the existing
            event stream, keep its run_id/counters, mark the manifest
            ``resumed``.  False (default): a pre-existing stream is a
            STALE run — it is rotated to ``*.prev`` (one generation kept)
            so re-running an experiment into the same deterministic dir
            never double-counts events in ``murmura report``.
        memory_stats: sample per-round device memory into ``memory`` events.
        profile_dir / profile_start_round / profile_rounds: the profiler
            trace window ``murmura run --profile`` captures
            (core/network.py drives start/stop at round boundaries).
    """

    def __init__(
        self,
        run_dir,
        *,
        run_id: Optional[str] = None,
        config=None,
        record_taps: bool = True,
        phase_times: bool = True,
        memory_stats: bool = False,
        profile_dir: Optional[str] = None,
        profile_start_round: int = 0,
        profile_rounds: int = 0,
        resume: bool = False,
    ):
        self.run_dir = Path(run_dir)
        self.run_dir.mkdir(parents=True, exist_ok=True)
        self.record_taps = record_taps
        self.record_phase_times = phase_times
        self.memory_stats = memory_stats
        self.profile_dir = profile_dir
        self.profile_start_round = int(profile_start_round)
        self.profile_rounds = int(profile_rounds)

        events_path = self.run_dir / EVENTS_FILE
        has_prior = events_path.exists() and events_path.stat().st_size > 0
        if has_prior and not resume:
            # A fresh run into an existing dir: rotate the stale stream
            # (keep one generation) instead of appending — otherwise every
            # re-run of a deterministically-named experiment doubles the
            # report's event sums.
            os.replace(events_path, self.run_dir / (EVENTS_FILE + ".prev"))
            mpath = self.run_dir / MANIFEST_FILE
            if mpath.exists():
                os.replace(mpath, self.run_dir / (MANIFEST_FILE + ".prev"))
        resumed = has_prior and resume
        existing = read_manifest(self.run_dir) if resumed else None
        if run_id is None:
            run_id = (existing or {}).get("run_id") or uuid.uuid4().hex[:12]
        self.run_id = run_id
        self._counters: Dict[str, float] = dict(
            (existing or {}).get("counters", {})
        )
        self._seq = 0
        self._events = open(events_path, "a", encoding="utf-8")
        self._manifest: Dict[str, Any] = {
            "schema_version": MANIFEST_SCHEMA_VERSION,
            "kind": KIND_RUN,
            "run_id": run_id,
            "created_unix": (existing or {}).get("created_unix", time.time()),
            "finalized": False,
            "resumed": bool(resumed),
        }
        if config is not None:
            try:
                self._manifest["config"] = _jsonable(config.model_dump())
            except Exception:  # noqa: BLE001 — a snapshot failure must not kill the run
                self._manifest["config"] = None
        self._write_manifest()
        self.emit("run", status="resumed" if resumed else "started")

    # ------------------------------------------------------------------
    # events

    def emit(self, etype: str, _t: Optional[float] = None, **fields) -> None:
        """Append one event line (flushed whole; crash loses at most one).

        Every line carries ``t``, the emit wall-clock timestamp (schema
        v2) — the anchor for trace spans and the offline metrics fold.
        ``_t`` backdates an event whose real time predates the writer
        (the daemon's retroactive ``submitted`` lifecycle event)."""
        rec = {
            "type": etype,
            "seq": self._seq,
            "t": float(_t) if _t is not None else time.time(),
            **_jsonable(fields),
        }
        self._seq += 1
        self._events.write(json.dumps(rec) + "\n")
        self._events.flush()

    def serve_event(self, event: str, _t: Optional[float] = None,
                    **context) -> None:
        """One serve-daemon lifecycle transition (schema v2 ``serve``
        events: submitted/admitted/generation_start/generation_done/
        evicted/frozen/resumed) — the stream-side twin of the ledger."""
        self.emit("serve", _t=_t, event=str(event), **context)

    def phase_times(self, round_idx: int, mode: str, wall_s: float, **extra) -> None:
        """One round's time record.  ``mode`` carries the dispatch
        semantics (schema.py): per_round = wall round time, fused =
        elapsed/k amortized over the chunk.  Pipelined programs
        (exchange.pipeline) additionally pass ``overlap="pipelined"``:
        the round's train and (delayed) exchange+aggregate phases run
        concurrently inside one dispatch, so ``wall_s`` is the round's
        CRITICAL PATH — per-phase profiler brackets (murmura.train /
        murmura.aggregate) overlap in trace time and must not be summed
        (`murmura report` renders a critical_path section instead)."""
        if not self.record_phase_times:
            return
        self.emit(
            "phase_times", round=int(round_idx), mode=mode,
            wall_s=float(wall_s), **extra,
        )

    def round_event(
        self,
        round_num: int,
        metrics: Dict[str, Any],
        in_degree=None,
    ) -> None:
        """Per-node metric arrays of one recorded round.

        ``agg_tap_*`` keys are the in-jit audit taps; they are dropped here
        when ``record_taps`` is off (a host-side recording decision — the
        compiled program is unchanged, MUR402)."""
        payload = {
            k: v for k, v in metrics.items()
            if self.record_taps or not k.startswith("agg_tap_")
        }
        fields: Dict[str, Any] = {"round": int(round_num), "metrics": payload}
        if in_degree is not None:
            fields["in_degree"] = in_degree
        self.emit("round", **fields)

    def memory_event(self, round_idx: int) -> None:
        """Sample device memory_stats() (no-op unless enabled).  CPU
        legitimately reports ``None``; an error from an accelerator
        surfaces — a TPU run whose memory line silently vanished is the
        kind of hidden degradation the run manifest exists to prevent."""
        if not self.memory_stats:
            return
        import jax

        dev = jax.local_devices()[0]
        kind = dev.device_kind
        stats = dev.memory_stats()
        self.emit("memory", round=int(round_idx), device_kind=kind, stats=stats)

    def checkpoint_event(
        self, round_idx: int, duration_s: float, action: str = "save",
        path: Optional[str] = None,
    ) -> None:
        self.emit(
            "checkpoint", round=int(round_idx), action=action,
            duration_s=float(duration_s), path=path,
        )

    def add_counters(self, counters: Dict[str, float]) -> None:
        """Accumulate distributed counters into the manifest totals."""
        for k, v in counters.items():
            try:
                self._counters[k] = self._counters.get(k, 0.0) + float(v)
            except (TypeError, ValueError):
                continue

    # ------------------------------------------------------------------
    # manifest

    def _write_manifest(self) -> None:
        blob = dict(self._manifest)
        blob["counters"] = dict(self._counters)
        durable_replace(
            self.run_dir, MANIFEST_FILE,
            json.dumps(_jsonable(blob), indent=2).encode("utf-8"),
        )

    def finalize(
        self,
        history: Optional[Dict[str, list]] = None,
        summary: Optional[Dict[str, Any]] = None,
    ) -> Path:
        """Atomically commit the manifest (durable_replace).  Callable more
        than once — each train() call re-finalizes with the latest history,
        so the manifest is always the last *complete* view."""
        if history is not None:
            self._manifest["history"] = history
        if summary is not None:
            self._manifest["summary"] = summary
        self._manifest["finalized"] = True
        self._manifest["finalized_unix"] = time.time()
        self._manifest["num_events"] = self._seq
        self._write_manifest()
        return self.run_dir / MANIFEST_FILE

    def close(self) -> None:
        try:
            self._events.close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass


# ----------------------------------------------------------------------
# readers (murmura report, tests)


def read_manifest(run_dir) -> Optional[Dict[str, Any]]:
    """Parsed manifest.json, or None when absent/unreadable."""
    path = Path(run_dir) / MANIFEST_FILE
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return None


def iter_events(run_dir) -> Iterator[Dict[str, Any]]:
    """Yield event dicts in append order, tolerating a torn final line."""
    path = Path(run_dir) / EVENTS_FILE
    if not path.exists():
        return
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                # A crash mid-append leaves at most one torn line — the
                # valid prefix is the stream.
                return


def events_of_type(run_dir, etype: str) -> List[Dict[str, Any]]:
    return [e for e in iter_events(run_dir) if e.get("type") == etype]
