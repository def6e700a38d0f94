"""Unified telemetry subsystem (ISSUE 4; docs/OBSERVABILITY.md).

One versioned run manifest + JSONL event stream (schema.py, writer.py)
that all three backends emit through, plus the
``murmura report`` renderer (report.py).  Default off: with no
``telemetry:`` config block the compiled programs, histories, and random
streams are byte-identical to a build without this package.
"""

from murmura_tpu.telemetry.schema import (
    EVENTS_FILE,
    MANIFEST_FILE,
    MANIFEST_SCHEMA_VERSION,
    MONITOR_KNOWN_KEYS,
)
from murmura_tpu.telemetry.writer import (
    TelemetryWriter,
    events_of_type,
    iter_events,
    read_manifest,
)

__all__ = [
    "EVENTS_FILE",
    "MANIFEST_FILE",
    "MANIFEST_SCHEMA_VERSION",
    "MONITOR_KNOWN_KEYS",
    "TelemetryWriter",
    "events_of_type",
    "iter_events",
    "read_manifest",
]
