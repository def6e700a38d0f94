"""The elastic dispatch envelope: retry classification, backoff, and the
``--require-tpu`` hard-fail (docs/ROBUSTNESS.md "Run durability").

A run that loses its device mid-way must either come back on the same
device or stop — never carry on somewhere slower under the same label.  The
envelope gives every long-lived driver (CLI runs, the ``murmura serve``
daemon) three primitives:

- :func:`classify_error` — transient (device/transport) vs fatal.
  Deliberately conservative: only errors that a reconnect or a re-dispatch
  can plausibly cure classify transient; everything else (shape errors,
  OOM, config errors) is fatal and re-raised immediately — retrying a
  deterministic failure just burns the backoff budget.
- :class:`RetryPolicy` / :func:`run_with_retry` — exponential backoff with
  deterministic seeded jitter (reproducible schedules in tests; decorrelated
  retries in a fleet).  The attempt callable receives the try index so the
  caller can restore from its last snapshot before re-dispatching —
  retrying with donated (consumed) buffers is never safe, so the restore
  IS the retry mechanism, not an optimization.
- :func:`require_tpu` / :func:`tpu_required` — the hard-fail:
  ``--require-tpu``, ``durability.require_tpu``, or
  ``MURMURA_REQUIRE_TPU=1`` abort loudly when the default JAX backend is
  not a TPU, instead of producing CPU numbers under a device's name.
  ``chip_smoke.py`` requires it unconditionally.
"""

import errno
import os
import random
import time
from dataclasses import dataclass
from typing import Callable, Iterator, Optional


class BackendRequirementError(RuntimeError):
    """The run required a TPU backend and did not get one."""


# Substrings that mark an exception message as transient: transport
# deaths, device unavailability, and gRPC/PJRT deadline failures.  Matched
# case-insensitively against str(exc) and its type name.
TRANSIENT_ERROR_MARKERS = (
    "deadline_exceeded",
    "deadline exceeded",
    "unavailable",
    "connection reset",
    "connection refused",
    "connection closed",
    "broken pipe",
    "socket closed",
    "timed out",
    "timeout",
    "failed to connect",
    "transport",
    "heartbeat",
    "address already in use",
)

# Exception types that are transient by construction (transport layer).
# ConnectionResetError / BrokenPipeError / ConnectionRefusedError are
# ConnectionError subclasses and socket.timeout aliases TimeoutError, so
# the daemon's socket layer (serve/protocol.py) is covered wholesale.
TRANSIENT_ERROR_TYPES = (ConnectionError, TimeoutError)

# OSError errnos that mark a socket-layer transient even when the
# exception is a bare OSError (no ConnectionError subclass): a killed
# daemon's stale socket file (EADDRINUSE on rebind), a peer that died
# mid-write, a refused/aborted connect during restart.
TRANSIENT_ERRNOS = frozenset(
    getattr(errno, name)
    for name in (
        "EADDRINUSE",
        "ECONNRESET",
        "ECONNREFUSED",
        "ECONNABORTED",
        "EPIPE",
        "ETIMEDOUT",
        "EAGAIN",
    )
    if hasattr(errno, name)
)


def classify_error(exc: BaseException) -> str:
    """``"transient"`` (retry may cure it) or ``"fatal"`` (re-raise).

    A :class:`BackendRequirementError` is always fatal — retrying cannot
    conjure a chip, and the whole point of ``--require-tpu`` is to stop.
    """
    if isinstance(exc, BackendRequirementError):
        return "fatal"
    if isinstance(exc, TRANSIENT_ERROR_TYPES):
        return "transient"
    if (
        isinstance(exc, OSError)
        and getattr(exc, "errno", None) in TRANSIENT_ERRNOS
    ):
        return "transient"
    text = f"{type(exc).__name__}: {exc}".lower()
    if any(marker in text for marker in TRANSIENT_ERROR_MARKERS):
        return "transient"
    return "fatal"


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff with jitter.

    Delay before retry ``i`` (0-based) is
    ``min(max_delay_s, base_delay_s * 2**i) * (1 + U(-jitter, +jitter))``,
    with the uniform draw from a seeded stream so schedules are
    reproducible (``seed=None`` derives one from the PID — decorrelated
    across fleet processes, still loggable).
    """

    max_retries: int = 3
    base_delay_s: float = 1.0
    max_delay_s: float = 60.0
    jitter: float = 0.25
    seed: Optional[int] = None

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.base_delay_s < 0 or self.max_delay_s < self.base_delay_s:
            raise ValueError(
                f"need 0 <= base_delay_s <= max_delay_s, got "
                f"{self.base_delay_s}/{self.max_delay_s}"
            )
        if not 0.0 <= self.jitter < 1.0:
            raise ValueError(f"jitter must be in [0, 1), got {self.jitter}")


def backoff_delays(policy: RetryPolicy) -> Iterator[float]:
    """The policy's delay sequence (one entry per retry)."""
    rng = random.Random(
        policy.seed if policy.seed is not None else os.getpid()
    )
    for i in range(policy.max_retries):
        base = min(policy.max_delay_s, policy.base_delay_s * (2.0 ** i))
        yield base * (1.0 + rng.uniform(-policy.jitter, policy.jitter))


class RetryStats:
    """Mutable retry accounting for one dispatch envelope.

    The observability plane's view of the retry loop (ISSUE 19): pass
    :meth:`hook` as ``run_with_retry(on_retry=...)`` (or chain it from
    an existing hook) and the envelope's transient retries and
    cumulative backoff become scrapeable — the offline fold turns the
    matching ``backend_degraded`` events into
    ``murmura_degradations``/``murmura_backoff_seconds``
    (telemetry/metrics.py)."""

    def __init__(self):
        self.retries = 0
        self.backoff_s = 0.0
        self.last_reason: Optional[str] = None

    def hook(self, exc: BaseException, try_idx: int, delay: float) -> None:
        self.retries += 1
        self.backoff_s += float(delay)
        self.last_reason = f"{type(exc).__name__}: {exc}"

    def counters(self) -> dict:
        """The accumulated totals, keyed for
        ``TelemetryWriter.add_counters`` / the manifest counter fold."""
        return {
            "dispatch_retries": self.retries,
            "dispatch_backoff_s": self.backoff_s,
        }


def run_with_retry(
    attempt: Callable[[int], object],
    *,
    policy: RetryPolicy = RetryPolicy(),
    classify: Callable[[BaseException], str] = classify_error,
    on_retry: Optional[Callable[[BaseException, int, float], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
):
    """Call ``attempt(try_index)`` until it succeeds or retries exhaust.

    Fatal errors re-raise immediately; transient errors sleep the
    policy's backoff delay and retry (``on_retry(exc, next_try, delay)``
    fires first — the hook for ``backend_degraded`` telemetry and the
    caller's snapshot restore logging).  The final transient failure
    re-raises the original exception, so the caller's stack trace is the
    real one.
    """
    delays = backoff_delays(policy)
    try_idx = 0
    while True:
        try:
            return attempt(try_idx)
        except BaseException as exc:  # noqa: BLE001 — classified below
            if classify(exc) != "transient":
                raise
            delay = next(delays, None)
            if delay is None:
                raise
            try_idx += 1
            if on_retry is not None:
                on_retry(exc, try_idx, delay)
            sleep(delay)


# ----------------------------------------------------------------------
# --require-tpu


def tpu_required(config=None) -> bool:
    """Whether this run demands a TPU: the ``MURMURA_REQUIRE_TPU=1`` env
    twin, or ``durability.require_tpu`` in the config."""
    if os.environ.get("MURMURA_REQUIRE_TPU") == "1":
        return True
    if config is not None:
        dur = getattr(config, "durability", None)
        if dur is not None and getattr(dur, "require_tpu", False):
            return True
    return False


def require_tpu(source: str = "--require-tpu") -> None:
    """Hard-fail unless the default JAX backend is a TPU.

    Asked once, in the process that will do the work (a chip belongs to
    one process).  ``source`` names the knob or script that demanded the
    chip so the error is self-explaining.
    """
    import jax

    try:
        backend = jax.default_backend()
        kind = jax.devices()[0].device_kind
    except Exception as e:  # noqa: BLE001 — surfacing WHY counts as loud
        raise BackendRequirementError(
            f"{source}: TPU required but the JAX backend failed to "
            f"initialize ({type(e).__name__}: {e})"
        ) from e
    if backend != "tpu":
        raise BackendRequirementError(
            f"{source}: TPU required but the default JAX backend is "
            f"'{backend}' (device_kind={kind!r}); refusing to run on "
            "another device — attach a TPU or drop the requirement"
        )
