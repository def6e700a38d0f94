"""Single-machine launcher for the ZMQ backend
(reference: murmura/distributed/runner.py:33-213).

Computes a shared t_start = monotonic() + startup_grace, prints run_id +
t_start for multi-machine operators, spawns the monitor first and then one
process per node (picklable module-level entry points), joins the monitor
for the history, and terminates stragglers.
"""

import multiprocessing as mp
import uuid
from typing import Any, Dict, List

from murmura_tpu.config.schema import Config
from murmura_tpu.distributed.endpoints import Endpoints


def _monitor_main(config: Config, run_id: str, t_start: float,
                  compromised: List[int], queue) -> None:
    from murmura_tpu.distributed.monitor import Monitor

    history = Monitor(
        config, run_id, t_start, compromised_ids=set(compromised)
    ).run()
    queue.put(history)


def _node_main(config: Config, node_id: int, run_id: str, t_start: float,
               compromised: List[int], resume: bool = False) -> None:
    from murmura_tpu.distributed.node_process import NodeProcess

    # DMTT configs get the trust-protocol process (reference: runner.py:88-103)
    if config.dmtt is not None:
        from murmura_tpu.dmtt.node_process import DMTTNodeProcess

        cls = DMTTNodeProcess
    else:
        cls = NodeProcess
    cls(
        config,
        node_id=node_id,
        run_id=run_id,
        t_start=t_start,
        compromised_ids=compromised,
        resume=resume,
    ).run()


class DistributedRunner:
    """Launches monitor + N node processes on this machine.

    ``run()`` is ``start()`` + ``wait()``.  The split exists so callers can
    reach the spawned processes mid-run — the fault-injection test SIGKILLs
    a node between rounds and asserts the survivors degrade per the
    deadline semantics (reference: node_process.py:249-276).
    """

    def __init__(self, config: Config):
        self.config = config
        self.node_procs: List[Any] = []
        self.t_start: float = 0.0
        self._monitor = None
        self._queue = None
        # Fault-injection state (config.faults.enabled with churn): the
        # injector thread SIGKILLs scheduled nodes mid-round and respawns
        # them (resume-from-checkpoint) at their scheduled recovery.
        self.injector = None
        self._ctx = None
        self._run_id = None
        self._compromised: List[int] = []

    def run(self) -> Dict[str, List[Any]]:
        self.start()
        return self.wait()

    def start(self) -> None:
        import importlib.util
        import os

        from murmura_tpu.utils.factories import build_attack

        if self.config.dmtt is not None:
            # Fail fast in the parent rather than letting every child die
            # and the monitor idle until its hard deadline.
            if importlib.util.find_spec("murmura_tpu.dmtt.node_process") is None:
                raise RuntimeError(
                    "config.dmtt is set but the DMTT protocol module is not "
                    "available in this build"
                )

        # Same fail-fast principle for data/model wiring: a mismatch would
        # otherwise crash all N children with raw tracebacks while the head
        # idles on monitor.join for the full time budget.  resolve_model
        # raises ConfigError with the config-level explanation.
        # max_samples=32 keeps the head's throwaway stacking cheap — the
        # shape check only needs one sample's dimensionality.
        from murmura_tpu.data.registry import build_federated_data
        from murmura_tpu.utils.factories import resolve_model

        resolve_model(
            self.config,
            build_federated_data(
                self.config.data.adapter,
                self.config.data.params,
                num_nodes=self.config.topology.num_nodes,
                seed=self.config.experiment.seed,
                max_samples=min(32, self.config.training.max_samples or 32),
            ),
        )

        # Children must never reach for the chip (it belongs to one
        # process): pin the CPU platform in the env for the spawn window —
        # spawn inherits os.environ.  ZMQ-backend local training is a CPU
        # path by design.  The parent's env is restored afterwards so later
        # simulation/tpu runs in the same process are unaffected.
        saved_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
        os.environ["JAX_PLATFORMS"] = "cpu"

        cfg = self.config
        attack = build_attack(cfg)
        compromised = sorted(attack.get_compromised_nodes()) if attack else []

        run_id = uuid.uuid4().hex[:8]
        endpoints = Endpoints(cfg.distributed, run_id)
        endpoints.ensure_dirs()

        import time

        t_start = time.monotonic() + cfg.distributed.startup_grace_s
        print(
            f"[runner] run_id={run_id} t_start={t_start:.3f} "
            f"(grace {cfg.distributed.startup_grace_s}s) — pass these to "
            "`murmura_tpu run-node` on other machines",
            flush=True,
        )

        ctx = mp.get_context("spawn")
        self._ctx = ctx
        self._run_id = run_id
        self._compromised = compromised
        self._queue = ctx.Queue()
        self._monitor = ctx.Process(
            target=_monitor_main,
            args=(cfg, run_id, t_start, compromised, self._queue),
            daemon=False,
        )
        self._monitor.start()

        self.t_start = t_start
        self.node_procs = []
        for node_id in range(cfg.topology.num_nodes):
            p = ctx.Process(
                target=_node_main,
                args=(cfg, node_id, run_id, t_start, compromised),
                daemon=False,
            )
            p.start()
            self.node_procs.append(p)

        # All children are spawned; restore the parent's env.
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

        from murmura_tpu.utils.factories import build_fault_schedule

        schedule = build_fault_schedule(cfg)
        if schedule is not None and cfg.faults.crash_prob > 0:
            from murmura_tpu.faults.injector import FaultInjector

            self.injector = FaultInjector(
                schedule,
                rounds=cfg.experiment.rounds,
                round_duration=cfg.distributed.round_duration_s,
                t_start=t_start,
                kill=self._kill_node,
                respawn=self._respawn_node,
            )
            self.injector.start()

    def _kill_node(self, node_id: int) -> None:
        """SIGKILL a node's current process (FaultInjector callback)."""
        import os
        import signal

        p = self.node_procs[node_id]
        if p.is_alive():
            os.kill(p.pid, signal.SIGKILL)

    def _respawn_node(self, node_id: int) -> None:
        """Start a fresh resume-from-checkpoint process for a recovering
        node (FaultInjector callback).  Same JAX_PLATFORMS pin/restore as
        start(): spawn inherits os.environ at process creation (there is no
        per-Process env with multiprocessing).  Runs on the injector
        watcher thread, so a host that embeds DistributedRunner and touches
        JAX_PLATFORMS on another thread mid-run can observe the brief pin
        window; the CLI single-run path cannot."""
        import os

        old = self.node_procs[node_id]
        if old.is_alive():  # pragma: no cover - schedule/kill race
            return
        saved_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            p = self._ctx.Process(
                target=_node_main,
                args=(self.config, node_id, self._run_id, self.t_start,
                      self._compromised, True),
                daemon=False,
            )
            p.start()
            self.node_procs[node_id] = p
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def wait(self) -> Dict[str, List[Any]]:
        cfg = self.config
        history: Dict[str, List[Any]] = {}
        try:
            # generous join: rounds * duration + grace + hard-deadline margin
            budget = (
                cfg.distributed.startup_grace_s
                + (cfg.experiment.rounds + 3) * cfg.distributed.round_duration_s
                + 60.0
            )
            self._monitor.join(timeout=budget)
            if self._monitor.is_alive():
                self._monitor.terminate()
            while not self._queue.empty():
                history = self._queue.get_nowait()
        finally:
            if self.injector is not None:
                self.injector.stop()
            for p in self.node_procs:
                p.join(timeout=5.0)
            for p in self.node_procs:
                if p.is_alive():
                    p.terminate()
        if cfg.telemetry.enabled:
            # The Monitor process owns the manifest (one writer per run);
            # the runner only points the operator at it.
            from murmura_tpu.utils.factories import default_telemetry_dir

            print(
                f"[runner] telemetry run written to "
                f"{default_telemetry_dir(cfg)} — render with "
                "`murmura report <dir>`",
                flush=True,
            )
        return history
