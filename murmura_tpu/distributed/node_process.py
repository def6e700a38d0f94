"""Per-node worker process (reference: murmura/distributed/node_process.py:8-364).

Socket layout: one PULL bind (receives from neighbors), lazy PUSH per
neighbor, one PUSH to the monitor.  Round protocol: sleep until
t_start + k*round_duration -> local train (honest only) -> overrun check ->
attack own outgoing state -> PUSH to current neighbors -> PULL until all
expected arrived or deadline (aggregate with whatever arrived) -> aggregate
-> evaluate -> PUSH metrics.  Round sync is the system clock; there are no
control messages.
"""

import os
import time
from typing import Dict, List, Optional

import numpy as np

from murmura_tpu.config.schema import Config
from murmura_tpu.distributed.endpoints import Endpoints
from murmura_tpu.distributed.messaging import (
    MsgType,
    decode,
    encode,
    pack_obj,
    pack_state,
    unpack_state,
)


def _force_cpu_jax() -> None:
    """Worker processes never touch the TPU: a chip belongs to one process,
    and local training in the ZMQ backend runs on CPU by design (the tpu
    backend is the device path).

    The env mutation alone is NOT enough: jax captures JAX_PLATFORMS when
    it is imported, and the package import (``python -m murmura_tpu`` /
    a spawned worker) happens before this runs.  jax.config.update works
    as long as no backend has initialized yet — same technique as
    tests/conftest.py.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


class NodeProcess:
    """One FL node in its own OS process."""

    def __init__(
        self,
        config: Config,
        node_id: int,
        run_id: str,
        t_start: float,
        compromised_ids: List[int],
        host: Optional[str] = None,
        resume: bool = False,
    ):
        self.config = config
        self.node_id = node_id
        self.run_id = run_id
        self.t_start = t_start
        self.compromised_ids = set(compromised_ids)
        self.host = host
        self.is_compromised = node_id in self.compromised_ids
        # Crash recovery (faults.enabled): a respawned process restores its
        # last per-node checkpoint and rejoins at the wall-clock-current
        # round instead of replaying from round 0.
        self.resume = resume
        self.start_round = 0

        self.endpoints = Endpoints(config.distributed, run_id)
        self.rounds = config.experiment.rounds
        self.round_duration = config.distributed.round_duration_s

        self.node = None
        self.attack = None
        self.mobility = None
        self.fault_schedule = None
        self.static_neighbors: List[int] = []
        self._ctx = None
        self._pull = None
        self._push: Dict[int, object] = {}
        self._monitor_push = None
        # Telemetry counters (docs/OBSERVABILITY.md): operational events
        # that were previously only visible as per-process stdout lines.
        # Ride every METRICS frame under the known 'counters' key; the
        # Monitor folds them into the run manifest (a pre-telemetry
        # monitor drops the unknown key harmlessly — forward-compat).
        self._counters: Dict[str, float] = {
            "send_retries": 0.0,
            "send_failures": 0.0,
            "reconnects": 0.0,
            "rounds_skipped": 0.0,
            "nonfinite_drops": 0.0,
            "checkpoint_saves": 0.0,
            "checkpoint_s": 0.0,
        }

    # ------------------------------------------------------------------

    def run(self) -> None:
        """Entry point inside the child process (reference: node_process.py:111-124)."""
        _force_cpu_jax()
        from murmura_tpu.utils.factories import apply_compilation_cache
        from murmura_tpu.utils.seed import set_seed

        apply_compilation_cache()
        # per-node seeding (node_process.py:113)
        set_seed(self.config.experiment.seed + self.node_id)
        self._build_node()
        if self.resume:
            self._restore_node_checkpoint()
            # Rejoin at the wall-clock-current round: round k occupies
            # [t_start + k*dur, t_start + (k+1)*dur).  Scheduled-dead
            # rounds between boot and recovery are self-skipped below.
            self.start_round = max(
                0,
                int((time.monotonic() - self.t_start) // self.round_duration),
            )
        self._setup_sockets()
        try:
            self._run_all_rounds()
        finally:
            self._teardown()

    # ------------------------------------------------------------------

    def _build_node(self) -> None:
        """Factories + full dataset load in every process, then subset
        (reference behavior: node_process.py:333-364)."""
        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.data.registry import build_federated_data
        from murmura_tpu.distributed.local import LocalNode
        from murmura_tpu.topology.generators import create_topology
        from murmura_tpu.utils.factories import (
            build_attack,
            build_fault_schedule,
            build_mobility,
            resolve_model,
        )

        cfg = self.config
        # Same deterministic schedule every process reconstructs from the
        # seed — dead peers are excluded from expected-neighbor sets
        # without any control messages (faults/schedule.py).
        self.fault_schedule = build_fault_schedule(cfg)
        data = build_federated_data(
            cfg.data.adapter,
            cfg.data.params,
            num_nodes=cfg.topology.num_nodes,
            seed=cfg.experiment.seed,
            max_samples=cfg.training.max_samples,
        )
        # Shared model construction: wearables input_dim auto-sync + the
        # fail-fast data/model shape check, same as the in-process backends.
        model = resolve_model(cfg, data)
        x, y = data.get_client_data(self.node_id)
        # Only pass separate eval arrays when a real test split exists;
        # otherwise LocalNode aliases its training shard (no second device
        # copy of the same data).
        eval_x = eval_y = None
        if data.x_test is not None:
            eval_x, eval_y = data.get_client_eval_data(self.node_id)

        self.mobility = build_mobility(cfg)
        if self.mobility is None:
            topo = create_topology(
                cfg.topology.type,
                num_nodes=cfg.topology.num_nodes,
                p=cfg.topology.p,
                k=cfg.topology.k,
                seed=cfg.topology.seed,
            )
            self.static_neighbors = topo.neighbors[self.node_id]
            max_deg = max(len(ns) for ns in topo.neighbors)
        else:
            max_deg = cfg.topology.num_nodes - 1

        self.attack = build_attack(cfg)

        from murmura_tpu.ops.flatten import model_dimension
        import jax

        model_dim = model_dimension(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        agg_params = dict(cfg.aggregation.params)
        if cfg.aggregation.algorithm == "evidential_trust":
            probe_size = int(agg_params.get("max_eval_samples", 100))
        else:
            probe_size = cfg.training.batch_size
        agg = build_aggregator(
            cfg.aggregation.algorithm, agg_params, model_dim=model_dim,
            total_rounds=cfg.experiment.rounds,
        )

        self.node = LocalNode(
            node_id=self.node_id,
            model=model,
            agg=agg,
            x=x,
            y=y,
            eval_x=eval_x,
            eval_y=eval_y,
            max_neighbors=max_deg,
            local_epochs=cfg.training.local_epochs,
            batch_size=cfg.training.batch_size,
            lr=cfg.training.lr,
            total_rounds=cfg.experiment.rounds,
            probe_size=probe_size,
            annealing_rounds=max(1, cfg.experiment.rounds // 2),
            seed=cfg.experiment.seed + self.node_id,
        )

    def _setup_sockets(self) -> None:
        """PULL bind + PUSH to monitor; neighbor PUSH sockets are lazy
        (reference: node_process.py:130-155)."""
        import zmq

        self._ctx = zmq.Context()
        self._pull = self._ctx.socket(zmq.PULL)
        self._pull.bind(self.endpoints.node_bind(self.node_id, self.host))
        self._monitor_push = self._ctx.socket(zmq.PUSH)
        self._monitor_push.setsockopt(zmq.LINGER, 2000)
        self._monitor_push.connect(self.endpoints.monitor_connect())

    def _push_to(self, neighbor_id: int):
        import zmq

        if neighbor_id not in self._push:
            sock = self._ctx.socket(zmq.PUSH)
            sock.setsockopt(zmq.LINGER, 2000)
            sock.connect(self.endpoints.node_connect(neighbor_id))
            self._push[neighbor_id] = sock
        return self._push[neighbor_id]

    def _teardown(self) -> None:
        for sock in self._push.values():
            sock.close()
        if self._pull is not None:
            self._pull.close()
        if self._monitor_push is not None:
            self._monitor_push.close()
        if self._ctx is not None:
            self._ctx.term()

    # ------------------------------------------------------------------

    def current_neighbors(self, round_idx: int) -> List[int]:
        """Static topology or mobility G^t (reference: node_process.py:292-323)."""
        if self.mobility is not None:
            return self.mobility.neighbors_at(round_idx)[self.node_id]
        return list(self.static_neighbors)

    def _scheduled_dead(self, round_idx: int) -> bool:
        return (
            self.fault_schedule is not None
            and self.fault_schedule.alive_at(round_idx)[self.node_id] <= 0
        )

    def _run_all_rounds(self) -> None:
        for k in range(self.start_round, self.rounds):
            target = self.t_start + k * self.round_duration
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if self._scheduled_dead(k):
                # Self-enforced crash window: a dead process neither
                # trains nor reports (reporting_nodes drops — the
                # monitor's degradation telemetry).  Self-enforcement
                # keeps multi-machine runs (no FaultInjector parent)
                # honoring the schedule, and gives a respawned process a
                # boot round before its scheduled recovery.  With the
                # injector armed this is belt-and-suspenders: the process
                # is normally SIGKILLed before it gets here.
                continue
            self._execute_round(k)
            if self.fault_schedule is not None:
                self._save_node_checkpoint(k)

    @property
    def _is_colluder(self) -> bool:
        """Colluding attacks (ALIE, IPM) estimate population statistics
        from the coalition's own benign states on this backend."""
        return (
            self.attack is not None
            and self.attack.name in ("alie", "ipm")
            and self.is_compromised
        )

    def _execute_round(self, round_idx: int) -> None:
        """One wall-clock round (reference: node_process.py:193-247)."""
        deadline = self.t_start + (round_idx + 1) * self.round_duration
        # 0. already past this round's deadline (a previous round's
        # training overran the whole window, or a recovery boot landed
        # late): publish the SKIPPED frame so the monitor stays
        # index-aligned, instead of training into the next window and
        # silently advancing.
        if time.monotonic() >= deadline:
            print(
                f"[node {self.node_id}] round {round_idx}: round window "
                "already elapsed; skipping",
                flush=True,
            )
            self._send_metrics(round_idx, skipped=True)
            return
        neighbors = self.current_neighbors(round_idx)
        if self.fault_schedule is not None:
            # Re-resolve the expected-neighbor set from the schedule:
            # no waiting out the full deadline on a known-dead peer or a
            # dropped link.  Symmetric link masks keep sender and receiver
            # expectations consistent without communication.
            alive = self.fault_schedule.alive_at(round_idx)
            link = self.fault_schedule.link_mask_at(round_idx)
            neighbors = [
                j for j in neighbors
                if alive[j] > 0 and link[self.node_id, j] > 0
            ]

        # 1. local training (honest only — node_process.py:205-207).
        # ALIE/IPM colluders ALSO train: their benign states are the
        # coalition sample the papers' estimators run on (alie.py module
        # docstring); the benign result never leaves the coalition.
        faults = self.config.faults if self.config.faults.enabled else None
        pre_flat = None
        if faults is not None and faults.nan_quarantine:
            # Pre-round snapshot: a divergent (non-finite) local step rolls
            # back to this instead of poisoning the exchange — the ZMQ twin
            # of the in-jit sentinel (core/rounds.py, docs/ROBUSTNESS.md).
            pre_flat = self.node.get_flat_state()
        t_train0 = time.monotonic()
        if not self.is_compromised or self._is_colluder:
            self.node.local_train(round_idx)

        # 1b. straggler realization: the schedule's boolean becomes an
        # actual delay — (factor-1) x the measured training time, capped
        # just past the round window.  Deliberately WEAKER than the jitted
        # backends' model (which drops a straggler's outgoing column
        # unconditionally): here the delay is physical, so whether the
        # update misses the delivery deadline depends on real timing —
        # a 2x slowdown that still fits the window delivers on time, as
        # it would in production (docs/ROBUSTNESS.md).
        if (
            self.fault_schedule is not None
            and self.fault_schedule.straggler_at(round_idx)[self.node_id]
        ):
            train_time = time.monotonic() - t_train0
            delay = min(
                (self.fault_schedule.straggler_factor - 1.0) * train_time,
                max(0.0, deadline - time.monotonic()) + 0.5,
            )
            if delay > 0:
                print(
                    f"[node {self.node_id}] round {round_idx}: straggling "
                    f"{delay:.2f}s (factor "
                    f"{self.fault_schedule.straggler_factor})",
                    flush=True,
                )
                time.sleep(delay)

        # 2. overrun check: skip exchange if training blew the window
        # (node_process.py:210-218)
        if time.monotonic() >= deadline:
            print(
                f"[node {self.node_id}] round {round_idx}: training overran "
                "the round window; skipping exchange",
                flush=True,
            )
            self._send_metrics(round_idx, skipped=True)
            return

        # 2b. numerical sentinel (faults.nan_quarantine): a non-finite
        # post-training state quarantines this node for the round — params
        # roll back to the pre-round snapshot and the exchange is skipped
        # (neighbors degrade via the normal deadline semantics; they ALSO
        # drop non-finite arrivals in _collect_states as defense in depth).
        flat = self.node.get_flat_state()
        if (
            faults is not None
            and self.node_id in faults.nan_inject_nodes
            and round_idx >= faults.nan_inject_from_round
        ):
            # Deterministic divergence injection for chaos testing, same
            # semantics as the jitted backends' nan_inject_nodes.
            flat = np.full_like(flat, np.nan)
        if pre_flat is not None and not np.isfinite(flat).all():
            print(
                f"[node {self.node_id}] round {round_idx}: non-finite local "
                "update quarantined; rolling back to the pre-round state",
                flush=True,
            )
            self.node.set_flat_state(pre_flat)
            self._send_metrics(round_idx, skipped=False)
            return

        # 3. attack own outgoing state (node_process.py:221-225).
        # ALIE/IPM colluders first exchange benign states within the
        # coalition; neighbor MODEL_STATEs arriving during that window are
        # buffered and handed to the collection in step 5.
        prebuffered: Dict[int, np.ndarray] = {}
        if self._is_colluder:
            out_flat, prebuffered = self._colluding_state(
                flat, round_idx, deadline
            )
        else:
            out_flat = self._attacked_state(flat, round_idx)

        # 4. PUSH to current neighbors (node_process.py:227-232)
        payload = pack_state(out_flat)
        for nid in neighbors:
            self._send_to(
                nid, encode(MsgType.MODEL_STATE, self.node_id, payload, round_idx)
            )

        # 5. collect neighbor states until expected or deadline
        # (node_process.py:249-276)
        received = self._collect_states(
            set(neighbors), round_idx, deadline, prebuffered=prebuffered
        )

        # 6. aggregate with whatever arrived (partial OK)
        if received:
            self.node.aggregate_with_neighbors(received, round_idx)

        # 7. evaluate + metrics to monitor
        self._send_metrics(round_idx, skipped=False)

    def _reject_nonfinite(self, sender: int, state: np.ndarray) -> bool:
        """Receiver-side sentinel (faults.nan_quarantine): drop a neighbor
        state carrying non-finite values before it reaches any rule math
        (0 * nan == nan in every Gram/matmul path) — defense in depth
        behind the sender-side rollback, and the only line of defense
        against a peer running without the sentinel."""
        if (
            self.config.faults.enabled
            and self.config.faults.nan_quarantine
            and not np.isfinite(state).all()
        ):
            print(
                f"[node {self.node_id}] dropped non-finite state from "
                f"{sender}",
                flush=True,
            )
            self._counters["nonfinite_drops"] += 1
            return True
        return False

    def _send_to(self, neighbor_id: int, frames, attempts: int = 3) -> bool:
        """Send with exponential-backoff reconnect.

        A PUSH socket wedged by a peer restart (stale IPC inode, refused
        TCP connect at send time) raises; dropping the cached socket and
        reconnecting fresh is the recovery — ZMQ re-resolves the endpoint.
        Failure after the retry budget degrades to the round's
        partial-aggregation semantics (the peer just misses this state).
        """
        delay = 0.05
        for attempt in range(attempts):
            try:
                self._push_to(neighbor_id).send_multipart(frames, copy=False)
                return True
            except Exception as e:
                print(
                    f"[node {self.node_id}] push to {neighbor_id} failed "
                    f"(attempt {attempt + 1}/{attempts}): {e}",
                    flush=True,
                )
                self._counters["send_retries"] += 1
                self._counters["reconnects"] += 1
                sock = self._push.pop(neighbor_id, None)
                if sock is not None:
                    try:
                        sock.close(linger=0)
                    except Exception:  # pragma: no cover - teardown races
                        pass
                if attempt + 1 < attempts:
                    time.sleep(delay)
                    delay *= 2
        self._counters["send_failures"] += 1
        return False

    def _attacked_state(self, flat: np.ndarray, round_idx: int) -> np.ndarray:
        if self.attack is None or not self.is_compromised:
            return flat
        import jax
        import jax.numpy as jnp

        key = jax.random.fold_in(
            jax.random.PRNGKey(self.config.experiment.seed + 7919), round_idx
        )
        key = jax.random.fold_in(key, self.node_id)
        out = self.attack.apply(
            jnp.asarray(flat)[None, :], jnp.ones((1,)), key, round_idx
        )
        return np.asarray(out[0], dtype=np.float32)

    def _colluding_state(
        self, flat: np.ndarray, round_idx: int, deadline: float
    ) -> tuple:
        """Coalition-estimated colluding vector — ALIE's mu - z*sigma
        (Baruch et al.) or IPM's -epsilon*mu (Xie et al.), both estimated
        from the corrupted workers' own benign states, which is the
        papers' construction (module docstrings of attacks/alie.py and
        attacks/ipm.py have the omniscient-vs-estimated distinction).

        Protocol: push own benign state to every other colluder
        (COLLUDE_STATE), collect theirs until half the remaining round
        window is spent, then broadcast the colluding vector over whatever
        coalition sample arrived (always >= the own state — the same
        partial-collect degradation the model exchange uses).  Neighbor
        MODEL_STATEs arriving early are buffered and returned for step 5.
        """
        import zmq
        peers = sorted(self.compromised_ids - {self.node_id})
        if self.fault_schedule is not None:
            # Dead colluders can neither contribute nor receive: shrink
            # the coalition instead of burning half the round window
            # waiting on them.
            alive = self.fault_schedule.alive_at(round_idx)
            peers = [p for p in peers if alive[p] > 0]
        payload = pack_state(flat)
        for nid in peers:
            self._send_to(
                nid,
                encode(MsgType.COLLUDE_STATE, self.node_id, payload, round_idx),
            )

        coalition: Dict[int, np.ndarray] = {self.node_id: np.asarray(flat)}
        prebuffered: Dict[int, np.ndarray] = {}
        # Leave at least half the remaining window for the real exchange.
        sub_deadline = min(
            deadline, time.monotonic() + 0.5 * max(0.0, deadline - time.monotonic())
        )
        poller = zmq.Poller()
        poller.register(self._pull, zmq.POLLIN)
        while set(peers) - set(coalition) and time.monotonic() < sub_deadline:
            timeout_ms = max(1, int((sub_deadline - time.monotonic()) * 1000))
            events = dict(poller.poll(min(timeout_ms, 200)))
            if self._pull not in events:
                continue
            msg_type, sender, msg_round, data = decode(self._pull.recv_multipart())
            if msg_round != round_idx:
                continue  # straggler from an earlier round window
            if msg_type == MsgType.COLLUDE_STATE and sender in peers:
                state = unpack_state(data)
                if not self._reject_nonfinite(sender, state):
                    coalition[sender] = state
            elif msg_type == MsgType.MODEL_STATE:
                state = unpack_state(data)
                if not self._reject_nonfinite(sender, state):
                    prebuffered[sender] = state
        missing = set(peers) - set(coalition)
        if missing:
            print(
                f"[node {self.node_id}] {self.attack.name}: coalition "
                f"sample {len(coalition)}/{len(peers) + 1} "
                f"(missing {sorted(missing)})",
                flush=True,
            )
        sample = np.stack(list(coalition.values()))
        p = self.config.attack.params
        if self.attack.name == "ipm":
            from murmura_tpu.attacks.ipm import ipm_vector, resolve_ipm_epsilon

            out = ipm_vector(sample, resolve_ipm_epsilon(p.get("epsilon")))
        else:
            from murmura_tpu.attacks.alie import (
                colluding_vector,
                resolve_alie_z,
            )

            out = colluding_vector(
                sample,
                resolve_alie_z(
                    self.config.topology.num_nodes,
                    len(self.compromised_ids),
                    p.get("z"),
                ),
            )
        return out, prebuffered

    def _collect_states(
        self,
        expected: set,
        round_idx: int,
        deadline: float,
        prebuffered: Optional[Dict[int, np.ndarray]] = None,
    ) -> Dict[int, np.ndarray]:
        import zmq

        received: Dict[int, np.ndarray] = {
            s: v for s, v in (prebuffered or {}).items() if s in expected
        }
        poller = zmq.Poller()
        poller.register(self._pull, zmq.POLLIN)
        while expected - set(received) and time.monotonic() < deadline:
            timeout_ms = max(1, int((deadline - time.monotonic()) * 1000))
            events = dict(poller.poll(min(timeout_ms, 200)))
            if self._pull in events:
                msg_type, sender, msg_round, payload = decode(
                    self._pull.recv_multipart()
                )
                # round tag drops stragglers from earlier round windows
                if (
                    msg_type == MsgType.MODEL_STATE
                    and sender in expected
                    and msg_round == round_idx
                ):
                    state = unpack_state(payload)
                    if self._reject_nonfinite(sender, state):
                        expected = expected - {sender}
                        continue
                    received[sender] = state
        missing = expected - set(received)
        if missing:
            print(
                f"[node {self.node_id}] deadline: aggregating with "
                f"{len(received)}/{len(expected)} neighbors (missing {sorted(missing)})",
                flush=True,
            )
        return received

    # ------------------------------------------------------------------
    # crash-recovery checkpoints (faults.enabled runs)

    def _save_node_checkpoint(self, round_idx: int) -> None:
        """Atomically snapshot this node's state after a completed round.

        Flat params + RNG key + per-node ('node'-kind) aggregator state;
        per-edge trust is deliberately not persisted — a recovered peer
        re-earns link trust, which is the conservative (Byzantine-safe)
        choice.  fsync'd write + os.replace so a crash mid-save leaves the
        previous checkpoint intact (utils/checkpoint.py semantics).
        """
        import io

        from murmura_tpu.utils.checkpoint import durable_replace

        t0 = time.monotonic()
        path = self.endpoints.node_checkpoint_path(self.node_id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {
            "flat": self.node.get_flat_state(),
            "rng": np.asarray(self.node.rng),
            "round": np.int64(round_idx),
        }
        for k, v in getattr(self.node, "_node_state", {}).items():
            payload[f"node_state.{k}"] = np.asarray(v)
        buf = io.BytesIO()
        np.savez(buf, **payload)
        durable_replace(
            os.path.dirname(path), os.path.basename(path), buf.getvalue()
        )
        self._counters["checkpoint_saves"] += 1
        self._counters["checkpoint_s"] += time.monotonic() - t0

    def _restore_node_checkpoint(self) -> Optional[int]:
        """Restore the last checkpoint; returns its round or None."""
        import jax.numpy as jnp

        path = self.endpoints.node_checkpoint_path(self.node_id)
        if not os.path.exists(path):
            print(
                f"[node {self.node_id}] resume requested but no checkpoint "
                f"at {path}; rejoining from the initial model",
                flush=True,
            )
            return None
        with np.load(path) as data:
            self.node.set_flat_state(data["flat"])
            self.node.rng = jnp.asarray(data["rng"])
            for k in list(getattr(self.node, "_node_state", {})):
                key = f"node_state.{k}"
                if key in data:
                    self.node._node_state[k] = np.asarray(data[key])
            restored = int(data["round"])
        print(
            f"[node {self.node_id}] restored checkpoint from round "
            f"{restored}",
            flush=True,
        )
        return restored

    def _send_metrics(self, round_idx: int, skipped: bool) -> None:
        metrics = {"round": round_idx, "node": self.node_id, "skipped": skipped}
        if skipped:
            self._counters["rounds_skipped"] += 1
        else:
            metrics.update(self.node.evaluate())
            metrics["stats"] = self.node.get_aggregator_statistics()
        metrics["compromised"] = self.is_compromised
        # Cumulative operational counters ride every frame: the monitor
        # folds the LAST value per node into the manifest, so losing any
        # individual frame loses nothing (each frame carries the totals).
        metrics["counters"] = dict(self._counters)
        try:
            self._monitor_push.send_multipart(
                encode(MsgType.METRICS, self.node_id, pack_obj(metrics), round_idx)
            )
        except Exception as e:  # pragma: no cover
            print(f"[node {self.node_id}] metrics push failed: {e}", flush=True)


def run_single_node(
    config: Config,
    node_id: int,
    t_start: float,
    run_id: str,
    host: Optional[str] = None,
    resume: bool = False,
) -> None:
    """Multi-machine worker entry (reference: cli.py:143-208).  The operator
    copies run_id/t_start printed by the head node; t_start must be valid on
    this machine's monotonic clock."""
    # Pin the CPU platform BEFORE importing anything jax-backed —
    # build_attack pulls in the factories module, which imports jax.
    _force_cpu_jax()
    if not 0 <= node_id < config.topology.num_nodes:
        raise ValueError(
            f"--node-id {node_id} out of range for "
            f"topology.num_nodes={config.topology.num_nodes}"
        )
    from murmura_tpu.utils.factories import build_attack

    attack = build_attack(config)
    compromised = sorted(attack.get_compromised_nodes()) if attack else []
    NodeProcess(
        config,
        node_id=node_id,
        run_id=run_id,
        t_start=t_start,
        compromised_ids=compromised,
        host=host,
        resume=resume,
    ).run()
