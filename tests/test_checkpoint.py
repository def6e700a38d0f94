"""Checkpoint/resume: an interrupted run restored from disk must produce
bit-identical state to an uninterrupted run (no reference counterpart —
the reference has no checkpointing, SURVEY §5)."""

import jax
import numpy as np

from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.core.network import Network
from murmura_tpu.core.rounds import build_round_program
from murmura_tpu.data.base import stack_partitions
from murmura_tpu.data.partitioners import iid_partition
from murmura_tpu.data.synthetic import make_synthetic
from murmura_tpu.models.registry import build_model
from murmura_tpu.topology import create_topology
from murmura_tpu.utils.checkpoint import has_checkpoint


def _make_network(seed=0):
    n, rounds = 4, 6
    x, y = make_synthetic(num_samples=200, input_shape=(8,), num_classes=3, seed=seed)
    parts = iid_partition(len(y), n, seed=seed)
    data = stack_partitions(x, y, parts, num_classes=3)
    model = build_model("mlp", {"input_dim": 8, "hidden_dims": [16], "num_classes": 3})
    agg = build_aggregator("balance", {}, total_rounds=rounds)
    program = build_round_program(
        model, agg, data, local_epochs=1, batch_size=16, lr=0.1,
        total_rounds=rounds, seed=seed,
    )
    return Network(program, create_topology("ring", num_nodes=n), seed=seed,
                   donate=False)


def test_checkpoint_resume_bit_identical(tmp_path):
    ckpt = tmp_path / "ckpt"

    # Uninterrupted: 6 rounds straight.
    full = _make_network()
    full.train(rounds=6)

    # Interrupted: 3 rounds, checkpoint, fresh network, restore, 3 more.
    first = _make_network()
    first.train(rounds=3, checkpoint_dir=str(ckpt))
    assert has_checkpoint(ckpt)

    resumed = _make_network()
    assert resumed.restore_checkpoint(str(ckpt)) == 3
    resumed.train(rounds=3)

    for a, b in zip(
        jax.tree_util.tree_leaves(full.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in full.agg_state:
        np.testing.assert_array_equal(
            np.asarray(full.agg_state[k]), np.asarray(resumed.agg_state[k]), err_msg=k
        )
    assert full.history["round"] == resumed.history["round"]
    np.testing.assert_allclose(
        full.history["mean_accuracy"], resumed.history["mean_accuracy"]
    )


def test_spliced_state_file_detected(tmp_path):
    """The embedded-round cross-check (defense in depth behind the
    commit-point ordering): a state blob copied in from another snapshot
    under the committed generation's name must be refused, not silently
    restored at the wrong round."""
    import json
    import shutil

    import pytest

    ckpt = tmp_path / "ckpt"
    net = _make_network()
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    round2 = ckpt / "state.2.msgpack"
    keep = tmp_path / "state.round2.bak"
    shutil.copy(round2, keep)
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    meta = json.loads((ckpt / "meta.json").read_text())
    assert meta["round"] == 4
    # Splice the round-2 blob under the committed round-4 name.
    shutil.copy(keep, ckpt / "state.4.msgpack")

    fresh = _make_network()
    with pytest.raises(ValueError, match="[Tt]orn"):
        fresh.restore_checkpoint(str(ckpt))


def test_crash_before_meta_commit_restores_previous_snapshot(tmp_path):
    """THE durability guarantee (ISSUE 10): meta.json is the commit
    point, so a crash landing after the new state generation is written
    but BEFORE the meta replace must leave the PREVIOUS snapshot fully
    restorable — not a torn pair that loses the run.  Reproduced with two
    real checkpoints: put the round-2 meta back beside the round-4 state
    generation (exactly the on-disk picture such a crash leaves, old
    generation not yet GC'd) and restore must come back at round 2."""
    import shutil

    ckpt = tmp_path / "ckpt"
    net = _make_network()
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    old_meta = (ckpt / "meta.json").read_bytes()
    old_state = (ckpt / "state.2.msgpack").read_bytes()
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    # Reconstruct the crash window: new state.4.msgpack on disk, meta
    # still the round-2 commit, round-2 generation still present.
    (ckpt / "meta.json").write_bytes(old_meta)
    (ckpt / "state.2.msgpack").write_bytes(old_state)

    fresh = _make_network()
    assert fresh.restore_checkpoint(str(ckpt)) == 2
    assert fresh.current_round == 2


def test_legacy_unsuffixed_snapshot_restores(tmp_path):
    """A pre-commit-point v3 checkpoint (plain state.msgpack beside
    meta.json) must still restore — and the next save must migrate the
    directory to the suffixed layout."""
    ckpt = tmp_path / "ckpt"
    net = _make_network()
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    (ckpt / "state.2.msgpack").rename(ckpt / "state.msgpack")
    assert has_checkpoint(ckpt)

    fresh = _make_network()
    assert fresh.restore_checkpoint(str(ckpt)) == 2
    fresh.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    assert not (ckpt / "state.msgpack").exists()
    assert (ckpt / "state.4.msgpack").exists()


def test_old_generations_garbage_collected(tmp_path):
    """After a committed save, exactly one state generation remains."""
    ckpt = tmp_path / "ckpt"
    net = _make_network()
    net.train(rounds=4, checkpoint_dir=str(ckpt), checkpoint_every=2)
    assert [p.name for p in sorted(ckpt.glob("state.*"))] == [
        "state.4.msgpack"
    ]


def test_save_leaves_no_temp_files(tmp_path):
    """The fsync'd write path must clean up its .tmp staging files — a
    leftover would be restored as garbage by naive directory scans and
    signals a torn write sequence."""
    ckpt = tmp_path / "ckpt"
    net = _make_network()
    net.train(rounds=2, checkpoint_dir=str(ckpt), checkpoint_every=2)
    leftovers = list(ckpt.glob("*.tmp"))
    assert not leftovers, leftovers
    assert has_checkpoint(ckpt)


def test_krum_f_num_compromised_conflict():
    import pytest

    # Alias and canonical name agreeing is fine…
    build_aggregator("krum", {"f": 1, "num_compromised": 1})
    # …but conflicting values must be rejected, not silently resolved.
    with pytest.raises(ValueError, match="num_compromised"):
        build_aggregator("krum", {"f": 1, "num_compromised": 2})


def test_round_counter_persists_across_train_calls():
    net = _make_network()
    net.train(rounds=2)
    net.train(rounds=2)
    assert net.current_round == 4
    assert net.history["round"] == [1, 2, 3, 4]


def test_defer_metrics_history_identical():
    """Throughput mode (defer_metrics=True) must record the exact same
    history as the per-round sync path."""
    sync = _make_network()
    sync.train(rounds=4)
    deferred = _make_network()
    deferred.train(rounds=4, defer_metrics=True)
    assert sync.history["round"] == deferred.history["round"]
    np.testing.assert_allclose(
        sync.history["mean_accuracy"], deferred.history["mean_accuracy"]
    )
    np.testing.assert_allclose(
        sync.history["mean_loss"], deferred.history["mean_loss"]
    )


def test_stale_checkpoint_version_rejected(tmp_path):
    """A v2 checkpoint (split()-chain rng semantics) must fail loudly, not
    resume with a silently different random stream."""
    import json
    import pytest

    net = _make_network()
    net.train(rounds=2, checkpoint_dir=str(tmp_path))
    meta_path = tmp_path / "meta.json"
    meta = json.loads(meta_path.read_text())
    meta["version"] = 2
    meta_path.write_text(json.dumps(meta))
    fresh = _make_network()
    with pytest.raises(ValueError, match="fold_in"):
        fresh.restore_checkpoint(str(tmp_path))


# ---------------------------------------------------------------------------
# Mesh-sharded checkpointing (round-4 verdict missing #4): the preemption
# story a real 256-node TPU run needs — save under a sharded mesh in one
# PROCESS, restore into a fresh process with a different mesh size (or a
# single device) and land exactly where the uninterrupted run lands.
# ---------------------------------------------------------------------------

_MESH_CFG = {
    "experiment": {"name": "mesh-ckpt", "seed": 11, "rounds": 6},
    "topology": {"type": "ring", "num_nodes": 8},
    "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
    "attack": {"enabled": True, "type": "gaussian", "percentage": 0.25,
                "params": {"noise_std": 5.0}},
    "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
    "data": {"adapter": "synthetic",
              "params": {"num_samples": 800, "input_dim": 24,
                         "num_classes": 4}},
    "model": {"factory": "mlp",
               "params": {"input_dim": 24, "hidden_dims": [32],
                          "num_classes": 4}},
    "backend": "tpu",
    # float32 end to end so the three mesh layouts are numerically
    # comparable (same rationale as tests/test_backends.py).
    "tpu": {"compute_dtype": "float32", "num_devices": 8},
}


def _mesh_cfg(**overrides):
    from murmura_tpu.config import Config

    raw = {**_MESH_CFG}
    for key, val in overrides.items():
        raw[key] = {**raw.get(key, {}), **val} if isinstance(val, dict) else val
    return Config.model_validate(raw)


import pytest  # noqa: E402


@pytest.mark.slow
def test_mesh_checkpoint_cross_process_cross_mesh_restore(tmp_path):
    """3 rounds under an 8-virtual-device mesh in a SEPARATE PROCESS
    (checkpoint written on exit), then restore in this process into (a) a
    4-device mesh and (b) the single-device simulation backend, finish the
    remaining 3 rounds in each, and compare against an uninterrupted
    8-device run: identical round lists, matching accuracy/loss curves,
    matching final params.  Exercises the host-gather on save
    (checkpoint.py device_get over sharded arrays) and the re-placement on
    restore under a DIFFERENT device layout — the preemption/resume path a
    real 256-node run would take."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    from murmura_tpu.utils.factories import build_network_from_config

    ckpt = tmp_path / "ckpt"
    cfg_file = tmp_path / "cfg.json"
    cfg_file.write_text(json.dumps(_MESH_CFG))

    # Uninterrupted reference: 6 rounds on the 8-device mesh, in-process.
    full = build_network_from_config(_mesh_cfg())
    full.train(rounds=6)

    # Phase 1 in a fresh OS process: 3 rounds on the 8-device mesh, save.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    script = textwrap.dedent(
        f"""
        import json
        from murmura_tpu.config import Config
        from murmura_tpu.utils.factories import build_network_from_config

        cfg = Config.model_validate(json.load(open({str(cfg_file)!r})))
        net = build_network_from_config(cfg)
        net.train(rounds=3, checkpoint_dir={str(ckpt)!r})
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert has_checkpoint(ckpt)

    # Phase 2a: restore into a DIFFERENT mesh size (4 devices).
    resumed4 = build_network_from_config(_mesh_cfg(tpu={"num_devices": 4}))
    assert resumed4.restore_checkpoint(str(ckpt)) == 3
    resumed4.train(rounds=3)

    # Phase 2b: restore into the single-device simulation backend.
    resumed1 = build_network_from_config(_mesh_cfg(backend="simulation"))
    assert resumed1.restore_checkpoint(str(ckpt)) == 3
    resumed1.train(rounds=3)

    for resumed, label in ((resumed4, "mesh4"), (resumed1, "sim")):
        assert resumed.history["round"] == full.history["round"], label
        np.testing.assert_allclose(
            resumed.history["mean_accuracy"], full.history["mean_accuracy"],
            atol=1e-4, err_msg=label,
        )
        np.testing.assert_allclose(
            resumed.history["mean_loss"], full.history["mean_loss"],
            rtol=1e-3, atol=1e-4, err_msg=label,
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(full.params),
            jax.tree_util.tree_leaves(resumed.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, err_msg=label
            )
