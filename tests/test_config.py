"""Config schema + loader tests (reference surface: murmura/config/)."""

import pytest
from pydantic import ValidationError

from murmura_tpu.config import Config, load_config, save_config

BASIC = {
    "experiment": {"name": "t", "seed": 1, "rounds": 3},
    "topology": {"type": "ring", "num_nodes": 4},
    "aggregation": {"algorithm": "fedavg"},
    "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
    "data": {"adapter": "synthetic", "params": {"num_samples": 64}},
    "model": {"factory": "mlp", "params": {"input_dim": 32, "num_classes": 10}},
}


def test_defaults():
    cfg = Config.model_validate(BASIC)
    assert cfg.backend == "simulation"
    assert cfg.attack.enabled is False
    assert cfg.distributed.transport == "ipc"
    assert cfg.tpu.exchange == "allgather"
    assert cfg.mobility is None and cfg.dmtt is None


def test_reference_yaml_surface_loads(tmp_path):
    """A reference-style YAML (basic_fedavg shape) validates unchanged."""
    yaml_text = """
experiment:
  name: "basic-fedavg-test"
  seed: 42
  rounds: 20
  verbose: true
topology:
  type: "fully"
  num_nodes: 5
aggregation:
  algorithm: "fedavg"
  params: {}
attack:
  enabled: false
training:
  local_epochs: 3
  batch_size: 64
  lr: 0.001
  max_samples: null
data:
  adapter: "leaf.femnist"
  params:
    synthetic: true
model:
  factory: "examples.leaf.LEAFFEMNISTModel"
  params:
    num_classes: 62
"""
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml_text)
    cfg = load_config(p)
    assert cfg.topology.type == "fully"
    assert cfg.model.factory == "examples.leaf.LEAFFEMNISTModel"


def test_tpu_backend_enum():
    cfg = Config.model_validate({**BASIC, "backend": "tpu"})
    assert cfg.backend == "tpu"


def test_extra_fields_forbidden():
    with pytest.raises(Exception):
        Config.model_validate({**BASIC, "bogus": 1})


@pytest.mark.parametrize("field,value", [
    ("conv_impl", "direct"),
    ("donate_state", True),
])
def test_retired_tpu_switches_are_refused_by_name(field, value):
    """``tpu.conv_impl`` and ``tpu.donate_state`` left the schema (the
    direct convolution and donation are the only paths): a YAML that still
    sets one, even to the old default, is refused with the field named."""
    refused = rf"tpu\.{field}\s+Extra inputs are not permitted"
    with pytest.raises(ValidationError, match=refused):
        Config.model_validate(
            {**BASIC, "backend": "tpu", "tpu": {field: value}}
        )


def test_roundtrip(tmp_path):
    cfg = Config.model_validate(BASIC)
    for name in ("c.yaml", "c.json"):
        path = tmp_path / name
        save_config(cfg, path)
        again = load_config(path)
        assert again.experiment.name == cfg.experiment.name
        assert again.topology.num_nodes == 4


def test_dmtt_requires_mobility():
    with pytest.raises(Exception, match="mobility"):
        Config.model_validate({**BASIC, "dmtt": {"budget_B": 3}})
    # Explicit opt-in verifies claims against the static topology instead.
    cfg = Config.model_validate(
        {**BASIC, "dmtt": {"budget_B": 3, "allow_static": True}}
    )
    assert cfg.dmtt.allow_static
    # With mobility present the validator is satisfied.
    cfg = Config.model_validate(
        {**BASIC, "dmtt": {"budget_B": 3}, "mobility": {"comm_range": 30.0}}
    )
    assert cfg.mobility is not None


def test_param_dtype_auto_large_n_default():
    """tpu.param_dtype None = auto: bfloat16 from 64 nodes (the documented
    large-N setting), float32 below;
    an explicit setting always wins (factories.resolved_param_dtype)."""
    from murmura_tpu.utils.factories import resolved_param_dtype

    def cfg(nodes, **tpu):
        return Config.model_validate(
            {
                "experiment": {"name": "pd", "seed": 0, "rounds": 1},
                "topology": {"type": "ring", "num_nodes": nodes},
                "aggregation": {"algorithm": "fedavg"},
                "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.1},
                "data": {"adapter": "synthetic",
                          "params": {"num_samples": 64, "input_dim": 4,
                                     "num_classes": 2}},
                "model": {"factory": "mlp",
                           "params": {"input_dim": 4, "num_classes": 2}},
                "backend": "tpu",
                "tpu": tpu,
            }
        )

    assert resolved_param_dtype(cfg(8)) == "float32"
    assert resolved_param_dtype(cfg(64)) == "bfloat16"
    assert resolved_param_dtype(cfg(256, param_dtype="float32")) == "float32"
    assert resolved_param_dtype(cfg(8, param_dtype="bfloat16")) == "bfloat16"
    sim = cfg(256).model_copy(update={"backend": "simulation"})
    assert resolved_param_dtype(sim) is None
