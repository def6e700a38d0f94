"""``attention_kernel_roofline`` on the real decoder cells: the counted work
of ``roofline/causal_attention.py`` against hand values, and the
``kernel_roofline`` reader finding the attention kernels by the one name
they share among the innermost operations of a trace."""

import types

import pytest

from benchmark.cells import Cell, load_peaks
from benchmark.readers import kernel_roofline
from benchmark.roofline import causal_attention


@pytest.mark.parametrize("name,layer_forward,round_ms", [
    # 2 x 16 heads x (192 + 128) x 4,096 x 4,097 / 2 pairs; 5 layers
    ("moonlight_fedavg_full_n3", 2.0 * 16 * 320 * 4096 * 4097 / 2, 45.8),
    # 2 x 8 query heads x (128 + 128) x the same pairs; 4 layers
    ("zaya1_fedavg_full_n3", 2.0 * 8 * 256 * 4096 * 4097 / 2, 14.7),
], ids=["moonlight", "zaya1"])
def test_the_causal_attention_cores_work_against_hand_values(name, layer_forward, round_ms):
    """The exact lower triangle a layer and sequence (Moonlight: 85.9
    GFLOP), three passes a trained sequence and one an evaluated: 3 nodes
    x (2 x 3 + 1) = 21 passes a round; flops bind.  The reader finds the
    forward, the recomputed forward and the backward kernel by the one
    name they share, among the innermost operations."""
    cell = Cell(name)
    flops, bytes_ = causal_attention.work(cell.config, 2)
    layers = cell.config["num_layers"]
    assert flops == layers * layer_forward
    if name.startswith("moonlight"):
        assert layer_forward == pytest.approx(85.9e9, rel=1e-3)
    peaks = load_peaks("TPU v5 lite")
    seconds, bound = causal_attention.least_seconds(cell, peaks, "bfloat16")
    assert bound == "flops" and seconds == pytest.approx(21 * flops / peaks["flops_bf16"])
    assert seconds * 1e3 == pytest.approx(round_ms, abs=0.05)
    kernels = {"causal_attention_fwd.3": 2e-3, "jvp_causal_attention_fwd_.1": 1e-3,
               "transpose_jvp_causal_attention_bwd__.2": 5e-3}
    context = {"cell": cell, "traced_rounds": 1, "peaks": peaks, "param_dtype": "bfloat16",
               "trace": types.SimpleNamespace(op_s={"while.3": 1.0},
                                              leaf_op_s={**kernels, "fusion.2": 0.5})}
    spec = cell.layer_metric("attention_kernel_roofline")
    assert kernel_roofline.read(context, **spec["args"]) == pytest.approx(
        100.0 * seconds / 8e-3)
