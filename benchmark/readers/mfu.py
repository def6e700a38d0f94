"""The whole round's share of the chip's peak: the forward and backward
operations of the cell's model for the samples a round trains on (a
function of the model's shapes, kept beside its configuration), over the
traced window's time per round and the peak bf16 rate of the device."""


def read(context):
    cell, rounds = context["cell"], context["traced_rounds"]
    window = context["traced_window_s"]
    if not rounds or window <= 0:
        return None
    flops = cell.module("configs", cell.config["flops"]).train_flops_per_sample(
        cell.config
    ) * context["samples_per_round"]
    peak = context["peaks"]["flops_bf16"] * cell.chips
    return 100.0 * flops * rounds / (window * peak)
