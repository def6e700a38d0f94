"""A rule's share of its roofline: the least time the chip could take for
the rule's own work at the cell's shapes (``roofline/<rule>.py``: a
function of the rule, the graph and the model's width, whatever implements
it) over the device time of the rule's scopes in a round."""

from benchmark.readers.scope_device_ms import scope_seconds


def read(context, scopes):
    cell, rounds = context["cell"], context["traced_rounds"]
    seconds = scope_seconds(context, scopes)
    if not seconds or not rounds:
        return None
    rule = cell.job["aggregation"]["algorithm"]
    least, bound = cell.module("roofline", rule).least_seconds(
        cell, context["peaks"], context["param_dtype"]
    )
    print(f"[bench] roofline {rule}: least {least * 1e3:.4f} ms a round, "
          f"bound by {bound}; measured {seconds / rounds * 1e3:.4f} ms", flush=True)
    return 100.0 * least / (seconds / rounds)
