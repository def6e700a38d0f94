"""Milliseconds a round of the traced window left the device idle: the
window less the union of the device-busy intervals, over the rounds."""


def read(context):
    trace, rounds = context["trace"], context["traced_rounds"]
    if not trace.devices or not rounds:
        return None
    return (trace.window_s - trace.busy_s) / rounds * 1e3
