"""Per cent of the traced window in which no operation ran on the device."""


def read(context):
    trace = context["trace"]
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
