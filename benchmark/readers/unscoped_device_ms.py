"""Device milliseconds a round spent in operations under no ``murmura.*``
scope: what the round program runs outside every ``jax.named_scope``
bracket of ``core/rounds.py``, and whatever programs the join has no text
of (the orchestrator's per-round fold of the key)."""


def read(context):
    trace, rounds = context["trace"], context["traced_rounds"]
    if not trace.devices or not rounds:
        return None
    return trace.unscoped_s / rounds * 1e3
