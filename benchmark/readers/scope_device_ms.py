"""Device milliseconds a round spent in operations under the given
``murmura.*`` scopes (``jax.named_scope`` in ``core/rounds.py``)."""


def scope_seconds(context, scopes):
    trace = context["trace"]
    found = [trace.scope_s[s] for s in scopes if s in trace.scope_s]
    return sum(found) if found else None


def read(context, scopes):
    seconds, rounds = scope_seconds(context, scopes), context["traced_rounds"]
    if seconds is None or not rounds:
        return None
    return seconds / rounds * 1e3
