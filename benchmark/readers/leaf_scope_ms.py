"""Device milliseconds a round spent in the innermost operations (those
that run no other: the operations inside a loop, not the loop) under a
chain of ``murmura.*`` labels: ``chain`` itself and every chain below it,
so ``murmura.train`` reads all of the training loop's operations and
``murmura.train/murmura.attention`` those under that label inside it.
What the loop's own event has beyond them is the loop's overhead."""


def read(context, chain: str):
    trace, rounds = context["trace"], context["traced_rounds"]
    found = [s for c, s in trace.leaf_s.items()
             if c == chain or c.startswith(chain + "/")]
    if not found or not rounds:
        return None
    return sum(found) / rounds * 1e3
