"""Device milliseconds a round spent in the innermost operations whose
chain of ``murmura.*`` labels is exactly ``chain``, with none of the chains
below it (``leaf_scope_ms`` sums those too): ``murmura.train`` reads the
training loop's operations that carry no inner label (the scanned stack's
slices, a node's slice of the state, the compiler's copies without an
``op_name``), ``murmura.train/murmura.experts`` what carries that label
and none inside it.  What a chain holds follows the labels the program
opens: on a program without ``murmura.rows`` and ``murmura.pairs``
``murmura.train/murmura.experts`` is all of the experts, a reading that
does not compare with one taken under those labels."""


def read(context, chain: str):
    trace, rounds = context["trace"], context["traced_rounds"]
    if chain not in trace.leaf_s or not rounds:
        return None
    return trace.leaf_s[chain] / rounds * 1e3
