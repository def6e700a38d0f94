"""Operations of the ZAYA1 decoder as one chip's share runs it, from the
sizes in ``zaya1_8b_ep2.json``.

A multiply-add counts two operations.  Counted for one sample, a sequence
of ``seq_len`` positions: every product of a position (2 x rows x columns:
the four attention projections, the grouped convolution's taps, the
router's four products, the head over the held rows of the vocabulary,
which is the embedding's transpose), the depthwise convolution's
multiply-adds (2 a channel and tap), attention's causal pairs (a position
meets itself and what precedes it: ``T (T + 1) / 2`` pairs a query head,
``2 dh`` for the score and ``2 dh`` for the value), and the routed experts
a position touches **by expectation under a uniform router**: of its
``num_experts_per_tok`` chosen out of the published experts, the share that
is held here (1 x 8 / 16 = 0.5 experts a position).  What the router really
sends here is the run's (``moe.held_share``).  The lookup is free; norms,
the L2 norms, rotary positions, the value shift, the q-k mean, the
softmaxes, the temperatures, the residual scales and SwiGLU's elementwise
part are left out (under 1 %).  Training a sample is one forward and two
backward passes' worth (3x), as is usual for an MFU: the layers'
recomputation in the backward pass is not counted.
"""


def _sizes(doc: dict):
    routed = doc.get("published", {}).get("num_experts", doc["num_experts"])
    return doc["num_layers"], routed


def _attention_matrices(doc: dict) -> int:
    """The projections' entries: q, k, v and o."""
    h, hq, hk, dh = (doc["hidden_size"], doc["num_attention_heads"],
                     doc["num_key_value_heads"], doc["head_dim"])
    return h * hq * dh + 2 * h * hk * dh + hq * dh * h


def _convolution_taps(doc: dict) -> int:
    """The two convolutions' entries: depthwise over the q~ and k~ channels,
    then dh x dh a head group and tap."""
    groups, dh = doc["num_attention_heads"] + doc["num_key_value_heads"], doc["head_dim"]
    return doc["cca_time0"] * groups * dh + doc["cca_time1"] * groups * dh * dh


def _router_matrices(doc: dict, routed: int) -> int:
    r = doc["router_hidden_size"]
    return doc["hidden_size"] * r + 2 * r * r + r * routed


def _pairs_flops(doc: dict) -> float:
    """Attention's products between positions, one layer, one sequence."""
    t = doc["seq_len"]
    return 2.0 * doc["num_attention_heads"] * 2 * doc["head_dim"] * (t * (t + 1) / 2)


def forward_flops_per_sample(doc: dict) -> float:
    layers, routed = _sizes(doc)
    h, t = doc["hidden_size"], doc["seq_len"]
    expert = 3 * h * doc["moe_intermediate_size"]
    touched = doc["num_experts_per_tok"] * doc["num_experts"] / routed
    position = (
        layers * (_attention_matrices(doc) + _convolution_taps(doc)
                  + _router_matrices(doc, routed) + touched * expert)
        + h * doc["vocab_size"]
    )
    return 2.0 * position * t + layers * _pairs_flops(doc)


def forward_flops_from_shapes(shapes, doc: dict) -> float:
    """The same from the program's leaves (``(path, shape)`` of each): a
    stacked matrix or convolution is multiplied at every position, once a
    layer it holds; an expert's matrices at the share of positions that
    choose it (``num_experts_per_tok`` over the router's width, which the
    router's last leaf gives); the embedding once, as the head; norms,
    scales, temperatures, the depth's scalars and the selection bias
    multiply nothing; attention's pairs once for every layer the query
    projection's leaf holds."""
    t = doc["seq_len"]
    routed = next(s[-1] for p, s in shapes if p.endswith("['router']['w3']"))
    skipped = ("norm", "bias", "scales", "temperature", "depth")
    total = 0.0
    for path, shape in shapes:
        if any(word in path for word in skipped):
            continue
        size = 1.0
        for d in shape:
            size *= d
        if "['experts']" in path:
            size *= doc["num_experts_per_tok"] / routed
        total += 2.0 * size * t
        if path.endswith("['cca']['q']"):
            total += shape[0] * _pairs_flops(doc)
    return total


def train_flops_per_sample(doc: dict) -> float:
    return 3.0 * forward_flops_per_sample(doc)


def parameter_count(doc: dict) -> int:
    layers, routed = _sizes(doc)
    h, r = doc["hidden_size"], doc["router_hidden_size"]
    layer = (
        _attention_matrices(doc) + _convolution_taps(doc) + doc["num_attention_heads"]
        + 6 * h  # the two norms and the four residual scales
        + _router_matrices(doc, routed) + 1 + r + routed  # depth, norm, selection bias
        + doc["num_experts"] * 3 * h * doc["moe_intermediate_size"]
    )
    return doc["vocab_size"] * h + h + layers * layer
