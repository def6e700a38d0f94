"""Operations of the DeepSeek-V3-family decoder as one chip's share runs it,
from the sizes in ``moonlight_16b_a3b_ep8.json``.

A multiply-add counts two operations.  Counted for one sample, a sequence
of ``seq_len`` positions: every product of a position (2 x rows x columns:
the four attention projections, the dense layer's and the shared experts'
SwiGLU, the router over all published experts, the head over the held rows
of the vocabulary), attention's causal pairs (a position meets itself and
what precedes it: ``T (T + 1) / 2`` pairs a head, ``2 (nope + rope)`` for
the score and ``2 v`` for the value), and the routed experts a position
touches **by expectation under a uniform router**: of its
``num_experts_per_tok`` chosen out of the published experts, the share
that is held here (6 x 8 / 64 = 0.75 experts a position).  What the router
really sends here is the run's (``moe.held_share``).  The lookup is free;
norms, the softmax, rotary positions, SwiGLU's elementwise part and the
sort are left out (under 1 %).  Training a sample is one forward and two
backward passes' worth (3x), as is usual for an MFU: the layers'
recomputation in the backward pass is not counted.
"""


def _sizes(doc: dict):
    dense = min(doc["first_k_dense_replace"], doc["num_layers"])
    routed = doc.get("published", {}).get("n_routed_experts", doc["n_routed_experts"])
    return dense, doc["num_layers"] - dense, routed


def _attention_matrices(doc: dict) -> int:
    h, heads = doc["hidden_size"], doc["num_attention_heads"]
    nope, rope, v = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"]
    latent = doc["kv_lora_rank"]
    return (h * heads * (nope + rope) + h * (latent + rope)
            + latent * heads * (nope + v) + heads * v * h)


def _pairs_flops(doc: dict) -> float:
    """Attention's products between positions, one layer, one sequence."""
    t, heads = doc["seq_len"], doc["num_attention_heads"]
    width = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"] + doc["v_head_dim"]
    return 2.0 * heads * width * (t * (t + 1) / 2)


def forward_flops_per_sample(doc: dict) -> float:
    dense, moe, routed = _sizes(doc)
    h, t = doc["hidden_size"], doc["seq_len"]
    expert = 3 * h * doc["moe_intermediate_size"]
    touched = doc["num_experts_per_tok"] * doc["n_routed_experts"] / routed
    position = (
        (dense + moe) * _attention_matrices(doc)
        + dense * 3 * h * doc["intermediate_size"]
        + moe * (h * routed + doc["n_shared_experts"] * expert + touched * expert)
        + h * doc["vocab_size"]
    )
    return 2.0 * position * t + (dense + moe) * _pairs_flops(doc)


def forward_flops_from_shapes(shapes, doc: dict) -> float:
    """The same from the program's leaves (``(path, shape)`` of each): a
    stacked matrix is multiplied at every position, once a layer it holds;
    an expert's matrices at the share of positions that choose it
    (``num_experts_per_tok`` over the router's width, which the router's
    own leaf gives); the lookup is free; norms and the selection bias
    multiply nothing; attention's pairs once for every layer the query
    projection's leaf holds."""
    t = doc["seq_len"]
    width = {p: s for p, s in shapes}
    routed = next(s[-1] for p, s in shapes if p.endswith("['router']['w']"))
    total = 0.0
    for path, shape in shapes:
        size = 1.0
        for d in shape:
            size *= d
        if "norm" in path or "bias" in path or path == "['embed']":
            continue
        if "['experts']" in path:
            size *= doc["num_experts_per_tok"] / routed
        total += 2.0 * size * t
        if path.endswith("['attn']['q']"):
            total += shape[0] * _pairs_flops(doc)
    assert width, "no leaves"
    return total


def train_flops_per_sample(doc: dict) -> float:
    return 3.0 * forward_flops_per_sample(doc)


def parameter_count(doc: dict) -> int:
    dense, moe, routed = _sizes(doc)
    h = doc["hidden_size"]
    block = _attention_matrices(doc) + doc["kv_lora_rank"] + 2 * h
    expert = 3 * h * doc["moe_intermediate_size"]
    return (
        2 * doc["vocab_size"] * h + h
        + dense * (block + 3 * h * doc["intermediate_size"])
        + moe * (block + h * routed + routed
                 + (doc["n_shared_experts"] + doc["n_routed_experts"]) * expert)
    )
