"""The decoders' causal attention core: the scores of every query head
against the keys at and before its position and the values they weigh,
``2 x heads x (dqk + dv)`` operations a (query, key) pair of the exact
lower triangle, ``T (T + 1) / 2`` pairs a sequence, in every layer.  A
sequence trained on costs one forward and two backward passes' worth, a
sequence evaluated one forward; the recomputed forward, the blocks above
the diagonal and the padding of a block are an implementation's and not
counted.  A pass reads ``q``, ``k`` and ``v`` and writes the result once,
at the resident width.

Read from the configuration's own keys: latent attention
(``qk_nope_head_dim + qk_rope_head_dim`` against ``v_head_dim``, as
Moonlight's) or one ``head_dim`` for both, key/value heads
(``num_key_value_heads``) as published, else as many as the query heads.
"""

PASSES_TRAINED, PASSES_EVALUATED = 3, 1


def work(doc: dict, itemsize: int):
    """(operations, bytes) of one pass over one sequence, all layers."""
    heads, t = doc["num_attention_heads"], doc["seq_len"]
    if "qk_nope_head_dim" in doc:
        dqk, dv = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"], doc["v_head_dim"]
    else:
        dqk = dv = doc["head_dim"]
    kv_heads = doc.get("num_key_value_heads", heads)
    flops = 2.0 * heads * (dqk + dv) * t * (t + 1) / 2
    bytes_ = t * itemsize * (heads * (dqk + dv) + kv_heads * (dqk + dv))
    return doc["num_layers"] * flops, doc["num_layers"] * bytes_


def least_seconds(cell, peaks: dict, param_dtype: str):
    from benchmark.roofline.shapes import least, shapes

    n, _, _, itemsize = shapes(cell, param_dtype)
    data, training = cell.config["data"], cell.job["training"]
    held_out = int(data["held_out_per_node"])
    trained = (int(data["samples_per_node"]) - held_out) * int(training["local_epochs"])
    evaluated = held_out / int(cell.job["dispatch"].get("eval_every", 1))
    passes = n * (PASSES_TRAINED * trained + PASSES_EVALUATED * evaluated)
    flops, bytes_ = work(cell.config, itemsize)
    return least(passes * flops, passes * bytes_, peaks)
