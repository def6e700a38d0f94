"""The routed experts' grouped products: the three products of a held
expert's SwiGLU (2 x hidden x ``moe_intermediate_size`` a row each) on the
(position, chosen expert) pairs that fall on the experts held here, **by
expectation under a uniform router** (``num_experts_per_tok`` x held /
published experts a position: 0.75 at 6 x 8 / 64; what the router really
sends here is the run's, ``moe.held_share``).  A sample trained on costs
one forward and two backward passes' worth, a sample evaluated one forward;
recomputation, the rows that pad a group to the kernel's tile or a layer
to a floor, and the gathers around the products are an implementation's
and not counted.  A
pass reads the held experts' matrices once and moves a pair's rows in and
out once (hidden in and out, the inner width twice in and once out of the
three products), all at the resident width.
"""

PASSES_TRAINED, PASSES_EVALUATED = 3, 1


def work(doc: dict, itemsize: int):
    """(operations, bytes) of one pass over one sample, all expert layers."""
    layers = doc["num_layers"] - min(doc["first_k_dense_replace"], doc["num_layers"])
    held, h, w = doc["n_routed_experts"], doc["hidden_size"], doc["moe_intermediate_size"]
    routed = doc.get("published", {}).get("n_routed_experts", held)
    rows = doc["seq_len"] * doc["num_experts_per_tok"] * held / routed
    flops = 2.0 * rows * 3 * h * w
    bytes_ = held * 3 * h * w * itemsize + rows * itemsize * (2 * h + 3 * w)
    return layers * flops, layers * bytes_


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
