"""The shapes a rule's roofline is a function of."""


def shapes(cell, param_dtype: str):
    """(nodes, mean degree, parameters, bytes of a resident parameter);
    ``param_dtype`` as the harness read it off the built state."""
    topo = cell.job["topology"]
    n = int(topo["num_nodes"])
    if topo["type"] == "k-regular":
        degree = float(topo["k"])
    elif topo["type"] == "erdos":
        degree = float(topo.get("p", 0.3)) * (n - 1)
    elif topo["type"] == "ring":
        degree = 2.0
    elif topo["type"] == "fully":
        degree = float(n - 1)
    else:
        raise KeyError(f"no degree known for topology {topo['type']!r}")
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[param_dtype]
    return n, degree, int(cell.config["num_parameters"]), itemsize


def least(flops: float, bytes_: float, peaks: dict):
    by_flops = flops / peaks["flops_bf16"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops > by_bytes else "bytes"
