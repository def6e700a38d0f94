"""Operations of LEAF's FEMNIST CNN, from the sizes in ``femnist_cnn.json``.

A multiply-add counts two operations; bias adds, ReLU, pooling and the
softmax are left out (under 1 % here).  Training a sample costs one forward
and two backward passes' worth of the matmul work (3x), the first layer's
input gradient included, as is usual for an MFU.
"""


def forward_flops_per_sample(doc: dict) -> float:
    side, c_in, k = doc["image_size"], doc["channels_in"], doc["kernel_size"]
    total = 0.0
    for c_out in doc["conv_channels"]:  # SAME conv, then a 2x2 pool
        total += 2.0 * side * side * k * k * c_in * c_out
        side, c_in = side // 2, c_out
    width = side * side * c_in
    for units in list(doc["dense_units"]) + [doc["num_classes"]]:
        total += 2.0 * width * units
        width = units
    return total


def train_flops_per_sample(doc: dict) -> float:
    return 3.0 * forward_flops_per_sample(doc)


def parameter_count(doc: dict) -> int:
    side, c_in, k = doc["image_size"], doc["channels_in"], doc["kernel_size"]
    total = 0
    for c_out in doc["conv_channels"]:
        total += k * k * c_in * c_out + c_out
        side, c_in = side // 2, c_out
    width = side * side * c_in
    for units in list(doc["dense_units"]) + [doc["num_classes"]]:
        total += width * units + units
        width = units
    return total
