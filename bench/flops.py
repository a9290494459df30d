"""Operations and bytes of the benchmark's programs, counted from shapes.

Counts follow the configuration file's sizes alone, so a change to the
program cannot change them.  A multiply-add is two operations.
"""
from __future__ import annotations

F32_BYTES = 4


def layers(cfg: dict) -> list[dict]:
    """Every weighted layer with its multiply-adds per sample.

    Convolutions are "SAME"-padded at stride 1, each followed by a
    ``pool`` x ``pool`` max-pool; the classifier is a chain of dense
    layers from the flattened features to ``num_classes``."""
    out = []
    size, cin = cfg["image_size"], cfg["in_channels"]
    for conv in cfg["convs"]:
        k, cout = conv["kernel"], conv["out_channels"]
        out.append({"kind": "conv", "weights": k * k * cin * cout,
                    "bias": cout, "macs": size * size * k * k * cin * cout})
        size //= conv["pool"]
        cin = cout
    dims = [size * size * cin, *cfg["fc_hidden"], cfg["num_classes"]]
    for d_in, d_out in zip(dims, dims[1:]):
        out.append({"kind": "fc", "weights": d_in * d_out, "bias": d_out,
                    "macs": d_in * d_out})
    return out


def param_count(cfg: dict) -> int:
    return sum(l["weights"] + l["bias"] for l in layers(cfg))


def forward_flops_per_sample(cfg: dict) -> int:
    """Operations of the convolutions and dense layers of one sample's
    forward pass.  Bias, activation, pooling and the softmax are left
    out, as is usual for a model's operation count."""
    return 2 * sum(l["macs"] for l in layers(cfg))


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward operations of one sample: the forward pass,
    the weight gradient of every layer, and the input gradient of every
    layer but the first (the images need none)."""
    ls = layers(cfg)
    fwd = 2 * sum(l["macs"] for l in ls)
    return fwd + fwd + 2 * sum(l["macs"] for l in ls[1:])


def server_step_bytes(cfg: dict, members: int) -> int:
    """HBM bytes one server step has to move at the least: ``members``
    float32 gradients, the parameters and the accumulator read, the new
    parameters and accumulator written."""
    return (members + 4) * param_count(cfg) * F32_BYTES


def server_step_flops(cfg: dict, members: int) -> int:
    """Operations of one server step per parameter: a multiply-add per
    member for the weighted mean, then the square, add, rsqrt, two
    multiplies and the subtract of the AdaGrad update."""
    return (2 * members + 6) * param_count(cfg)
