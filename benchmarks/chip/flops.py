"""Operations and bytes of CosmoFlow, from the shapes of a configuration.

Counts follow the model, not the program: each layer once per sample,
whatever layout runs it, a multiply-add as two operations, and bytes as
the float32 tensors a layer must at least read and write (no padding, no
re-reads). The counts cover the convolutions and the fully connected
layers; batch-norm, activations and pooling are a rounding error in
operations (they bound bytes, which ``conv_bytes`` leaves to the trace).
"""
from __future__ import annotations

import math
from typing import List

F32 = 4


def conv_layers(m: dict) -> List[dict]:
    """One record per conv block: input and output width, channels,
    kernel, stride, and whether a 2x2x2 max-pool follows."""
    chans = list(m["conv_channels"])
    n_pool = min(int(math.log2(m["input_width"])) - 2, len(chans))
    out, w, cin = [], m["input_width"], m["in_channels"]
    for i, c in enumerate(chans):
        stride = 2 if i == 3 else 1
        w_out = w // stride
        out.append({"block": i, "w_in": w, "w_out": w_out, "c_in": cin,
                    "c_out": c, "k": m.get("kernel_size", 3),
                    "stride": stride, "pool": i < n_pool})
        w = w_out // 2 if i < n_pool else w_out
        cin = c
    return out


def fc_layers(m: dict) -> List[tuple]:
    """(fan_in, fan_out) of each fully connected layer."""
    last = conv_layers(m)[-1]
    w = last["w_out"] // 2 if last["pool"] else last["w_out"]
    dims = [last["c_out"] * w ** 3] + list(m["fc_dims"]) + [m["out_dim"]]
    return list(zip(dims[:-1], dims[1:]))


def _conv_fwd_flops(layer: dict) -> float:
    return (2.0 * layer["w_out"] ** 3 * layer["k"] ** 3 * layer["c_in"]
            * layer["c_out"])


def _conv_fwd_bytes(layer: dict) -> float:
    act_in = layer["w_in"] ** 3 * layer["c_in"]
    act_out = layer["w_out"] ** 3 * layer["c_out"]
    weights = layer["k"] ** 3 * layer["c_in"] * layer["c_out"]
    return F32 * (act_in + act_out + weights)


def forward_flops(m: dict) -> float:
    """Operations of one sample's forward pass."""
    return (sum(_conv_fwd_flops(c) for c in conv_layers(m))
            + sum(2.0 * a * b for a, b in fc_layers(m)))


def train_flops(m: dict) -> float:
    """Operations of one sample's forward and backward pass: the weight
    gradient of every layer, and the input gradient of every layer but
    the first (the input volume needs none)."""
    convs = conv_layers(m)
    f = sum(3 * _conv_fwd_flops(c) for c in convs) - _conv_fwd_flops(convs[0])
    return f + sum(3 * 2.0 * a * b for a, b in fc_layers(m))


def conv_flops(m: dict, train: bool) -> float:
    """Operations of one sample's convolutions (forward, or forward and
    backward)."""
    convs = conv_layers(m)
    fwd = sum(_conv_fwd_flops(c) for c in convs)
    if not train:
        return fwd
    return 3 * fwd - _conv_fwd_flops(convs[0])


def conv_bytes(m: dict, train: bool) -> float:
    """Least bytes one sample's convolutions move: each reads its input
    and weights and writes its output; backward, the input gradient reads
    the output gradient and weights and writes the input gradient, and
    the weight gradient reads input and output gradient and writes the
    weights' gradient."""
    convs = conv_layers(m)
    fwd = sum(_conv_fwd_bytes(c) for c in convs)
    if not train:
        return fwd
    return 3 * fwd - _conv_fwd_bytes(convs[0])
