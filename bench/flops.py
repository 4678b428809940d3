"""Model operations computed from shapes, never from the compiled
program.

``train_flops_per_image``: the multiply-adds of every convolution and of
the FC layer of a bottleneck ResNet at its input size, times 2 FLOP per
multiply-add, times 3 for a training step (the forward pass, and a
backward pass that computes the gradients of both activations and
weights). BN, ReLU, pooling and the update are left out: they are
memory-bound and a few tenths of a percent of the operations.
"""
from __future__ import annotations

from typing import Dict


def _out(size: int, stride: int) -> int:
    return -(-size // stride)  # SAME padding


def forward_macs_per_image(model: Dict) -> int:
    w = model["conv_width"]
    exp = model["bottleneck_expansion"]
    s = _out(model["image_size"], 2)
    macs = s * s * 7 * 7 * model["image_channels"] * w  # stem conv
    s = _out(s, 2)  # 3x3/2 max pool
    c_in = w
    for si, blocks in enumerate(model["conv_stages"]):
        mid = w * 2 ** si
        c_out = mid * exp
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            s_out = _out(s, stride)
            macs += s * s * c_in * mid            # conv1, 1x1
            macs += s_out * s_out * 9 * mid * mid  # conv2, 3x3, strided
            macs += s_out * s_out * mid * c_out    # conv3, 1x1
            if bi == 0:
                macs += s_out * s_out * c_in * c_out  # projection
            s, c_in = s_out, c_out
    return macs + c_in * model["num_classes"]


def train_flops_per_image(model: Dict) -> float:
    return 3.0 * 2.0 * forward_macs_per_image(model)


def param_count(model: Dict) -> int:
    w = model["conv_width"]
    exp = model["bottleneck_expansion"]
    n = 7 * 7 * model["image_channels"] * w + 2 * w
    c_in = w
    for si, blocks in enumerate(model["conv_stages"]):
        mid = w * 2 ** si
        c_out = mid * exp
        for bi in range(blocks):
            n += c_in * mid + 9 * mid * mid + mid * c_out
            n += 2 * (mid + mid + c_out)
            if bi == 0:
                n += c_in * c_out + 2 * c_out
            c_in = c_out
    return n + c_in * model["num_classes"] + model["num_classes"]
