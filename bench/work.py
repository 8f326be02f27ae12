"""The work a routed ESSR frame needs: multiply-accumulates and bytes.

The benchmark's own yardstick for rooflines and utilisation. It counts the
algorithm's work from the configuration and the routing, whatever kernels
implement it: a BSConv is a 1x1 pointwise then a 3x3 depthwise, an SFB two
BSConvs and a 1x1 fuse, the reconstruction a 3x3 depthwise then a 1x1 to
3*scale^2 channels (arXiv:2503.20245, Sec. III). Bias adds are not counted.
"""
from __future__ import annotations

from typing import Sequence

BILINEAR, C27, C54 = 0, 1, 2
FP32_BYTES = 4


def subnet_widths(model: dict) -> tuple:
    """(bilinear, C/2, C): the paper's three subnets, width 0 is bilinear."""
    c = int(model["channels"])
    return (0, c // 2, c)


def macs_per_lr_pixel(model: dict, width: int) -> int:
    """Multiply-accumulates per LR pixel of the subnet of ``width``."""
    s, cin = int(model["scale"]), int(model["in_channels"])
    if width == 0:
        return 4 * cin * s * s          # 4 bilinear taps per HR value
    c, n_sfb = int(width), int(model["n_sfb"])
    cout = cin * s * s
    first = cin * c + 9 * c
    sfb = 2 * (c * c + 9 * c) + c * c
    recon = 9 * c + c * cout
    return first + n_sfb * sfb + recon


def param_count(model: dict) -> int:
    """Weights and biases of the full-width supernet."""
    c, cin, s = int(model["channels"]), int(model["in_channels"]), int(model["scale"])
    b = 1 if model["bias"] else 0
    cout = cin * s * s
    first = cin * c + b * c + 9 * c + b * c
    sfb = 2 * (c * c + b * c + 9 * c + b * c) + c * c + b * c
    recon = 9 * c + b * c + c * cout + b * cout
    return first + int(model["n_sfb"]) * sfb + recon


def frame_macs(model: dict, patch: int, counts: Sequence[int]) -> int:
    """MACs of one frame routed as ``counts`` = (bilinear, C27, C54) patches,
    each on its full ``patch`` x ``patch`` extent."""
    area = patch * patch
    return sum(int(n) * macs_per_lr_pixel(model, w) * area
               for n, w in zip(counts, subnet_widths(model)))


def subnet_macs(model: dict, patch: int, counts: Sequence[int]) -> int:
    """MACs of the conv subnets only (C27 and C54): the work of the kernels."""
    return frame_macs(model, patch, (0, counts[C27], counts[C54]))


def subnet_bytes(model: dict, patch: int, counts: Sequence[int]) -> int:
    """HBM bytes the conv subnets must move: each routed LR patch read once
    and its HR patch written once, in fp32. Weights (under 0.25 MB) are
    left out."""
    s, cin = int(model["scale"]), int(model["in_channels"])
    n = int(counts[C27]) + int(counts[C54])
    per_patch = patch * patch * cin * (1 + s * s)
    return n * per_patch * FP32_BYTES


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time at the peaks."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
