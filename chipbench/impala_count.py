"""Operations and bytes of IMPALA-CNN (torso and dense head), from a
configuration file (``configs/impala-*.json``).

Counts use the real channel counts, and a multiply-add is two
operations.  Only the convs and the dense layer count: max-pools,
residual adds, biases and activations do not.  Every conv is 3x3,
stride 1, SAME: a stack's entry conv runs at the stack's input size, its
residual convs at the pooled size ``ceil(n / 2)``.
"""
from __future__ import annotations


def _stacks(cfg: dict):
    """(input h, input w, c_in, depth) of each stack."""
    h, w, c = cfg["in_h"], cfg["in_w"], cfg["c_in"]
    for d in cfg["depths"]:
        yield h, w, c, d
        h, w, c = -(-h // 2), -(-w // 2), d


def feature_shape(cfg: dict) -> tuple[int, int, int]:
    h, w = cfg["in_h"], cfg["in_w"]
    for _ in cfg["depths"]:
        h, w = -(-h // 2), -(-w // 2)
    return h, w, cfg["depths"][-1]


def flat_features(cfg: dict) -> int:
    h, w, c = feature_shape(cfg)
    return h * w * c


def conv_flops_per_frame(cfg: dict) -> int:
    total = 0
    for h, w, c, d in _stacks(cfg):
        ph, pw = -(-h // 2), -(-w // 2)
        total += 2 * h * w * 9 * c * d                     # entry conv
        total += cfg["blocks"] * 2 * (2 * ph * pw * 9 * d * d)
    return total


def head_flops_per_frame(cfg: dict) -> int:
    return 2 * flat_features(cfg) * cfg["head_dim"]


def flops_per_frame(cfg: dict) -> int:
    """Torso and head of one frame."""
    return conv_flops_per_frame(cfg) + head_flops_per_frame(cfg)


def parameters(cfg: dict) -> int:
    """Weight and bias elements."""
    n = 0
    for _, _, c, d in _stacks(cfg):
        n += 9 * c * d + d + cfg["blocks"] * 2 * (9 * d * d + d)
    return n + (flat_features(cfg) + 1) * cfg["head_dim"]


def weight_bytes(cfg: dict, itemsize: int = 4) -> int:
    return itemsize * parameters(cfg)


def frame_bytes(cfg: dict, itemsize: int = 4) -> int:
    return itemsize * cfg["in_h"] * cfg["in_w"] * cfg["c_in"]


def bytes_per_launch(cfg: dict, batch: int, itemsize: int = 4) -> int:
    """HBM bytes one launch over ``batch`` frames must move at least: the
    frames in, the weights once, the outputs out."""
    return (batch * frame_bytes(cfg, itemsize) + weight_bytes(cfg, itemsize)
            + batch * cfg["head_dim"] * itemsize)


def roofline_s(cfg: dict, batch: int, peaks: dict,
               itemsize: int = 4) -> tuple[float, str]:
    """Least time of one launch on a chip with ``peaks``, and which bound
    sets it (``compute`` or ``memory``)."""
    compute = batch * flops_per_frame(cfg) / peaks["bf16_flops_per_s"]
    memory = bytes_per_launch(cfg, batch, itemsize) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
