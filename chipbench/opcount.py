"""Operations and bytes of the MiniConv encoder and its projection, from
the layer shapes of a configuration file.

Counts use the real channel counts: no texture (RGBA) or lane padding is
counted, so the work reads the same whatever implements it.  A
multiply-add is two operations.
"""
from __future__ import annotations

import dataclasses


def out_size(size: int, stride: int) -> int:
    """Output extent of a SAME convolution."""
    return -(-size // stride)


@dataclasses.dataclass(frozen=True)
class Layer:
    kernel: int
    stride: int
    c_in: int
    c_out: int
    in_h: int
    in_w: int
    out_h: int
    out_w: int

    @property
    def flops(self) -> int:
        return (2 * self.out_h * self.out_w * self.kernel * self.kernel
                * self.c_in * self.c_out)

    @property
    def weights(self) -> int:
        """Weight and bias elements."""
        return self.kernel * self.kernel * self.c_in * self.c_out + self.c_out


def layers(cfg: dict) -> list[Layer]:
    h, w = cfg["in_h"], cfg["in_w"]
    out = []
    for l in cfg["layers"]:
        oh, ow = out_size(h, l["stride"]), out_size(w, l["stride"])
        out.append(Layer(l["kernel"], l["stride"], l["c_in"], l["c_out"],
                         h, w, oh, ow))
        h, w = oh, ow
    return out


def feature_shape(cfg: dict) -> tuple[int, int, int]:
    last = layers(cfg)[-1]
    return last.out_h, last.out_w, last.c_out


def flat_features(cfg: dict) -> int:
    h, w, c = feature_shape(cfg)
    return h * w * c


def conv_flops_per_frame(cfg: dict) -> int:
    return sum(l.flops for l in layers(cfg))


def head_flops_per_frame(cfg: dict) -> int:
    return 2 * flat_features(cfg) * cfg["head_dim"]


def flops_per_frame(cfg: dict) -> int:
    """Encoder and projection of one frame."""
    return conv_flops_per_frame(cfg) + head_flops_per_frame(cfg)


def weight_bytes(cfg: dict, itemsize: int = 4) -> int:
    head = (flat_features(cfg) + 1) * cfg["head_dim"]
    return itemsize * (sum(l.weights for l in layers(cfg)) + head)


def frame_bytes(cfg: dict, itemsize: int = 4) -> int:
    return itemsize * cfg["in_h"] * cfg["in_w"] * cfg["layers"][0]["c_in"]


def bytes_per_launch(cfg: dict, batch: int, itemsize: int = 4) -> int:
    """HBM bytes one encoder+projection launch over ``batch`` frames must
    move at least: the frames in, the weights once, the projections out."""
    return (batch * frame_bytes(cfg, itemsize) + weight_bytes(cfg, itemsize)
            + batch * cfg["head_dim"] * itemsize)


def roofline_s(cfg: dict, batch: int, peaks: dict,
               itemsize: int = 4) -> tuple[float, str]:
    """Least time of one launch on a chip with ``peaks``, and which bound
    sets it (``compute`` or ``memory``)."""
    compute = batch * flops_per_frame(cfg) / peaks["bf16_flops_per_s"]
    memory = bytes_per_launch(cfg, batch, itemsize) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
