"""IMPALA-CNN: the residual visual encoder of IMPALA and Procgen.

Espeholt et al. 2018 (arXiv:1802.01561), Figure 3, the "deep" torso, at
the widths Procgen (Cobbe et al. 2019, arXiv:1912.01588) runs every
baseline with.  ``x`` is the frame in [0, 1]; every conv is 3x3, stride
1, SAME, with bias::

    for d in depths:                              # one stack
        x = conv_d(x)                             # no activation
        x = maxpool_3x3_stride2_SAME(x)           # pads never win
        for _ in range(blocks):                   # residual block
            x = x + conv_d(relu(conv_d(relu(x))))
    z = act(dense(relu(flatten_hwc(x))))          # the head

The DMLab agent's LSTM core is left out, as Procgen leaves it out.  It
deploys server-only (``repro.deploy``): the client sends the frame and
the server runs the whole encoder, so every parameter is the server's.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn.layers import conv2d, conv2d_init, dense, dense_init
from repro.nn.module import KeyGen
from repro.tracing import scope


@dataclasses.dataclass(frozen=True)
class ImpalaSpec:
    """The torso: one stack per entry of ``depths`` (its conv's output
    channels), each a conv, a 3x3 stride-2 max-pool and ``blocks``
    residual blocks, on frames of ``c_in`` channels."""

    depths: tuple[int, ...] = (16, 32, 32)
    blocks: int = 2
    c_in: int = 3

    def __post_init__(self):
        object.__setattr__(self, "depths", tuple(self.depths))

    def validate(self) -> None:
        if not self.depths or min(self.depths) < 1:
            raise ValueError(f"impala depths must be positive: "
                             f"{self.depths}")
        if self.blocks < 0 or self.c_in < 1:
            raise ValueError(f"impala blocks must be >= 0 and c_in >= 1, "
                             f"got {self.blocks}, {self.c_in}")

    def feature_shape(self, h: int, w: int) -> tuple[int, int, int]:
        """(H', W', C) of the torso's output: each pool gives ceil(n/2)."""
        for _ in self.depths:
            h, w = -(-h // 2), -(-w // 2)
        return h, w, self.depths[-1]


def init(key, spec: ImpalaSpec, *, h: int, w: int, head_dim: int):
    """``{"stack<i>": {"conv", "res<j>": {"conv0", "conv1"}}, "proj"}``:
    fan-in scaled conv and dense weights, zero biases."""
    kg = KeyGen(key)
    params, c = {}, spec.c_in
    for i, d in enumerate(spec.depths):
        stack = {"conv": conv2d_init(kg(), 3, 3, c, d)}
        for j in range(spec.blocks):
            stack[f"res{j}"] = {f"conv{k}": conv2d_init(kg(), 3, 3, d, d)
                                for k in range(2)}
        params[f"stack{i}"] = stack
        c = d
    fh, fw, fc = spec.feature_shape(h, w)
    params["proj"] = dense_init(kg(), fh * fw * fc, head_dim, use_bias=True)
    return params


def max_pool(x):
    """3x3 stride-2 SAME max-pool (the odd pad at the bottom and right)."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def apply(spec: ImpalaSpec, params, x, act=jax.nn.relu):
    """(B, H, W, c_in) frames -> (B, head_dim): the torso, then
    ``act(dense(relu(flatten(x))))``."""
    with scope("impala.encode"):
        for i in range(len(spec.depths)):
            p = params[f"stack{i}"]
            with scope("impala.conv"):
                x = conv2d(p["conv"], x)
            with scope("impala.pool"):
                x = max_pool(x)
            with scope("impala.res"):
                for j in range(spec.blocks):
                    r = p[f"res{j}"]
                    y = conv2d(r["conv0"], jax.nn.relu(x))
                    x = x + conv2d(r["conv1"], jax.nn.relu(y))
        with scope("impala.head"):
            z = jax.nn.relu(x.reshape(x.shape[0], -1))
            return act(dense(params["proj"], z))


__all__ = ["ImpalaSpec", "apply", "init", "max_pool"]
