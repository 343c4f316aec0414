"""Plain reference of the MiniConv split policy, from a configuration file.

The encoder is a stack of SAME convolutions with a per-layer activation;
the server half decodes the uint8 wire (``data * scale + zero``),
flattens the feature map in (row, column, channel) order and applies a
dense projection with its activation.  Everything is float32
``jax.numpy``/``lax`` with no kernel, cache or batching of the program's.

``precision`` is ``"highest"`` for the reference, and ``"bf16_3x"`` for
the control: every product is taken over operands split into a bfloat16
high part and a bfloat16 remainder, keeping the three largest of the four
partial products, as the TPU's ``high`` matmul precision does.  The split
is explicit (the parts are cut by bit masks), so the control reads the
same on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
ACTS = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
        "linear": lambda x: x}
PRECISIONS = ("highest", "bf16_3x")


def _bf16_part(x):
    """``x`` rounded to bfloat16 (to nearest, ties to even) and held in
    float32, by integer operations on its bits: a compiler may drop a
    float32 -> bfloat16 -> float32 round trip as excess precision, but
    not these."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    hi = _bf16_part(x)
    return hi, _bf16_part(x - hi)


def _product(op, a, b, precision: str):
    """``op(a, b)`` for a bilinear ``op``, at ``precision``."""
    if precision == "highest":
        return op(a, b)
    if precision != "bf16_3x":
        raise ValueError(f"precision must be one of {PRECISIONS}: "
                         f"{precision!r}")
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return op(a_hi, b_hi) + (op(a_hi, b_lo) + op(a_lo, b_hi))


def _conv(stride, x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HIGHEST)


def _dense(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def init_params(cfg: dict, key):
    """Weights in the program's layout: ``{"edge": {"layer<i>": {"kernel":
    (k, k, c_in, c_out), "bias": (c_out,)}}, "server": {"proj": {"kernel":
    (F, D), "bias": (D,)}}}``, float32, N(0, 1/fan_in) kernels and
    N(0, 0.01) biases."""
    n = len(cfg["layers"])
    keys = jax.random.split(key, 2 * n + 2)
    edge = {}
    for i, l in enumerate(cfg["layers"]):
        k, ci, co = l["kernel"], l["c_in"], l["c_out"]
        edge[f"layer{i}"] = {
            "kernel": jax.random.normal(keys[2 * i], (k, k, ci, co))
            / np.sqrt(k * k * ci),
            "bias": 0.1 * jax.random.normal(keys[2 * i + 1], (co,))}
    f = flat_features(cfg)
    proj = {"kernel": jax.random.normal(keys[-2], (f, cfg["head_dim"]))
            / np.sqrt(f),
            "bias": 0.1 * jax.random.normal(keys[-1], (cfg["head_dim"],))}
    return {"edge": edge, "server": {"proj": proj}}


def flat_features(cfg: dict) -> int:
    h, w = cfg["in_h"], cfg["in_w"]
    for l in cfg["layers"]:
        h, w = -(-h // l["stride"]), -(-w // l["stride"])
    return h * w * cfg["layers"][-1]["c_out"]


def features(cfg: dict, edge, x, precision: str = "highest"):
    """(B, H, W, C_in) frames -> (B, H', W', K) feature maps."""
    for i, l in enumerate(cfg["layers"]):
        p = edge[f"layer{i}"]
        y = _product(functools.partial(_conv, l["stride"]), x, p["kernel"],
                     precision)
        x = ACTS[l["activation"]](y + p["bias"])
    return x


def project(cfg: dict, server, feats, precision: str = "highest"):
    """(B, ...) feature maps -> (B, head_dim) projections."""
    p = server["proj"]
    z = _product(_dense, feats.reshape(feats.shape[0], -1), p["kernel"],
                 precision)
    return ACTS[cfg["head_act"]](z + p["bias"])


def encode_project(cfg: dict, params, x, precision: str = "highest"):
    """Frames -> projections: the encoder and the server half, no wire."""
    return project(cfg, params["server"],
                   features(cfg, params["edge"], x, precision), precision)


def quantize_uint8(feats):
    """One request's feature map -> the uint8 wire payload: per-tensor
    affine, ``scale = max(hi - lo, 1e-8) / 255``, ``zero = lo``."""
    lo, hi = jnp.min(feats), jnp.max(feats)
    scale = jnp.maximum(hi - lo, 1e-8) / 255.0
    q = jnp.clip(jnp.round((feats - lo) / scale), 0, 255).astype(jnp.uint8)
    return {"data": q, "scale": scale, "zero": lo}


def decode_project(cfg: dict, server, data, scale, zero,
                   precision: str = "highest"):
    """Stacked uint8 payloads (B, ...) with per-request ``scale``/``zero``
    (B,) -> (B, head_dim) projections."""
    b = data.shape[0]
    feats = (data.reshape(b, -1).astype(jnp.float32) * scale[:, None]
             + zero[:, None])
    return project(cfg, server, feats, precision)
