"""Plain reference of IMPALA-CNN, from a configuration file.

Espeholt et al. 2018 (arXiv:1802.01561), Figure 3, the deep torso; ``x``
is the frame in [0, 1] and every conv is 3x3, stride 1, SAME, with bias::

    for d in depths:                             # one stack
        x = conv_d(x)                            # no activation
        x = maxpool_3x3_stride2_SAME(x)          # TF SAME, pads never win
        for _ in range(blocks):                  # residual block
            x = x + conv_d(relu(conv_d(relu(x))))
    z = head_act(dense(relu(flatten_hwc(x))))

Everything is float32 ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, with nothing of the program's: a conv is the sum of its 9
shifted-slice products, taken through ``miniconv._product`` so that
``precision="bf16_3x"`` gives the control; a max-pool is the largest of 9
strided slices of the input padded with ``-inf`` the TensorFlow way (for
an extent ``n``, ``ceil(n / 2)`` outputs and a pad of ``max(2 *
(ceil(n / 2) - 1) + 3 - n, 0)``, its odd element at the bottom and
right).  Neither goes through ``lax.conv_general_dilated`` or
``reduce_window``, so a wrong padding convention in the program shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.miniconv import ACTS, _product

_HIGHEST = jax.lax.Precision.HIGHEST


def _conv_taps(x, w):
    """3x3 stride-1 SAME conv without bias: the 9 shifted slices of the
    zero-padded input, stacked on a tap axis and contracted with their
    taps in one product (the sum of the 9 slice-by-tap products; one
    product and not nine keeps the check's compile short)."""
    h, wd = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = jnp.stack([xp[:, dy:dy + h, dx:dx + wd]
                      for dy in range(3) for dx in range(3)], axis=3)
    return jnp.einsum("bhwtc,tcd->bhwd", taps,
                      w.reshape(9, w.shape[2], w.shape[3]),
                      precision=_HIGHEST)


def conv(p, x, precision: str = "highest"):
    return _product(_conv_taps, x, p["kernel"], precision) + p["bias"]


def _same_pads(n: int) -> tuple[int, int]:
    out = -(-n // 2)
    total = max(2 * (out - 1) + 3 - n, 0)
    return total // 2, total - total // 2


def max_pool(x):
    """3x3 stride-2 SAME max-pool: the largest of 9 strided slices."""
    (t, b), (l, r) = _same_pads(x.shape[1]), _same_pads(x.shape[2])
    oh, ow = -(-x.shape[1] // 2), -(-x.shape[2] // 2)
    xp = jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)),
                 constant_values=-jnp.inf)
    out = None
    for dy in range(3):
        for dx in range(3):
            s = xp[:, dy:dy + 2 * (oh - 1) + 1:2, dx:dx + 2 * (ow - 1) + 1:2]
            out = s if out is None else jnp.maximum(out, s)
    return out


def init_params(cfg: dict, key):
    """Weights in the program's layout (``repro.core.impala.init``):
    ``{"edge": {}, "server": {"stack<i>": {"conv", "res<j>": {"conv0",
    "conv1"}}, "proj"}}``, each conv ``{"kernel": (3, 3, c_in, d), "bias":
    (d,)}``, float32, N(0, 1/fan_in) weights and N(0, 0.01) biases."""
    shapes, c = [], cfg["c_in"]
    for i, d in enumerate(cfg["depths"]):
        shapes.append(((f"stack{i}", "conv"), 9 * c, (3, 3, c, d)))
        for j in range(cfg["blocks"]):
            for k in range(2):
                shapes.append(((f"stack{i}", f"res{j}", f"conv{k}"), 9 * d,
                               (3, 3, d, d)))
        c = d
    f = flat_features(cfg)
    shapes.append((("proj",), f, (f, cfg["head_dim"])))
    keys = jax.random.split(key, 2 * len(shapes))
    server: dict = {}
    for n, (path, fan_in, shape) in enumerate(shapes):
        node = server
        for part in path:
            node = node.setdefault(part, {})
        node["kernel"] = (jax.random.normal(keys[2 * n], shape)
                          / np.sqrt(fan_in))
        node["bias"] = 0.1 * jax.random.normal(keys[2 * n + 1],
                                               (shape[-1],))
    return {"edge": {}, "server": server}


def feature_shape(cfg: dict) -> tuple[int, int, int]:
    h, w = cfg["in_h"], cfg["in_w"]
    for _ in cfg["depths"]:
        h, w = -(-h // 2), -(-w // 2)
    return h, w, cfg["depths"][-1]


def flat_features(cfg: dict) -> int:
    h, w, c = feature_shape(cfg)
    return h * w * c


def encode_project(cfg: dict, params, x, precision: str = "highest"):
    """(B, H, W, c_in) frames -> (B, head_dim): torso and head."""
    server = params["server"]
    with jax.default_matmul_precision("highest"):
        for i in range(len(cfg["depths"])):
            s = server[f"stack{i}"]
            x = max_pool(conv(s["conv"], x, precision))
            for j in range(cfg["blocks"]):
                r = s[f"res{j}"]
                y = conv(r["conv0"], jax.nn.relu(x), precision)
                x = x + conv(r["conv1"], jax.nn.relu(y), precision)
        z = jax.nn.relu(x.reshape(x.shape[0], -1))
        p = server["proj"]
        z = _product(lambda a, b: jnp.dot(a, b, precision=_HIGHEST), z,
                     p["kernel"], precision)
        return ACTS[cfg["head_act"]](z + p["bias"])


def decode_project(cfg: dict, params, data, scale, zero,
                   precision: str = "highest"):
    """Stacked uint8 frame payloads (B, H, W, c_in) with per-request
    ``scale``/``zero`` (B,) -> (B, head_dim): the server-only placement's
    decode (``data * scale + zero``), torso and head."""
    frames = (data.astype(jnp.float32) * scale[:, None, None, None]
              + zero[:, None, None, None])
    return encode_project(cfg, params, frames, precision)
