"""Public jit'd wrappers around the Pallas kernels.

``interpret=None`` resolves through
:func:`repro.kernels.interpret.resolve_interpret`: interpreted on the CPU
backend, compiled on the chip.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.interpret import resolve_interpret
from repro.kernels.miniconv_pass import (miniconv_encoder,
                                         miniconv_layer_grouped,
                                         miniconv_pass)


def same_pad(x, kernel: int, stride: int):
    """SAME padding for a square kernel so the Pallas pass (VALID) matches
    XLA's SAME conv."""
    _, h, w, _ = x.shape
    out_h = -(-h // stride)
    out_w = -(-w // stride)
    pad_h = max((out_h - 1) * stride + kernel - h, 0)
    pad_w = max((out_w - 1) * stride + kernel - w, 0)
    return jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                       (pad_w // 2, pad_w - pad_w // 2), (0, 0)))


def _pad_groups(kernel, bias):
    """Zero-pad the output channels to a multiple of 4 (RGBA packing).

    ``LayerSpec.n_passes = ceil(c_out/4)`` admits c_out % 4 != 0; the final
    output group then renders a partially-used RGBA target.  The kernels
    always write full 4-channel groups, so we pad the weights/bias with
    zero channels and the caller slices the result back.
    """
    c_out = kernel.shape[-1]
    pad = (-c_out) % 4
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, 0), (0, 0), (0, pad)))
        bias = jnp.pad(bias, ((0, pad),))
    return kernel, bias, c_out


def miniconv_layer(x, kernel, bias, *, stride: int = 1,
                   interpret: Optional[bool] = None,
                   fused_groups: bool = False):
    """One MiniConv layer = ceil(c_out/4) shader passes (SAME padding).

    x: (B,H,W,C_in); kernel: (kh,kw,C_in,C_out); bias: (C_out,).
    ``fused_groups=True`` executes all output groups in a single
    pallas_call (output-group as a grid dimension); the default runs one
    pallas_call per pass — the legacy reference path.
    """
    interpret = resolve_interpret(interpret)
    kh = kernel.shape[0]
    kernel, bias, c_out = _pad_groups(kernel, bias)
    xp = same_pad(x, kh, stride)
    if fused_groups:
        out = miniconv_layer_grouped(xp, kernel, bias, stride=stride,
                                     interpret=interpret)
    else:
        outs = [miniconv_pass(xp, kernel[..., g:g + 4], bias[g:g + 4],
                              stride=stride, interpret=interpret)
                for g in range(0, kernel.shape[-1], 4)]
        out = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
    return out[..., :c_out]


def causal_attention(q, k, v, *, sliding_window: Optional[int] = None,
                     block_q: int = 128, block_k: int = 128,
                     interpret: Optional[bool] = None):
    """(B, H, S, D) flash attention wrapper (causal)."""
    interpret = resolve_interpret(interpret)
    return flash_attention(q, k, v, causal=True,
                           sliding_window=sliding_window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


__all__ = ["miniconv_layer", "causal_attention", "miniconv_pass",
           "miniconv_layer_grouped", "miniconv_encoder", "flash_attention",
           "same_pad"]
