"""Wire codecs for the split boundary.

The paper transmits the on-device encoder's K-channel feature map as an
uncompressed uint8 buffer.  We generalise this into a codec interface so the
same machinery serves (a) the RL split policy (uint8 feature maps) and
(b) the pod-boundary transformer split (uint8/int8 affine-quantised hidden
states crossing the inter-pod link).

All codecs are jit-compatible pure functions; ``wire_bytes`` gives the exact
on-the-wire size used by the latency model and by the collective-bytes
accounting in the roofline analysis.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.tracing import scope

Payload = dict[str, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class WireCodec:
    """Base: float32 passthrough."""

    name: str = "float32"
    itemsize: float = 4.0
    overhead_bytes_per_tensor: int = 0

    def encode(self, x: jnp.ndarray) -> Payload:
        return {"data": x.astype(jnp.float32)}

    def decode(self, payload: Payload, dtype=jnp.float32) -> jnp.ndarray:
        with scope("wire.decode"):
            return payload["data"].astype(dtype)

    def wire_bytes(self, shape: tuple) -> int:
        return math.prod(shape) * int(self.itemsize) + \
            self.overhead_bytes_per_tensor

    def wire_bits(self, shape: tuple) -> int:
        return 8 * self.wire_bytes(shape)

    # ---- batched serving ---------------------------------------------------
    def encode_batch(self, x: jnp.ndarray) -> Payload:
        """Encode a stacked batch with PER-EXAMPLE quantisation parameters.

        ``encode`` computes one scale/zero over the whole tensor, which
        would couple the dynamic ranges of unrelated requests in a
        micro-batch; vmapping over the leading axis keeps each request's
        wire numerics identical to the single-frame path.
        """
        return jax.vmap(self.encode)(x)

    def decode_batch(self, payload: Payload, dtype=jnp.float32):
        return jax.vmap(lambda p: self.decode(p, dtype))(payload)

    def wire_bytes_batch(self, shape: tuple, batch: int) -> int:
        """Exact link bytes of a ``batch``-request micro-batch (each
        request carries its own quantisation header)."""
        return batch * self.wire_bytes(shape)


@dataclasses.dataclass(frozen=True)
class BF16Codec(WireCodec):
    name: str = "bf16"
    itemsize: float = 2.0

    def encode(self, x):
        return {"data": x.astype(jnp.bfloat16)}

    def decode(self, payload, dtype=jnp.float32):
        with scope("wire.decode"):
            return payload["data"].astype(dtype)


@dataclasses.dataclass(frozen=True)
class Uint8AffineCodec(WireCodec):
    """Per-tensor affine quantisation to uint8 (the paper's wire format for
    features in [0,1]; scale/zero travel as an 8-byte header)."""

    name: str = "uint8"
    itemsize: float = 1.0
    overhead_bytes_per_tensor: int = 8

    def encode(self, x):
        xf = x.astype(jnp.float32)
        lo = jnp.min(xf)
        hi = jnp.max(xf)
        scale = jnp.maximum(hi - lo, 1e-8) / 255.0
        q = jnp.clip(jnp.round((xf - lo) / scale), 0, 255).astype(jnp.uint8)
        return {"data": q, "scale": scale, "zero": lo}

    def decode(self, payload, dtype=jnp.float32):
        with scope("wire.decode"):
            return (payload["data"].astype(jnp.float32) * payload["scale"]
                    + payload["zero"]).astype(dtype)


@dataclasses.dataclass(frozen=True)
class Int8ChannelCodec(WireCodec):
    """Per-channel (last axis) symmetric int8 — used for transformer hidden
    states at the pod boundary, where per-channel scales matter."""

    name: str = "int8_channel"
    itemsize: float = 1.0

    def encode(self, x):
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=tuple(range(xf.ndim - 1)),
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        return {"data": q, "scale": scale}

    def decode(self, payload, dtype=jnp.float32):
        with scope("wire.decode"):
            return (payload["data"].astype(jnp.float32)
                    * payload["scale"]).astype(dtype)

    def wire_bytes(self, shape):
        return math.prod(shape) + 4 * shape[-1]


CODECS: dict[str, WireCodec] = {
    "float32": WireCodec(),
    "bf16": BF16Codec(),
    "uint8": Uint8AffineCodec(),
    "int8_channel": Int8ChannelCodec(),
}


def get_codec(name: str) -> WireCodec:
    return CODECS[name]


def roundtrip(codec: WireCodec, x: jnp.ndarray) -> jnp.ndarray:
    """Quantise-dequantise (what the server-side half actually sees)."""
    return codec.decode(codec.encode(x), dtype=x.dtype)


def stack_payloads(payloads) -> Payload:
    """Stack single-request payload dicts into one micro-batch payload.

    The result has a new leading batch axis on every tensor (data AND
    quantisation headers) and round-trips through
    :meth:`WireCodec.decode_batch`.
    """
    payloads = list(payloads)
    if not payloads:
        raise ValueError("cannot stack an empty payload list")
    return {k: jnp.stack([p[k] for p in payloads]) for k in payloads[0]}


def unstack_payload(payload: Payload) -> list[Payload]:
    """Inverse of :func:`stack_payloads`."""
    n = next(iter(payload.values())).shape[0]
    return [{k: v[i] for k, v in payload.items()} for i in range(n)]


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """A codec's payload as one row of bytes per request.

    Derived once from an example per-request payload (:meth:`of`): the
    keys in sorted order (as ``realfleet.pack_payload`` orders them), each
    leaf's dtype and per-request shape, and its byte offset in the row.
    :meth:`pack` lays a stacked micro-batch out on the host as one
    ``(B, row_bytes)`` uint8 block, so it crosses to the device as one
    buffer; :meth:`unpack`, traced into the server half's program, slices
    each leaf's bytes back out and bitcasts them to its dtype.  Only the
    bytes' layout changes: every leaf comes back bitwise.
    """

    keys: tuple[str, ...]
    dtypes: tuple[np.dtype, ...]
    shapes: tuple[tuple[int, ...], ...]
    offsets: tuple[int, ...]
    row_bytes: int

    @classmethod
    def of(cls, payload) -> "RowLayout":
        """The layout of ``payload``, one request's leaves (arrays, or
        anything with a ``shape`` and a ``dtype``)."""
        keys = tuple(sorted(payload))
        dtypes = tuple(np.dtype(payload[k].dtype) for k in keys)
        shapes = tuple(tuple(payload[k].shape) for k in keys)
        sizes = [math.prod(s) * d.itemsize for s, d in zip(shapes, dtypes)]
        return cls(keys=keys, dtypes=dtypes, shapes=shapes,
                   offsets=tuple(itertools.accumulate(sizes[:-1],
                                                      initial=0)),
                   row_bytes=sum(sizes))

    def pack(self, stacked) -> np.ndarray:
        """Host half: a stacked payload (leading batch axis on every
        leaf) -> one ``(B, row_bytes)`` uint8 array, one allocation."""
        leaves = []
        for key, dtype, shape in zip(self.keys, self.dtypes, self.shapes):
            leaf = np.ascontiguousarray(stacked[key], dtype=dtype)
            if leaf.shape[1:] != shape:
                raise ValueError(f"payload leaf {key!r} has per-request "
                                 f"shape {leaf.shape[1:]}, the layout "
                                 f"{shape}")
            leaves.append(leaf.reshape(leaf.shape[0], -1).view(np.uint8))
        return np.concatenate(leaves, axis=1)

    def unpack(self, rows) -> Payload:
        """Traced half: ``(B, row_bytes)`` uint8 -> the stacked payload."""
        b = rows.shape[0]
        out = {}
        for key, dtype, shape, off in zip(self.keys, self.dtypes,
                                          self.shapes, self.offsets):
            n = math.prod(shape) * dtype.itemsize
            seg = rows[:, off:off + n]
            if dtype.itemsize > 1:
                seg = seg.reshape(b, n // dtype.itemsize, dtype.itemsize)
            out[key] = lax.bitcast_convert_type(seg, dtype).reshape(
                (b,) + shape)
        return out


def frame_bytes_rgba(x_size: int) -> int:
    """Bytes of a full RGBA frame (the server-only pipeline's payload)."""
    return 4 * x_size * x_size


def feature_bytes(x_size: int, n_stride2: int, k: int) -> int:
    """Bytes of the K-channel feature map after n stride-2 layers (paper).

    Derived via the PassPlan spatial rule (ceil per stride-2 layer, matching
    SAME convs and the real feature shape) — the old ``x // 2**n`` floor
    disagreed with the emitted tensor for non-divisible sizes (e.g. 100x100
    with n=3 produces a 13x13 map, not 12x12).
    """
    from repro.core.passplan import out_spatial_chain  # lazy: import order
    s = out_spatial_chain(x_size, (2,) * n_stride2)
    return k * s * s
