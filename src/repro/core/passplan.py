"""Pass-plan IR: the compiled form of a MiniConv encoder.

The paper (§3) compiles a small conv encoder into an ordered sequence of
fragment-shader passes, each subject to the embedded-GPU constraint model:

* a pass renders ONE RGBA target      -> ``ShaderPass.out_lo/out_hi``
  (<= 4 output channels);
* a pass binds <= 8 input textures    -> ``ShaderPass.texture_bindings``
  (4 packed channels per texture, so C_in <= 32);
* a pass has a per-pixel sampling
  budget (64 on the Pi Zero 2 W)      -> ``ShaderPass.samples``
  = k_h * k_w * ceil(C_in / 4).

:class:`PassPlan` makes that compiled schedule a first-class object: it
lowers a :class:`~repro.core.miniconv.MiniConvSpec` plus a concrete input
size into per-layer records (:class:`LayerPlan`: spatial shapes, SAME
padding, channel-group count) and a flat ordered pass list
(:class:`ShaderPass`: texture bindings, kernel slice, stride, activation,
output group, per-pass sample count).  Every pass is checked against the
:class:`~repro.core.miniconv.ShaderBudget` at *plan build time*, so an
un-buildable plan never reaches a kernel.

The plan is the single source of truth for derived quantities that were
previously re-computed (inconsistently — ceil vs floor) in several places:

* pass count             -> ``PassPlan.total_passes`` / :func:`count_passes`
* output spatial shape   -> ``PassPlan.out_h/out_w`` / :func:`out_spatial_chain`
* transmitted bytes      -> ``PassPlan.feature_bytes`` (uint8 wire)
* FLOPs per frame        -> ``PassPlan.flops_per_frame``

``MiniConvSpec.out_spatial/feature_bytes/flops_per_frame``,
``core.wire.feature_bytes``, ``core.latency.SplitConfig.feature_bytes`` and
the ``benchmarks/roofline_table --miniconv`` table all re-derive from here.

The Pallas execution paths consume the plan directly:
``repro.kernels.miniconv_pass.miniconv_encoder`` executes the whole plan as
ONE fused kernel (layers chained through VMEM-resident intermediates,
``TILE_H`` output rows per grid step, a stride-s first layer folded
space-to-depth: ``PassPlan.fold``, ``PassPlan.fused_layers``), while the
legacy per-pass kernel executes one ``pallas_call`` per
:class:`ShaderPass` and serves as the reference oracle.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Optional, Sequence

from repro.core.miniconv import (LayerSpec, MiniConvSpec, ShaderBudget,
                                 PI_ZERO_BUDGET)


# ---------------------------------------------------------------------------
# Spatial primitives (THE ceil rule — everything else derives from these)
# ---------------------------------------------------------------------------

def out_size(x: int, stride: int) -> int:
    """Output side of a SAME conv: ceil(x / stride)."""
    return -(-x // stride)


def out_spatial_chain(x: int, strides: Iterable[int]) -> int:
    """Spatial side after a chain of SAME convs with the given strides."""
    for s in strides:
        x = out_size(x, s)
    return x


def same_pads(size: int, kernel: int, stride: int) -> tuple[int, int]:
    """(lo, hi) zero padding so a VALID conv reproduces XLA's SAME conv."""
    total = max((out_size(size, stride) - 1) * stride + kernel - size, 0)
    return total // 2, total - total // 2


def count_passes(spec: MiniConvSpec) -> int:
    """Total shader passes for a spec (spatial-size independent)."""
    return sum(-(-l.c_out // 4) for l in spec.layers)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _round4(c: int) -> int:
    return _round_up(c, 4)


# The VMEM a fused launch may use.  The fused kernel keeps the WHOLE
# micro-batch input plus every layer's padded intermediate resident
# on-chip, so the deployable batch size is bounded by this budget (see
# ``PassPlan.vmem_bytes`` / ``max_safe_batch``), and the kernels hand the
# same number to the compiler as ``vmem_limit_bytes``, so plan and
# compiler agree on what fits.  A TPU v5e core has 128 MiB of VMEM; the
# rest is left to the compiler's own scratch.
DEFAULT_VMEM_LIMIT = 96 * 1024 * 1024


def tiled_bytes(shape: Sequence[int], itemsize: int = 4) -> int:
    """Bytes of one VMEM buffer of ``shape`` as the TPU compiler lays it
    out: the minor dimension padded to 128 lanes, the second-minor to the
    sublane tile (8 rows of 32-bit values, 16 of 16-bit, 32 of 8-bit),
    leading dimensions as they are."""
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sublanes = 8 * max(1, 4 // itemsize)
    return (math.prod(lead) * _round_up(sub, sublanes) * _round_up(lane, 128)
            * itemsize)


# ---------------------------------------------------------------------------
# IR records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One conv layer lowered onto a concrete input size."""

    index: int
    kernel: int
    stride: int
    activation: str
    c_in: int
    c_out: int
    in_h: int
    in_w: int
    out_h: int
    out_w: int
    pad_top: int
    pad_bottom: int
    pad_left: int
    pad_right: int

    @property
    def n_groups(self) -> int:
        return -(-self.c_out // 4)

    @property
    def c_in_pad(self) -> int:
        return _round4(self.c_in)

    @property
    def c_out_pad(self) -> int:
        return _round4(self.c_out)

    @property
    def padded_in_h(self) -> int:
        return self.in_h + self.pad_top + self.pad_bottom

    @property
    def padded_in_w(self) -> int:
        return self.in_w + self.pad_left + self.pad_right

    @property
    def flops(self) -> int:
        return (2 * self.out_h * self.out_w * self.kernel * self.kernel
                * self.c_in * self.c_out)


@dataclasses.dataclass(frozen=True)
class ShaderPass:
    """One fragment-shader pass: the unit the paper's compiler emits."""

    layer: int                  # owning layer index
    group: int                  # output-group index within the layer
    kernel: int
    stride: int
    activation: str
    c_in: int
    out_lo: int                 # output channel slice [out_lo, out_hi)
    out_hi: int                 # out_hi - out_lo <= 4 (one RGBA target)
    out_h: int
    out_w: int

    @property
    def texture_bindings(self) -> tuple[tuple[int, int], ...]:
        """Input channel ranges packed 4-per-texture, as bound by the pass."""
        return tuple((lo, min(lo + 4, self.c_in))
                     for lo in range(0, self.c_in, 4))

    @property
    def in_textures(self) -> int:
        return len(self.texture_bindings)

    @property
    def samples(self) -> int:
        """Texture samples per output pixel (the paper's budgeted quantity)."""
        return self.kernel * self.kernel * self.in_textures

    @property
    def flops(self) -> int:
        return (2 * self.out_h * self.out_w * self.kernel * self.kernel
                * self.c_in * (self.out_hi - self.out_lo))


@dataclasses.dataclass(frozen=True)
class HeadPlan:
    """The server-side linear projection fused into the encoder epilogue.

    ``repro.kernels.miniconv_pass.miniconv_encoder`` executes this as a
    per-tile matmul accumulated in VMEM (the ``head_w``/``head_b``
    arguments); ``in_dim`` is the flattened feature count of the owning
    :class:`PassPlan` and is validated against it at build time.
    """

    in_dim: int
    out_dim: int
    activation: str = "relu"

    @property
    def flops(self) -> int:
        return 2 * self.in_dim * self.out_dim

    @property
    def param_bytes(self) -> int:
        return 4 * (self.in_dim + 1) * self.out_dim


@dataclasses.dataclass(frozen=True)
class PassPlan:
    """An ordered, budget-checked shader-pass schedule for one input size."""

    spec: MiniConvSpec
    in_h: int
    in_w: int
    layers: tuple[LayerPlan, ...]
    passes: tuple[ShaderPass, ...]
    budget: ShaderBudget = PI_ZERO_BUDGET

    # ---- derived truths ---------------------------------------------------
    @property
    def out_h(self) -> int:
        return self.layers[-1].out_h

    @property
    def out_w(self) -> int:
        return self.layers[-1].out_w

    @property
    def k_out(self) -> int:
        return self.layers[-1].c_out

    @property
    def feature_shape(self) -> tuple[int, int, int]:
        return (self.out_h, self.out_w, self.k_out)

    @property
    def total_passes(self) -> int:
        return len(self.passes)

    @property
    def feature_bytes(self) -> int:
        """Bytes of the transmitted K-channel feature map (uint8 wire)."""
        return self.out_h * self.out_w * self.k_out

    @property
    def flat_features(self) -> int:
        """Flattened feature count — the fused head's input width."""
        return self.out_h * self.out_w * self.k_out

    @property
    def flops_per_frame(self) -> int:
        return sum(p.flops for p in self.passes)

    def head(self, out_dim: int, activation: str = "relu") -> HeadPlan:
        """Plan the fused projection epilogue for this feature shape."""
        if out_dim <= 0:
            raise ValueError(f"head out_dim must be positive, got {out_dim}")
        return HeadPlan(in_dim=self.flat_features, out_dim=out_dim,
                        activation=activation)

    def flops_per_batch(self, batch: int,
                        head: Optional[HeadPlan] = None) -> int:
        """FLOPs of one fused launch over a ``batch``-frame micro-batch."""
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        per_frame = self.flops_per_frame
        if head is not None:
            if head.in_dim != self.flat_features:
                raise ValueError(
                    f"head.in_dim {head.in_dim} != plan.flat_features "
                    f"{self.flat_features}")
            per_frame += head.flops
        return batch * per_frame

    @property
    def max_pass_samples(self) -> int:
        return max(p.samples for p in self.passes)

    # ---- the fused kernel's executed geometry ------------------------------
    @property
    def fold(self) -> int:
        """Space-to-depth factor of layer 0 in the fused kernel: its stride
        ``s`` when ``s > 1``, else 1 (layer 0 runs as planned)."""
        return self.layers[0].stride

    @property
    def fused_layers(self) -> tuple[LayerPlan, ...]:
        """The layers as the fused kernel executes them.

        With ``fold`` s > 1, layer 0 runs space-to-depth: each s x s block
        of its SAME-padded input (zero-padded up to a multiple of s) is one
        pixel of s*s*C_in channels, and its k x k stride-s conv is a
        ceil(k/s) x ceil(k/s) stride-1 conv over that input, with zero
        weights where k does not divide by s.  Same arithmetic, 1/s^2 of
        the taps, each contracting s^2 times the channels.  The other
        layers run as planned; ``layers`` stays the logical schedule that
        the passes, the wire and the counts read.
        """
        s = self.fold
        if s == 1:
            return self.layers
        first = self.layers[0]
        folded = dataclasses.replace(
            first, kernel=-(-first.kernel // s), stride=1,
            c_in=s * s * first.c_in, in_h=-(-first.padded_in_h // s),
            in_w=-(-first.padded_in_w // s), pad_top=0, pad_bottom=0,
            pad_left=0, pad_right=0)
        return (folded, *self.layers[1:])

    # ---- VMEM residency of the fused kernel --------------------------------
    def fused_tiling(self, tile_h: int = 8) -> tuple[int, int, int]:
        """(tile_h, n_tiles, scratch_rows) of the fused kernel: ``tile_h``
        clamped to the feature height, the number of output-row tiles,
        and the rows of the final layer's padded input as the kernel
        executes it (:attr:`fused_layers`), over-allocated so the last
        tile's reads stay in bounds."""
        last = self.fused_layers[-1]
        tile_h = max(1, min(tile_h, self.out_h))
        n_tiles = -(-self.out_h // tile_h)
        rows_need_max = (n_tiles * tile_h - 1) * last.stride + last.kernel
        return tile_h, n_tiles, max(last.padded_in_h, rows_need_max)

    def _vmem_terms(self, *, head: Optional[HeadPlan] = None,
                    tile_h: int = 8, itemsize: int = 4,
                    streamed: bool = False) -> tuple[int, int]:
        """(fixed_bytes, per_frame_bytes) of the fused-kernel VMEM residency.

        Mirrors the buffers of ``repro.kernels.miniconv_pass``'s fused
        launch, each counted as the compiler lays it out
        (:func:`tiled_bytes`) and twice when its block index changes
        across the grid (the compiler double-buffers those): the batch
        input block (scales with B; double-buffered when ``streamed``),
        the padded-input scratch of layers 1..L-1, per-layer weights and
        biases, the output tile and — with a fused head — the laid-out
        head weight, its bias, the projection block and its accumulator.
        Layer 0's input block and weights are counted in the shape the
        kernel holds them (:attr:`fused_layers`: folded when ``fold`` >
        1).  Affine in batch, which is what the deployability check needs.
        """
        layers = self.fused_layers
        first, last = layers[0], layers[-1]
        tile_h, n_tiles, scratch_rows = self.fused_tiling(tile_h)
        x0_rows = scratch_rows if len(layers) == 1 else first.padded_in_h
        per_frame = (2 if streamed else 1) * tiled_bytes(
            (x0_rows, first.padded_in_w, first.c_in_pad), itemsize)
        fixed = 2 * tiled_bytes((tile_h, last.out_w, last.c_out_pad),
                                itemsize)                        # out tile
        for i, l in enumerate(layers):
            fixed += (tiled_bytes((l.kernel, l.kernel, l.c_in_pad,
                                   l.c_out_pad), itemsize)
                      + tiled_bytes((1, l.c_out_pad), itemsize))
            if i:                                   # fp32 padded-input scratch
                rows = scratch_rows if l is last else l.padded_in_h
                fixed += tiled_bytes((rows, l.padded_in_w, l.c_in_pad))
        if head is not None:
            if head.in_dim != self.flat_features:
                raise ValueError(
                    f"head.in_dim {head.in_dim} != plan.flat_features "
                    f"{self.flat_features}")
            d_pad = _round_up(head.out_dim, 128)         # lane-padded
            fixed += tiled_bytes((n_tiles * tile_h * last.c_out_pad,
                                  last.out_w, d_pad), itemsize)   # weight
            fixed += tiled_bytes((1, d_pad), itemsize)            # bias
            fixed += 2 * tiled_bytes((1, 1, d_pad), itemsize)     # z out
            fixed += tiled_bytes((last.out_w, d_pad))             # z scratch
        return fixed, per_frame

    def vmem_bytes(self, batch: int = 1, *, head: Optional[HeadPlan] = None,
                   tile_h: int = 8, itemsize: int = 4,
                   streamed: bool = False) -> int:
        """VMEM bytes of ONE fused launch over a B-frame batch (or, with
        ``streamed``, a B-frame chunk of the streamed launch)."""
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        fixed, per_frame = self._vmem_terms(head=head, tile_h=tile_h,
                                            itemsize=itemsize,
                                            streamed=streamed)
        return fixed + batch * per_frame

    def max_safe_batch(self, *, head: Optional[HeadPlan] = None,
                       tile_h: int = 8, itemsize: int = 4,
                       vmem_limit: int = DEFAULT_VMEM_LIMIT,
                       streamed: bool = False) -> int:
        """Largest micro-batch whose fused launch fits the VMEM budget —
        with ``streamed``, the largest chunk of the streamed launch — (0
        when even the batch-independent residency exceeds it)."""
        fixed, per_frame = self._vmem_terms(head=head, tile_h=tile_h,
                                            itemsize=itemsize,
                                            streamed=streamed)
        return max(0, (vmem_limit - fixed) // per_frame)

    def check_batch(self, batch: int, *, head: Optional[HeadPlan] = None,
                    tile_h: int = 8, itemsize: int = 4,
                    vmem_limit: int = DEFAULT_VMEM_LIMIT) -> None:
        """Raise if a B-frame fused launch exceeds the VMEM budget."""
        need = self.vmem_bytes(batch, head=head, tile_h=tile_h,
                               itemsize=itemsize)
        if need > vmem_limit:
            raise ValueError(
                f"micro-batch {batch} needs ~{need / 2**20:.2f} MiB VMEM "
                f"> budget {vmem_limit / 2**20:.2f} MiB for a "
                f"{self.in_h}x{self.in_w} input; max safe batch is "
                f"{self.max_safe_batch(head=head, tile_h=tile_h, itemsize=itemsize, vmem_limit=vmem_limit)} "
                f"(split the batch or lower the input size)")

    def validate(self) -> None:
        errs: list[str] = []
        for p in self.passes:
            for e in self.budget.check_pass(p.kernel, p.c_in):
                errs.append(f"layer {p.layer} pass {p.group}: {e}")
        if errs:
            raise ValueError("PassPlan violates shader budget:\n  " +
                             "\n  ".join(errs))


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------

def build_pass_plan(spec: MiniConvSpec, h: int, w: Optional[int] = None, *,
                    validate: bool = True, batch: Optional[int] = None,
                    tile_h: int = 8,
                    vmem_limit: int = DEFAULT_VMEM_LIMIT) -> PassPlan:
    """Lower ``spec`` applied to an (h, w) input into a :class:`PassPlan`.

    Raises ``ValueError`` at build time if any emitted pass exceeds the
    spec's :class:`ShaderBudget` — the kernel layer can assume every plan it
    receives is deployable.  With ``batch=B`` the plan is additionally
    checked against the fused kernel's VMEM residency model: the WHOLE
    B-frame micro-batch input must fit the ``vmem_limit`` budget
    (:meth:`PassPlan.check_batch`), so an un-launchable micro-batch is
    rejected before it reaches a compiled kernel.
    """
    w = h if w is None else w
    layers: list[LayerPlan] = []
    passes: list[ShaderPass] = []
    cur_h, cur_w = h, w
    for i, l in enumerate(spec.layers):
        oh, ow = out_size(cur_h, l.stride), out_size(cur_w, l.stride)
        pt, pb = same_pads(cur_h, l.kernel, l.stride)
        pl_, pr = same_pads(cur_w, l.kernel, l.stride)
        layers.append(LayerPlan(index=i, kernel=l.kernel, stride=l.stride,
                                activation=l.activation, c_in=l.c_in,
                                c_out=l.c_out, in_h=cur_h, in_w=cur_w,
                                out_h=oh, out_w=ow, pad_top=pt, pad_bottom=pb,
                                pad_left=pl_, pad_right=pr))
        for g, lo in enumerate(range(0, l.c_out, 4)):
            passes.append(ShaderPass(layer=i, group=g, kernel=l.kernel,
                                     stride=l.stride, activation=l.activation,
                                     c_in=l.c_in, out_lo=lo,
                                     out_hi=min(lo + 4, l.c_out),
                                     out_h=oh, out_w=ow))
        cur_h, cur_w = oh, ow
    plan = PassPlan(spec=spec, in_h=h, in_w=w, layers=tuple(layers),
                    passes=tuple(passes), budget=spec.budget)
    if validate:
        plan.validate()
    if batch is not None:
        plan.check_batch(batch, tile_h=tile_h, vmem_limit=vmem_limit)
    return plan


__all__ = ["DEFAULT_VMEM_LIMIT", "HeadPlan", "LayerPlan", "PassPlan",
           "ShaderPass", "build_pass_plan", "count_passes", "out_size",
           "out_spatial_chain", "same_pads", "tiled_bytes"]
