"""MiniConv: a library of small convolutional encoders that compile cleanly
to per-pass execution under embedded-GPU ("fragment shader") constraints.

The paper's constraint model (retained verbatim, §3):

* one pass writes exactly 4 output channels (RGBA texture);
* a pass may bind at most 8 input textures => C_in <= 32 per pass;
* a pass has a finite per-pixel sampling budget (64 samples in the paper's
  Pi Zero 2 W deployment): ``k_h * k_w * ceil(C_in / 4) <= 64``.

On TPU these become VMEM-tiling constraints for the Pallas kernel
(`repro.kernels.miniconv_pass`): a pass is one kernel invocation whose
input block holds ceil(C_in/4) packed 4-channel planes and whose output
tile is one 4-channel plane.  ``MiniConvSpec.validate()`` enforces the
budget so that any encoder built here is deployable on both substrates.

Encoders are trained end-to-end with the downstream policy (PyTorch in the
paper, `repro.rl` here); at deployment only the encoder runs on-device and
its K-channel uint8 feature map crosses the network (`repro.core.wire`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.nn.layers import conv2d, conv2d_init
from repro.nn.module import KeyGen


# ---------------------------------------------------------------------------
# Constraint model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShaderBudget:
    """Embedded-GPU constraints a MiniConv pass must respect (paper §3)."""

    max_textures: int = 8        # bound input textures per pass
    channels_per_texture: int = 4  # RGBA packing
    max_samples: int = 64        # texture samples per output pixel
    out_channels_per_pass: int = 4  # one RGBA render target

    @property
    def max_in_channels(self) -> int:
        return self.max_textures * self.channels_per_texture

    def samples(self, kernel: int, c_in: int) -> int:
        textures = math.ceil(c_in / self.channels_per_texture)
        return kernel * kernel * textures

    def check_pass(self, kernel: int, c_in: int) -> list[str]:
        errs = []
        if c_in > self.max_in_channels:
            errs.append(
                f"pass reads {c_in} channels > {self.max_in_channels} "
                f"({self.max_textures} textures x {self.channels_per_texture})")
        s = self.samples(kernel, c_in)
        if s > self.max_samples:
            errs.append(
                f"pass needs {s} samples/pixel "
                f"({kernel}x{kernel} x {math.ceil(c_in / 4)} textures) "
                f"> budget {self.max_samples}")
        return errs


PI_ZERO_BUDGET = ShaderBudget()  # the paper's Raspberry Pi Zero 2 W numbers


# ---------------------------------------------------------------------------
# Encoder specification
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One conv layer = ceil(c_out/4) shader passes over the same input."""

    kernel: int
    stride: int
    c_in: int
    c_out: int
    activation: str = "relu"    # relu | sigmoid | linear

    @property
    def n_passes(self) -> int:
        return math.ceil(self.c_out / 4)


@dataclasses.dataclass(frozen=True)
class MiniConvSpec:
    layers: tuple[LayerSpec, ...]
    budget: ShaderBudget = PI_ZERO_BUDGET

    @property
    def c_in(self) -> int:
        return self.layers[0].c_in

    @property
    def k_out(self) -> int:
        return self.layers[-1].c_out

    @property
    def n_stride2(self) -> int:
        return sum(1 for l in self.layers if l.stride == 2)

    @property
    def total_passes(self) -> int:
        from repro.core.passplan import count_passes  # lazy: avoids cycle
        return count_passes(self)

    def validate(self) -> None:
        errs: list[str] = []
        for i, l in enumerate(self.layers):
            for e in self.budget.check_pass(l.kernel, l.c_in):
                errs.append(f"layer {i}: {e}")
            if i and l.c_in != self.layers[i - 1].c_out:
                errs.append(f"layer {i}: c_in {l.c_in} != previous c_out "
                            f"{self.layers[i - 1].c_out}")
        if errs:
            raise ValueError("MiniConvSpec violates shader budget:\n  " +
                             "\n  ".join(errs))

    def plan(self, h: int, w: Optional[int] = None, *,
             batch: Optional[int] = None):
        """Lower this spec onto an input size (see ``core.passplan``);
        ``batch=B`` additionally checks the fused kernel's B-frame VMEM
        residency against the budget."""
        from repro.core.passplan import build_pass_plan  # lazy: avoids cycle
        return build_pass_plan(self, h, w, batch=batch)

    def out_spatial(self, x: int) -> int:
        from repro.core.passplan import out_spatial_chain
        return out_spatial_chain(x, (l.stride for l in self.layers))

    def feature_bytes(self, x: int) -> int:
        """Transmitted feature bytes for an X-by-X input (uint8 wire)."""
        return self.plan(x).feature_bytes

    def flops_per_frame(self, x: int) -> int:
        return self.plan(x).flops_per_frame


def standard_spec(c_in: int = 12, k: int = 4, *, n_stride2: int = 3,
                  hidden: int = 16,
                  budget: ShaderBudget = PI_ZERO_BUDGET) -> MiniConvSpec:
    """The encoder family used in the paper's experiments.

    Defaults give the K=4, n=3 Pi-Zero configuration: three stride-2 layers,
    4x4 then 3x3 kernels, every pass within the 64-sample budget:
      4x4 x ceil(12/4)=3 textures = 48 samples; 3x3 x 4 = 36 samples.
    """
    layers = [LayerSpec(4, 2, c_in, hidden)]
    for _ in range(n_stride2 - 2):
        layers.append(LayerSpec(3, 2, hidden, hidden))
    layers.append(LayerSpec(3, 2, hidden, k, activation="sigmoid"))
    spec = MiniConvSpec(tuple(layers), budget)
    spec.validate()
    return spec


# ---------------------------------------------------------------------------
# init / apply
# ---------------------------------------------------------------------------

def miniconv_init(key, spec: MiniConvSpec, *, dtype=jnp.float32):
    kg = KeyGen(key)
    return {f"layer{i}": conv2d_init(kg(), l.kernel, l.kernel, l.c_in, l.c_out,
                                     dtype=dtype)
            for i, l in enumerate(spec.layers)}


_ACTS: dict[str, Callable] = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "linear": lambda x: x,
}


def _normalize_mode(use_kernel) -> str:
    """Resolve ``use_kernel`` to a kernel execution tier via the backend
    registry (``repro.core.backends``).  ``True`` keeps its historical
    meaning (the per-pass reference oracle); unknown strings raise with the
    full list of registered backends instead of falling through."""
    from repro.core.backends import get_backend  # lazy: avoids cycle
    return get_backend(use_kernel).mode


def miniconv_apply(params, spec: MiniConvSpec, x, *,
                   use_kernel=False, tile_h: int = 8, plan=None,
                   head=None, head_act: str = "relu", interpret=None,
                   stream_chunk=None):
    """x: (B, H, W, C_in) float in [0,1] -> (B, H', W', K).

    Execution modes (``use_kernel``):

    * ``False`` / ``"xla"``  — XLA SAME convs (the training path).
    * ``"per_pass"``         — legacy reference: one ``pallas_call`` per
      :class:`~repro.core.passplan.ShaderPass` (the shader oracle).
    * ``"grouped"``          — one ``pallas_call`` per layer; output-group is
      a grid dimension so the input row is loaded once per row and reused
      across groups.
    * ``"fused"``            — the whole :class:`~repro.core.passplan.PassPlan`
      as ONE ``pallas_call``: layers chained through VMEM-resident
      intermediates, ``tile_h`` output rows per grid step.

    ``use_kernel=True`` is accepted as an alias for ``"per_pass"``.
    ``plan`` lets callers that already compiled the PassPlan (e.g.
    ``core.split.make_miniconv_split``) reuse it instead of re-lowering
    per call; it must match the input's spatial size.

    ``head`` (dense params dict ``{"kernel": (F, D)[, "bias": (D,)]}`` or a
    ``(w, b)`` tuple) appends the server-side flatten + dense projection and
    makes the return value ``(features, head_act(flat @ w + b))``.  In
    ``"fused"`` mode the projection runs INSIDE the kernel as a per-tile
    epilogue (see ``kernels.miniconv_pass.miniconv_encoder``); other modes
    compute the same epilogue with XLA so training and deployment share one
    call signature.

    ``interpret`` forces Pallas interpret (True) or compiled (False)
    execution for the kernel tiers; ``None`` interprets on the CPU
    backend and compiles on the chip
    (``repro.kernels.interpret.resolve_interpret``).

    ``stream_chunk`` (fused tiers only) streams the micro-batch through
    VMEM in ``stream_chunk``-frame chunks
    (:func:`~repro.kernels.miniconv_pass.miniconv_encoder_stream`),
    lifting the batch-must-fit-VMEM cap.  ``use_kernel="fused+stream"``
    selects streaming with ``stream_chunk`` defaulting to the plan's
    streamed ``max_safe_batch``; batches within one chunk fall through to
    the plain fused launch, so results are bitwise identical either way.
    """
    from repro.core.backends import get_backend  # lazy: avoids cycle
    backend = get_backend(use_kernel)
    mode = backend.mode
    if head is not None:
        hw, hb = ((head["kernel"], head.get("bias"))
                  if isinstance(head, dict) else head)
    if mode == "fused":
        from repro.kernels.miniconv_pass import (miniconv_encoder,
                                                 miniconv_encoder_stream)
        if plan is None:
            plan = spec.plan(x.shape[1], x.shape[2])
        elif (plan.in_h, plan.in_w) != (x.shape[1], x.shape[2]):
            raise ValueError(
                f"plan was built for {(plan.in_h, plan.in_w)} input but got "
                f"{x.shape[1:3]}; rebuild with spec.plan(h, w)")
        ws = [params[f"layer{i}"]["kernel"] for i in range(len(spec.layers))]
        bs = [params[f"layer{i}"]["bias"] for i in range(len(spec.layers))]
        if backend.streamed and stream_chunk is None:
            hp = (plan.head(hw.shape[-1], activation=head_act)
                  if head is not None else None)
            stream_chunk = max(1, plan.max_safe_batch(head=hp, tile_h=tile_h,
                                                      streamed=True))
        if stream_chunk is not None:
            return miniconv_encoder_stream(
                x, ws, bs, plan, chunk_b=stream_chunk, tile_h=tile_h,
                head_w=hw if head is not None else None,
                head_b=hb if head is not None else None,
                head_act=head_act, interpret=interpret)
        if head is not None:
            return miniconv_encoder(x, ws, bs, plan, tile_h=tile_h,
                                    head_w=hw, head_b=hb, head_act=head_act,
                                    interpret=interpret)
        return miniconv_encoder(x, ws, bs, plan, tile_h=tile_h,
                                interpret=interpret)
    if mode in ("per_pass", "grouped"):
        from repro.kernels.ops import miniconv_layer  # lazy: avoids cycles
    for i, l in enumerate(spec.layers):
        p = params[f"layer{i}"]
        if mode == "xla":
            x = conv2d(p, x, stride=l.stride, padding="SAME")
        else:
            x = miniconv_layer(x, p["kernel"], p["bias"], stride=l.stride,
                               fused_groups=(mode == "grouped"),
                               interpret=interpret)
        x = _ACTS[l.activation](x)
    if head is not None:
        z = x.reshape(x.shape[0], -1) @ hw
        if hb is not None:
            z = z + hb
        return x, _ACTS[head_act](z)
    return x


def miniconv_feature_shape(spec: MiniConvSpec, h: int, w: int) -> tuple:
    return spec.plan(h, w).feature_shape
