"""Pallas TPU kernels for MiniConv shader passes — three execution tiers.

A fragment-shader pass computes each output pixel by sampling a k x k
neighbourhood of <= 8 bound textures (4 channels each) and writes one RGBA
(4-channel) output texture.  The TPU adaptation keeps the pass structure but
re-tiles it for VMEM/MXU.  This module provides the pass schedule's three
execution tiers (see ``repro.core.passplan`` for the schedule itself):

1. :func:`miniconv_pass` — the legacy reference: ONE pallas_call per
   :class:`~repro.core.passplan.ShaderPass`.  grid = (batch, out_row,
   kernel_row); each step loads one input row, multiplies it against one
   kernel row and accumulates into fp32 VMEM scratch.  This is the oracle
   the fused paths are tested against.

2. :func:`miniconv_layer_grouped` — one pallas_call per LAYER.  The
   output-group becomes a grid dimension (innermost), so consecutive grid
   steps share the same input-row block: the row is loaded into VMEM once
   and reused across all ceil(c_out/4) groups instead of once per pass.
   The per-group fp32 accumulator lives in a (n_groups, W_out, 4) VMEM
   scratch.

3. :func:`miniconv_encoder` — one pallas_call for the WHOLE encoder
   (the fused analogue of the paper's full pass sequence).  grid =
   (chunk, batch, out_row_tile); layer intermediates never leave the
   chip: layers 0..L-2 run once per batch element (on the first tile
   step), one output row at a time, each row stored into the interior of
   the next layer's zero-bordered (SAME-padded) VMEM buffer; every grid
   step then computes ``tile_h`` rows of the final feature map from the
   last buffer (multi-row output tiling).  Every (i, j) tap of a row is
   one ref load (strided, ``pl.ds(..., stride=s)``, in a stride-s layer:
   the TPU lowers strided ref loads, not strided value slices) and one
   (W_out, C_in) @ (C_in, C_out) matmul at ``Precision.HIGHEST``: all
   output groups of a layer in a single contraction, fp32 throughout.
   Channel counts are zero-padded to multiples of 4 (RGBA packing), so
   specs with c_out % 4 != 0 execute correctly; the wrapper slices the
   result back.

   A stride-s first layer (s > 1) runs space-to-depth
   (``PassPlan.fold``, ``PassPlan.fused_layers``): the wrapper folds each
   s x s block of the padded input into one pixel of s*s*C_in channels
   and each layer-0 weight to ceil(k/s)^2 taps of s*s*C_in rows, so the
   kernel runs layer 0 as a stride-1 conv of ceil(k/s)^2 contiguous tap
   loads per row instead of k^2 strided ones (4 taps of K=36 instead of
   16 of K=12 for the 4x4 stride-2 stem on 9 channels).  The same sums,
   in fp32 at ``Precision.HIGHEST``.

   The batch dimension is an outer grid dimension, so a (B, H, W, C)
   input is a single kernel launch: weight padding and dispatch are paid
   once for the whole micro-batch (the batched-serving path; see
   ``repro.serving.server.BatchingPolicyServer``).

   Optionally the server-side linear projection (the ``rl.networks``
   flatten + dense head) is FUSED into the kernel epilogue: each output
   row's channels are weighted against their (W_out, D) head-weight
   slices and accumulated in a (W_out, D) VMEM scratch, summed over W_out
   on the last tile, so the (B, D) projection leaves the kernel without
   the feature map ever being re-read from HBM.  Head-weight rows beyond
   ``plan.out_h`` and channels beyond ``plan.k_out`` are zero, which
   cancels the over-allocated tile rows and RGBA padding channels; the
   projection width D is lane-padded to a multiple of 128 (the zero
   columns are sliced off the returned projection).

4. :func:`miniconv_encoder_stream` — the fused encoder pipelined over
   BATCH CHUNKS, lifting the batch-must-fit-VMEM rule
   (``PassPlan.max_safe_batch``).  The micro-batch is split into
   ``chunk_b``-frame chunks; compiled, one pallas_call whose chunk grid
   dimension fetches each chunk's input block HBM->VMEM (double-buffered
   behind the previous chunk's compute); in interpret mode, one fused
   launch per chunk.  When the batch divides into whole chunks both
   strategies are bitwise equal to calling :func:`miniconv_encoder`
   chunk-by-chunk and concatenating.  Registered as the ``fused+stream``
   execution backend (``repro.core.backends``).

Every fused launch hands the compiler ``vmem_limit_bytes`` =
``repro.core.passplan.DEFAULT_VMEM_LIMIT``, the budget the PassPlan's
residency model (``PassPlan.vmem_bytes``) checks against.  The per-pass
and grouped tiers (1, 2) are interpret-mode oracles: their strided value
slices do not lower for the TPU, so on a chip they fail with the
compiler's error.

Stride-2 passes subsample the input rows/cols, mirroring the shader's
half-resolution render target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.miniconv import _ACTS
from repro.core.passplan import DEFAULT_VMEM_LIMIT
from repro.kernels.interpret import resolve_interpret
from repro.tracing import scope


# ---------------------------------------------------------------------------
# Tier 1: legacy single-pass kernel (the reference oracle)
# ---------------------------------------------------------------------------

def _pass_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, stride: int,
                 kw: int, w_out: int):
    """One (batch, out_row, kernel_row) grid step.

    x_ref: (1, 1, W_in, C_in) — the input row sampled by this step
    w_ref: (kh, kw, C_in, 4) — full pass weights (constant across grid)
    b_ref: (1, 4)            — bias
    o_ref: (1, 1, W_out, 4)  — output row (written on the last kernel row)
    acc_ref: (W_out, 4) fp32 scratch
    """
    i = pl.program_id(2)          # kernel row index
    kh = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(b_ref[0].astype(jnp.float32),
                                        acc_ref.shape)

    x = x_ref[0, 0].astype(jnp.float32)      # (W_in, C_in)
    w = w_ref[i].astype(jnp.float32)         # (kw, C_in, 4)

    acc = acc_ref[...]
    for j in range(kw):                       # the shader's column samples
        cols = jax.lax.slice(x, (j, 0),
                             (j + (w_out - 1) * stride + 1, x.shape[1]),
                             (stride, 1))     # (W_out, C_in)
        acc = acc + cols @ w[j]               # MXU: (W_out,C_in)@(C_in,4)
    acc_ref[...] = acc

    @pl.when(i == kh - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "interpret"))
def miniconv_pass(x, w, b, *, stride: int = 1, interpret=None):
    """One shader pass on a pre-padded input (VALID convolution).

    x: (B, H_in, W_in, C_in); w: (kh, kw, C_in, 4); b: (4,).
    Returns (B, H_out, W_out, 4) with
    H_out = (H_in - kh)//stride + 1, W_out = (W_in - kw)//stride + 1.
    """
    B, h_in, w_in, c_in = x.shape
    kh, kw, c_in_w, c_out = w.shape
    assert c_in == c_in_w and c_out == 4, (x.shape, w.shape)
    h_out = (h_in - kh) // stride + 1
    w_out = (w_in - kw) // stride + 1

    grid = (B, h_out, kh)
    return pl.pallas_call(
        functools.partial(_pass_kernel, stride=stride, kw=kw, w_out=w_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, w_in, c_in),
                         lambda b_, q, i: (b_, q * stride + i, 0, 0)),
            pl.BlockSpec((kh, kw, c_in, 4), lambda b_, q, i: (0, 0, 0, 0)),
            pl.BlockSpec((1, 4), lambda b_, q, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, w_out, 4),
                               lambda b_, q, i: (b_, q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, h_out, w_out, 4), x.dtype),
        scratch_shapes=[pltpu.VMEM((w_out, 4), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, w, b.reshape(1, 4))


# ---------------------------------------------------------------------------
# Tier 2: one pallas_call per layer, output-group as a grid dimension
# ---------------------------------------------------------------------------

def _layer_group_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, stride: int,
                        kw: int, w_out: int):
    """One (batch, out_row, kernel_row, group) grid step.

    The group dimension is innermost, so the input-row block index is
    constant across the group sweep — Pallas keeps the row resident in VMEM
    and only the (kw, C_in, 4) weight slice and (1, 4) bias change per step.

    x_ref: (1, 1, W_in, C_in); w_ref: (kh, kw, C_in, 4) group slice;
    b_ref: (1, 4) group slice; o_ref: (1, 1, W_out, 4) group output;
    acc_ref: (n_groups, W_out, 4) fp32 scratch (one accumulator per group).
    """
    i = pl.program_id(2)          # kernel row index
    g = pl.program_id(3)          # output-group index
    kh = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[pl.ds(g, 1)] = jnp.broadcast_to(
            b_ref[0].astype(jnp.float32), (1, w_out, 4))

    x = x_ref[0, 0].astype(jnp.float32)      # (W_in, C_in)
    w = w_ref[i].astype(jnp.float32)         # (kw, C_in, 4)

    acc = acc_ref[pl.ds(g, 1)][0]
    for j in range(kw):
        cols = jax.lax.slice(x, (j, 0),
                             (j + (w_out - 1) * stride + 1, x.shape[1]),
                             (stride, 1))     # (W_out, C_in)
        acc = acc + cols @ w[j]
    acc_ref[pl.ds(g, 1)] = acc[None]

    @pl.when(i == kh - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[pl.ds(g, 1)][0].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def miniconv_layer_grouped(x, w, b, *, stride: int = 1, interpret=None):
    """All output groups of one layer in a single pallas_call (VALID conv).

    x: (B, H_in, W_in, C_in); w: (kh, kw, C_in, C_out) with C_out % 4 == 0
    (callers pad; see ``repro.kernels.ops.miniconv_layer``); b: (C_out,).
    """
    B, h_in, w_in, c_in = x.shape
    kh, kw, c_in_w, c_out = w.shape
    assert c_in == c_in_w and c_out % 4 == 0, (x.shape, w.shape)
    n_groups = c_out // 4
    h_out = (h_in - kh) // stride + 1
    w_out = (w_in - kw) // stride + 1

    grid = (B, h_out, kh, n_groups)
    return pl.pallas_call(
        functools.partial(_layer_group_kernel, stride=stride, kw=kw,
                          w_out=w_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, w_in, c_in),
                         lambda b_, q, i, g: (b_, q * stride + i, 0, 0)),
            pl.BlockSpec((kh, kw, c_in, 4),
                         lambda b_, q, i, g: (0, 0, 0, g)),
            pl.BlockSpec((1, 4), lambda b_, q, i, g: (g, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, w_out, 4),
                               lambda b_, q, i, g: (b_, q, 0, g)),
        out_shape=jax.ShapeDtypeStruct((B, h_out, w_out, c_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((n_groups, w_out, 4), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, w, b.reshape(n_groups, 4))


# ---------------------------------------------------------------------------
# Tier 3: the whole encoder as ONE fused kernel
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST      # fp32 MXU contraction on TPU


def _conv_row(src_ref, lead, r, w_ref, bias, m):
    """Output row ``r`` of layer ``m``'s SAME conv, read from its padded
    input parked in VMEM (``src_ref[*lead]``: (H_pad, W_pad, C_in_pad)).

    ``m`` is the layer as the kernel executes it
    (``PassPlan.fused_layers``).  Each (i, j) tap is one ref load of the
    ``out_w`` input columns it samples (``pl.ds`` with the layer's
    stride: the TPU lowers strided REF loads, not strided value slices;
    contiguous in a folded layer 0) and one (out_w, C_in) @ (C_in, C_out)
    MXU matmul — all output groups of the layer in a single contraction.
    Returns the activated (out_w, C_out_pad) fp32 row.
    """
    acc = jnp.broadcast_to(bias, (m.out_w, m.c_out_pad))
    for i in range(m.kernel):
        for j in range(m.kernel):
            win = src_ref[(*lead, r * m.stride + i,
                           pl.ds(j, m.out_w, stride=m.stride), slice(None))]
            acc = acc + jnp.dot(win.astype(jnp.float32),
                                w_ref[i, j].astype(jnp.float32),
                                precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
    return _ACTS[m.activation](acc)


def _front_layer(src_ref, lead, dst_ref, w_ref, b_ref, m, nxt):
    """Run layer ``m`` over a whole frame, writing its activated output
    into the interior of ``dst_ref`` — the zero-bordered (SAME-padded)
    input of the next layer ``nxt``."""
    dst_ref[...] = jnp.zeros(dst_ref.shape, jnp.float32)
    bias = b_ref[...].astype(jnp.float32)

    def row(r, carry):
        dst_ref[nxt.pad_top + r, pl.ds(nxt.pad_left, m.out_w)] = _conv_row(
            src_ref, lead, r, w_ref, bias, m)
        return carry

    jax.lax.fori_loop(0, m.out_h, row, 0)


def _encoder_kernel(*refs, plan, tile_h: int, has_head: bool, head_act: str):
    """One (chunk, batch, out_row_tile) grid step of the fused encoder.

    refs layout: x_ref, w_0..w_{L-1}, b_0..b_{L-1}[, hw_ref, hb_ref],
    o_ref[, z_ref], buf_1..buf_{L-1}[, z_scr].
    ``x_ref`` holds layer 0's input as the kernel executes it
    (``plan.fused_layers[0]``: space-to-depth folded when ``plan.fold`` >
    1, with ``w_0`` folded to match).
    ``buf_l`` holds the SAME-padded input of layer l for the current batch
    element, (rows, W_pad, C_in_pad) fp32: layers 0..L-2 fill them once,
    on the first tile step, and the final layer's buffer is over-allocated
    to ``scratch_rows`` so every tile's reads stay in bounds.  With a fused
    head, ``hw_ref`` is the (rows * C_out_pad, W_out, D) head weight laid
    out by :func:`prepare_fused_head`, ``z_scr`` the (W_out, D) fp32
    projection accumulator and ``z_ref`` the (1, 1, D) projection block.

    ``x_ref`` is one batch chunk's input block — the whole micro-batch
    unless the launch streams (:func:`_fused_launch`) — and ``hw_ref`` a
    whole-array block; the kernel indexes the batch element ``b_i``
    within the chunk itself.  The chunk must fit VMEM:
    ``PassPlan.max_safe_batch``.
    """
    layers = plan.fused_layers
    L = len(layers)
    n_in = 1 + 2 * L + (2 if has_head else 0)
    x_ref = refs[0]
    w_refs = refs[1:1 + L]
    b_refs = refs[1 + L:1 + 2 * L]
    if has_head:
        hw_ref, hb_ref = refs[1 + 2 * L], refs[2 + 2 * L]
    o_ref = refs[n_in]
    z_ref = refs[n_in + 1] if has_head else None
    scr = refs[n_in + (2 if has_head else 1):]
    bufs = scr[:L - 1]
    z_scr = scr[-1] if has_head else None
    b_i = pl.program_id(1)
    t = pl.program_id(2)
    last = layers[-1]

    if L > 1:
        @pl.when(t == 0)
        def _chain_front_layers():
            # Layers 0..L-2 run once per batch element; intermediates stay
            # on-chip in the padded-input buffers.
            for l in range(L - 1):
                src, lead = (x_ref, (b_i,)) if l == 0 else (bufs[l - 1], ())
                _front_layer(src, lead, bufs[l], w_refs[l], b_refs[l],
                             layers[l], layers[l + 1])

    if has_head:
        @pl.when(t == 0)
        def _z_init():
            z_scr[...] = jnp.zeros(z_scr.shape, jnp.float32)

    # Final layer: tile_h output rows per grid step.
    src, lead = (bufs[-1], ()) if L > 1 else (x_ref, (b_i,))
    bias = b_refs[-1][...].astype(jnp.float32)

    def tile_row(k, carry):
        r = t * tile_h + k
        y = _conv_row(src, lead, r, w_refs[-1], bias, last)
        o_ref[0, k] = y.astype(o_ref.dtype)
        if has_head:
            # Fused projection epilogue: weight each output channel's
            # column of this row against its (W_out, D) head-weight slice.
            # Zero-padded weight rows (beyond plan.out_h) and channels
            # (beyond plan.k_out) null the over-allocated tile rows and
            # the RGBA padding.
            z = z_scr[...]
            for c in range(last.c_out_pad):
                z = z + y[:, c:c + 1] * hw_ref[r * last.c_out_pad + c].astype(
                    jnp.float32)
            z_scr[...] = z
        return carry

    jax.lax.fori_loop(0, tile_h, tile_row, 0)

    if has_head:
        @pl.when(t == pl.num_programs(2) - 1)
        def _z_flush():
            z = (jnp.sum(z_scr[...], axis=0, keepdims=True)
                 + hb_ref[...].astype(jnp.float32))
            z_ref[0] = _ACTS[head_act](z).astype(z_ref.dtype)


def _tile_head(head_w, plan, *, rows: int):
    """Lay a (plan.flat_features, D) head weight out for the kernel's
    epilogue: (rows * C_out_pad, W_out, D), one (W_out, D) slice per
    (feature row, channel), zero beyond plan.out_h rows and plan.k_out
    channels (they cancel the final tile's over-allocated rows and the
    RGBA padding)."""
    last = plan.layers[-1]
    assert head_w.shape[0] == plan.flat_features, (head_w.shape,
                                                   plan.flat_features)
    d_out = head_w.shape[1]
    hw = head_w.reshape(plan.out_h, plan.out_w, plan.k_out, d_out)
    hw = jnp.pad(hw.transpose(0, 2, 1, 3),
                 ((0, rows - plan.out_h), (0, last.c_out_pad - plan.k_out),
                  (0, 0), (0, 0)))
    return hw.reshape(rows * last.c_out_pad, plan.out_w, d_out)


@functools.partial(jax.jit, static_argnames=("plan", "tile_h"))
def prepare_fused_head(head_w, plan, *, tile_h: int = 8):
    """Pre-tile a (plan.flat_features, D) head weight for the fused-head
    epilogue.  :func:`miniconv_encoder` tiles a 2-D ``head_w`` per call
    (inside the launch, a multi-MB transpose+pad); hot serving paths should
    call this ONCE per head and pass the 3-D result instead."""
    tile_h = max(1, min(tile_h, plan.out_h))
    n_tiles = -(-plan.out_h // tile_h)
    with scope("miniconv.head_tile"):
        return _tile_head(head_w, plan, rows=n_tiles * tile_h)


def miniconv_encoder(x, weights, biases, plan, *, tile_h: int = 8,
                     head_w=None, head_b=None, head_act: str = "relu",
                     interpret=None):
    """Execute a whole :class:`~repro.core.passplan.PassPlan` as ONE kernel.

    x: (B, H, W, C_in) with (H, W) == (plan.in_h, plan.in_w); batch is the
    outer grid dimension, so a micro-batch of frames is a single launch.
    weights/biases: per-layer lists matching ``plan.spec.layers``.
    Returns (B, plan.out_h, plan.out_w, plan.k_out) in x.dtype — bitwise
    semantics match the per-pass path (SAME padding, fp32 accumulation,
    per-layer activation) within float tolerance.

    ``head_w`` ((plan.flat_features, D), optional) fuses the server-side
    linear projection into the kernel epilogue: the return value becomes
    ``(features, head_act(features.reshape(B, -1) @ head_w + head_b))``
    with the (B, D) projection accumulated tile-by-tile inside the kernel.
    A 3-D ``head_w`` is taken as already tiled by :func:`prepare_fused_head`
    (with the SAME ``tile_h``), skipping the per-call tiling copy.
    """
    return _fused_launch(x, weights, biases, plan, tile_h=tile_h,
                         head_w=head_w, head_b=head_b, head_act=head_act,
                         interpret=resolve_interpret(interpret))


def _space_to_depth(x, s: int):
    """(B, H, W, C) -> (B, H/s, W/s, s*s*C): each s x s block of pixels
    becomes one pixel, its channels ordered (block row, block column,
    channel)."""
    # Columns first, then the row phase: on a TPU v5e XLA's relayouts of
    # this form take 32.6 µs of device time for 8 84x84x9 frames, against
    # 39.0 µs for one 6-D transpose.
    B, h, w, c = x.shape
    x = x.reshape(B, h // s, s, w // s, s * c).transpose(0, 1, 3, 2, 4)
    return x.reshape(B, h // s, w // s, s * s * c)


def _fold_weight(wt, s: int):
    """(k, k, C, O) -> (k', k', s*s*C, O) with k' = ceil(k/s): the taps of
    a stride-s conv over the :func:`_space_to_depth` input, zero where k
    does not divide by s."""
    k, _, c, o = wt.shape
    kf = -(-k // s)
    wt = jnp.pad(wt, ((0, kf * s - k), (0, kf * s - k), (0, 0), (0, 0)))
    wt = wt.reshape(kf, s, kf, s, c, o).transpose(0, 2, 1, 3, 4, 5)
    return wt.reshape(kf, kf, s * s * c, o)


def _prep_fused_inputs(x, weights, biases, plan, *, tile_h: int,
                       head_w, head_b):
    """Shared argument preparation for the fused / streamed encoders.

    Pads the input batch to RGBA channel multiples with layer-0 SAME
    padding baked in, zero-pads per-layer weights/biases, tiles and
    lane-pads the optional head weight, and derives every static dimension
    both launch shapes need.  With ``plan.fold`` s > 1 the padded input
    and the layer-0 weights are folded space-to-depth for the kernel
    (``plan.fused_layers``).  Returns a plain dict so the single-launch
    and batch-streamed callers build their own grids/BlockSpecs over
    IDENTICAL kernel operands (this is what makes them bitwise-equal).
    """
    layers = plan.fused_layers
    L = len(layers)
    B, h, w_sz, c_in = x.shape
    assert (h, w_sz) == (plan.in_h, plan.in_w), (x.shape, plan.in_h,
                                                 plan.in_w)
    assert c_in == plan.layers[0].c_in and len(weights) == L == len(biases)
    has_head = head_w is not None

    tile_h, n_tiles, scratch_rows = plan.fused_tiling(tile_h)
    last = layers[-1]

    # Bake in layer-0 SAME padding (up to a multiple of the fold), then
    # zero-pad channels to RGBA multiples, folded first where s > 1.
    s, first = plan.fold, layers[0]
    x0_rows = scratch_rows if L == 1 else first.padded_in_h
    with scope("miniconv.input"):
        xp = jnp.zeros((B, x0_rows * s, first.padded_in_w * s,
                        c_in if s > 1 else first.c_in_pad), x.dtype)
        xp = jax.lax.dynamic_update_slice(
            xp, x, (0, plan.layers[0].pad_top, plan.layers[0].pad_left, 0))
        if s > 1:
            with scope("miniconv.s2d"):
                xp = _space_to_depth(xp, s)
                xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0),
                                  (0, first.c_in_pad - first.c_in)))
    ws, bs = [], []
    with scope("miniconv.weights"):
        for l, (wt, bi) in enumerate(zip(weights, biases)):
            m = layers[l]
            if l == 0 and s > 1:
                with scope("miniconv.s2d"):
                    wt = _fold_weight(wt, s)
            wp = jnp.zeros((m.kernel, m.kernel, m.c_in_pad, m.c_out_pad),
                           wt.dtype)
            wp = jax.lax.dynamic_update_slice(wp, wt, (0, 0, 0, 0))
            bp = jnp.zeros((1, m.c_out_pad), bi.dtype)
            bp = jax.lax.dynamic_update_slice(bp, bi[None], (0, 0))
            ws.append(wp)
            bs.append(bp)

    hw_pad = hb = None
    d_out = d_pad = 0
    head_rows = n_tiles * tile_h * last.c_out_pad
    if has_head:
        with scope("miniconv.head_tile"):
            if head_w.ndim == 3:          # pre-tiled by prepare_fused_head
                assert head_w.shape[:2] == (head_rows, last.out_w), \
                    (head_w.shape, head_rows, last.out_w)
                hw_pad = head_w
            else:
                hw_pad = _tile_head(head_w, plan, rows=n_tiles * tile_h)
            # Lane-pad the projection width to a multiple of 128 so the
            # epilogue fills whole vector lanes (D=512 is already aligned;
            # ragged widths gain zero columns that are sliced off below).
            d_out = hw_pad.shape[-1]
            d_pad = -(-d_out // 128) * 128
            if d_pad != d_out:
                hw_pad = jnp.pad(hw_pad,
                                 ((0, 0), (0, 0), (0, d_pad - d_out)))
            hb = (jnp.zeros((d_out,), hw_pad.dtype) if head_b is None
                  else head_b)
            if d_pad != d_out:
                hb = jnp.pad(hb, ((0, d_pad - d_out),))
            hb = hb.reshape(1, d_pad)

    # SAME-padded inputs of layers 1..L-1; the last is over-allocated to
    # scratch_rows so every tile's reads stay in bounds.
    scratch_shapes = [
        pltpu.VMEM((scratch_rows if l == L - 1 else m.padded_in_h,
                    m.padded_in_w, m.c_in_pad), jnp.float32)
        for l, m in enumerate(layers) if l > 0]
    if has_head:
        scratch_shapes.append(pltpu.VMEM((last.out_w, d_pad), jnp.float32))

    return dict(xp=xp, ws=ws, bs=bs, hw_pad=hw_pad, hb=hb,
                has_head=has_head, tile_h=tile_h, n_tiles=n_tiles,
                head_rows=head_rows, x0_rows=x0_rows, d_out=d_out,
                d_pad=d_pad, scratch_shapes=scratch_shapes, first=first,
                last=last)


@functools.partial(jax.jit, static_argnames=("plan", "tile_h", "head_act",
                                             "interpret", "chunk_b"))
def _fused_launch(x, weights, biases, plan, *, tile_h: int, head_w, head_b,
                  head_act: str, interpret: bool, chunk_b=None):
    """ONE pallas_call over a (n_chunks, chunk_b, n_tiles) grid.

    ``chunk_b=None`` makes the whole batch one chunk: the input is a
    whole-array block with a constant index map, fetched once and held in
    one VMEM buffer.  Otherwise the input BlockSpec covers one
    ``chunk_b``-frame chunk and its index map advances with the chunk grid
    dimension, so only that chunk is VMEM-resident; the compiler
    double-buffers it, fetching chunk c+1 HBM->VMEM while chunk c
    computes.  The batch is zero-padded up to a whole number of chunks;
    padded frames compute garbage that is sliced off (each batch element
    is independent, so real frames are bitwise unaffected).
    """
    B = x.shape[0]
    chunk = B if chunk_b is None else chunk_b
    n_chunks = -(-B // chunk)
    b_pad = n_chunks * chunk
    if b_pad != B:
        with scope("miniconv.input"):
            x = jnp.pad(x, ((0, b_pad - B), (0, 0), (0, 0), (0, 0)))
    p = _prep_fused_inputs(x, weights, biases, plan, tile_h=tile_h,
                           head_w=head_w, head_b=head_b)
    first, last = p["first"], p["last"]
    tile_h, n_tiles = p["tile_h"], p["n_tiles"]

    def const(*shape):
        return pl.BlockSpec(shape, lambda c, b_, t: (0,) * len(shape))

    x_block = (chunk, p["x0_rows"], first.padded_in_w, first.c_in_pad)
    in_specs = [const(*x_block) if chunk_b is None else
                pl.BlockSpec(x_block, lambda c, b_, t: (c, 0, 0, 0))]
    in_specs += [const(m.kernel, m.kernel, m.c_in_pad, m.c_out_pad)
                 for m in plan.fused_layers]
    in_specs += [const(1, m.c_out_pad) for m in plan.fused_layers]
    args = [p["xp"], *p["ws"], *p["bs"]]
    out_specs = [pl.BlockSpec(
        (1, tile_h, last.out_w, last.c_out_pad),
        lambda c, b_, t: (c * chunk + b_, t, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(
        (b_pad, n_tiles * tile_h, last.out_w, last.c_out_pad), x.dtype)]
    if p["has_head"]:
        d_pad = p["d_pad"]
        in_specs += [const(p["head_rows"], last.out_w, d_pad),
                     const(1, d_pad)]
        args += [p["hw_pad"], p["hb"]]
        out_specs.append(pl.BlockSpec(
            (1, 1, d_pad), lambda c, b_, t: (c * chunk + b_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b_pad, 1, d_pad), x.dtype))

    with scope("miniconv.kernel"):
        out = pl.pallas_call(
            functools.partial(_encoder_kernel, plan=plan, tile_h=tile_h,
                              has_head=p["has_head"], head_act=head_act),
            grid=(n_chunks, chunk, n_tiles),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=p["scratch_shapes"],
            # the VMEM limit is the budget the PassPlan's residency model
            # checks against, so plan and compiler agree on what fits
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "parallel", "arbitrary"),
                vmem_limit_bytes=DEFAULT_VMEM_LIMIT),
            interpret=interpret,
        )(*args)
    with scope("miniconv.out"):
        feats = out[0][:B, :plan.out_h, :, :plan.k_out]
        if p["has_head"]:
            return feats, out[1][:B, 0, :p["d_out"]]
        return feats


# ---------------------------------------------------------------------------
# Tier 4: large-batch streaming (the batch no longer has to fit VMEM)
# ---------------------------------------------------------------------------

def miniconv_encoder_stream(x, weights, biases, plan, *, chunk_b: int,
                            tile_h: int = 8, head_w=None, head_b=None,
                            head_act: str = "relu", interpret=None,
                            pipelined=None):
    """Fused encoder over a micro-batch LARGER than the VMEM budget allows.

    Splits the (B, H, W, C) batch into ``chunk_b``-frame chunks so only one
    chunk's input is VMEM-resident at a time (``chunk_b`` should come from
    ``PassPlan.max_safe_batch(streamed=True)``).  Two execution strategies:

    * ``pipelined=True`` — ONE pallas_call whose grid iterates chunks;
      per-chunk input BlockSpecs give the double-buffered HBM->VMEM fetch.
      The default for compiled kernels.  Bitwise equal to the single
      whole-batch fused launch.
    * ``pipelined=False`` — one fused launch per chunk (at most two
      compiled programs: the full chunk and the remainder).  The default
      in interpret mode, where per-step block fetches are slow.  Bitwise
      equal to running :func:`miniconv_encoder` chunk-by-chunk and
      concatenating — by construction.

    When ``B % chunk_b == 0`` the two strategies are themselves bitwise
    identical (every chunk launch runs the same kernel body as the
    streamed grid's inner steps).

    Returns the same (features[, projection]) as :func:`miniconv_encoder`.
    """
    if chunk_b < 1:
        raise ValueError(f"chunk_b must be >= 1, got {chunk_b}")
    interpret = resolve_interpret(interpret)
    kw = dict(tile_h=tile_h, head_b=head_b, head_act=head_act,
              interpret=interpret)
    B = x.shape[0]
    if B <= chunk_b:                      # fits one launch: nothing to stream
        return _fused_launch(x, weights, biases, plan, head_w=head_w, **kw)
    if pipelined is None:
        pipelined = not interpret
    if pipelined:
        return _fused_launch(x, weights, biases, plan, head_w=head_w,
                             chunk_b=chunk_b, **kw)
    # One launch per chunk: tile the head ONCE (not per chunk).
    if head_w is not None and head_w.ndim == 2:
        head_w = prepare_fused_head(head_w, plan, tile_h=tile_h)
    chunks = [_fused_launch(x[i:i + chunk_b], weights, biases, plan,
                            head_w=head_w, **kw)
              for i in range(0, B, chunk_b)]
    if head_w is not None:
        return (jnp.concatenate([c[0] for c in chunks]),
                jnp.concatenate([c[1] for c in chunks]))
    return jnp.concatenate(chunks)


__all__ = ["miniconv_pass", "miniconv_layer_grouped", "miniconv_encoder",
           "miniconv_encoder_stream", "prepare_fused_head"]
