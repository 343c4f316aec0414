"""PassPlan IR: budget properties, shape truth, and agreement between every
place that used to duplicate the ceil/floor shape math."""
import math

import jax
import pytest

from repro.core.latency import SplitConfig
from repro.core.miniconv import (LayerSpec, MiniConvSpec, ShaderBudget,
                                 miniconv_feature_shape, standard_spec)
from repro.core.passplan import (build_pass_plan, count_passes, out_size,
                                 out_spatial_chain, same_pads, tiled_bytes)
from repro.core.wire import feature_bytes
from repro.kernels.miniconv_pass import miniconv_encoder_stream

SPECS = {
    "k4": standard_spec(12, 4),
    "k16": standard_spec(12, 16),
    "c6": MiniConvSpec((LayerSpec(4, 2, 4, 6),
                        LayerSpec(3, 2, 6, 16),
                        LayerSpec(3, 1, 16, 6, activation="sigmoid"))),
    "single": MiniConvSpec((LayerSpec(3, 1, 8, 4),)),
}
SIZES = [64, 84, 100, 101, 400]


@pytest.mark.parametrize("name", sorted(SPECS))
@pytest.mark.parametrize("x", SIZES)
def test_every_pass_respects_budget(name, x):
    spec = SPECS[name]
    plan = build_pass_plan(spec, x)
    for p in plan.passes:
        assert spec.budget.check_pass(p.kernel, p.c_in) == []
        assert p.samples <= spec.budget.max_samples
        assert p.in_textures <= spec.budget.max_textures
        assert 1 <= p.out_hi - p.out_lo <= 4
    assert plan.max_pass_samples <= spec.budget.max_samples


@pytest.mark.parametrize("name", sorted(SPECS))
@pytest.mark.parametrize("x", SIZES)
def test_total_passes_matches_spec(name, x):
    spec = SPECS[name]
    plan = build_pass_plan(spec, x)
    assert plan.total_passes == spec.total_passes == count_passes(spec)
    assert plan.total_passes == sum(l.n_passes for l in spec.layers)
    # groups partition the channels exactly
    for lp in plan.layers:
        slices = [(p.out_lo, p.out_hi) for p in plan.passes
                  if p.layer == lp.index]
        assert slices[0][0] == 0 and slices[-1][1] == lp.c_out
        for (a, b), (c, d) in zip(slices, slices[1:]):
            assert b == c


@pytest.mark.parametrize("name", sorted(SPECS))
@pytest.mark.parametrize("x", SIZES)
def test_plan_shapes_are_the_truth(name, x):
    """plan == MiniConvSpec.* == actual XLA conv output shapes."""
    import jax.numpy as jnp
    from repro.core.miniconv import miniconv_apply, miniconv_init

    spec = SPECS[name]
    plan = build_pass_plan(spec, x)
    assert plan.feature_shape == miniconv_feature_shape(spec, x, x)
    assert plan.out_h == spec.out_spatial(x)
    assert plan.feature_bytes == spec.feature_bytes(x)
    assert plan.flops_per_frame == spec.flops_per_frame(x)
    if x > 100:       # keep the conv check cheap
        return
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    obs = jnp.zeros((1, x, x, spec.layers[0].c_in))
    feats = miniconv_apply(params, spec, obs)
    assert feats.shape[1:] == plan.feature_shape


def test_wire_and_latency_agree_with_plan_for_non_divisible_sizes():
    """The ISSUE-1 satellite: 100x100 through 3 stride-2 layers is 13x13
    (ceil), not 12x12 (the old floor accounting)."""
    assert out_spatial_chain(100, (2, 2, 2)) == 13
    assert feature_bytes(100, 3, 4) == 4 * 13 * 13
    assert SplitConfig(100, 3, 4, 0.1).feature_bytes == 4 * 13 * 13
    # divisible sizes unchanged (paper numbers)
    assert feature_bytes(400, 3, 4) == 4 * 50 * 50
    spec = standard_spec(12, 4)
    assert spec.feature_bytes(100) == build_pass_plan(spec, 100).feature_bytes


def test_same_pads_matches_xla_rule():
    for size in (7, 8, 84, 101):
        for k, s in ((3, 1), (3, 2), (4, 2)):
            lo, hi = same_pads(size, k, s)
            out = out_size(size, s)
            assert lo + hi == max((out - 1) * s + k - size, 0)
            assert hi - lo in (0, 1)


def test_over_budget_plan_raises_at_build_time():
    bad = MiniConvSpec((LayerSpec(5, 2, 12, 16),))    # 75 samples > 64
    with pytest.raises(ValueError):
        build_pass_plan(bad, 64)
    tight = ShaderBudget(max_samples=48)
    ok = MiniConvSpec((LayerSpec(4, 2, 12, 16),), budget=tight)  # exactly 48
    build_pass_plan(ok, 64)


def test_texture_bindings_pack_rgba():
    plan = build_pass_plan(standard_spec(12, 4), 64)
    p0 = plan.passes[0]
    assert p0.texture_bindings == ((0, 4), (4, 8), (8, 12))
    assert p0.in_textures == 3 and p0.samples == 4 * 4 * 3


# ---------------------------------------------------------------------------
# space-to-depth fold of a stride-s first layer (the fused kernel's geometry)
# ---------------------------------------------------------------------------

def test_fold_keeps_the_logical_plan():
    """``fold`` is layer 0's stride; ``fused_layers`` folds layer 0 alone
    and leaves the logical layers (passes, wire, counts) as they were."""
    plan = build_pass_plan(standard_spec(12, 4), 84)
    assert plan.fold == 2
    f0, l0 = plan.fused_layers[0], plan.layers[0]
    assert (f0.kernel, f0.stride, f0.c_in, f0.c_in_pad) == (2, 1, 48, 48)
    assert (f0.padded_in_h, f0.padded_in_w) == (43, 43)
    assert (f0.out_h, f0.out_w, f0.c_out) == (l0.out_h, l0.out_w, l0.c_out)
    assert plan.fused_layers[1:] == plan.layers[1:]
    assert (l0.kernel, l0.stride, l0.c_in) == (4, 2, 12)
    assert plan.flops_per_frame == standard_spec(12, 4).flops_per_frame(84)
    # a k=3 first layer folds to 2x2 taps over the zero-padded kernel
    k3 = build_pass_plan(MiniConvSpec((LayerSpec(3, 2, 5, 8),)), 23, 17)
    assert (k3.fused_layers[0].kernel, k3.fused_layers[0].c_in_pad) == (2, 20)
    # a stride-1 first layer runs as planned
    s1 = build_pass_plan(SPECS["single"], 64)
    assert s1.fold == 1 and s1.fused_layers is s1.layers


def _input_block(plan, batch, chunk=None):
    """The block shape of the fused launch's input BlockSpec, read from
    the ``pallas_call`` in the launch's jaxpr."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jax.numpy.float32)
    ws = [S(l.kernel, l.kernel, l.c_in, l.c_out) for l in plan.layers]
    bs = [S(l.c_out) for l in plan.layers]
    x = S(batch, plan.in_h, plan.in_w, plan.layers[0].c_in)
    jaxpr = jax.make_jaxpr(lambda x, ws, bs: miniconv_encoder_stream(
        x, ws, bs, plan, chunk_b=chunk or batch, interpret=False,
        pipelined=True))(x, ws, bs)

    def find(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found

    block = find(jaxpr.jaxpr).params["grid_mapping"].block_mappings[0]
    return tuple(getattr(b, "block_size", b) for b in block.block_shape)


@pytest.mark.parametrize("spec,size,streamed", [
    (standard_spec(12, 4), (84, 84), False),
    (standard_spec(9, 4), (84, 84), False),
    (standard_spec(4, 4), (256, 256), True),
    (MiniConvSpec((LayerSpec(4, 2, 6, 6),)), (19, 22), False),
    (SPECS["c6"], (33, 19), True),
], ids=["84-c12", "84-c9", "256-c4-streamed", "single-layer", "c6"])
def test_fold_input_block_bytes_match_the_kernel_blockspec(spec, size,
                                                           streamed):
    """The VMEM model's per-frame input bytes are the tiled bytes of the
    block the kernel really takes: layer 0's input folded space-to-depth,
    counted twice where a streamed chunk is double-buffered."""
    plan = build_pass_plan(spec, *size)
    block = _input_block(plan, 3, chunk=1 if streamed else None)
    assert block[0] == (1 if streamed else 3)
    f0 = plan.fused_layers[0]
    assert block[2:] == (f0.padded_in_w, f0.c_in_pad)
    per_frame = (plan.vmem_bytes(2, streamed=streamed)
                 - plan.vmem_bytes(1, streamed=streamed))
    assert per_frame == (2 if streamed else 1) * tiled_bytes(block[1:])
    if size == (84, 84) and spec.layers[0].c_in == 12:
        assert block[1:] == (43, 43, 48)


def test_fold_max_safe_batch():
    """Launchable micro-batches of the folded kernel: the 84x84 c_in=12
    deployment (25 / 24 / 12 before the fold) and 256x256 c_in=4 (2 / 2 /
    1), which now takes B=8 in one whole-batch launch with the head."""
    plan = build_pass_plan(standard_spec(12, 4), 84)
    head = plan.head(512)
    assert plan.max_safe_batch() == 93
    assert plan.max_safe_batch(head=head) == 91
    assert plan.max_safe_batch(head=head, streamed=True) == 45
    plan = build_pass_plan(standard_spec(4, 4), 256)
    head = plan.head(512)
    assert plan.max_safe_batch() == 9
    assert plan.max_safe_batch(head=head) == 8
    assert plan.max_safe_batch(head=head, streamed=True) == 4
