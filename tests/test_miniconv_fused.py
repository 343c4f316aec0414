"""Parity of the fused execution tiers against the legacy per-pass kernel
(the reference oracle) and XLA SAME convs, across strides, kernel sizes,
odd/even inputs and c_out not divisible by 4 (interpret mode, fp32)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.miniconv import (LayerSpec, MiniConvSpec, miniconv_apply,
                                 miniconv_init, standard_spec)
from repro.kernels.miniconv_pass import miniconv_encoder_stream
from repro.kernels.ops import miniconv_layer

MODES = ("per_pass", "grouped", "fused")


def _run_all(spec, h, w, *, batch=1, seed=0):
    params = miniconv_init(jax.random.PRNGKey(seed), spec)
    x = jax.random.uniform(jax.random.PRNGKey(seed + 1),
                           (batch, h, w, spec.layers[0].c_in))
    ref = miniconv_apply(params, spec, x)                  # XLA oracle
    outs = {m: miniconv_apply(params, spec, x, use_kernel=m) for m in MODES}
    return ref, outs


@pytest.mark.parametrize("kernel,stride", [(3, 1), (3, 2), (4, 2)])
@pytest.mark.parametrize("size", [(16, 16), (17, 23)])   # even / odd
@pytest.mark.parametrize("c_out", [4, 6, 16])
def test_single_layer_parity(kernel, stride, size, c_out):
    spec = MiniConvSpec((LayerSpec(kernel, stride, 8, c_out),))
    ref, outs = _run_all(spec, *size)
    for mode, out in outs.items():
        assert out.shape == ref.shape, (mode, out.shape, ref.shape)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=mode)


@pytest.mark.parametrize("h,w", [(84, 84), (83, 59)])
@pytest.mark.parametrize("k", [4, 16])
def test_standard_spec_family_parity(h, w, k):
    """The ISSUE-1 acceptance criterion: fused matches per-pass within 1e-5
    on the standard_spec family."""
    spec = standard_spec(c_in=12, k=k)
    ref, outs = _run_all(spec, h, w, batch=2)
    np.testing.assert_allclose(outs["fused"], outs["per_pass"],
                               atol=1e-5, rtol=1e-5)
    for mode, out in outs.items():
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=mode)


def test_multi_layer_c_out_not_divisible_by_4():
    """Specs with K % 4 != 0 validate AND execute (the old kernel path
    crashed on an assert); sigmoid on an intermediate ragged layer must not
    leak through the zero-padded channels."""
    spec = MiniConvSpec((LayerSpec(4, 2, 4, 6, activation="sigmoid"),
                         LayerSpec(3, 2, 6, 16),
                         LayerSpec(3, 1, 16, 6)))
    spec.validate()
    ref, outs = _run_all(spec, 33, 19)
    assert ref.shape[-1] == 6
    for mode, out in outs.items():
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=mode)


def test_layer_kernel_c_out_6_no_crash():
    """Direct layer-level check of the padded final output group."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, 12, 12, 8))
    w = jax.random.normal(key, (3, 3, 8, 6)) * 0.1
    b = jnp.zeros((6,))
    from repro.nn.layers import conv2d
    ref = conv2d({"kernel": w, "bias": b}, x, stride=2, padding="SAME")
    for fused_groups in (False, True):
        out = miniconv_layer(x, w, b, stride=2, interpret=True,
                             fused_groups=fused_groups)
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tile_h", [1, 3, 8, 64])
def test_fused_tile_h_sweep(tile_h):
    """Every row tiling (including tile_h > out_h and non-divisible
    out_h) produces identical features."""
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 84, 84, 4))
    ref = miniconv_apply(params, spec, x)
    out = miniconv_apply(params, spec, x, use_kernel="fused", tile_h=tile_h)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_fused_batch_independence():
    """Scratch re-initialisation across batch grid steps: batched run ==
    stacked single runs."""
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (3, 32, 32, 4))
    batched = miniconv_apply(params, spec, x, use_kernel="fused")
    singles = jnp.concatenate(
        [miniconv_apply(params, spec, x[i:i + 1], use_kernel="fused")
         for i in range(3)])
    np.testing.assert_allclose(batched, singles, atol=1e-6, rtol=1e-6)


def test_use_kernel_true_is_per_pass_alias():
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 16, 16, 4))
    a = miniconv_apply(params, spec, x, use_kernel=True)
    b = miniconv_apply(params, spec, x, use_kernel="per_pass")
    np.testing.assert_allclose(a, b, atol=0, rtol=0)


def test_bad_mode_raises():
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jnp.zeros((1, 16, 16, 4))
    with pytest.raises(ValueError):
        miniconv_apply(params, spec, x, use_kernel="warp")


FOLD_CASES = {
    # name: (spec, (h, w), batch, head_dim, stream chunk)
    "84-c9-b8-head": (standard_spec(c_in=9, k=4), (84, 84), 8, 64, None),
    "84-c12-b1": (standard_spec(c_in=12, k=4), (84, 84), 1, None, None),
    "256-c4-streamed": (standard_spec(c_in=4, k=4), (256, 256), 3, 32, 2),
    "k3s2-first": (MiniConvSpec((LayerSpec(3, 2, 5, 8),
                                 LayerSpec(3, 2, 8, 4))), (23, 17), 2, None,
                   None),
    "single-layer": (MiniConvSpec((LayerSpec(4, 2, 6, 6),)), (19, 22), 2,
                     24, None),
    "stride1-first": (MiniConvSpec((LayerSpec(3, 1, 8, 8),
                                    LayerSpec(3, 2, 8, 4))), (16, 16), 2,
                      None, None),
}


@pytest.mark.parametrize("case", list(FOLD_CASES))
def test_fused_fold_parity(case):
    """The fused kernel with layer 0 folded space-to-depth (plan.fold > 1)
    matches the plain XLA reference: features, and the fused projection
    where the case has a head; the streamed case runs one pipelined
    launch over whole and ragged chunks.  A stride-1 first layer is not
    folded: the plan says so and the compiled step holds no op under
    ``miniconv.s2d``."""
    spec, (h, w), batch, head_dim, chunk = FOLD_CASES[case]
    plan = spec.plan(h, w)
    assert plan.fold == spec.layers[0].stride
    assert (plan.fused_layers == plan.layers) == (plan.fold == 1)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (batch, h, w, spec.layers[0].c_in))
    head = None
    if head_dim:
        kw, kb = jax.random.split(jax.random.PRNGKey(2))
        head = {"kernel": jax.random.normal(
                    kw, (plan.flat_features, head_dim)) / plan.flat_features,
                "bias": 0.1 * jax.random.normal(kb, (head_dim,))}

    def fused(p, x):
        if chunk is None:
            return miniconv_apply(p, spec, x, use_kernel="fused", plan=plan,
                                  head=head)
        n = len(spec.layers)
        return miniconv_encoder_stream(
            x, [p[f"layer{i}"]["kernel"] for i in range(n)],
            [p[f"layer{i}"]["bias"] for i in range(n)], plan, chunk_b=chunk,
            head_w=head["kernel"] if head else None,
            head_b=head["bias"] if head else None, pipelined=True)

    compiled = jax.jit(fused).lower(params, x).compile()
    assert ("miniconv.s2d" in compiled.as_text()) == (plan.fold > 1)
    out = compiled(params, x)
    ref = miniconv_apply(params, spec, x, head=head)
    if head is None:
        out, ref = (out,), (ref,)
    for got, want in zip(out, ref):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
