"""Compiled-kernel tier: runs on a TPU only.

Exercises the fused and fused+head kernels COMPILED (interpret=False) at
the ``max_safe_batch`` VMEM boundary and far past it through the
``fused+stream`` batch pipeline, against the interpret-mode oracle; and
checks that the oracle tiers off the main path (``reference`` and
``grouped``) fail on the chip with the compiler's error instead of running
interpreted.  Elsewhere every test skips: the backend is checked in a
fixture, never while the module is imported.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.miniconv import miniconv_init, standard_spec
from repro.kernels.miniconv_pass import (miniconv_encoder,
                                         miniconv_encoder_stream)

X = 48          # deployment-scale input, small enough for CI arrays


@pytest.fixture(scope="module")
def on_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas kernels need a TPU backend")


@pytest.fixture(scope="module")
def fixture(on_tpu):
    spec = standard_spec()
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    plan = spec.plan(X)
    ws = [params[f"layer{i}"]["kernel"] for i in range(len(spec.layers))]
    bs = [params[f"layer{i}"]["bias"] for i in range(len(spec.layers))]
    hw = jax.random.normal(jax.random.PRNGKey(1),
                           (plan.flat_features, 32)) * 0.05
    hb = jax.random.normal(jax.random.PRNGKey(2), (32,)) * 0.05
    return plan, ws, bs, hw, hb


def _x(b):
    return jax.random.uniform(jax.random.PRNGKey(b), (b, X, X, 12))


@pytest.mark.parametrize("with_head", [False, True])
def test_compiled_fused_at_max_safe_boundary(fixture, with_head):
    """A compiled fused launch at exactly max_safe_batch frames runs and
    matches the interpret-mode oracle."""
    plan, ws, bs, hw, hb = fixture
    head = plan.head(32) if with_head else None
    b = min(plan.max_safe_batch(head=head), 32)
    assert b >= 1
    kw = dict(head_w=hw, head_b=hb) if with_head else {}
    got = miniconv_encoder(_x(b), ws, bs, plan, interpret=False, **kw)
    want = miniconv_encoder(_x(b), ws, bs, plan, interpret=True, **kw)
    if with_head:
        np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-4)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("with_head", [False, True])
def test_compiled_stream_past_max_safe(fixture, with_head):
    """B = 4x the chunk streams through one compiled pipelined launch,
    bitwise-equal to compiled chunk-by-chunk fused execution."""
    plan, ws, bs, hw, hb = fixture
    chunk = min(plan.max_safe_batch(head=plan.head(32) if with_head
                                    else None, streamed=True), 8)
    assert chunk >= 1
    b = 4 * chunk
    kw = dict(head_w=hw, head_b=hb) if with_head else {}
    x = _x(b)
    pipe = miniconv_encoder_stream(x, ws, bs, plan, chunk_b=chunk,
                                   interpret=False, pipelined=True, **kw)
    multi = miniconv_encoder_stream(x, ws, bs, plan, chunk_b=chunk,
                                    interpret=False, pipelined=False, **kw)
    if with_head:
        np.testing.assert_array_equal(pipe[0], multi[0])
        np.testing.assert_array_equal(pipe[1], multi[1])
    else:
        np.testing.assert_array_equal(pipe, multi)


@pytest.mark.parametrize("fused_groups", [False, True])
def test_oracle_tiers_fail_on_chip_instead_of_interpreting(on_tpu,
                                                           fused_groups):
    """The per-pass and grouped oracles resolve to compiled mode on the
    chip, where their BlockSpecs do not compile: the compiler's error
    surfaces instead of an interpreted run."""
    from repro.kernels.ops import miniconv_layer
    x = jnp.zeros((1, 12, 12, 4))
    w = jnp.zeros((3, 3, 4, 4))
    with pytest.raises(Exception):
        jax.block_until_ready(miniconv_layer(x, w, jnp.zeros((4,)),
                                             stride=2,
                                             fused_groups=fused_groups))
