"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(interpret mode executes the kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.miniconv_pass import miniconv_pass
from repro.kernels.ops import causal_attention, miniconv_layer, same_pad
from repro.kernels.ref import attention_ref, miniconv_pass_ref


@pytest.mark.parametrize("h,w", [(16, 16), (20, 28), (33, 17)])
@pytest.mark.parametrize("kernel,stride", [(3, 1), (3, 2), (4, 2), (1, 1)])
@pytest.mark.parametrize("c_in", [4, 8, 12])
def test_miniconv_pass_shapes(h, w, kernel, stride, c_in):
    key = jax.random.PRNGKey(h * w + kernel)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, h, w, c_in), jnp.float32)
    wgt = jax.random.normal(k2, (kernel, kernel, c_in, 4)) * 0.1
    b = jax.random.normal(k3, (4,)) * 0.1
    if h < kernel or w < kernel:
        pytest.skip("kernel larger than input")
    out = miniconv_pass(x, wgt, b, stride=stride, interpret=True)
    ref = miniconv_pass_ref(x, wgt, b, stride=stride)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_miniconv_pass_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 12, 12, 8)).astype(dtype)
    w = (jax.random.normal(key, (3, 3, 8, 4)) * 0.1).astype(dtype)
    b = jnp.zeros((4,), dtype)
    out = miniconv_pass(x, w, b, stride=1, interpret=True)
    ref = miniconv_pass_ref(x, w, b, stride=1)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_miniconv_layer_matches_same_conv():
    """Multi-pass layer (c_out > 4, SAME padding) == XLA SAME conv."""
    from repro.nn.layers import conv2d
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, 21, 21, 8))
    w = jax.random.normal(key, (3, 3, 8, 12)) * 0.1
    b = jnp.zeros((12,))
    out = miniconv_layer(x, w, b, stride=2, interpret=True)
    ref = conv2d({"kernel": w, "bias": b}, x, stride=2, padding="SAME")
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("s", [128, 256])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 64)])
def test_flash_attention_vs_ref(s, window, blocks):
    bq, bk = blocks
    key = jax.random.PRNGKey(s)
    q, k, v = [jax.random.normal(kk, (1, 2, s, 32)) for kk in
               jax.random.split(key, 3)]
    out = flash_attention(q, k, v, causal=True, sliding_window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    q, k, v = [jax.random.normal(kk, (1, 2, 128, 32)).astype(dtype)
               for kk in jax.random.split(key, 3)]
    out = causal_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_same_pad_matches_xla_same():
    from repro.nn.layers import conv2d
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, 13, 17, 4))
    w = jax.random.normal(key, (4, 4, 4, 4)) * 0.1
    xp = same_pad(x, 4, 2)
    ref = conv2d({"kernel": w}, x, stride=2, padding="SAME")
    out = miniconv_pass_ref(xp, w, jnp.zeros((4,)), stride=2)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_resolve_interpret_only_on_cpu():
    """One switch: an explicit choice wins; otherwise kernels interpret on
    the CPU backend only, and perf stamps resolve the same way."""
    from repro.kernels.interpret import resolve_interpret
    from repro.perfstamp import execution_mode
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    on_cpu = jax.default_backend() == "cpu"
    assert resolve_interpret(None) is on_cpu
    assert execution_mode() == ("interpret" if on_cpu else "compiled")
    assert execution_mode(False) == "compiled"
