"""Ahead-of-time compiles of the fused kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode accepts
and the chip refuses: unsupported primitives, misaligned slices and
blocks, and more VMEM than the launch may use.  They compile the main
path — ``Deployment.build`` -> the fused edge half / encoder — at the
paper's standard deployment (k=4, c_in=12, 84x84, head_dim=512) at B=1,
at B=8 and streamed, a fused launch at exactly ``max_safe_batch``, and
whole-batch launches of RGBA frames at 256x256 and 400x400.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.miniconv import standard_spec
from repro.deploy import Deployment, DeploymentConfig
from repro.kernels.miniconv_pass import miniconv_encoder

X, C_IN, HEAD_DIM = 84, 12, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on_chip(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _deployment(backend, max_batch=8):
    cfg = DeploymentConfig.standard(k=4, c_in=C_IN, h=X, head_dim=HEAD_DIM,
                                    max_batch=max_batch, backend=backend,
                                    interpret=False)
    return Deployment.build(cfg)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _compile_main_path(dep, batch, one_chip):
    """Compile the deployment's fused call at ``batch`` frames: the edge
    half for ``fused``, the encoder + projection for the head backends."""
    params = _on_chip(jax.eval_shape(dep.init, jax.random.PRNGKey(0)),
                      one_chip)
    obs = jax.ShapeDtypeStruct((batch, X, X, C_IN), jnp.float32,
                               sharding=one_chip)
    if dep.backend.fused_head:
        return _compile(dep.encoder.apply, params, obs)
    return _compile(dep.split.edge_apply, params["edge"], obs)


@pytest.mark.parametrize("backend", ["fused", "fused+head", "fused+stream"])
@pytest.mark.parametrize("batch", [1, 8])
def test_fused_backends_compile(one_chip, backend, batch):
    _compile_main_path(_deployment(backend), batch, one_chip)


def test_fused_stream_compiles_streamed(one_chip):
    """fused+stream at 4x its chunk: one pipelined launch whose grid walks
    the chunks, past what a whole-batch launch could hold."""
    dep = _deployment("fused+stream", max_batch=128)
    batch = 4 * dep.stream_chunk
    assert batch > dep.max_safe_batch
    _compile_main_path(dep, batch, one_chip)


def test_fused_over_budget_batch_compiles_streamed(one_chip):
    """A plain fused deployment whose max_batch exceeds max_safe_batch is
    pipelined by Deployment.build, and that launch compiles."""
    dep = _deployment("fused", max_batch=128)
    assert dep.stream_chunk is not None
    assert dep.stream_chunk <= dep.max_safe_batch < 128
    _compile_main_path(dep, 128, one_chip)


@pytest.mark.parametrize("with_head", [False, True])
def test_fused_launch_compiles_at_max_safe_batch(one_chip, with_head):
    """The VMEM model and the compiler agree at the boundary: a whole-batch
    fused launch of exactly max_safe_batch frames fits the limit the
    kernel hands the compiler."""
    dep = _deployment("fused+head" if with_head else "fused")
    head = dep.head_plan if with_head else None
    batch = dep.plan.max_safe_batch(head=head)
    assert batch == dep.max_safe_batch >= 8
    _compile_whole_batch(dep.plan, head, batch, one_chip)


@pytest.mark.parametrize("size,c_in,batch", [(256, 4, 8), (400, 4, 2)])
def test_folded_launch_compiles_at_larger_frames(one_chip, size, c_in,
                                                 batch):
    """Folding layer 0 space-to-depth shrinks the input block about 3x:
    256x256 RGBA takes B=8 with the head in one whole-batch launch, and
    400x400 RGBA, which no launch could hold before, takes 2."""
    plan = standard_spec(c_in=c_in, k=4).plan(size)
    head = plan.head(HEAD_DIM)
    assert plan.fold == 2 and plan.max_safe_batch(head=head) == batch
    _compile_whole_batch(plan, head, batch, one_chip)


def _compile_whole_batch(plan, head, batch, one_chip):
    """Compile one whole-batch fused launch of ``batch`` frames."""
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    ws = [S((l.kernel, l.kernel, l.c_in, l.c_out)) for l in plan.layers]
    bs = [S((l.c_out,)) for l in plan.layers]
    hw = S((plan.flat_features, head.out_dim)) if head else None
    hb = S((head.out_dim,)) if head else None

    def launch(x, ws, bs, hw, hb):
        return miniconv_encoder(x, ws, bs, plan, head_w=hw, head_b=hb,
                                interpret=False)

    _compile(launch, S((batch, plan.in_h, plan.in_w, plan.layers[0].c_in)),
             ws, bs, hw, hb)
