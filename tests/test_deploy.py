"""ISSUE-3 conformance: the declarative Deployment API.

* MANIFEST: ``DeploymentConfig`` ``to_dict``/``from_dict``/JSON round-trip
  (including property-based, when hypothesis is available) and a reloaded
  manifest rebuilds a pipeline with IDENTICAL encoder outputs and wire
  payloads.
* SHIMS: the legacy constructors (``rl.networks.make_encoder``,
  ``core.split.make_miniconv_split``) are thin shims whose outputs
  bitwise-match ``Deployment.build`` across execution backends.
* REGISTRY: unknown backends/modes fail loudly listing the registered set.
* VMEM: the batch-size-aware budget check (``build_pass_plan(batch=B)``,
  ``PassPlan.max_safe_batch``) and its surfacing on ``Deployment``.
* KERNEL: the lane-padded fused-head epilogue (D % 128 != 0) matches the
  unpadded XLA reference.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import backend_names, get_backend
from repro.core.miniconv import (LayerSpec, MiniConvSpec, ShaderBudget,
                                 miniconv_apply, miniconv_init,
                                 standard_spec)
from repro.core.passplan import (DEFAULT_VMEM_LIMIT, build_pass_plan,
                                 tiled_bytes)
from repro.core.split import make_miniconv_split
from repro.deploy import CONFIG_VERSION, Deployment, DeploymentConfig
from repro.rl.networks import make_encoder
from repro.serving.client import EdgeClient
from repro.serving.server import BatchingPolicyServer


SMALL = DeploymentConfig.standard(k=4, c_in=4, h=24)


# ---------------------------------------------------------------- manifest
def test_config_dict_roundtrip():
    cfg = DeploymentConfig.standard(k=4, c_in=12, h=84, backend="fused",
                                    codec="uint8", max_batch=4,
                                    max_wait_ms=2.5, quantize_in_train=True)
    d = cfg.to_dict()
    assert d["version"] == CONFIG_VERSION
    json.dumps(d)                         # JSON-safe
    assert DeploymentConfig.from_dict(d) == cfg
    assert DeploymentConfig.from_json(cfg.to_json()) == cfg


def test_config_backend_aliases_canonicalise():
    a = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="per_pass")
    b = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="reference")
    assert a == b and a.backend == "reference"
    # the legacy use_kernel booleans resolve too
    assert DeploymentConfig.standard(k=4, c_in=4, h=24,
                                     backend=False).backend == "xla"
    assert DeploymentConfig.standard(k=4, c_in=4, h=24,
                                     backend=True).backend == "reference"


def test_config_rejects_unknown_fields_loudly():
    with pytest.raises(ValueError, match="registered backends"):
        DeploymentConfig.standard(k=4, c_in=4, h=24, backend="warp")
    with pytest.raises(ValueError, match="codec"):
        DeploymentConfig.standard(k=4, c_in=4, h=24,
                                  codec="zip").validate()
    with pytest.raises(ValueError, match="head_placement"):
        dataclasses.replace(SMALL, head_placement="edge").validate()
    with pytest.raises(ValueError, match="version"):
        DeploymentConfig.from_dict({**SMALL.to_dict(), "version": 99})


def test_config_roundtrip_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    layer = st.builds(
        LayerSpec,
        kernel=st.integers(1, 5), stride=st.integers(1, 3),
        c_in=st.integers(1, 32), c_out=st.integers(1, 24),
        activation=st.sampled_from(["relu", "sigmoid", "linear"]))
    spec = st.builds(
        MiniConvSpec,
        layers=st.lists(layer, min_size=1, max_size=4).map(tuple),
        budget=st.builds(ShaderBudget,
                         max_textures=st.integers(1, 16),
                         max_samples=st.integers(1, 256)))
    config = st.builds(
        DeploymentConfig,
        spec=spec,
        in_h=st.integers(1, 128), in_w=st.integers(1, 128),
        backend=st.sampled_from(backend_names(include_aliases=True)),
        interpret=st.sampled_from([None, True, False]),
        codec=st.sampled_from(["float32", "bf16", "uint8", "int8_channel"]),
        head_dim=st.integers(1, 640),
        head_act=st.sampled_from(["relu", "sigmoid", "linear"]),
        head_placement=st.sampled_from(["server", "fused"]),
        max_batch=st.integers(1, 32),
        max_wait_ms=st.floats(0, 10, allow_nan=False),
        tile_h=st.integers(1, 16),
        quantize_in_train=st.booleans())

    @hyp.given(cfg=config)
    @hyp.settings(max_examples=50, deadline=None)
    def roundtrips(cfg):
        assert DeploymentConfig.from_dict(cfg.to_dict()) == cfg
        assert DeploymentConfig.from_json(cfg.to_json()) == cfg

    roundtrips()


def test_reloaded_manifest_reproduces_outputs_and_payloads():
    """The acceptance criterion: a serialised DeploymentConfig reloaded
    from dict reproduces identical encoder outputs and wire payloads."""
    cfg = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="fused")
    dep = Deployment.build(cfg)
    dep2 = Deployment.build(DeploymentConfig.from_dict(cfg.to_dict()))
    key = jax.random.PRNGKey(0)
    params, params2 = dep.init(key), dep2.init(key)
    obs = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 4))
    np.testing.assert_array_equal(dep.encoder.apply(params, obs),
                                  dep2.encoder.apply(params2, obs))
    p1 = dep.split.edge_step(params["edge"], obs)
    p2 = dep2.split.edge_step(params2["edge"], obs)
    assert set(p1) == set(p2)
    for k in p1:
        np.testing.assert_array_equal(p1[k], p2[k])


# ---------------------------------------------------------------- shims
@pytest.mark.parametrize("use_kernel", [False, "fused", "per_pass",
                                        "grouped"])
def test_make_encoder_shim_bitwise_matches_deployment(use_kernel):
    enc = make_encoder("miniconv4", c_in=4, use_kernel=use_kernel)
    dep = Deployment.build(DeploymentConfig.from_encoder_name(
        "miniconv4", c_in=4, backend=use_kernel))
    key = jax.random.PRNGKey(0)
    params, dparams = enc.init(key), dep.init(key)
    jax.tree.map(np.testing.assert_array_equal, params, dparams)
    obs = jax.random.uniform(jax.random.PRNGKey(1), (2, 84, 84, 4))
    np.testing.assert_array_equal(enc.apply(params, obs),
                                  dep.encoder.apply(dparams, obs))


def test_make_encoder_fused_head_shim_bitwise_matches_deployment():
    enc = make_encoder("miniconv4", c_in=4, use_kernel="fused",
                       fused_head=True)
    dep = Deployment.build(DeploymentConfig.from_encoder_name(
        "miniconv4", c_in=4, backend="fused", head_placement="fused"))
    key = jax.random.PRNGKey(2)
    params = enc.init(key)
    obs = jax.random.uniform(jax.random.PRNGKey(3), (3, 84, 84, 4))
    np.testing.assert_array_equal(enc.apply(params, obs),
                                  dep.encoder.apply(dep.init(key), obs))


@pytest.mark.parametrize("use_kernel", ["fused", "per_pass"])
def test_make_miniconv_split_shim_bitwise_matches_deployment(use_kernel):
    spec = standard_spec(c_in=4, k=4)
    split = make_miniconv_split(spec, lambda p, f: f, h=24,
                                use_kernel=use_kernel)
    dep = Deployment.build(DeploymentConfig(spec=spec, in_h=24, in_w=24,
                                            backend=use_kernel))
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    obs = jax.random.uniform(jax.random.PRNGKey(1), (1, 24, 24, 4))
    a = split.edge_step(params, obs)
    b = dep.split.edge_step(params, obs)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert split.wire_bytes() == dep.wire_bytes
    # custom server half survives the shim
    feats = split.server_step(None, a)
    np.testing.assert_allclose(feats, dep.codec.decode(b), rtol=1e-6)


def test_split_shim_rejects_wrong_deploy_size():
    """The deployment split stays size-strict in fused mode (a plan built
    for 24x24 must not silently serve 32x32 frames)."""
    spec = standard_spec(c_in=4, k=4)
    split = make_miniconv_split(spec, lambda p, f: f, h=24)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    with pytest.raises(ValueError, match="plan was built"):
        split.edge_apply(params, jnp.zeros((1, 32, 32, 4)))


# ---------------------------------------------------------------- registry
def test_unknown_backend_error_lists_registered():
    with pytest.raises(ValueError) as ei:
        get_backend("warp")
    msg = str(ei.value)
    for name in ("xla", "reference", "grouped", "fused", "fused+head"):
        assert name in msg


def test_miniconv_apply_unknown_mode_lists_backends():
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jnp.zeros((1, 16, 16, 4))
    with pytest.raises(ValueError, match="registered backends"):
        miniconv_apply(params, spec, x, use_kernel="warp")


# ---------------------------------------------------------------- serving
def test_serving_pair_from_config():
    cfg = dataclasses.replace(SMALL, max_batch=3, max_wait_ms=4.0)
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(0))
    client, server = dep.serving_pair(params)
    assert isinstance(client, EdgeClient)
    assert isinstance(server, BatchingPolicyServer)
    assert server.max_batch == 3
    assert server.max_wait_s == pytest.approx(0.004)
    obs = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 4))
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(2)]
    assert client.wire_bytes == dep.wire_bytes
    served = server.serve(payloads)
    ref = dep.encoder.apply(params, obs)
    np.testing.assert_allclose(jnp.stack(served), ref, atol=5e-2)
    with pytest.raises(ValueError):
        server.serve(payloads * 2)        # 4 > max_batch


# ---------------------------------------------------------------- VMEM
def test_vmem_bytes_affine_in_batch():
    plan = standard_spec(c_in=4, k=4).plan(84)
    d1 = plan.vmem_bytes(2) - plan.vmem_bytes(1)
    d2 = plan.vmem_bytes(9) - plan.vmem_bytes(8)
    assert d1 == d2 > 0
    head = plan.head(512)
    assert plan.vmem_bytes(1, head=head) > plan.vmem_bytes(1)
    # a streamed chunk's input block changes across the grid, so the
    # compiler double-buffers it
    s1 = plan.vmem_bytes(2, streamed=True) - plan.vmem_bytes(1, streamed=True)
    assert s1 == 2 * d1


def test_vmem_input_block_counts_compiler_tiles():
    """A (B, 86, 86, 12) block is laid out in (8, 128) tiles: 86 x 88 x
    128 x 4 bytes per frame, not the 86 x 86 x 12 x 4 it holds.  The
    standard 84x84 plan's input block is that padded input folded
    space-to-depth, (B, 43, 43, 48): 43 x 48 x 128 x 4 bytes per frame."""
    per_frame = 86 * 88 * 128 * 4
    assert tiled_bytes((86, 86, 12)) == per_frame
    assert tiled_bytes((48, 86, 86, 12)) == 48 * per_frame
    plan = standard_spec(c_in=12, k=4).plan(84)
    assert plan.vmem_bytes(2) - plan.vmem_bytes(1) == tiled_bytes(
        (43, 43, 48)) == 43 * 48 * 128 * 4
    # sub-32-bit types pack more rows per sublane tile
    assert tiled_bytes((86, 86, 12), itemsize=2) == 86 * 96 * 128 * 2
    assert tiled_bytes((5,)) == 8 * 128 * 4


def test_standard_deployment_max_safe_batch():
    """The standard deployment's launchable micro-batches under the
    compiler-shaped model (tests/test_tpu_compile.py compiles the fused
    launch at exactly these sizes)."""
    plan = standard_spec(c_in=12, k=4).plan(84)
    head = plan.head(512)
    assert plan.max_safe_batch() == 93
    assert plan.max_safe_batch(head=head) == 91
    assert plan.max_safe_batch(head=head, streamed=True) == 45
    dep = Deployment.build(DeploymentConfig.standard(
        k=4, c_in=12, h=84, backend="fused+stream", interpret=False,
        max_batch=64))
    assert dep.max_safe_batch == 91 and dep.stream_chunk == 45


def test_unlaunchable_when_one_frame_exceeds_vmem():
    """c_in=4 at 640x640: one tile-padded frame plus the intermediates
    exceeds the budget, so a compiled fused build has no launchable batch
    (400x400, unlaunchable before layer 0 was folded, now takes 3)."""
    assert standard_spec(c_in=4, k=4).plan(400).max_safe_batch() == 3
    plan = standard_spec(c_in=4, k=4).plan(640)
    assert plan.max_safe_batch() == 0
    assert plan.vmem_bytes(1) > DEFAULT_VMEM_LIMIT
    cfg = DeploymentConfig.standard(k=4, c_in=4, h=640, backend="fused",
                                    interpret=False)
    with pytest.raises(ValueError, match="max_safe_batch=0"):
        Deployment.build(cfg)


def test_build_pass_plan_batch_budget_check():
    spec = standard_spec(c_in=4, k=4)
    plan = build_pass_plan(spec, 84, batch=8)        # fits the real budget
    safe = plan.max_safe_batch()
    assert safe >= 8
    with pytest.raises(ValueError, match="max safe batch"):
        build_pass_plan(spec, 84, batch=safe + 1,
                        vmem_limit=plan.vmem_bytes(safe))
    # spec.plan passthrough
    with pytest.raises(ValueError, match="VMEM"):
        spec.plan(84, batch=10 ** 6)
    assert plan.max_safe_batch(vmem_limit=plan.vmem_bytes(3)) == 3


def test_deployment_surfaces_max_safe_batch():
    dep = Deployment.build(SMALL)
    assert dep.max_safe_batch == dep.plan.max_safe_batch(
        tile_h=SMALL.tile_h)
    assert dep.plan.max_safe_batch(tile_h=SMALL.tile_h, streamed=True) \
        < dep.max_safe_batch
    # fusing the head consumes VMEM for the tiled weight -> smaller B
    fused_head = Deployment.build(
        dataclasses.replace(SMALL, backend="fused+head"))
    assert fused_head.max_safe_batch <= dep.max_safe_batch
    assert fused_head.max_safe_batch == dep.plan.max_safe_batch(
        head=dep.head_plan, tile_h=SMALL.tile_h)


def test_deployment_build_rejects_unlaunchable_compiled_batch():
    """Compiled fused deployments whose micro-batch busts VMEM must fail
    at build time, not on the device."""
    big = DeploymentConfig.standard(k=4, c_in=12, h=2048, backend="fused",
                                    interpret=False, max_batch=64)
    with pytest.raises(ValueError, match="VMEM"):
        Deployment.build(big)
    # the same config is buildable in interpret mode (no VMEM ceiling)
    Deployment.build(dataclasses.replace(big, interpret=None))


# ---------------------------------------------------------------- lane pad
@pytest.mark.parametrize("d_out", [96, 160])
def test_fused_head_lane_padding_parity(d_out):
    """Projection widths that are NOT lane-multiples (D % 128 != 0) are
    zero-padded to 128 lanes inside the kernel; the sliced result must
    equal the unpadded XLA epilogue exactly as before."""
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 4))
    plan = spec.plan(24)
    hw = jax.random.normal(jax.random.PRNGKey(2),
                           (plan.flat_features, d_out)) * 0.1
    hb = jax.random.normal(jax.random.PRNGKey(3), (d_out,))
    feats_ref = miniconv_apply(params, spec, x)
    z_ref = jax.nn.relu(feats_ref.reshape(2, -1) @ hw + hb)
    feats, z = miniconv_apply(params, spec, x, use_kernel="fused",
                              head=(hw, hb))
    assert z.shape == (2, d_out)
    np.testing.assert_allclose(feats, feats_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(z, z_ref, atol=1e-5, rtol=1e-5)


def test_fused_head_lane_padding_sigmoid_garbage_cancelled():
    """sigmoid(0) = 0.5 in the padded lanes must never leak into the
    returned projection (the slice must drop exactly the padding)."""
    spec = standard_spec(c_in=4, k=4)
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 24, 24, 4))
    plan = spec.plan(24)
    hw = jax.random.normal(jax.random.PRNGKey(2),
                           (plan.flat_features, 48)) * 0.1
    _, z = miniconv_apply(params, spec, x, use_kernel="fused",
                          head=(hw, None), head_act="sigmoid")
    feats_ref = miniconv_apply(params, spec, x)
    z_ref = jax.nn.sigmoid(feats_ref.reshape(1, -1) @ hw)
    assert z.shape == (1, 48)
    np.testing.assert_allclose(z, z_ref, atol=1e-5, rtol=1e-5)
