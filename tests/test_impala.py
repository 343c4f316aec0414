"""IMPALA-CNN through ``Deployment.build``: the server-only placement
against the plain reference (``chipbench/reference/impala.py``) on seeded
random weights at a small size, its manifest, and its trace scopes.

The limit on the largest gap, as a share of the reference's largest
magnitude, is 2e-6: both sides are float32, and the program's convs and
pools (``lax.conv_general_dilated``, ``reduce_window``) sum in another
order than the reference's stacked taps, which over 15 convs and 6
residual adds read 2.6e-7 to 7.3e-7 here (4 seeds at each size).  The
reference at ``bf16_3x`` (the TPU's ``high``, one step below the
``highest`` the deployment states) read 1.0e-5 or more on the same
seeds, so a program that computed in that precision would fail the
limit.
"""
import dataclasses
import json
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chipbench.reference import impala as ref  # noqa: E402
from chipbench.reference.miniconv import quantize_uint8  # noqa: E402
from repro.core import impala  # noqa: E402
from repro.core.impala import ImpalaSpec  # noqa: E402
from repro.core.wire import stack_payloads  # noqa: E402
from repro.deploy import (CONFIG_VERSION, Deployment,  # noqa: E402
                          DeploymentConfig)

LIMIT = 2e-6
SIZES = [(16, 16), (15, 13)]
IMPALA_SCOPES = ("impala.conv", "impala.pool", "impala.res", "impala.head")


def _cfg(h, w, **kw):
    return {"depths": [4, 8, 8], "blocks": 2, "c_in": 3, "in_h": h,
            "in_w": w, "head_dim": 32, "head_act": "relu", **kw}


def _deployment(h, w, **kw):
    c = _cfg(h, w)
    return Deployment.build(DeploymentConfig(
        spec=ImpalaSpec(depths=tuple(c["depths"]), blocks=c["blocks"],
                        c_in=c["c_in"]),
        in_h=h, in_w=w, backend="xla", head_dim=c["head_dim"], max_batch=4,
        **kw))


def _inputs(h, w, seed=0, batch=4):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    return (ref.init_params(_cfg(h, w), kp),
            jax.random.uniform(kx, (batch, h, w, 3)))


def _rel_gap(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("h,w", SIZES)
@pytest.mark.parametrize("seed", [0, 1])
def test_encoder_matches_reference(h, w, seed):
    dep = _deployment(h, w)
    params, x = _inputs(h, w, seed)
    got = jax.jit(dep.encoder.apply)(params, x)
    want = ref.encode_project(_cfg(h, w), params, x)
    assert got.shape == (4, 32)
    assert _rel_gap(got, want) <= LIMIT
    control = ref.encode_project(_cfg(h, w), params, x, "bf16_3x")
    assert _rel_gap(control, want) > LIMIT


@pytest.mark.parametrize("h,w", SIZES)
def test_server_batch_fn_on_uint8_frames_matches_reference(h, w):
    dep = _deployment(h, w)
    params, x = _inputs(h, w, seed=2)
    edge = [dep.split.edge_step(params["edge"], x[i:i + 1])
            for i in range(x.shape[0])]
    want_wire = [quantize_uint8(x[i:i + 1]) for i in range(x.shape[0])]
    for p, q in zip(edge, want_wire):      # the frame itself is the payload
        np.testing.assert_array_equal(p["data"], q["data"])
    batch = stack_payloads(edge)
    got = dep.server_batch_fn(params)(batch)
    data, scale, zero = (batch["data"][:, 0], batch["scale"],
                         batch["zero"])
    want = ref.decode_project(_cfg(h, w), params, data, scale, zero)
    assert got.shape == (4, 32)
    assert _rel_gap(got, want) <= LIMIT
    control = ref.decode_project(_cfg(h, w), params, data, scale, zero,
                                 "bf16_3x")
    assert _rel_gap(control, want) > LIMIT
    one = dep.server_fn(params)(edge[0])
    np.testing.assert_allclose(one[0], got[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(2, 9, 7, 3), (1, 4, 5, 2), (1, 2, 2, 1)])
def test_max_pool_pads_the_tensorflow_way(shape):
    """All-negative inputs: a zero pad, or one on the wrong side, would
    change the answer."""
    x = -1.0 - jax.random.uniform(jax.random.PRNGKey(3), shape)
    got = impala.max_pool(x)
    assert got.shape[1:3] == (-(-shape[1] // 2), -(-shape[2] // 2))
    np.testing.assert_array_equal(got, ref.max_pool(x))


def test_init_matches_the_reference_layout():
    dep = _deployment(15, 13)
    params = dep.init(jax.random.PRNGKey(0))
    want = ref.init_params(_cfg(15, 13), jax.random.PRNGKey(0))
    assert params["edge"] == {}
    assert (jax.tree.map(jnp.shape, params)
            == jax.tree.map(jnp.shape, want))
    assert dep.spec.feature_shape(15, 13) == (2, 2, 8)


def test_manifest_round_trip():
    cfg = _deployment(16, 16).config
    d = json.loads(cfg.to_json())
    assert d["version"] == CONFIG_VERSION == 3
    assert d["spec"] == {"family": "impala", "depths": [4, 8, 8],
                         "blocks": 2, "c_in": 3}
    back = DeploymentConfig.from_dict(d)
    assert back == cfg and back.family == "impala"
    assert isinstance(back.spec.depths, tuple)
    params, x = _inputs(16, 16)
    np.testing.assert_array_equal(
        Deployment.build(cfg).encoder.apply(params, x),
        Deployment.build(back).encoder.apply(params, x))


def test_version_2_miniconv_manifest_loads_unchanged():
    cfg = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="xla")
    d = cfg.to_dict()
    assert d["spec"]["family"] == "miniconv"
    old = json.loads(json.dumps(d))
    del old["spec"]["family"]
    old["version"] = 2
    back = DeploymentConfig.from_dict(old)
    assert back == cfg and back.family == "miniconv"
    dep, dep2 = Deployment.build(cfg), Deployment.build(back)
    params = dep.init(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 4))
    np.testing.assert_array_equal(dep.encoder.apply(params, x),
                                  dep2.encoder.apply(params, x))
    with pytest.raises(ValueError, match="family"):
        DeploymentConfig.from_dict({**d, "spec": {**d["spec"],
                                                  "family": "nature"}})


@pytest.mark.parametrize("backend", ["fused", "fused+head", "fused+stream",
                                     "reference"])
def test_kernel_backends_refuse_the_family_naming_xla(backend):
    cfg = dataclasses.replace(_deployment(16, 16).config, backend=backend)
    with pytest.raises(ValueError, match="xla"):
        cfg.validate()
    with pytest.raises(ValueError, match="xla"):
        Deployment.build(cfg)


def test_head_placement_fused_is_refused():
    with pytest.raises(ValueError, match="server-only"):
        _deployment(16, 16, head_placement="fused")


def test_accounting_answers_for_the_family():
    dep = Deployment.build(DeploymentConfig.from_encoder_name(
        "impala", c_in=3, h=64))
    assert dep.config.family == "impala" and dep.backend.name == "xla"
    assert dep.config.head_dim == 256 and dep.plan is None
    assert isinstance(dep.spec, ImpalaSpec)
    assert dep.frame_bytes == 64 * 64 * 3 == 12_288
    assert dep.wire_bytes == 12_288 + 8
    assert dep.wire_bytes_batch(4) == 4 * 12_296
    mc = Deployment.build(DeploymentConfig.from_encoder_name(
        "miniconv4", c_in=9, h=84, backend="xla"))
    assert mc.frame_bytes == 84 * 84 * 12        # RGBA-packed
    assert mc.wire_bytes == 11 * 11 * 4 + 8
    with pytest.raises(ValueError, match="impala"):
        DeploymentConfig.from_encoder_name("full_cnn", c_in=3)


def test_fleet_worker_builds_and_warms_the_family():
    """A fleet worker rebuilds the server half from the manifest and
    warms every batch size on zero frames of the manifest's shape."""
    from repro.serving.realfleet import WorkerServer, _build_worker
    dep = _deployment(15, 13)
    params, _ = _inputs(15, 13)
    params = jax.tree.map(np.asarray, params)
    ws = _build_worker(dep.config.to_dict(), params, max_batch=2)
    assert isinstance(ws, WorkerServer)


def _op_names(fn, *args) -> set:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_scopes_in_the_lowered_step_and_server_half():
    dep = _deployment(16, 16)
    params, x = _inputs(16, 16)
    names = _op_names(dep.encoder.apply, params, x)
    for name in IMPALA_SCOPES:
        assert any(n.startswith(f"jit(encoder_apply)/impala.encode/{name}/")
                   for n in names), name
    batch = stack_payloads([dep.split.edge_step(params["edge"], x[:1])] * 2)
    names = _op_names(dep.server_batch_fn(params), batch)
    assert any("vmap(wire.decode)" in n for n in names)
    assert any("impala.encode/impala.res/" in n for n in names)
