"""One host block per served micro-batch (``repro.core.wire.RowLayout``).

A fleet worker (``realfleet._build_worker``) packs a stacked payload into
one ``(B, row_bytes)`` uint8 block on the host and unpacks it inside the
server half's program.  Only the bytes' layout changes, so:

* the block unpacks to every leaf bitwise, with its dtype and shape;
* the worker's actions are bitwise those of ``Deployment.server_batch_fn``
  on the stacked dict, for every codec and both encoder families;
* the worker's program takes one argument;
* whatever ``Deployment.server_batch_fn`` returns is what the worker
  serves, so the benchmark's controls (``chipbench/controls.py``), which
  replace that method, still replace the served program.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chipbench import controls  # noqa: E402
from repro.core.impala import ImpalaSpec  # noqa: E402
from repro.core.wire import RowLayout  # noqa: E402
from repro.deploy import Deployment, DeploymentConfig  # noqa: E402
from repro.serving import realfleet  # noqa: E402

MAX_BATCH = 4

CONFIGS = {
    "mc4-uint8": lambda: DeploymentConfig.standard(
        k=4, c_in=4, h=24, backend="xla", max_batch=MAX_BATCH),
    "mc4-int8_channel": lambda: DeploymentConfig.standard(
        k=4, c_in=4, h=24, backend="xla", max_batch=MAX_BATCH,
        codec="int8_channel"),
    "mc4-bf16": lambda: DeploymentConfig.standard(
        k=4, c_in=4, h=24, backend="xla", max_batch=MAX_BATCH,
        codec="bf16"),
    "impala-uint8": lambda: DeploymentConfig(
        spec=ImpalaSpec(depths=(4, 8, 8), blocks=2, c_in=3), in_h=16,
        in_w=16, backend="xla", head_dim=32, max_batch=MAX_BATCH),
}


def _stacked(dep, params, b: int, seed: int = 1) -> dict:
    """``b`` requests' payloads from the edge half, stacked as the worker
    stacks them (each keeps its leading 1-axis)."""
    cfg = dep.config
    obs = jax.random.uniform(jax.random.PRNGKey(seed),
                             (b, 1, cfg.in_h, cfg.in_w, cfg.spec.c_in))
    edge = Deployment._split_params(params)["edge"]
    payloads = [{k: np.asarray(v) for k, v in
                 dep.split.edge_step(edge, obs[i]).items()}
                for i in range(b)]
    return {k: np.stack([p[k] for p in payloads]) for k in payloads[0]}


def _main_args(lowered) -> list[str]:
    (sig,) = re.findall(r"func\.func public @main\((.*?)\) ->",
                        lowered.as_text())
    return re.findall(r"%arg\d+", sig)


@pytest.mark.parametrize("b", [1, MAX_BATCH])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_worker_serves_one_block_bitwise(name, b):
    dep = Deployment.build(CONFIGS[name]())
    params = jax.tree.map(np.asarray, dep.init(jax.random.PRNGKey(0)))
    stacked = _stacked(dep, params, b)
    ws = realfleet._build_worker(dep.config.to_dict(), params, MAX_BATCH,
                                 precompile=False)
    serve = ws.serve_batch_fn
    layout = serve.layout
    assert layout == RowLayout.of({k: v[0] for k, v in stacked.items()})
    assert serve.buffers == 1

    rows = layout.pack(stacked)
    assert rows.dtype == np.uint8 and rows.shape == (b, layout.row_bytes)
    back = jax.jit(layout.unpack)(rows)
    assert list(back) == sorted(stacked)
    for k, v in stacked.items():
        assert back[k].dtype == v.dtype and back[k].shape == v.shape
        assert np.asarray(back[k]).tobytes() == v.tobytes()

    want = np.asarray(dep.server_batch_fn(params)(stacked))
    got = np.asarray(serve(stacked))
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    assert len(_main_args(serve.fn.lower(rows))) == 1


def test_row_bytes_of_the_benchmark_payloads():
    """The 84x84 K=4 feature map's uint8 payload is 484 + 4 + 4 bytes a
    request; a 64x64x3 IMPALA frame's is 12,288 + 8; one leaf (bf16)
    packs to its own bytes."""
    def layout(cfg):
        dep = Deployment.build(cfg)
        edge = jax.eval_shape(dep.init, jax.random.PRNGKey(0))["edge"]
        return RowLayout.of(jax.eval_shape(
            dep.split.edge_step, edge,
            jax.ShapeDtypeStruct((1, cfg.in_h, cfg.in_w, cfg.spec.c_in),
                                 np.float32)))

    mc4 = layout(DeploymentConfig.standard(k=4, c_in=9, h=84,
                                           backend="xla"))
    assert mc4.keys == ("data", "scale", "zero")
    assert mc4.offsets == (0, 484, 488) and mc4.row_bytes == 492
    imp = layout(DeploymentConfig.from_encoder_name("impala", c_in=3, h=64))
    assert imp.row_bytes == 12_288 + 8
    bf16 = layout(DeploymentConfig.standard(k=4, c_in=9, h=84,
                                            backend="xla", codec="bf16"))
    assert bf16.keys == ("data",) and bf16.row_bytes == 2 * 484


def test_pack_refuses_a_payload_of_another_shape():
    layout = RowLayout.of({"data": np.zeros((1, 3), np.uint8),
                           "scale": np.zeros((), np.float32)})
    with pytest.raises(ValueError, match="per-request shape"):
        layout.pack({"data": np.zeros((2, 1, 4), np.uint8),
                     "scale": np.zeros((2,), np.float32)})


@pytest.mark.parametrize("control", ["reference_in_place", "altered_answer"])
def test_in_process_worker_serves_the_patched_server_half(monkeypatch,
                                                          control):
    """With ``Deployment.server_batch_fn`` replaced as the benchmark's
    controls replace it, an in-process replica answers the replacement's
    actions, not the deployment's own."""
    monkeypatch.setattr(realfleet, "_spawns_workers", lambda: False)
    cfg = CONFIGS["mc4-uint8"]()
    dep = Deployment.build(cfg)
    params = jax.tree.map(np.asarray, dep.init(jax.random.PRNGKey(0)))
    stacked = _stacked(dep, params, 3)
    payloads = [{k: v[i] for k, v in stacked.items()} for i in range(3)]

    def one_by_one(fn):     # the client sends one request at a time
        return np.concatenate([np.asarray(fn({k: v[i:i + 1] for k, v in
                                              stacked.items()}))
                               for i in range(3)])
    own = one_by_one(dep.server_batch_fn(params))
    patch = (controls.reference_in_place("bf16_3x")
             if control == "reference_in_place"
             else controls.altered_answer(1.0))
    with patch:
        patched = Deployment.build(cfg)
        want = one_by_one(patched.server_batch_fn(params))
        fleet = patched.fleet(params, n_servers=1, max_batch=MAX_BATCH)
        try:
            assert fleet.in_process
            got = np.stack([fleet.request(p) for p in payloads])
        finally:
            assert fleet.close() == []
    assert not np.array_equal(want, own)
    np.testing.assert_array_equal(got, want)
