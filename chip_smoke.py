"""Smoke run of the split-policy system on a TPU.

Drives the main paths once, through the entry points a user calls
(``repro.deploy``, ``repro.serving``, ``repro.rl.train``), at the paper's
standard deployment: MiniConv k=4 over 84x84 frames with 12 input
channels, a 512-wide server projection, micro-batches of up to 8.  Weights
are random, made from fixed seeds.  Every phase compares what it served
with the same pipeline built on the ``xla`` backend, the plain float32
reference, and the script exits non-zero if any phase fails.

    python chip_smoke.py             # one chip: serve, fused head and
                                     # streaming, train then serve
    python chip_smoke.py --chips 4   # only the fleet: four in-process
                                     # replicas, one per chip

It refuses to run where JAX finds no TPU.  The whole run uses float32
matmul precision so that the ``xla`` reference is a float32 reference (the
TPU's default would round its operands to bfloat16); the fused kernels
always contract in float32.  Times printed before the last line are
compile/set-up and smoke timings, not performance results.  The last line
of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the tests' tolerances: fused vs xla features and projections, and the
# served actions of a quantised (uint8 wire) pipeline
FEATURE_TOL = dict(atol=1e-5, rtol=1e-5)
ACTION_ATOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def timed(label: str, fn, *args):
    """Call ``fn`` twice: the first call (compile + run) is set-up time,
    the second a steady smoke timing.  Returns the second result."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    log(f"  {label}: set-up {t1 - t0:.2f} s, smoke timing "
        f"{(t2 - t1) * 1e3:.2f} ms")
    return out


def require_tpu(n_chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); refusing to run")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, JAX found {len(devices)}")
    return devices


def uses_kernel(fn, *args) -> bool:
    """Whether ``fn`` lowers to a compiled Pallas kernel on the chip."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def assert_close(got, want, what: str, **tol) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    log(f"  {what}: max |fused - xla| = {err:.3e}")
    np.testing.assert_allclose(got, want, err_msg=what, **tol)


def standard_config(backend: str, max_batch: int = 8, **overrides):
    from repro.deploy import DeploymentConfig
    return DeploymentConfig.standard(k=4, c_in=12, h=84, head_dim=512,
                                     max_batch=max_batch, backend=backend,
                                     **overrides)


def phase_serve() -> None:
    """Deployment.build -> serving_pair -> serve 8 encoded observations."""
    import jax
    import numpy as np
    from repro.deploy import Deployment
    log("phase serve: fused edge half + batched server, vs xla")
    dep = Deployment.build(standard_config("fused"))
    ref = Deployment.build(standard_config("xla"))
    check(dep.compiled, "fused deployment resolved to interpret mode")
    params = dep.init(jax.random.PRNGKey(0))
    obs = jax.random.uniform(jax.random.PRNGKey(1), (8, 84, 84, 12))

    edge = dep.split.edge_apply
    check(uses_kernel(edge, params["edge"], obs),
          "fused edge half did not lower to a Pallas kernel")
    feats = timed("fused encoder, B=8", edge, params["edge"], obs)
    assert_close(feats, ref.split.edge_apply(params["edge"], obs),
                 "features B=8", **FEATURE_TOL)

    client, server = dep.serving_pair(params)
    rclient, rserver = ref.serving_pair(params)
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(8)]
    timed("fused client encode, B=1", client.encode_fn, obs[:1])
    rpayloads = [rclient.encode_fn(obs[i:i + 1]) for i in range(8)]
    for i, (p, q) in enumerate(zip(payloads, rpayloads)):
        codes = np.abs(np.asarray(p["data"], np.int32)
                       - np.asarray(q["data"], np.int32))
        check(int(codes.max()) <= 1,
              f"payload {i}: uint8 codes differ by {int(codes.max())}")
        for k in ("scale", "zero"):
            assert_close(p[k], q[k], f"payload {i} {k}", **FEATURE_TOL)
    actions = timed("server.serve, 8 payloads", server.serve, payloads)
    assert_close(np.stack(actions), np.stack(rserver.serve(rpayloads)),
                 "served actions", atol=ACTION_ATOL)


def phase_head_and_stream() -> None:
    """fused+head at B=1 and B=8; fused+stream at 4x its stream chunk."""
    import jax
    from repro.deploy import Deployment
    log("phase fused+head / fused+stream, vs xla")
    ref = Deployment.build(standard_config("xla"))
    head = Deployment.build(standard_config("fused+head"))
    # a stream chunk, and 4 of them past what one launch holds, need a
    # max_batch above max_safe_batch
    stream = Deployment.build(standard_config("fused+stream",
                                              max_batch=128))
    params = head.init(jax.random.PRNGKey(0))
    n = 4 * stream.stream_chunk
    log(f"  max_safe_batch fused+head={head.max_safe_batch}, "
        f"stream_chunk={stream.stream_chunk}, streamed batch={n}")
    check(n > head.max_safe_batch, "streamed batch does not pass "
                                   "max_safe_batch")
    obs = jax.random.uniform(jax.random.PRNGKey(2), (n, 84, 84, 12))
    for name, dep, batch in (("fused+head", head, 1), ("fused+head", head, 8),
                             ("fused+stream", stream, 1),
                             ("fused+stream", stream, n)):
        x = obs[:batch]
        check(uses_kernel(dep.encoder.apply, params, x),
              f"{name} B={batch} did not lower to a Pallas kernel")
        z = timed(f"{name} encoder+projection, B={batch}",
                  dep.encoder.apply, params, x)
        assert_close(z, ref.encoder.apply(params, x),
                     f"{name} projection B={batch}", **FEATURE_TOL)


def phase_train() -> None:
    """A few update chunks of DDPG (pendulum) and PPO (walker), then serve
    one action from the trained parameters."""
    import jax
    import numpy as np
    from repro.deploy import Deployment, DeploymentConfig
    from repro.envs import make_pixel_env
    from repro.rl.agent import make_agent
    from repro.rl.train import train
    # pendulum: 300-step warmup + 2 chunks of 128 x 2 envs;
    # walker: 2 PPO iterations of 128 steps x 8 envs
    for task, steps in (("pendulum", 812), ("walker", 2048)):
        log(f"phase train: {task}")
        t0 = time.perf_counter()
        res = train(task, "miniconv4", total_steps=steps, seed=0)
        log(f"  {res.algo} {res.env_steps} env steps in "
            f"{time.perf_counter() - t0:.1f} s (compile included, "
            f"compile phases {res.compile_s:.1f} s)")
        returns = np.asarray(res.all_returns)
        check(returns.size > 0 and bool(np.isfinite(returns).all()),
              f"{task}: returns not finite: {returns}")
        check(all(bool(np.isfinite(np.asarray(leaf)).all())
                  for leaf in jax.tree.leaves(res.params)),
              f"{task}: trained parameters not finite")
        env = make_pixel_env(task, train=False)
        _, o = env.reset(jax.random.PRNGKey(3))
        served = {}
        for backend in ("fused", "xla"):
            dep = Deployment.build(DeploymentConfig.from_encoder_name(
                "miniconv4", c_in=env.obs_shape[-1], h=84, backend=backend))
            agent = make_agent(res.algo, dep.encoder, env.action_dim)
            client, server = dep.serving_pair(
                res.params, head=agent.policy_head(res.params))
            served[backend] = server.serve([client.encode_fn(o[None])])[0]
        check(served["fused"].shape == (env.action_dim,),
              f"{task}: action shape {served['fused'].shape}")
        assert_close(served["fused"], served["xla"], f"{task} served action",
                     atol=ACTION_ATOL)


def phase_fleet(n_chips: int, n_requests: int = 32) -> None:
    """Deployment.fleet with one in-process replica per chip."""
    import jax
    import numpy as np
    from repro.deploy import Deployment
    log(f"phase fleet: {n_chips} replicas, {n_requests} requests per router")
    dep = Deployment.build(standard_config("fused", n_servers=n_chips))
    params = dep.init(jax.random.PRNGKey(0))
    client, server = dep.serving_pair(params)        # one replica, device 0
    obs = jax.random.uniform(jax.random.PRNGKey(4),
                             (n_requests, 84, 84, 12))
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(n_requests)]
    want = [np.asarray(server.serve([p])[0]) for p in payloads]
    t0 = time.perf_counter()
    fleet = dep.fleet(params)
    log(f"  fleet start (build + precompile): "
        f"{time.perf_counter() - t0:.1f} s")
    try:
        check(fleet.in_process, "fleet replicas were not in-process")
        for router in ("round_robin", "client_affinity"):
            fleet.set_router(router)
            got = [fleet.request(p, client=i % 8)
                   for i, p in enumerate(payloads)]
            for i, (w, g) in enumerate(zip(want, got)):
                np.testing.assert_array_equal(
                    g, w, err_msg=f"{router} request {i}")
            log(f"  {router}: {n_requests} actions bitwise-equal to one "
                f"in-process replica; per-server "
                f"{list(fleet.stats['per_server'])}")
        per_worker = [sorted(str(d) for d in w.devices)
                      for w in fleet.workers]
        log(f"  replica devices: {per_worker}")
        check(all(len(d) == 1 for d in per_worker),
              "a replica served on other than one device")
        check(len({d[0] for d in per_worker}) == n_chips,
              f"replicas did not serve on {n_chips} distinct devices")
    finally:
        leaked = fleet.close()
    check(not leaked, f"leaked replicas: {leaked}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-replica fleet phase")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    import jax
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        if args.chips == 4:
            phase_fleet(args.chips)
        else:
            phase_serve()
            phase_head_and_stream()
            phase_train()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
