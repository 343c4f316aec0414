"""Paper Table 5: end-to-end decision latency under bandwidth shaping.

Median over N decisions of (observation available -> action received),
server-only (full RGBA frame transmitted, Full-CNN + head on the server)
vs split-policy (MiniConv on-device, K=4 uint8 features transmitted).
Compute-stage times are measured on this host with the real jitted
networks; the link is the deterministic token-bucket shaper.

The whole split pipeline — encoder, plan, codec, serving halves, payload
accounting — is constructed from ONE declarative
:class:`repro.deploy.DeploymentConfig` via ``Deployment.build``
(``--manifest`` loads that config from a serialised JSON manifest
instead, the same file ``python -m repro.deploy`` writes).

``--clients N`` additionally reports p95 decision latency for N clients
sharing one split-policy server, FIFO vs micro-batching (the batch-aware
queue simulation fed by the measured batched service-time curve).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from repro.deploy import Deployment, DeploymentConfig
from repro.rl.networks import full_cnn_apply, full_cnn_init, mlp_apply, mlp_init
from repro.serving.client import DecisionLoop, EdgeClient
from repro.serving.netsim import shaped
from repro.serving.server import (BatchingPolicyServer, BatchQueueSim,
                                  PolicyServer, QueueSim)

X_SIZE = 84           # paper's task-scale observation (84x84, 3 frames)
C_IN = 12             # RGBA x 3 stacked frames at the upload boundary


@dataclasses.dataclass(frozen=True)
class ServingSetup:
    """Jitted halves + payload accounting shared by the serving benchmarks.

    Everything here is RESOLVED from ``deployment`` (one
    ``Deployment.build``); the fields are kept flat because the latency
    and scalability loops consume them directly.
    """

    deployment: Deployment
    edge_fn: object               # obs -> single-request payload
    split_server_fn: object       # payload -> action
    split_server_batch_fn: object  # stacked micro-batch payload -> actions
    mono_server_fn: object        # obs -> action
    obs: object
    wire_bytes: int
    frame_bytes: int
    params: object = None         # deployment params (real-fleet workers
    #                               rebuild their jitted halves from these)


def standard_config(*, k: int = 4, backend: str = "xla",
                    max_batch: int = 8) -> DeploymentConfig:
    """The benchmark's canonical deployment: the paper's K-channel encoder
    at task scale.  ``xla`` is the timing-portable default on this host;
    pass ``backend="fused"`` (or a manifest) for the kernel path."""
    return DeploymentConfig.standard(k=k, c_in=C_IN, h=X_SIZE,
                                     backend=backend, max_batch=max_batch)


def build(*, k: int = 4, seed: int = 0,
          config: DeploymentConfig | None = None) -> ServingSetup:
    cfg = config or standard_config(k=k)
    dep = Deployment.build(cfg)
    c_in = cfg.spec.layers[0].c_in      # manifests may deviate from C_IN
    key = jax.random.PRNGKey(seed)
    params = dep.init(key)
    cnn = full_cnn_init(key, c_in, h=cfg.in_h, w=cfg.in_w)
    head = mlp_init(key, [cfg.head_dim, 256, 3])

    def head_fn(z):
        return mlp_apply(head, z)

    edge_fn = dep.edge_fn(params)
    split_server_fn = dep.server_fn(params, head=head_fn)
    split_server_batch_fn = dep.server_batch_fn(params, head=head_fn)

    @jax.jit
    def mono_server_fn(obs):
        return mlp_apply(head, full_cnn_apply(cnn, obs))

    obs = jax.random.uniform(key, (1, cfg.in_h, cfg.in_w, c_in))
    return ServingSetup(dep, edge_fn, split_server_fn, split_server_batch_fn,
                        mono_server_fn, obs, dep.wire_bytes, dep.frame_bytes,
                        params)


def run(bandwidths=(10, 25, 50, 100), *, n_decisions: int = 1000,
        k: int = 4, config: DeploymentConfig | None = None):
    setup = build(k=k, config=config)
    wire_bytes, frame_bytes = setup.wire_bytes, setup.frame_bytes
    client = EdgeClient(encode_fn=setup.edge_fn, wire_bytes=wire_bytes)
    j = client.measure(setup.obs)
    payload = setup.edge_fn(setup.obs)
    s_split = PolicyServer(serve_fn=setup.split_server_fn).measure(payload)
    s_mono = PolicyServer(serve_fn=setup.mono_server_fn).measure(setup.obs)
    print(f"  stages: edge={j*1e3:.2f}ms split_srv={s_split*1e3:.2f}ms "
          f"mono_srv={s_mono*1e3:.2f}ms wire={wire_bytes}B "
          f"frame={frame_bytes}B")

    rows = []
    for mbps in bandwidths:
        so = DecisionLoop(link=shaped(mbps), server_time_s=s_mono,
                          split=False, payload_bytes=frame_bytes)
        sp = DecisionLoop(link=shaped(mbps), server_time_s=s_split,
                          split=True, edge_time_s=j,
                          payload_bytes=wire_bytes)
        row = {"mbps": mbps,
               "server_only_ms": so.median_latency(n_decisions) * 1e3,
               "split_ms": sp.median_latency(n_decisions) * 1e3}
        rows.append(row)
        print(f"  {mbps:>5} Mb/s  server-only {row['server_only_ms']:7.1f} "
              f"ms   split {row['split_ms']:7.1f} ms")
    return rows


def measure_service_curve(setup: ServingSetup, *, max_batch: int = 8,
                          max_wait_s: float = 0.0, iters: int = 10):
    """Measure the batched split server's t(B) curve on this host.

    Shared by this benchmark and ``benchmarks.scalability`` so the two
    FIFO-vs-batched reports can never drift apart in how they sample the
    curve.  The server comes from the deployment's own batching policy
    (``Deployment.server``), overridden by the sweep arguments.
    Returns ({batch: seconds}, BatchServiceModel).
    """
    payload = setup.edge_fn(setup.obs)
    bsrv = BatchingPolicyServer(serve_batch_fn=setup.split_server_batch_fn,
                                max_batch=max_batch, max_wait_s=max_wait_s)
    times = bsrv.measure(payload, batch_sizes=tuple(
        b for b in (1, 2, 4, 8, 16) if b <= max_batch), iters=iters)
    model = bsrv.service_model()
    curve = " ".join(f"t({b})={t*1e3:.2f}ms" for b, t in sorted(times.items()))
    print(f"  batched service curve: {curve}")
    return times, model


def run_queue(*, n_clients: int = 8, mbps: float = 100.0, k: int = 4,
              max_batch: int = 8, max_wait_ms: float = 0.0,
              rate_hz: float = 10.0, setup: ServingSetup = None,
              real_fleet: bool = False):
    """p95 decision latency at N clients: FIFO server vs micro-batching.

    The batched p95 uses the MEASURED service-time curve t(B) of the
    batched split server, so the comparison reflects real amortisation on
    this host, not an assumed speedup.  When the deployment manifest sets
    ``n_servers > 1`` the sharded fleet p95 is reported too — same
    measured curve on every server, routed by the configured policy.

    ``real_fleet=True`` additionally SPAWNS the manifest's fleet on
    localhost (``repro.serving.realfleet``) and prints measured wall-clock
    p95 under the same open-loop load next to the loopback-link sim
    prediction — the per-run sim-to-real calibration
    (``benchmarks/realfleet.py`` is the full sweep).
    """
    setup = setup or build(k=k)
    times, model = measure_service_curve(setup, max_batch=max_batch,
                                         max_wait_s=max_wait_ms / 1e3)
    common = dict(service_time_s=model(1), uplink=shaped(mbps),
                  payload_bytes=setup.wire_bytes, rate_hz=rate_hz,
                  horizon_s=5.0)
    fifo = QueueSim(**common)
    bat = BatchQueueSim(**common, max_batch=max_batch,
                        max_wait_s=max_wait_ms / 1e3, service_model=model)
    row = {"n_clients": n_clients,
           "service_ms": {b: t * 1e3 for b, t in times.items()},
           "fifo_p95_ms": fifo.p95(n_clients) * 1e3,
           "batched_p95_ms": bat.p95(n_clients) * 1e3}
    print(f"  N={n_clients} @ {rate_hz:.0f}Hz: p95 FIFO "
          f"{row['fifo_p95_ms']:.2f} ms vs micro-batched "
          f"{row['batched_p95_ms']:.2f} ms "
          f"(max_batch={max_batch}, max_wait={max_wait_ms:.0f}ms)")
    cfg = setup.deployment.config
    if cfg.n_servers > 1:
        # same batching policy as the FIFO/batched rows above (and as the
        # measured t(B) curve), not the manifest's — the three p95s must
        # be comparable
        fleet = setup.deployment.fleet_sim(model, uplink=shaped(mbps),
                                           rate_hz=rate_hz,
                                           max_batch=max_batch,
                                           max_wait_s=max_wait_ms / 1e3)
        row["fleet_p95_ms"] = fleet.p95(n_clients) * 1e3
        row["n_servers"] = cfg.n_servers
        row["router"] = cfg.router
        print(f"  N={n_clients} fleet ({cfg.n_servers} servers, "
              f"{cfg.router}): p95 {row['fleet_p95_ms']:.2f} ms")
    if real_fleet:
        row.update(run_real_fleet(setup, n_clients=n_clients,
                                  rate_hz=rate_hz))
    return row


def run_real_fleet(setup: ServingSetup, *, n_clients: int = 8,
                   rate_hz: float = 10.0, duration_s: float = 2.0,
                   timeout_s: float = 30.0) -> dict:
    """Measured p95 from the manifest's REAL fleet vs the loopback sim.

    The service curve is re-measured on ``Deployment.server_batch_fn``
    exactly as the workers serve it (no benchmark-local head), so the sim
    prediction and the spawned fleet charge the same t(B); the uplink is
    the localhost loopback, so both sides see negligible transfer time.
    """
    import numpy as np
    from repro.serving.realfleet import pack_payload, run_load

    dep = setup.deployment
    cfg = dep.config
    payload = setup.edge_fn(setup.obs)
    srv = dep.server(setup.params)
    srv.measure(payload, batch_sizes=tuple(
        b for b in (1, 2, 4, 8, 16) if b <= cfg.max_batch), iters=10)
    model = srv.service_model()
    fleet = dep.fleet(setup.params, service_model=model,
                      timeout_s=timeout_s)
    try:
        sim = dep.fleet_sim(model, uplink=shaped(10_000.0, rtt_ms=0.2),
                            rate_hz=rate_hz, horizon_s=duration_s,
                            max_batch=fleet.max_batch, max_wait_s=0.0)
        predicted = sim.p95(n_clients)
        body = pack_payload({k: np.asarray(v) for k, v in payload.items()})
        rep = run_load(fleet.client, body, n_clients=n_clients,
                       rate_hz=rate_hz, duration_s=duration_s)
    finally:
        leaked = fleet.close()
    out = {"real_predicted_p95_ms": predicted * 1e3,
           "real_measured_p95_ms": rep.p95() * 1e3,
           "real_n_failures": rep.n_failures,
           "real_leaked_workers": len(leaked)}
    print(f"  N={n_clients} REAL fleet ({cfg.n_servers} servers, "
          f"{cfg.router}, localhost): measured p95 "
          f"{out['real_measured_p95_ms']:.2f} ms vs loopback-sim "
          f"{out['real_predicted_p95_ms']:.2f} ms "
          f"({rep.n_requests} reqs, {rep.n_failures} failed, "
          f"{len(leaked)} leaked)")
    return out


def load_manifest(path: str) -> DeploymentConfig:
    """Load a serialised DeploymentConfig (``python -m repro.deploy``)."""
    with open(path) as f:
        return DeploymentConfig.from_dict(json.load(f))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bandwidths", default="10,25,50,100")
    ap.add_argument("--decisions", type=int, default=1000)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--manifest", default=None,
                    help="deployment manifest JSON to build the pipeline "
                         "from (overrides --k)")
    ap.add_argument("--clients", type=int, default=8,
                    help="N clients for the FIFO-vs-batched p95 report "
                         "(0 disables)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--real-fleet", action="store_true",
                    help="also spawn the manifest's real multi-process "
                         "fleet on localhost and report measured p95 "
                         "next to the loopback sim prediction")
    args = ap.parse_args(argv)
    config = load_manifest(args.manifest) if args.manifest else None
    run(tuple(float(b) for b in args.bandwidths.split(",")),
        n_decisions=args.decisions, k=args.k, config=config)
    if args.clients:
        run_queue(n_clients=args.clients, k=args.k,
                  max_batch=args.max_batch,
                  setup=build(k=args.k, config=config),
                  real_fleet=args.real_fleet)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
