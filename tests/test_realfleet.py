"""Real multi-process fleet (repro.serving.realfleet).

Three layers, cheap to expensive:

* framing — pack/unpack is bitwise for every registered wire codec, and
  frames round-trip over a real socket pair;
* threaded WorkerServer + FleetClient — continuous-batching admission,
  timeout-not-hang, crash re-routing, graceful drain, open-loop load
  generation (no process spawn, no jax model);
* spawned processes — the acceptance test: a 2-server fleet built from
  one deployment manifest serves actions over sockets BITWISE-equal to
  in-process serving, through all three registered routers, survives a
  worker kill, and shuts down without leaking processes.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.wire import CODECS
from repro.serving.realfleet import (MSG_REQ, MSG_RESP, MSG_SHUTDOWN,
                                     FleetClient, FleetTimeout, WorkerServer,
                                     _recv_frame, _send_frame, pack_payload,
                                     run_load, unpack_payload)


# ------------------------------------------------------------------ framing
@pytest.mark.parametrize("name", sorted(CODECS))
def test_pack_unpack_bitwise_per_codec(name):
    """Socket serialisation reproduces every codec's payload (data AND
    quantisation headers) bitwise — the wire format adds framing, never
    numerics."""
    x = jax.random.uniform(jax.random.PRNGKey(0), (1, 5, 5, 4))
    payload = {k: np.asarray(v) for k, v in CODECS[name].encode(x).items()}
    back = unpack_payload(pack_payload(payload))
    assert set(back) == set(payload)
    for k in payload:
        assert back[k].dtype == payload[k].dtype
        assert back[k].shape == payload[k].shape
        assert back[k].tobytes() == payload[k].tobytes()


def test_frame_roundtrip_over_socket():
    a, b = socket.socketpair()
    try:
        _send_frame(a, MSG_REQ, b"\x00\x01payload")
        mtype, body = _recv_frame(b)
        assert mtype == MSG_REQ and body == b"\x00\x01payload"
        _send_frame(b, MSG_RESP)               # empty body is legal
        assert _recv_frame(a) == (MSG_RESP, b"")
        a.close()
        assert _recv_frame(b) == (None, None)  # clean EOF, not an exception
    finally:
        a.close()
        b.close()


# ------------------------------------------- threaded worker + front door
def _payload(value, n=2):
    return {"data": np.full((n,), float(value), np.float32)}


def test_continuous_batching_admits_during_service():
    """Requests arriving while a micro-batch is in service form the NEXT
    batch — the service time is the batching window, no max_wait hold."""
    in_service = threading.Event()
    release = threading.Event()

    def slow_double(stacked):
        in_service.set()
        release.wait(5.0)
        return stacked["data"] * 2.0

    ws = WorkerServer(slow_double, max_batch=8)
    addr = ws.start()
    fc = FleetClient([addr], timeout_s=10.0, retries=0)
    results = {}

    def issue(i):
        results[i] = fc.request(_payload(i))

    threads = [threading.Thread(target=issue, args=(0,))]
    threads[0].start()
    assert in_service.wait(5.0)        # batch [0] is on the "GPU"
    for i in (1, 2, 3):                # these arrive during its service
        t = threading.Thread(target=issue, args=(i,))
        t.start()
        threads.append(t)
    deadline = time.monotonic() + 5.0
    while ws._q.qsize() < 3 and time.monotonic() < deadline:
        time.sleep(0.01)               # all three queued at the worker
    release.set()
    for t in threads:
        t.join(10.0)
    for i in range(4):
        np.testing.assert_array_equal(results[i],
                                      np.full((2,), 2.0 * i, np.float32))
    assert ws.batch_sizes[0] == 1      # lone first request never held
    assert ws.batch_sizes[1] == 3      # the backlog launched as ONE batch
    assert fc.stats["max_served_batch"] == 3
    fc.shutdown()
    ws.join(5.0)


def test_timeout_surfaces_instead_of_hanging():
    def stuck(stacked):
        time.sleep(3.0)
        return stacked["data"]

    ws = WorkerServer(stuck, max_batch=2)
    addr = ws.start()
    fc = FleetClient([addr], timeout_s=0.15, retries=0)
    t0 = time.monotonic()
    with pytest.raises(FleetTimeout):
        fc.request(_payload(0))
    assert time.monotonic() - t0 < 1.5
    assert fc.stats["timeouts"] == 1
    ws.stop()
    fc.shutdown(wait_pending_s=0.1)


def test_crash_mid_request_reroutes_retry():
    """A worker dying mid-request fails the pending request immediately
    (connection EOF, not a timeout) and the retry re-routes to a live
    worker."""
    crashing = {}

    def crash(stacked):
        crashing["ws"].stop()          # drops every connection, no response
        raise RuntimeError("worker crashed mid-batch")

    ws0 = WorkerServer(crash, max_batch=2)
    crashing["ws"] = ws0
    ws1 = WorkerServer(lambda s: s["data"] + 1.0, max_batch=2)
    a0, a1 = ws0.start(), ws1.start()
    fc = FleetClient([a0, a1], router="round_robin", timeout_s=5.0,
                     retries=2)
    out = fc.request(_payload(0))      # seq 0 -> server 0 -> crash -> retry
    np.testing.assert_array_equal(out, np.ones((2,), np.float32))
    assert fc.stats["retries"] >= 1
    assert fc.stats["per_server"][1] == 1
    assert not fc.conns[0].alive       # marked dead for future requests
    out2 = fc.request(_payload(1))     # routes straight to the live worker
    np.testing.assert_array_equal(out2, np.full((2,), 2.0, np.float32))
    fc.shutdown()
    ws1.join(5.0)


def test_graceful_shutdown_drains_queued_requests():
    """Every request received before SHUTDOWN is served and answered
    before the worker exits."""
    def slowish(stacked):
        time.sleep(0.03)
        return stacked["data"]

    ws = WorkerServer(slowish, max_batch=2)
    addr = ws.start()
    s = socket.create_connection(addr)
    try:
        body = pack_payload(_payload(7, n=3))
        for rid in range(3):
            _send_frame(s, MSG_REQ, struct.pack("!I", rid) + body)
        _send_frame(s, MSG_SHUTDOWN)
        got = set()
        for _ in range(3):
            mtype, b = _recv_frame(s)
            assert mtype == MSG_RESP
            rid, _bsz = struct.unpack_from("!IH", b)
            got.add(rid)
            np.testing.assert_array_equal(
                unpack_payload(b[6:])["action"],
                np.full((3,), 7.0, np.float32))
        assert got == {0, 1, 2}
    finally:
        s.close()
    ws.join(5.0)
    assert ws.n_served == 3


def test_run_load_open_loop():
    ws = WorkerServer(lambda s: s["data"] * 2.0, max_batch=4)
    addr = ws.start()
    fc = FleetClient([addr], timeout_s=5.0)
    rep = run_load(fc, _payload(1), n_clients=2, rate_hz=20.0,
                   duration_s=0.5)
    assert rep.n_requests == 20        # 2 clients x 20 Hz x 0.5 s
    assert rep.n_failures == 0
    assert 0.0 < rep.p50() <= rep.p95()
    fc.shutdown()
    ws.join(5.0)


# ----------------------------------------------------- spawned processes
def test_real_fleet_two_servers_bitwise_and_crash():
    """The acceptance test: a manifest-built 2-worker fleet on localhost
    serves socket actions bitwise-equal to in-process serving through all
    three registered routers, re-routes around a killed worker, and shuts
    down without leaking processes."""
    from repro.deploy import Deployment, DeploymentConfig

    cfg = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="xla",
                                    max_batch=2, n_servers=2,
                                    router="round_robin")
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(0))
    client, server = dep.serving_pair(params)
    n = 6
    obs = jax.random.uniform(jax.random.PRNGKey(1), (n, 24, 24, 4))
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(n)]
    want = [np.asarray(server.serve([p])[0]) for p in payloads]

    fleet = dep.fleet(params, timeout_s=60.0)
    try:
        got = [fleet.request(p, client=i) for i, p in enumerate(payloads)]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert all(c > 0 for c in fleet.stats["per_server"])  # RR spread
        # same fleet, other routers: routing is a parent-side decision
        for router in ("least_loaded", "client_affinity"):
            fleet.set_router(router)
            np.testing.assert_array_equal(
                want[0], fleet.request(payloads[0], client=3))
        # kill a worker: requests re-route and results stay bitwise-equal
        fleet.processes[0].kill()
        fleet.processes[0].join(10.0)
        fleet.set_router("round_robin")
        got2 = [fleet.request(p, client=i) for i, p in enumerate(payloads)]
        for w, g in zip(want, got2):
            np.testing.assert_array_equal(w, g)
        assert fleet.stats["per_server"][1] >= n
    finally:
        leaked = fleet.close()
    assert leaked == []


def test_real_fleet_in_process_replicas_bitwise(monkeypatch):
    """The accelerator layout, run on the CPU: in-process replicas, each
    pinned to a device (cycled over the devices there are), serve actions
    bitwise-equal to in-process serving and stop without leaking."""
    from repro.deploy import Deployment, DeploymentConfig
    from repro.serving import realfleet
    monkeypatch.setattr(realfleet, "_spawns_workers", lambda: False)

    cfg = DeploymentConfig.standard(k=4, c_in=4, h=24, backend="xla",
                                    max_batch=2, n_servers=2)
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(0))
    client, server = dep.serving_pair(params)
    obs = jax.random.uniform(jax.random.PRNGKey(1), (4, 24, 24, 4))
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(4)]
    want = [np.asarray(server.serve([p])[0]) for p in payloads]

    fleet = dep.fleet(params)
    try:
        assert fleet.in_process
        assert fleet.processes == [] and len(fleet.workers) == 2
        for router in ("round_robin", "client_affinity"):
            fleet.set_router(router)
            got = [fleet.request(p, client=i) for i, p in enumerate(payloads)]
            for w, g in zip(want, got):
                np.testing.assert_array_equal(w, g)
        devices = jax.devices()
        for i, w in enumerate(fleet.workers):
            assert w.devices == {devices[i % len(devices)]}
    finally:
        leaked = fleet.close()
    assert leaked == []
    assert not any(w.is_alive() for w in fleet.workers)


# ------------------------------------------------------- ingress shaping
def test_token_bucket_gcra_with_injected_clock():
    from repro.serving.realfleet import TokenBucket
    now = [0.0]
    tb = TokenBucket(rate_bps=8e6, burst_bytes=10_000,  # 1 MB/s, 10 kB burst
                     clock=lambda: now[0])
    assert tb.reserve(10_000) == 0.0          # the burst rides free
    assert tb.reserve(10_000) == pytest.approx(0.01)   # 10 kB at 1 MB/s
    now[0] = 1.0                              # bucket refills while idle
    assert tb.reserve(10_000) == 0.0
    # sustained over-rate with a frozen clock: debt grows linearly
    for _ in range(100):
        wait = tb.reserve(1_000)
    assert wait == pytest.approx(0.1)         # 110 kB since t=1, 10 kB burst


def test_shaping_config_roundtrip_and_bucket():
    from repro.serving.realfleet import ShapingConfig, TokenBucket
    cfg = ShapingConfig(rate_mbps=2.0, burst_bytes=4096)
    assert ShapingConfig.from_dict(cfg.to_dict()) == cfg
    assert isinstance(cfg.bucket(), TokenBucket)
    with pytest.raises(ValueError):
        ShapingConfig(rate_mbps=0.0)
    with pytest.raises(ValueError):
        ShapingConfig(rate_mbps=1.0, burst_bytes=0)


def test_worker_front_door_shapes_ingress():
    """A shaped WorkerServer answers correctly AND spaces its admissions:
    past the burst, each request reaches the batching queue at least one
    transmission time (its bytes at the shaped rate) after the one
    before.  The GCRA bucket refills while a closed-loop client waits for
    its answer, so the sleeps alone need not add up to the transmission
    times; the spacing of the admissions does."""
    from repro.serving.realfleet import ShapingConfig
    body = pack_payload(_payload(1, n=256))   # ~1 kB on the wire
    rate_bps = 0.125e6
    tx_s = len(body) * 8 / rate_bps           # ~68 ms a request
    # a request is stamped (``_Request.arrived``) after its sleep and its
    # payload's unpack: a stamp may run late by the sleep's wake-up on a
    # loaded host and a thread switch (Python's switch interval is 5 ms),
    # which this tolerance covers, taken off the spacing of the stamp that
    # follows.  An unshaped worker spaces them by a round trip, ~1 ms.
    tol_s = 0.25 * tx_s
    # tiny burst, 0.125 Mb/s: every request after the first must wait
    shaper = ShapingConfig(rate_mbps=rate_bps / 1e6,
                           burst_bytes=len(body)).bucket()
    arrived: list[float] = []

    class Recording(WorkerServer):
        def _serve(self, batch):
            arrived.extend(r.arrived for r in batch)
            super()._serve(batch)

    ws = Recording(lambda s: s["data"] * 2.0, max_batch=4, shaper=shaper)
    addr = ws.start()
    fc = FleetClient([addr], timeout_s=10.0)
    t0 = time.monotonic()
    for _ in range(4):
        np.testing.assert_array_equal(fc.request(_payload(1, n=256)),
                                      _payload(2, n=256)["data"])
    elapsed = time.monotonic() - t0
    fc.shutdown()
    ws.join(5.0)
    assert len(arrived) == 4
    assert np.all(np.diff(arrived) >= tx_s - tol_s), np.diff(arrived)
    assert elapsed >= 3 * tx_s                # 3 post-burst transmissions
    # unshaped control: no accumulated sleep
    ws2 = WorkerServer(lambda s: s["data"] * 2.0, max_batch=4)
    addr2 = ws2.start()
    fc2 = FleetClient([addr2], timeout_s=10.0)
    fc2.request(_payload(3))
    assert ws2.shaped_sleep_s == 0.0
    fc2.shutdown()
    ws2.join(5.0)
