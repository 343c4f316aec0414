"""Serve traffic: edge clients at a fixed decision rate against the
deployment's server fleet (``Deployment.fleet``), over its localhost
sockets.

Each client sends one decision every ``1 / rate_hz`` seconds and waits
for its action before the next; a decision is due at its slot of the
grid whatever happened before, and its latency runs from that slot to
the action's arrival, so a backlog counts.  The clients' phases are the
uniform stagger of ``clients`` slots over one period, dealt to the
clients in an order drawn from the seed: every seed offers the same
arrivals.  Each client cycles ``payloads_per_client`` uint8 payloads
drawn from a pool of ``payload_pool``, made in set-up by the reference
encoder from seeded frames (the edge devices' work, not timed).

Traffic parameters (``traffic/<name>.json``): ``backend``,
``n_servers``, ``clients``, ``rate_hz``, ``payload_pool``,
``payloads_per_client``, ``warm_requests``.

Every action the window produced is checked once it has closed, against
the reference's decode and projection of its payload.  A request that
fails counts as missing for the latency and makes the run incorrect.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import common
from chipbench.reference import miniconv as ref

END_TO_END = "decision_p95_ms"


def _stagger(clients: int, period: float, rng) -> np.ndarray:
    """Start offsets of the clients within one period."""
    return rng.permutation(clients) * (period / clients)


class Serve:
    def __init__(self, cell, seed: int, spans: common.Spans):
        import jax
        from repro.deploy import Deployment, DeploymentConfig
        from repro.serving.realfleet import pack_payload

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.spans = cfg, spans
        self.rate = float(tr["rate_hz"])
        self.rng = np.random.default_rng(seed)
        k_params, k_frames = common.jax_keys(self.rng, 2)
        pool = int(tr["payload_pool"])
        shape = (pool, 1, cfg["in_h"], cfg["in_w"], cfg["layers"][0]["c_in"])

        @jax.jit
        def make(kp, kf):
            params = ref.init_params(cfg, kp)
            frames = jax.random.uniform(kf, shape)
            feats = jax.vmap(lambda x: ref.features(
                cfg, params["edge"], x))(frames)
            return params, jax.vmap(ref.quantize_uint8)(feats)

        self.params, wire = make(k_params, k_frames)
        wire = jax.tree.map(np.asarray, wire)
        self.wire = wire
        self.bodies = [pack_payload({k: v[i] for k, v in wire.items()})
                       for i in range(pool)]
        self.pool, self.per = pool, int(tr["payloads_per_client"])
        self.deal(int(tr["clients"]))
        dep = Deployment.build(DeploymentConfig.from_dict(
            common.manifest(cfg, backend=tr["backend"])))
        self.fleet = dep.fleet(self.params, n_servers=int(tr["n_servers"]))
        self.records: list = []
        for i in range(int(tr["warm_requests"])):
            self.fleet.request(self.bodies[i % pool], client=i)

    def deal(self, clients: int) -> None:
        """Phases and payloads of ``clients`` clients, from the seed."""
        self.clients = clients
        self.client_payloads = [
            self.rng.choice(self.pool, self.per, replace=False)
            for _ in range(clients)]
        self.offsets = _stagger(clients, 1.0 / self.rate, self.rng)

    def _client(self, c: int, t0: float, seconds: float, out: list) -> None:
        span, request = self.spans.span, self.fleet.request
        period = 1.0 / self.rate
        payloads = self.client_payloads[c]
        free = t0
        k = 0
        while self.offsets[c] + k * period < seconds:
            with span("generate"):
                due = t0 + self.offsets[c] + k * period
                j = int(payloads[k % len(payloads)])
                now = time.monotonic()
                if now < due:
                    time.sleep(due - now)
            sent = time.monotonic()
            try:
                with span("serve_wait"):
                    action = request(self.bodies[j], client=c)
            except Exception as e:  # repro: allow(broad-except) -- any failure is a missing answer, counted and reported
                action = e
            done = time.monotonic()
            out.append((c, j, due, sent - max(due, free), done - due, action))
            free = done
            k += 1

    def window(self, seconds: float) -> dict:
        worker = self.fleet.workers[0]
        n0 = len(worker.batch_sizes)
        t0 = time.monotonic() + 0.25
        outs = [[] for _ in range(self.clients)]
        threads = [threading.Thread(target=self._client,
                                    args=(c, t0, seconds, outs[c]))
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_end = time.monotonic()
        batches = list(worker.batch_sizes[n0:])
        self.records = [r for o in outs for r in o]
        lat = np.array([r[4] for r in self.records])
        lag = np.array([r[3] for r in self.records])
        failed = sum(isinstance(r[5], Exception) for r in self.records)
        half = t0 + seconds / 2
        early = [r[4] for r in self.records if r[2] < half]
        late = [r[4] for r in self.records if r[2] >= half]
        return {"metrics": {END_TO_END: 1e3 * common.percentile(lat, 95)},
                "attempted": len(self.records), "failed": failed,
                "counters": {
                    "batches": batches, "requests": len(self.records),
                    "gen_lag_p95_ms": 1e3 * common.percentile(lag, 95),
                    "p50_ms": 1e3 * common.percentile(lat, 50),
                    "p95_first_half_ms": 1e3 * common.percentile(early, 95),
                    "p95_second_half_ms": 1e3 * common.percentile(late, 95),
                    "window_s": t_end - t0}}

    def release(self) -> None:
        leaked = self.fleet.close()
        self.fleet = None
        if leaked:
            raise RuntimeError(f"fleet replicas did not stop: {leaked}")

    def check(self, precision: str = "highest") -> list[dict]:
        """Widest gap between a served action and the reference's, as a
        share of the reference's largest magnitude, over every answer."""
        import jax
        import jax.numpy as jnp
        cfg, wire = self.cfg, self.wire
        want = np.asarray(jax.jit(lambda p, d, s, z: ref.decode_project(
            cfg, p["server"], d, s, z, precision))(
                self.params, wire["data"], wire["scale"], wire["zero"]))
        answered = [r for r in self.records
                    if not isinstance(r[5], Exception)]
        gap = 0.0
        if answered:
            got = np.stack([np.asarray(r[5], np.float32) for r in answered])
            idx = np.array([r[1] for r in answered])
            gap = float(jnp.max(jnp.abs(got - want[idx])))
        scale = float(np.max(np.abs(want)))
        return [{"name": "action_rel_gap",
                 "value": gap / max(scale, 1e-30),
                 "answers": len(answered)}]


build = Serve
