"""Serve traffic: edge clients at a fixed decision rate against the
deployment's server fleet (``Deployment.fleet``), over its localhost
sockets.

The clients run in a process of their own (``chipbench/loadgen.py``,
started in set-up): one thread over one socket to the one replica, so
that they share neither the interpreter lock nor the CPU time of the
process that serves.  Each client sends one decision every
``1 / rate_hz`` seconds and waits for its action before the next; a
decision is due at its slot of the grid whatever happened before, and
its latency runs from that slot to the action's arrival, so a backlog
counts.  The clients' phases are the uniform stagger of ``clients``
slots over one period, dealt to the clients in an order drawn from the
seed: every seed offers the same arrivals.  Each client cycles
``payloads_per_client`` uint8 payloads drawn from a pool of
``payload_pool``, made in set-up by the reference encoder from seeded
frames (the edge devices' work, not timed).

Traffic parameters (``traffic/<name>.json``): ``backend``,
``n_servers`` (1), ``clients``, ``rate_hz``, ``payload_pool``,
``payloads_per_client``, ``warm_requests`` (sent one at a time over the
clients' socket in set-up).

The end-to-end metric is the median latency of every decision due in
the window; the p95, its two halves and the largest are logged.  Every
action the window produced is checked once it has closed, against the
reference's decode and projection of its payload.  A request that fails,
or gets no answer, makes the run incorrect.
"""
from __future__ import annotations

import pickle
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import common
from chipbench.reference import miniconv as ref

LOADGEN = Path(__file__).resolve().parents[1] / "loadgen.py"

END_TO_END = "decision_p50_ms"


def _stagger(clients: int, period: float, rng) -> np.ndarray:
    """Start offsets of the clients within one period."""
    return rng.permutation(clients) * (period / clients)


class Serve:
    def __init__(self, cell, seed: int, spans: common.Spans):
        import jax
        from repro.deploy import Deployment, DeploymentConfig
        from repro.serving import realfleet

        cfg, tr = cell.config, cell.traffic
        self.cfg = cfg
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
        self.bodies = [realfleet.pack_payload({k: v[i]
                                                for k, v in wire.items()})
                       for i in range(pool)]
        self.pool, self.per = pool, int(tr["payloads_per_client"])
        self.deal(int(tr["clients"]))
        if int(tr["n_servers"]) != 1:
            raise ValueError("serve traffic drives one replica; "
                             f"n_servers is {tr['n_servers']}")
        dep = Deployment.build(DeploymentConfig.from_dict(
            common.manifest(cfg, backend=tr["backend"])))
        self.fleet = dep.fleet(self.params, n_servers=1)
        self.records: list = []
        self.clients_proc = subprocess.Popen(
            [sys.executable, str(LOADGEN)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE)
        self._tell({"addr": self.fleet.workers[0].addr,
                    "bodies": self.bodies, "warm": int(tr["warm_requests"]),
                    "msg": {"req": realfleet.MSG_REQ,
                            "resp": realfleet.MSG_RESP,
                            "err": realfleet.MSG_ERR}})
        if self._hear() != "ready":
            raise RuntimeError("the load generator did not start")

    def _tell(self, message) -> None:
        pickle.dump(message, self.clients_proc.stdin)
        self.clients_proc.stdin.flush()

    def _hear(self):
        try:
            return pickle.load(self.clients_proc.stdout)
        except EOFError as e:
            raise RuntimeError(f"the load generator exited with "
                               f"{self.clients_proc.wait()}") from e

    def deal(self, clients: int) -> None:
        """Phases and payloads of ``clients`` clients, from the seed."""
        self.clients = clients
        self.client_payloads = [
            self.rng.choice(self.pool, self.per, replace=False)
            for _ in range(clients)]
        self.offsets = _stagger(clients, 1.0 / self.rate, self.rng)

    @staticmethod
    def _action(answer):
        """The action of an answer body (``!H`` batch size, packed
        action), or the exception that stands for a failed request."""
        from repro.serving.realfleet import unpack_payload
        if isinstance(answer, bytes):
            return unpack_payload(answer[2:])["action"]
        if answer is None:
            return TimeoutError("no answer")
        return RuntimeError(f"the worker answered an error: {answer}")

    def window(self, seconds: float) -> dict:
        worker = self.fleet.workers[0]
        n0 = len(worker.batch_sizes)
        self._tell({"seconds": seconds, "rate_hz": self.rate,
                    "offsets": [float(o) for o in self.offsets],
                    "payloads": [[int(j) for j in p]
                                 for p in self.client_payloads]})
        got = self._hear()
        t_end = time.monotonic()
        batches = list(worker.batch_sizes[n0:])
        t0 = got["t0"]
        self.records = [(c, j, due, lag, latency, self._action(answer))
                        for c, j, due, lag, latency, answer
                        in got["records"]]
        lat = np.array([r[4] for r in self.records])
        lag = np.array([r[3] for r in self.records])
        failed = sum(isinstance(r[5], Exception) for r in self.records)
        half = t0 + seconds / 2
        early = [r[4] for r in self.records if r[2] < half]
        late = [r[4] for r in self.records if r[2] >= half]
        counters = {
            "batches": batches, "requests": len(self.records),
            "gen_lag_p95_ms": 1e3 * common.percentile(lag, 95),
            "p95_ms": 1e3 * common.percentile(lat, 95),
            "p95_first_half_ms": 1e3 * common.percentile(early, 95),
            "p95_second_half_ms": 1e3 * common.percentile(late, 95),
            "max_ms": 1e3 * float(lat.max()), "window_s": t_end - t0}
        common.log("decision latency: " + ", ".join(
            f"{k} {counters[k]:.4f}" for k in (
                "p95_ms", "p95_first_half_ms", "p95_second_half_ms",
                "max_ms", "gen_lag_p95_ms")))
        return {"metrics": {END_TO_END: 1e3 * common.percentile(lat, 50)},
                "attempted": len(self.records), "failed": failed,
                "counters": counters}

    def release(self) -> None:
        self._tell(None)
        self.clients_proc.stdin.close()
        self.clients_proc.wait(timeout=60)
        self.clients_proc.stdout.close()
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
