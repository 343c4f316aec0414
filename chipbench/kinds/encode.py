"""Encode traffic: micro-batches of frames through the deployed encoder and
projection (``Deployment.encoder.apply``), launched back to back and
blocked per launch, as a server that encodes for its clients answers one
micro-batch at a time.

Traffic parameters (``traffic/<name>.json``): ``backend`` (the
deployment's execution backend), ``batch`` (frames per launch) and
``pool`` (distinct device-resident batches, cycled in an order drawn
from the seed).

What the window produced is checked once it has closed: every
projection of every launch against the plain reference of the
configuration at the same frames.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import common
from chipbench.reference import miniconv as ref

END_TO_END = "frames_per_s"


class Encode:
    def __init__(self, cell, seed: int, spans: common.Spans):
        import jax
        from repro.deploy import Deployment, DeploymentConfig

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.spans = cfg, spans
        self.batch, self.pool = int(tr["batch"]), int(tr["pool"])
        self.rng = np.random.default_rng(seed)
        k_params, k_frames = common.jax_keys(self.rng, 2)
        self.dep = Deployment.build(DeploymentConfig.from_dict(
            common.manifest(cfg, backend=tr["backend"])))
        for line in self.dep.build_log:
            common.log(f"build: {line}")
        common.log(f"launch of {self.batch}: max_safe_batch "
                   f"{self.dep.max_safe_batch}, stream_chunk "
                   f"{self.dep.stream_chunk}")
        shape = (self.pool, self.batch, cfg["in_h"], cfg["in_w"],
                 cfg["layers"][0]["c_in"])
        self.params, frames = jax.jit(
            lambda kp, kf: (ref.init_params(cfg, kp),
                            jax.random.uniform(kf, shape)))(k_params,
                                                            k_frames)
        self.frames = [frames[i] for i in range(self.pool)]
        self.apply = jax.jit(self.dep.encoder.apply)
        jax.block_until_ready(self.apply(self.params, self.frames[0]))
        self.outputs: list = []          # (pool index, projections)

    def window(self, seconds: float) -> dict:
        """Launch for ``seconds``; the rate is taken over all of them."""
        apply, params, frames = self.apply, self.params, self.frames
        outputs, span = self.outputs, self.spans.span
        order: list = []
        t0 = time.perf_counter()  # repro: allow(timing-warmup) -- __init__ warmed and blocked
        while True:
            if not order:
                order = list(self.rng.permutation(self.pool))
            i = order.pop()
            with span("dispatch"):
                z = apply(params, frames[i])
            with span("host_sync"):
                z.block_until_ready()
            outputs.append((i, z))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        n = len(outputs)
        return {"metrics": {END_TO_END: n * self.batch / elapsed},
                "attempted": n * self.batch, "failed": 0,
                "counters": {"launches": n, "batch": self.batch,
                             "window_s": elapsed}}

    def release(self) -> None:
        """Drop the program's state; keep the window's outputs and the
        inputs the reference needs."""
        self.dep = self.apply = None

    def check(self, precision: str = "highest") -> list[dict]:
        """Widest gap between the window's projections and the reference's,
        as a share of the reference's largest magnitude."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        want = jax.jit(lambda p, x: ref.encode_project(cfg, p, x, precision))
        gap = scale = 0.0
        by_batch: dict = {}
        for i, z in self.outputs:
            by_batch.setdefault(i, []).append(z)
        for i, zs in by_batch.items():
            r = want(self.params, self.frames[i])
            got = jnp.stack(zs)
            gap = max(gap, float(jnp.max(jnp.abs(got - r[None]))))
            scale = max(scale, float(jnp.max(jnp.abs(r))))
        return [{"name": "proj_rel_gap", "value": gap / max(scale, 1e-30),
                 "frames": len(self.outputs) * self.batch}]


build = Encode
