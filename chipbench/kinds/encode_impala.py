"""Encode traffic through IMPALA-CNN's server-only deployment: batches of
frames through ``Deployment.encoder.apply`` (torso and head on ``xla``),
launched back to back and blocked per launch, as a central inference
server answers one batch of raw frames at a time.  The window is
``kinds/encode.py``'s; only the build and the check differ.

Traffic parameters (``traffic/<name>.json``): ``backend``, ``batch``
(frames per launch) and ``pool`` (distinct device-resident batches of
float32 frames uniform in [0, 1], cycled in an order drawn from the
seed).

At set-up the step is compiled once, and the executable the window
launches is read for each operation's name stack (its HLO ``op_name``,
the ``tf_op`` the trace shows), which the window's counters carry as
``op_scopes``, so that a per-layer metric can
sum the device time under one of the program's scopes (:func:`scope_us`).

What the window produced is checked once it has closed: every output of
every launch against ``reference/impala.py`` at the same frames.
"""
from __future__ import annotations

import re

import numpy as np

from chipbench import common, scopes, trace
from chipbench.kinds.encode import Encode
from chipbench.reference import impala as ref

_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?'
                      r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def manifest(cfg: dict, **overrides) -> dict:
    """The deployment manifest (``DeploymentConfig.from_dict``) of an
    IMPALA configuration file."""
    m = {"spec": {"family": "impala", "depths": cfg["depths"],
                  "blocks": cfg["blocks"], "c_in": cfg["c_in"]},
         "in_h": cfg["in_h"], "in_w": cfg["in_w"],
         "head_dim": cfg["head_dim"], "head_act": cfg["head_act"],
         "codec": cfg["codec"], "max_batch": cfg["max_batch"]}
    m.update(overrides)
    return m


def op_scopes(hlo_text: str) -> dict:
    """Instruction name -> HLO ``op_name`` of every instruction of a
    compiled program's text that has one."""
    return dict(_OP_NAME.findall(hlo_text))


def scope_us(ctx, name: str):
    """Device µs per launch of the encode step's operations whose name
    stack (the window's ``op_scopes``) holds the scope ``name``."""
    stacks = ctx.counters.get("op_scopes")
    if ctx.trace is None or not stacks:
        return None

    def in_step(module):
        return module.startswith(scopes.ENCODE_STEP)

    def under(op):
        return name in scopes.scope_names(
            stacks.get(trace.short_name(op), ""))

    t, launches = scopes.module_ops_time(ctx.trace, in_step, op=under)
    if launches == 0 or t <= 0:
        return None
    return 1e6 * t / launches


class EncodeImpala(Encode):
    def __init__(self, cell, seed: int, spans: common.Spans):
        import jax
        from repro.deploy import Deployment, DeploymentConfig

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.spans = cfg, spans
        self.batch, self.pool = int(tr["batch"]), int(tr["pool"])
        self.rng = np.random.default_rng(seed)
        k_params, k_frames = common.jax_keys(self.rng, 2)
        self.dep = Deployment.build(DeploymentConfig.from_dict(
            manifest(cfg, backend=tr["backend"])))
        shape = (self.pool, self.batch, cfg["in_h"], cfg["in_w"],
                 cfg["c_in"])
        self.params, frames = jax.jit(
            lambda kp, kf: (ref.init_params(cfg, kp),
                            jax.random.uniform(kf, shape)))(k_params,
                                                            k_frames)
        self.frames = [frames[i] for i in range(self.pool)]
        # compiled once: the window launches this executable, and its
        # text gives the scope map
        self.apply = jax.jit(self.dep.encoder.apply).lower(
            self.params, self.frames[0]).compile()
        jax.block_until_ready(self.apply(self.params, self.frames[0]))
        self.op_scopes = op_scopes(self.apply.as_text())
        self.outputs: list = []          # (pool index, outputs)

    def window(self, seconds: float) -> dict:
        out = super().window(seconds)
        out["counters"]["op_scopes"] = self.op_scopes
        return out

    def check(self, precision: str = "highest") -> list[dict]:
        """Widest gap between the window's outputs and the reference's, as
        a share of the reference's largest magnitude."""
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


build = EncodeImpala
