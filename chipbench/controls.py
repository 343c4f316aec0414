"""Controls and planted faults: the program's timed path replaced
underneath the harness, to show that the comparison that decides
``correct`` fails them.

* :func:`reference_in_place` puts the plain reference, at a lower
  precision (``bf16_3x``), in the place of the deployed encoder and of
  the server half.
* :func:`altered_answer` alters one element of every answer where it is
  produced.

Both patch ``repro.deploy.Deployment`` for the length of a ``with`` block.
"""
from __future__ import annotations

import contextlib
import dataclasses

from chipbench.reference import miniconv as ref


def config_of(dep) -> dict:
    """The configuration-file form of a built deployment."""
    c = dep.config
    return {"layers": [dataclasses.asdict(l) for l in c.spec.layers],
            "in_h": c.in_h, "in_w": c.in_w, "head_dim": c.head_dim,
            "head_act": c.head_act}


@contextlib.contextmanager
def _patched(encoder_apply, server_batch):
    """``encoder_apply(dep, original) -> apply`` and ``server_batch(dep,
    params, original) -> fn`` replace the deployment's timed paths."""
    from repro.deploy import Deployment
    build, batch_fn = Deployment.__dict__["build"], Deployment.server_batch_fn

    def patched_build(cls, config):
        dep = build.__func__(cls, config)
        apply = encoder_apply(dep, dep.encoder.apply)
        return dataclasses.replace(
            dep, encoder=dataclasses.replace(dep.encoder, apply=apply))

    def patched_batch_fn(self, params, head=None):
        return server_batch(self, params, batch_fn(self, params, head))

    Deployment.build = classmethod(patched_build)
    Deployment.server_batch_fn = patched_batch_fn
    try:
        yield
    finally:
        Deployment.build = build
        Deployment.server_batch_fn = batch_fn


def reference_in_place(precision: str = "bf16_3x"):
    """The reference at ``precision`` serves in the program's place."""
    import jax

    def encoder(dep, original):
        cfg = config_of(dep)
        return lambda params, x: ref.encode_project(cfg, params, x,
                                                    precision)

    def server(dep, params, original):
        cfg, server_params = config_of(dep), params["server"]

        def fn(payload):
            return ref.decode_project(cfg, server_params, payload["data"],
                                      payload["scale"], payload["zero"],
                                      precision)
        return jax.jit(fn)

    return _patched(encoder, server)


def altered_answer(delta: float = 1.0):
    """Every answer leaves with its first element moved by ``delta``."""
    def encoder(dep, original):
        return lambda params, x: original(params, x).at[:, 0].add(delta)

    def server(dep, params, original):
        return lambda payload: original(payload).at[:, 0].add(delta)

    return _patched(encoder, server)
