"""The program's trace names, in one registry.

Two kinds of name go into a ``jax.profiler`` trace:

* **scopes** (:data:`SCOPES`) are entries of JAX's name stack.  They land
  in the compiled program's HLO ``op_name`` metadata, so each device
  operation in a trace carries the scopes it was traced under (its
  ``tf_op`` stat).  They cost nothing at run time.
* **spans** (:data:`SPANS`) are host intervals
  (``jax.profiler.TraceAnnotation``), with keyword stats such as
  ``req_id``.  They record only while a profiler is active, into the same
  trace as the device's events, so they share its clock.

:func:`scope` and :func:`span` refuse a name that is not registered here.
A scope cannot split a Pallas kernel: the whole ``pallas_call`` is one
device operation (``tpu_custom_call``).
"""
from __future__ import annotations

import jax

SCOPES = {
    "miniconv.encode": "the whole encode step: Deployment.encoder.apply",
    "miniconv.input": "batch padding, RGBA channel padding and the layer-0 "
                      "SAME border of the fused kernels' input",
    "miniconv.weights": "per-layer weight and bias padding to RGBA "
                        "multiples",
    "miniconv.s2d": "the space-to-depth fold of a stride-s first layer's "
                    "padded input and weights (inside miniconv.input and "
                    "miniconv.weights); absent where the plan does not fold",
    "miniconv.head_tile": "the projection weight tiled for the kernel's "
                          "epilogue, and its lane padding",
    "miniconv.kernel": "the fused encoder pallas_call",
    "miniconv.out": "features and projection sliced out of the kernel's "
                    "padded outputs",
    "impala.encode": "the whole IMPALA-CNN step: torso and head (the "
                     "encode step, and the server half after wire.decode)",
    "impala.conv": "each IMPALA stack's entry conv",
    "impala.pool": "each IMPALA stack's 3x3 stride-2 max-pool",
    "impala.res": "each IMPALA stack's residual blocks",
    "impala.head": "the IMPALA head: flatten, ReLU, dense and activation",
    "wire.decode": "the wire codec's decode of a payload on the server",
    "split.project": "the server half's dense projection and activation",
}

SPANS = {
    "fleet.request": "one attempt of FleetClient.request, send to answer "
                     "(req_id, client)",
    "serve.admit": "WorkerServer's non-blocking sweep of its queue into a "
                   "micro-batch",
    "serve.batch": "one micro-batch served (n, req_ids, wait_us: the "
                   "longest queue wait)",
    "serve.stack": "the batch's payloads stacked on the host",
    "serve.device": "the server half's call (dispatch, and the copy to "
                    "the device; buffers: the host arrays copied for the "
                    "micro-batch, 1 for a fleet worker's packed block)",
    "serve.fetch": "the actions copied back to the host",
    "serve.send": "the answers framed and sent",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a registered scope."""
    if name not in SCOPES:
        raise ValueError(f"unregistered scope {name!r}; one of "
                         f"{', '.join(SCOPES)}")
    return jax.named_scope(name)


def span(name: str, **ids):
    """``jax.profiler.TraceAnnotation(name, **ids)`` for a registered
    span.  A stat's value is recorded as text; a comma in it ends the
    value, so a list goes as space-separated items."""
    if name not in SPANS:
        raise ValueError(f"unregistered span {name!r}; one of "
                         f"{', '.join(SPANS)}")
    return jax.profiler.TraceAnnotation(name, **ids)


__all__ = ["SCOPES", "SPANS", "scope", "span"]
