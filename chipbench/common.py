"""Small pieces the traffic kinds share."""
from __future__ import annotations

import contextlib
import sys

import numpy as np

# The harness's own host spans; the trace reduction names idle gaps by
# them.
SPAN_NAMES = ("window", "dispatch", "host_sync")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Spans:
    """Host spans written into the profiler's trace by name."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str):
        if name not in SPAN_NAMES:
            raise ValueError(f"unknown span {name!r}; one of {SPAN_NAMES}")
        return self._annotation(name)


class NoSpans:
    """Stand-in where no trace is taken (the tests' CPU runs)."""

    @staticmethod
    def span(name: str):
        return contextlib.nullcontext()


def jax_keys(rng: np.random.Generator, n: int):
    """``n`` JAX PRNG keys drawn from a seeded NumPy generator (seeds may
    exceed 32 bits)."""
    import jax
    return [jax.random.PRNGKey(int(s))
            for s in rng.integers(0, 2 ** 31 - 1, size=n)]


def manifest(cfg: dict, **overrides) -> dict:
    """The deployment manifest (``DeploymentConfig.from_dict``) of a
    configuration file."""
    m = {"spec": {"layers": [dict(l) for l in cfg["layers"]]},
         "in_h": cfg["in_h"], "in_w": cfg["in_w"],
         "head_dim": cfg["head_dim"], "head_act": cfg["head_act"],
         "codec": cfg["codec"], "max_batch": cfg["max_batch"]}
    m.update(overrides)
    return m


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, float), q))
