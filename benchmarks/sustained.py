"""Paper Figure 3: sustained inference over many consecutive frames.

Reports per-frame time drift over a long run (the paper observes Jetson
thermal throttling and CPU-vs-GPU stability on the Pi Zero).  Thermal
state does not exist here; the reproducible part is the *stability*
comparison between an op-by-op interpreted path (the paper's CPU/PyTorch
condition) and the compiled path (the OpenGL condition), plus drift
detection over the horizon.

Execution paths come from :mod:`repro.deploy`: every condition is one
:class:`DeploymentConfig` resolved by ``Deployment.build``, so the run
honours frozen ``tuning`` blocks and streaming decisions exactly like a
served policy would.  ``--manifest DEPLOY.json`` sustains the manifest's
own deployment (tuned backend included) instead of the default pair.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import perfstamp
from repro.deploy import Deployment, DeploymentConfig


def sustained(fn, x, n_frames: int, *, warmup: int = 3) -> np.ndarray:
    for _ in range(warmup):
        jax.block_until_ready(fn(x))  # compile + settle before the clock
    ts = np.empty(n_frames)
    for i in range(n_frames):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts[i] = time.perf_counter() - t0
    return ts


def _edge_fn(dep: Deployment, *, jit: bool, seed: int = 0):
    """The encoder (edge half) path of a deployment, optionally jitted."""
    edge_params = dep.init(jax.random.PRNGKey(seed))["edge"]
    fn = lambda x: dep.split.edge_apply(edge_params, x)
    return jax.jit(fn) if jit else fn


def run(*, n_frames: int = 200, x_size: int = 128, k: int = 4,
        manifest: str | None = None):
    if manifest is not None:
        with open(manifest) as f:
            cfg = DeploymentConfig.from_dict(json.load(f))
        dep = Deployment.build(cfg)
        x_size = cfg.in_h
        label = dep.backend.name
        if cfg.tuning is not None:
            label += f"[tuned tile_h={dep.tile_h}]"
        # jit only the xla path: pallas tiers are already jitted inside,
        # and the outer-jit vs raw-dispatch contrast is the experiment
        conditions = ((label, _edge_fn(dep, jit=dep.backend.mode == "xla"),
                       n_frames),)
        for line in dep.build_log:
            print(f"  {line}")
    else:
        dep = Deployment.build(DeploymentConfig.standard(
            k=k, c_in=4, h=x_size, backend="xla"))
        conditions = (
            ("compiled", _edge_fn(dep, jit=True), n_frames),
            ("eager", _edge_fn(dep, jit=False), max(n_frames // 10, 10)),
        )
    c_in = dep.config.spec.layers[0].c_in
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (1, x_size, x_size, c_in))

    out = {}
    for name, fn, n in conditions:
        ts = sustained(fn, x, n)
        head, tail = ts[: n // 4].mean(), ts[-n // 4:].mean()
        out[name] = perfstamp.stamp({
            "mean_ms": ts.mean() * 1e3, "p99_ms":
                float(np.percentile(ts, 99) * 1e3),
            "drift_pct": 100.0 * (tail - head) / head,
            "cv_pct": 100.0 * ts.std() / ts.mean(),
        }, backend=dep.backend.name)
        print(f"  {name:<9} mean={out[name]['mean_ms']:.3f}ms "
              f"p99={out[name]['p99_ms']:.3f}ms "
              f"drift={out[name]['drift_pct']:+.1f}% "
              f"cv={out[name]['cv_pct']:.1f}% "
              f"[{out[name]['mode']}]")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--frames", type=int, default=200)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--manifest", metavar="DEPLOY.json",
                    help="sustain this deployment manifest's execution "
                         "path (tuning block honoured) instead of the "
                         "compiled/eager default pair")
    args = ap.parse_args(argv)
    run(n_frames=args.frames, x_size=args.size, manifest=args.manifest)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
