"""Per-manifest kernel autotuning: measure the live kernel, freeze the winner.

RLtools wins its speed comparisons by exhaustively specialising kernels to
the deployment target; DistrEdge shows edge-CNN serving throughput is won
by matching tiling/partitioning to the device.  This module does the same
for a :class:`~repro.deploy.DeploymentConfig`, automatically:

1. :func:`default_candidates` spans the search space — execution backend
   (registry-driven, ``repro.core.backends``) x ``tile_h`` x micro-batch
   size — for the manifest's serving shape.
2. :func:`prune_candidates` cuts the grid with a cost model derived from
   the :class:`~repro.core.passplan.PassPlan` (VMEM residency, FLOPs,
   moved bytes, launch/grid-step overheads), so only a handful of
   plausible candidates are ever measured.
3. :func:`tune` benchmarks the survivors through the REAL pipeline
   (``Deployment.build`` + ``encoder.apply``) and returns the winning
   :class:`TunedPlan`, stamped with the execution mode and host it was
   measured on.

The ``TunedPlan`` freezes into the manifest (``DeploymentConfig.tuning``,
JSON round-trip) and ``Deployment.build`` resolves it automatically — so
every entry point (serving t(B) curves, fleet sims, ``rl/train``, all
benchmarks) inherits tuned kernels with zero call-site changes.

Both the timer and the measurement function are injectable, which makes
the tuner deterministic under test stubs and lets the pruning tests drive
it with the cost model itself.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Callable, Iterable, Optional, Sequence

from repro.core.backends import backend_names, get_backend
from repro.core.passplan import DEFAULT_VMEM_LIMIT
from repro.schema import check_version

TUNING_VERSION = 1

# Coarse per-unit costs for the pruning model.  Absolute values are
# irrelevant — pruning only compares candidates against each other — but
# the ratios encode what actually dominates: per-launch dispatch and (in
# interpret mode especially) per-grid-step overhead, not arithmetic.
_FLOP_RATE = 5e9            # sustained f32 FLOPs/s
_BYTES_RATE = 2e9           # HBM<->VMEM bytes/s
_LAUNCH_OVERHEAD_S = 5e-4   # one pallas_call / XLA dispatch
_STEP_OVERHEAD_S = 5e-5     # one grid step (interpret-mode loop iteration)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the search grid: HOW to execute the serving batch."""

    backend: str             # execution-backend name (registry)
    tile_h: int              # fused-kernel output-row tile height
    micro_batch: int         # frames per launch (splits max_batch)


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """The measured winner, frozen into the deployment manifest.

    ``time_s`` is the median launch time at ``micro_batch`` frames;
    ``per_frame_s`` the serving cost per frame at the manifest's
    ``max_batch`` (``ceil(max_batch/micro_batch)`` launches amortised).
    ``mode``/``host`` record WHERE the measurement holds
    (``repro.perfstamp``) so a manifest tuned interpret-on-CPU is not
    mistaken for compiled-TPU truth.  All fields are scalars, keeping
    :class:`~repro.deploy.DeploymentConfig` hashable.
    """

    backend: str
    tile_h: int
    micro_batch: int
    time_s: float = 0.0
    per_frame_s: float = 0.0
    mode: str = "interpret"
    host: str = ""
    searched: int = 0        # candidates actually measured
    pruned: int = 0          # candidates cut by the cost model
    version: int = TUNING_VERSION

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunedPlan":
        d = dict(d)
        version = check_version("TunedPlan tuning block",
                                d.pop("version", TUNING_VERSION),
                                (TUNING_VERSION,))
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown TunedPlan fields: {sorted(unknown)}")
        return cls(version=version, **d)


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def _plan_and_head(config):
    """(plan, vmem_head_plan_or_None) for a config-like object."""
    plan = config.spec.plan(config.in_h, config.in_w)
    head = plan.head(config.head_dim, activation=config.head_act)
    return plan, head


def _fused_head(config, backend) -> bool:
    """Mirror of ``Deployment.build``'s head-fusion decision."""
    return backend.fused_head or (config.head_placement == "fused"
                                  and backend.mode == "fused")


def estimated_cost_s(config, cand: Candidate) -> float:
    """Modelled per-frame serving cost of ``cand`` at ``config.max_batch``.

    Derived entirely from the PassPlan: FLOPs (encoder + projection),
    bytes moved through VMEM, grid-step counts per execution tier, and
    launch dispatch — affine in the quantities the tuner actually trades
    off (launch amortisation vs per-step overhead vs VMEM feasibility).
    """
    backend = get_backend(cand.backend)
    plan, head_plan = _plan_and_head(config)
    micro = max(1, min(cand.micro_batch, config.max_batch))
    n_launch_groups = math.ceil(config.max_batch / micro)

    flops = plan.flops_per_frame + head_plan.flops
    first = plan.layers[0]
    in_bytes = first.padded_in_h * first.padded_in_w * first.c_in_pad * 4
    out_bytes = plan.feature_bytes * 4 + head_plan.out_dim * 4
    per_frame = flops / _FLOP_RATE + (in_bytes + out_bytes) / _BYTES_RATE

    tile_h = max(1, min(cand.tile_h, plan.out_h))
    n_tiles = math.ceil(plan.out_h / tile_h)
    if backend.mode == "xla":
        launches, steps = 1, 0
    elif backend.mode == "per_pass":
        # grid = (batch, out_row, kernel_row) per ShaderPass
        launches = plan.total_passes
        steps = micro * sum(l.out_h * l.kernel * math.ceil(l.c_out / 4)
                            for l in plan.layers)
    elif backend.mode == "grouped":
        # one launch per layer, grid = (batch, out_row, group)
        launches = len(plan.layers)
        steps = micro * sum(l.out_h * math.ceil(l.c_out / 4)
                            for l in plan.layers)
    else:                                  # fused tiers
        launches = 1
        steps = micro * n_tiles
        if backend.streamed:
            # streaming re-fetches each chunk's input block; extra chunks
            # only appear past the VMEM-safe size, modelled as extra
            # launch groups below
            max_safe = plan.max_safe_batch(
                head=head_plan if _fused_head(config, backend) else None,
                tile_h=tile_h, streamed=True)
            if max_safe >= 1 and micro > max_safe:
                launches = math.ceil(micro / max_safe)
    t_launch = (launches * _LAUNCH_OVERHEAD_S + steps * _STEP_OVERHEAD_S
                + micro * per_frame)
    return n_launch_groups * t_launch / config.max_batch


def vmem_feasible(config, cand: Candidate, *,
                  compiled: Optional[bool] = None,
                  vmem_limit: int = DEFAULT_VMEM_LIMIT) -> bool:
    """Can ``cand`` launch at all?  Compiled fused launches must fit the
    VMEM residency budget; streamed backends only need ONE frame to fit;
    interpret / non-fused tiers are unconstrained."""
    backend = get_backend(cand.backend)
    if compiled is None:
        from repro.perfstamp import execution_mode
        compiled = execution_mode(config.interpret) == "compiled"
    if not compiled or backend.mode != "fused":
        return True
    plan, head_plan = _plan_and_head(config)
    head = head_plan if _fused_head(config, backend) else None
    max_safe = plan.max_safe_batch(head=head, tile_h=cand.tile_h,
                                   vmem_limit=vmem_limit,
                                   streamed=backend.streamed)
    if backend.streamed:
        return max_safe >= 1
    return cand.micro_batch <= max_safe


# ---------------------------------------------------------------------------
# Search space
# ---------------------------------------------------------------------------

def default_candidates(config, *,
                       backends: Optional[Sequence[str]] = None,
                       tile_hs: Optional[Sequence[int]] = None,
                       micro_batches: Optional[Sequence[int]] = None
                       ) -> tuple[Candidate, ...]:
    """The registry-driven search grid for one manifest.

    Backends default to every registered execution backend; ``tile_h``
    spans powers of two up to the feature height; micro-batches span
    powers of two up to ``max_batch`` plus ``max_batch`` itself and the
    plan's VMEM-safe size.  The grid is canonically ordered (sorted,
    deduplicated), which is what makes the tuner deterministic.
    """
    plan, head_plan = _plan_and_head(config)
    if backends is None:
        backends = backend_names()
    if tile_hs is None:
        tile_hs = sorted({t for t in (4, 8, 16, plan.out_h)
                          if 1 <= t <= plan.out_h}) or [plan.out_h]
    if micro_batches is None:
        mbs = {1 << i for i in range(config.max_batch.bit_length())
               if 1 << i <= config.max_batch}
        mbs.add(config.max_batch)
        max_safe = plan.max_safe_batch(head=head_plan, tile_h=config.tile_h)
        if 1 <= max_safe <= config.max_batch:
            mbs.add(max_safe)
        micro_batches = sorted(mbs)
    out = []
    for b in backends:
        name = get_backend(b).name
        for t in sorted(set(tile_hs)):
            for m in sorted(set(micro_batches)):
                out.append(Candidate(backend=name, tile_h=t, micro_batch=m))
    # non-fused tiers ignore tile_h — collapse their duplicates
    seen, uniq = set(), []
    for c in out:
        key = (c.backend, c.tile_h if get_backend(c.backend).mode == "fused"
               else 0, c.micro_batch)
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    return tuple(uniq)


def baseline_candidate(config) -> Candidate:
    """The manifest's current (untuned) execution point, with ``tile_h``
    clamped the way the kernel clamps it (so it matches the grid's
    canonical form)."""
    plan, _ = _plan_and_head(config)
    return Candidate(backend=get_backend(config.backend).name,
                     tile_h=max(1, min(config.tile_h, plan.out_h)),
                     micro_batch=config.max_batch)


def prune_candidates(config, candidates: Iterable[Candidate], *,
                     keep_ratio: float = 3.0,
                     compiled: Optional[bool] = None
                     ) -> tuple[tuple[Candidate, ...], int]:
    """(survivors, n_pruned) after VMEM-feasibility + cost-ratio cuts.

    A candidate survives when it can launch (``vmem_feasible``) and its
    modelled cost is within ``keep_ratio`` of the cheapest feasible
    candidate.  The manifest's own baseline point always survives, so
    tuning can never regress below "measure what you already had".
    """
    cands = list(candidates)
    base = baseline_candidate(config)
    feasible = [c for c in cands
                if vmem_feasible(config, c, compiled=compiled)]
    if not feasible:
        raise ValueError(
            "no VMEM-feasible tuning candidate: even a single frame "
            "exceeds the fused-kernel budget — lower in_h/in_w or split "
            "the spec")
    costs = {c: estimated_cost_s(config, c) for c in feasible}
    best = min(costs.values())
    kept = [c for c in feasible if costs[c] <= keep_ratio * best]
    if base not in kept and vmem_feasible(config, base, compiled=compiled):
        kept.append(base)
    return tuple(kept), max(0, len(cands) - len(kept))


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def measure_candidate(config, cand: Candidate, *, iters: int = 5,
                      timer: Callable[[], float] = time.perf_counter,
                      seed: int = 0) -> float:
    """Median wall-clock seconds of ONE encoder launch at
    ``cand.micro_batch`` frames, through the real pipeline
    (``Deployment.build`` -> ``encoder.apply``)."""
    import jax
    import jax.numpy as jnp
    from repro.deploy import Deployment
    cfg = dataclasses.replace(config, backend=cand.backend,
                              tile_h=cand.tile_h, tuning=None,
                              max_batch=max(config.max_batch,
                                            cand.micro_batch))
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(seed))
    x = jax.random.uniform(
        jax.random.PRNGKey(seed + 1),
        (cand.micro_batch, config.in_h, config.in_w,
         config.spec.layers[0].c_in))
    apply = dep.encoder.apply
    jax.block_until_ready(apply(params, x))       # compile / warm caches
    samples = []
    for _ in range(iters):
        t0 = timer()
        jax.block_until_ready(apply(params, x))
        samples.append(timer() - t0)
    return statistics.median(samples)


def _serving_cost(config, cand: Candidate, t_launch: float) -> float:
    """Per-frame cost of serving ``max_batch`` frames in
    ``micro_batch``-sized launches, each costing ``t_launch``."""
    micro = max(1, min(cand.micro_batch, config.max_batch))
    return math.ceil(config.max_batch / micro) * t_launch / config.max_batch


def tune(config, *, candidates: Optional[Sequence[Candidate]] = None,
         iters: int = 5, keep_ratio: float = 3.0,
         timer: Callable[[], float] = time.perf_counter,
         measure: Optional[Callable] = None,
         log: Optional[Callable[[str], None]] = None) -> TunedPlan:
    """Autotune one manifest: prune the grid, measure survivors, freeze
    the winner.

    ``measure(config, cand)`` -> launch seconds is injectable (tests use
    the cost model itself, or a stub timer); the default measures the
    live kernel via :func:`measure_candidate`.  Scoring is per-frame
    serving cost at ``config.max_batch``; ties break toward the
    canonical candidate order, so identical measurements always pick the
    same winner (determinism).
    """
    from repro.perfstamp import execution_mode, host_fingerprint
    if candidates is None:
        candidates = default_candidates(config)
    kept, n_pruned = prune_candidates(config, candidates,
                                      keep_ratio=keep_ratio)
    if measure is None:
        def measure(cfg, cand):
            return measure_candidate(cfg, cand, iters=iters, timer=timer)
    best_c, best_t, best_cost = None, None, float("inf")
    for cand in kept:
        t_launch = measure(config, cand)
        cost = _serving_cost(config, cand, t_launch)
        if log is not None:
            log(f"  {cand.backend:>12} tile_h={cand.tile_h:<3} "
                f"micro={cand.micro_batch:<3} t={t_launch * 1e3:8.3f} ms "
                f"-> {cost * 1e6:9.1f} us/frame")
        if cost < best_cost:
            best_c, best_t, best_cost = cand, t_launch, cost
    assert best_c is not None
    return TunedPlan(backend=best_c.backend, tile_h=best_c.tile_h,
                     micro_batch=best_c.micro_batch, time_s=best_t,
                     per_frame_s=best_cost,
                     mode=execution_mode(config.interpret),
                     host=host_fingerprint(), searched=len(kept),
                     pruned=n_pruned)


def suggest_tuning(config) -> Candidate:
    """Cheapest cost-model candidate WITHOUT measuring — used for
    over-budget diagnostics (``Deployment.build``'s VMEM error reports
    this as the suggested ``tile_h``/micro-batch) and as a starting point
    when a full tune is too expensive."""
    kept, _ = prune_candidates(config, default_candidates(config))
    return min(kept, key=lambda c: estimated_cost_s(config, c))


__all__ = ["Candidate", "TunedPlan", "TUNING_VERSION", "baseline_candidate",
           "default_candidates", "estimated_cost_s", "measure_candidate",
           "prune_candidates", "suggest_tuning", "tune", "vmem_feasible"]
