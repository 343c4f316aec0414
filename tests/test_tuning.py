"""Autotuner + streaming-backend conformance suite.

Covers the ISSUE-6 tentpole: tuner determinism under stubbed
timing/measurement, TunedPlan manifest round-trips (including pre-tuning
manifests), cost-model pruning never excluding the modelled optimum on the
seed spec grid, the ``fused+stream`` parity suite (B in {1, max_safe,
max_safe+1, 4*max_safe} x odd/even X x head on/off), and
``Deployment.build`` pipelining over-budget batches instead of rejecting
them.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import backend_names, get_backend
from repro.core.miniconv import miniconv_apply, miniconv_init, standard_spec
from repro.core.tuning import (Candidate, TunedPlan, baseline_candidate,
                               default_candidates, estimated_cost_s,
                               measure_candidate, prune_candidates,
                               suggest_tuning, tune, vmem_feasible)
from repro.deploy import CONFIG_VERSION, Deployment, DeploymentConfig
from repro.kernels.miniconv_pass import (miniconv_encoder,
                                         miniconv_encoder_stream)


def small_config(**overrides):
    kw = dict(k=4, c_in=12, h=12, max_batch=4)
    kw.update(overrides)
    return DeploymentConfig.standard(**kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_fused_stream_backend_registered():
    b = get_backend("fused+stream")
    assert b.mode == "fused" and b.streamed and b.fused_head
    assert get_backend("fused_stream") is b          # alias
    assert "fused+stream" in backend_names()
    # the established backends are not streamed
    for name in ("xla", "reference", "grouped", "fused", "fused+head"):
        assert not get_backend(name).streamed


# ---------------------------------------------------------------------------
# TunedPlan serialisation
# ---------------------------------------------------------------------------

def make_tuned(**overrides):
    kw = dict(backend="fused+head", tile_h=4, micro_batch=3, time_s=1.5e-3,
              per_frame_s=4e-4, mode="interpret", host="linux/x86_64/cpu/2",
              searched=7, pruned=11)
    kw.update(overrides)
    return TunedPlan(**kw)


def test_tunedplan_roundtrip():
    tp = make_tuned()
    assert TunedPlan.from_dict(tp.to_dict()) == tp


def test_tunedplan_rejects_unknown_fields_and_versions():
    tp = make_tuned()
    with pytest.raises(ValueError, match="unknown TunedPlan"):
        TunedPlan.from_dict({**tp.to_dict(), "wat": 1})
    with pytest.raises(ValueError, match="version"):
        TunedPlan.from_dict({**tp.to_dict(), "version": 99})


def test_manifest_roundtrip_with_tuning():
    cfg = dataclasses.replace(small_config(), tuning=make_tuned())
    d = cfg.to_dict()
    assert d["version"] == CONFIG_VERSION
    assert d["tuning"]["backend"] == "fused+head"
    cfg2 = DeploymentConfig.from_json(cfg.to_json())
    assert cfg2 == cfg and cfg2.tuning == cfg.tuning


def test_pre_tuning_manifest_defaults_cleanly():
    """A version-1 manifest (no tuning key) loads with tuning=None."""
    d = small_config().to_dict()
    del d["tuning"]
    d["version"] = 1
    cfg = DeploymentConfig.from_dict(d)
    assert cfg.tuning is None
    assert Deployment.build(cfg).backend.name == cfg.backend


def test_tuning_validated():
    cfg = dataclasses.replace(small_config(),
                              tuning=make_tuned(micro_batch=0))
    with pytest.raises(ValueError, match="micro_batch"):
        cfg.validate()
    with pytest.raises(ValueError, match="backend"):
        dataclasses.replace(small_config(),
                            tuning=make_tuned(backend="nope")).validate()


# ---------------------------------------------------------------------------
# Build honours the frozen TunedPlan
# ---------------------------------------------------------------------------

def test_build_resolves_tuning():
    cfg = dataclasses.replace(small_config(backend="fused"),
                              tuning=make_tuned(backend="fused+head",
                                                tile_h=2))
    dep = Deployment.build(cfg)
    assert dep.backend.name == "fused+head"
    assert dep.tile_h == 2
    assert any("tuning" in line for line in dep.build_log)
    # untouched config still resolves its own backend
    dep0 = Deployment.build(small_config(backend="fused"))
    assert dep0.backend.name == "fused" and dep0.build_log == ()


def test_tuned_streamed_backend_matches_fused(seed=0):
    """fused+stream via a frozen TunedPlan == fused+head, bitwise, at a
    batch divisible by the tuned micro-batch."""
    base = small_config(backend="fused+head", head_placement="fused")
    tuned = dataclasses.replace(
        base, tuning=make_tuned(backend="fused+stream", tile_h=2,
                                micro_batch=3))
    dep_f = Deployment.build(base)
    dep_s = Deployment.build(tuned)
    assert dep_s.stream_chunk == 3
    params = dep_f.init(jax.random.PRNGKey(seed))
    obs = jax.random.uniform(jax.random.PRNGKey(seed + 1), (12, 12, 12, 12))
    np.testing.assert_array_equal(dep_f.encoder.apply(params, obs),
                                  dep_s.encoder.apply(params, obs))


# ---------------------------------------------------------------------------
# Pruning / cost model
# ---------------------------------------------------------------------------

def test_pruning_never_excludes_modelled_optimum_on_seed_grid():
    """On the seed spec grid (standard k=4 c_in=12 at the paper's X=84
    and smaller), the candidate the cost model itself ranks best is never
    pruned — so measuring the pruned grid finds the modelled optimum."""
    for h, mb in ((12, 4), (48, 4), (84, 8)):
        cfg = DeploymentConfig.standard(k=4, c_in=12, h=h, max_batch=mb)
        cands = default_candidates(cfg)
        kept, n_pruned = prune_candidates(cfg, cands)
        feasible = [c for c in cands if vmem_feasible(cfg, c)]
        opt = min(feasible, key=lambda c: estimated_cost_s(cfg, c))
        assert opt in kept, (h, opt)
        assert baseline_candidate(cfg) in kept
        assert n_pruned > 0, "cost model pruned nothing"


def test_pruning_drops_vmem_infeasible_compiled_candidates():
    cfg = small_config(interpret=False)
    plan = cfg.spec.plan(cfg.in_h, cfg.in_w)
    safe = plan.max_safe_batch(tile_h=2)
    over = Candidate(backend="fused", tile_h=2, micro_batch=safe + 1)
    assert not vmem_feasible(cfg, over, compiled=True)
    # streamed backend only needs ONE (double-buffered) frame to fit
    assert plan.max_safe_batch(tile_h=2, streamed=True) >= 1
    streamed = Candidate(backend="fused+stream", tile_h=2,
                         micro_batch=safe + 1)
    assert vmem_feasible(cfg, streamed, compiled=True)
    kept, _ = prune_candidates(cfg, [over, streamed,
                                     baseline_candidate(cfg)],
                               compiled=True)
    assert over not in kept and streamed in kept


def test_suggest_tuning_is_feasible_and_deterministic():
    cfg = small_config()
    s1, s2 = suggest_tuning(cfg), suggest_tuning(cfg)
    assert s1 == s2
    assert vmem_feasible(cfg, s1)
    assert s1.micro_batch <= cfg.max_batch


# ---------------------------------------------------------------------------
# Tuner determinism
# ---------------------------------------------------------------------------

def test_tune_deterministic_under_measure_stub():
    cfg = small_config()
    stub = lambda c, cand: estimated_cost_s(c, cand)
    t1 = tune(cfg, measure=stub)
    t2 = tune(cfg, measure=stub)
    assert t1 == t2
    assert t1.searched > 0 and t1.pruned > 0
    assert t1.mode == "interpret"
    assert vmem_feasible(cfg, Candidate(t1.backend, t1.tile_h,
                                        t1.micro_batch))


def test_tune_deterministic_under_timer_stub():
    """With a fixed fake timer, the REAL measurement path (builds the
    deployment, runs the kernel) returns identical medians, so two tunes
    pick the identical winner."""
    cfg = small_config(max_batch=2)
    cands = [Candidate("xla", 2, 2), Candidate("fused", 2, 2),
             Candidate("fused+head", 2, 2)]

    def make_timer():
        t = itertools.count()
        return lambda: float(next(t))

    t1 = tune(cfg, candidates=cands, iters=3, timer=make_timer())
    t2 = tune(cfg, candidates=cands, iters=3, timer=make_timer())
    assert t1 == t2
    assert t1.backend in {c.backend for c in cands}


def test_measure_candidate_runs_live_kernel():
    cfg = small_config(max_batch=2)
    t = measure_candidate(cfg, Candidate("fused", 2, 2), iters=2)
    assert t > 0.0


# ---------------------------------------------------------------------------
# Streaming parity suite
# ---------------------------------------------------------------------------

def _stream_fixture(x_size, with_head, seed=0):
    spec = standard_spec()
    params = miniconv_init(jax.random.PRNGKey(seed), spec)
    plan = spec.plan(x_size)
    ws = [params[f"layer{i}"]["kernel"] for i in range(len(spec.layers))]
    bs = [params[f"layer{i}"]["bias"] for i in range(len(spec.layers))]
    hw = hb = None
    if with_head:
        hw = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (plan.flat_features, 20)) * 0.05
        hb = jax.random.normal(jax.random.PRNGKey(seed + 2), (20,)) * 0.05
    return plan, ws, bs, hw, hb


def _assert_pair_equal(got, want):
    if isinstance(want, tuple):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("x_size", [11, 12])        # odd / even X
@pytest.mark.parametrize("with_head", [False, True])
def test_stream_parity_across_vmem_boundary(x_size, with_head):
    """B in {1, max_safe, max_safe+1, 4*max_safe} under a synthetic VMEM
    budget: the multi-launch path is bitwise-equal to chunk-by-chunk
    fused calls, and at whole-chunk batches the pipelined grid is bitwise
    equal to both."""
    plan, ws, bs, hw, hb = _stream_fixture(x_size, with_head)
    head = plan.head(20) if with_head else None
    # synthetic budget: exactly 3 frames fit -> max_safe = 3
    limit = plan.vmem_bytes(3, head=head)
    max_safe = plan.max_safe_batch(head=head, vmem_limit=limit)
    assert max_safe == 3

    def fused(xb):
        return miniconv_encoder(xb, ws, bs, plan, head_w=hw, head_b=hb)

    def chunked(xb):
        outs = [fused(xb[i:i + max_safe])
                for i in range(0, xb.shape[0], max_safe)]
        if with_head:
            return (jnp.concatenate([o[0] for o in outs]),
                    jnp.concatenate([o[1] for o in outs]))
        return jnp.concatenate(outs)

    for b in (1, max_safe, max_safe + 1, 4 * max_safe):
        x = jax.random.uniform(jax.random.PRNGKey(b),
                               (b, x_size, x_size, 12))
        multi = miniconv_encoder_stream(x, ws, bs, plan, chunk_b=max_safe,
                                        head_w=hw, head_b=hb,
                                        pipelined=False)
        _assert_pair_equal(multi, chunked(x))
        if b % max_safe == 0:
            pipe = miniconv_encoder_stream(x, ws, bs, plan,
                                           chunk_b=max_safe, head_w=hw,
                                           head_b=hb, pipelined=True)
            _assert_pair_equal(pipe, chunked(x))
            _assert_pair_equal(pipe, multi)


def test_stream_pipelined_matches_whole_batch_launch():
    """The chunk-grid pipelined kernel is bitwise-equal to the single
    whole-batch fused launch, ragged remainder included."""
    plan, ws, bs, hw, hb = _stream_fixture(12, True)
    x = jax.random.uniform(jax.random.PRNGKey(3), (13, 12, 12, 12))
    whole = miniconv_encoder(x, ws, bs, plan, head_w=hw, head_b=hb)
    pipe = miniconv_encoder_stream(x, ws, bs, plan, chunk_b=3, head_w=hw,
                                   head_b=hb, pipelined=True)
    _assert_pair_equal(pipe, whole)


def test_stream_chunk_ge_batch_short_circuits():
    plan, ws, bs, hw, hb = _stream_fixture(12, False)
    x = jax.random.uniform(jax.random.PRNGKey(4), (2, 12, 12, 12))
    out = miniconv_encoder_stream(x, ws, bs, plan, chunk_b=8)
    np.testing.assert_array_equal(out, miniconv_encoder(x, ws, bs, plan))
    with pytest.raises(ValueError, match="chunk_b"):
        miniconv_encoder_stream(x, ws, bs, plan, chunk_b=0)


def test_miniconv_apply_stream_chunk_param():
    """miniconv_apply's stream_chunk splits any fused call; the
    fused+stream backend picks the plan's safe chunk automatically."""
    spec = standard_spec()
    params = miniconv_init(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (7, 12, 12, 12))
    ref = miniconv_apply(params, spec, x, use_kernel="fused")
    np.testing.assert_array_equal(
        miniconv_apply(params, spec, x, use_kernel="fused", stream_chunk=7),
        ref)
    np.testing.assert_array_equal(
        miniconv_apply(params, spec, x, use_kernel="fused+stream"), ref)


# ---------------------------------------------------------------------------
# Deployment pipelines over-budget batches
# ---------------------------------------------------------------------------

def test_build_pipelines_over_budget_compiled_batch():
    """The paper-scale serving config that USED to be rejected (X=84
    fused+head, max_batch=128 > max_safe_batch) now builds, streaming the
    launch in VMEM-safe chunks, and logs the decision with the computed
    max_safe_batch and the tuner's suggestion."""
    cfg = DeploymentConfig.standard(k=4, c_in=12, h=84, backend="fused+head",
                                    interpret=False, max_batch=128)
    dep = Deployment.build(cfg)
    assert 1 <= dep.stream_chunk <= dep.max_safe_batch < 128
    note = " ".join(dep.build_log)
    assert "pipelining" in note and "max_safe_batch" in note
    assert "tile_h" in note and "micro_batch" in note   # tuner suggestion


def test_build_still_rejects_single_frame_over_vmem():
    """Pipelining cannot rescue a frame that exceeds VMEM alone: build
    still fails, reporting max_safe_batch=0 and the tuner's suggestion."""
    cfg = DeploymentConfig.standard(k=4, c_in=12, h=2048, backend="fused",
                                    interpret=False, max_batch=64)
    with pytest.raises(ValueError, match="VMEM") as ei:
        Deployment.build(cfg)
    msg = str(ei.value)
    assert "max_safe_batch=0" in msg and "suggests" in msg


def test_interpret_build_does_not_stream_plain_fused():
    """Interpret-mode plain-fused builds keep the single-launch path (no
    VMEM constraint to pipeline around)."""
    dep = Deployment.build(DeploymentConfig.standard(
        k=4, c_in=12, h=84, backend="fused+head", max_batch=64,
        interpret=True))
    assert dep.stream_chunk is None


def test_streamed_deployment_serves_past_max_safe_batch():
    """End-to-end: a fused+stream deployment encodes B = 4x its chunk in
    one call, matching the fused+head deployment bitwise."""
    base = small_config(backend="fused+head", head_placement="fused",
                        max_batch=12)
    tuned = dataclasses.replace(
        base, tuning=make_tuned(backend="fused+stream", tile_h=2,
                                micro_batch=3))
    dep_s = Deployment.build(tuned)
    dep_f = Deployment.build(base)
    params = dep_f.init(jax.random.PRNGKey(0))
    obs = jax.random.uniform(jax.random.PRNGKey(1),
                             (4 * dep_s.stream_chunk, 12, 12, 12))
    np.testing.assert_array_equal(dep_f.encoder.apply(params, obs),
                                  dep_s.encoder.apply(params, obs))
