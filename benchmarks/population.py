"""Population-engine throughput: P members in ONE jitted program vs the
P=1-run-P-times sequential baseline, plus the correctness gates that make
the number trustworthy.

What is measured
----------------
Aggregate env-steps/sec (summed over members, end-to-end wall including
XLA compile) for P in {1, 4, 16} population runs against running the
single-run engine P times from scratch — each sequential run rebuilds its
engine and recompiles, exactly like ``benchmarks/learning.py`` runs its
conditions today.  That is the cost the population engine removes: the
population compiles its chunk ONCE for all P members (``lax.map`` lanes),
so on CPU hosts — where compile dominates smoke-scale runs — aggregate
throughput scales with P.  Steady-state (cache-warm) numbers are reported
alongside for honesty; rows are stamped via ``repro.perfstamp`` and
marked ``regime: "collection"`` (warmup-only budget, as in the PR 5
off-policy comparison — both sides run the identical random-action
program).

``--smoke`` additionally gates (CI):
* P=16 aggregate collection throughput >= 3x the P=1 sequential baseline;
* member 0 of a P=2 population (with gradient updates, tiny config) is
  BITWISE-equal to ``repro.rl.train.train`` at the same seed;
* the eval protocol is deterministic: bitwise replay at a fixed seed and
  a finite ``final_100_mean`` on a shortened episode window.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import perfstamp
from repro.envs import make_pixel_env
from repro.rl.agent import make_agent
from repro.rl.ddpg import DDPGConfig
from repro.rl.population import (PopulationSpec, evaluate, final_100_mean,
                                 make_population_engine, split_member_keys,
                                 train_population)
from repro.rl.rollout import make_engine
from repro.rl.train import train, _pipeline_encoder

TASK = "pendulum"
ENCODER = "miniconv4"
BENCH_PATH = "BENCH_population.json"
DEFAULT_POPS = (1, 4, 16)


def _collection_cfg(total_steps: int, n_envs: int = 2) -> DDPGConfig:
    """learning_starts above the budget -> the whole run is random-action
    collection (PR 5's regime): population and sequential sides execute
    the identical warmup program, so the comparison isolates compile
    amortisation + launch overhead from learning compute."""
    return DDPGConfig(n_envs=n_envs, learning_starts=total_steps + n_envs,
                      buffer_size=max(total_steps * n_envs, n_envs),
                      batch_size=n_envs)


def measure_single(total_steps: int, *, seed: int = 0,
                   n_envs: int = 2) -> dict:
    """One FROM-SCRATCH single-run engine pass (fresh build -> fresh XLA
    compile, like every ``benchmarks/learning.py`` condition), plus a
    cache-warm second pass for the steady-state number."""
    env = make_pixel_env(TASK, train=True)
    encoder = _pipeline_encoder(ENCODER, env.obs_shape[-1])
    cfg = _collection_cfg(total_steps, n_envs)
    agent = make_agent("ddpg", encoder, env.action_dim, cfg=cfg)
    engine = make_engine(env, agent, total_steps)
    phases = engine.plan()

    def one_pass(key):
        k_init, key = jax.random.split(key)
        carry = engine.init(k_init)
        jax.block_until_ready(carry.obs)    # init outside the window
        t0 = time.perf_counter()
        steps = 0
        for phase in phases:
            key, sub = jax.random.split(key)
            carry, rewards, dones, _ = engine.run(carry, sub, phase)
            steps += int(np.asarray(rewards).size)
        jax.block_until_ready(dones)
        return steps, time.perf_counter() - t0

    steps, wall = one_pass(jax.random.PRNGKey(seed))       # compiles
    _, steady = one_pass(jax.random.PRNGKey(seed + 1))     # cache-warm
    return {"steps": steps, "wall_s": wall, "steady_s": steady}


def measure_population(P: int, total_steps: int, *, seed: int = 0,
                      n_envs: int = 2) -> dict:
    """One from-scratch population pass (P members, one compile) plus a
    cache-warm second pass."""
    env = make_pixel_env(TASK, train=True)
    encoder = _pipeline_encoder(ENCODER, env.obs_shape[-1])
    cfg = _collection_cfg(total_steps, n_envs)
    engine = make_population_engine(env, "ddpg", encoder, env.action_dim,
                                    cfg, {}, P, total_steps)
    phases = engine.plan()

    def one_pass(seed0):
        keys = jnp.stack([jax.random.PRNGKey(seed0 + i) for i in range(P)])
        k_init, keys = split_member_keys(keys)
        carry = engine.init(k_init)
        jax.block_until_ready(carry.obs)    # init outside the window
        t0 = time.perf_counter()
        steps = 0
        for phase in phases:
            keys, subs = split_member_keys(keys)
            carry, rewards, dones, _ = engine.run(carry, subs, phase)
            steps += int(np.asarray(rewards).size)   # all P members
        jax.block_until_ready(dones)
        return steps, time.perf_counter() - t0

    steps, wall = one_pass(seed)             # compiles (once, for all P)
    _, steady = one_pass(seed + P)           # cache-warm
    return {"steps": steps, "wall_s": wall, "steady_s": steady}


def run_grid(pops=DEFAULT_POPS, *, total_steps: int = 64, seed: int = 0,
             n_envs: int = 2) -> list[dict]:
    """Rows: per P, population aggregate throughput vs the sequential
    baseline P x (one from-scratch single run)."""
    base = measure_single(total_steps, seed=seed, n_envs=n_envs)
    print(f"  baseline single run: {base['steps']} steps in "
          f"{base['wall_s']:.1f}s (steady pass {base['steady_s']:.2f}s)")
    rows = []
    for P in pops:
        pop = measure_population(P, total_steps, seed=seed, n_envs=n_envs)
        seq_wall = P * base["wall_s"]                # P from-scratch runs
        agg_sps = pop["steps"] / pop["wall_s"]
        seq_sps = (P * base["steps"]) / seq_wall
        row = {"P": P, "task": TASK, "algo": "ddpg", "encoder": ENCODER,
               "regime": "collection", "includes_compile": True,
               "total_steps_per_member": total_steps, "n_envs": n_envs,
               "population_steps": pop["steps"],
               "population_wall_s": pop["wall_s"],
               "population_steady_s": pop["steady_s"],
               "sequential_wall_s": seq_wall,
               "aggregate_steps_per_sec": agg_sps,
               "sequential_steps_per_sec": seq_sps,
               "steady_aggregate_steps_per_sec":
                   pop["steps"] / pop["steady_s"],
               "speedup_vs_sequential": agg_sps / seq_sps}
        rows.append(row)
        print(f"  P={P:<3} population {pop['wall_s']:6.1f}s "
              f"({agg_sps:7.1f} agg steps/s, steady "
              f"{row['steady_aggregate_steps_per_sec']:7.1f}) vs "
              f"sequential {seq_wall:6.1f}s -> "
              f"{row['speedup_vs_sequential']:.1f}x")
    return rows


def check_member0_parity(*, total_steps: int = 32) -> dict:
    """Member 0 of a P=2 population (WITH gradient updates — tiny config
    so the update path is exercised, not just collection) vs a single
    ``train()`` run at the same seed: params and episode returns must be
    bitwise identical."""
    small = {"batch_size": 8, "buffer_size": 64, "learning_starts": 8,
             "n_envs": 2}
    spec = PopulationSpec(tasks=(TASK,), seeds=(0, 1),
                          total_steps=total_steps, encoder=ENCODER,
                          cfg_overrides=small)
    pop = train_population(spec, eval_episodes=0)
    single = train(TASK, ENCODER, total_steps=total_steps, seed=0,
                   cfg=DDPGConfig(**small))
    m0 = pop.members[0]
    params_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(m0.params),
                        jax.tree.leaves(single.params)))
    returns_equal = (m0.episode_returns == single.episode_returns
                     and m0.truncated_returns == single.truncated_returns)
    row = {"total_steps": total_steps, "n_members": len(pop.members),
           "params_bitwise": bool(params_equal),
           "returns_bitwise": bool(returns_equal),
           "bitwise": bool(params_equal and returns_equal)}
    print(f"  member-0 parity (P=2, with updates): params "
          f"{'BITWISE' if params_equal else 'DIFFER'}, returns "
          f"{'BITWISE' if returns_equal else 'DIFFER'}")
    return row


def check_eval_protocol(*, n_episodes: int = 4, max_steps: int = 40,
                        seed: int = 7) -> dict:
    """The final-100-episode protocol on a shortened window: same seed
    twice must replay bitwise, and the summary metric must be finite."""
    env = make_pixel_env(TASK, train=False)
    encoder = _pipeline_encoder(ENCODER, env.obs_shape[-1])
    agent = make_agent("ddpg", encoder, env.action_dim)
    params = agent.init(jax.random.PRNGKey(0)).params
    r1 = evaluate(agent, params, n_episodes, env=env, seed=seed,
                  max_steps=max_steps)
    r2 = evaluate(agent, params, n_episodes, env=env, seed=seed,
                  max_steps=max_steps)
    row = {"n_episodes": n_episodes, "max_steps": max_steps,
           "final_100_mean": final_100_mean(r1),
           "bitwise_replay": bool(np.array_equal(r1, r2))}
    print(f"  eval protocol: replay "
          f"{'BITWISE' if row['bitwise_replay'] else 'DIFFERS'}, "
          f"final_100_mean={row['final_100_mean']:.1f} "
          f"({n_episodes} episodes x {max_steps} steps)")
    return row


def write_bench(rows, parity, eval_row, *, total_steps: int,
                path: str = BENCH_PATH) -> dict:
    doc = perfstamp.stamp({
        "benchmark": "population",
        "host_detail": {"platform": platform.platform(),
                        "backend": jax.default_backend()},
        "total_steps_per_member": total_steps,
        "lane_mode": "exact",
        "rows": rows,
        "member0_parity": parity,
        "eval_protocol": eval_row,
    }, backend=jax.default_backend())
    Path(path).write_text(json.dumps(doc, indent=2))
    print(f"  wrote {path}")
    return doc


def check_smoke(doc: dict) -> None:
    """CI gate for the population engine (see module docstring)."""
    assert doc["member0_parity"]["bitwise"], \
        "member 0 of the population is not bitwise-equal to the " \
        "single-run engine"
    ev = doc["eval_protocol"]
    assert ev["bitwise_replay"], "eval protocol is not deterministic"
    assert np.isfinite(ev["final_100_mean"]), \
        f"non-finite eval metric: {ev['final_100_mean']}"
    by_p = {r["P"]: r for r in doc["rows"]}
    for r in doc["rows"]:
        assert r["aggregate_steps_per_sec"] > 0, f"P={r['P']}: zero agg"
        if r["P"] > 1:
            assert r["speedup_vs_sequential"] >= 1.0, \
                f"P={r['P']}: population slower than sequential " \
                f"({r['speedup_vs_sequential']:.2f}x)"
    top = max(by_p)
    sp = by_p[top]["speedup_vs_sequential"]
    assert sp >= 3.0, \
        f"P={top} aggregate throughput only {sp:.2f}x sequential (< 3x)"
    print(f"  smoke gate OK: P={top} {sp:.1f}x sequential, member-0 "
          "bitwise, eval deterministic")


def compare_against(doc: dict, against_path: str) -> None:
    """Refuse cross-mode comparisons; report per-P speedup deltas."""
    old = json.loads(Path(against_path).read_text())
    try:
        perfstamp.check_comparable(old, doc, what="population benchmarks")
    except ValueError as e:
        print(f"  --against: {e}")
        sys.exit(2)
    old_by_p = {r["P"]: r for r in old.get("rows", [])}
    for r in doc["rows"]:
        o = old_by_p.get(r["P"])
        if o is None:
            continue
        print(f"  P={r['P']}: speedup {o['speedup_vs_sequential']:.1f}x -> "
              f"{r['speedup_vs_sequential']:.1f}x; agg steps/s "
              f"{o['aggregate_steps_per_sec']:.1f} -> "
              f"{r['aggregate_steps_per_sec']:.1f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=64,
                    help="collection steps per member")
    ap.add_argument("--pops", default=",".join(map(str, DEFAULT_POPS)))
    ap.add_argument("--n-envs", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="gate: >=3x at the largest P, member-0 bitwise "
                         "parity, deterministic eval")
    ap.add_argument("--against", default=None,
                    help="prior BENCH_population.json to diff against "
                         "(refuses cross-mode artifacts)")
    ap.add_argument("--json", default=BENCH_PATH)
    args = ap.parse_args(argv)
    pops = tuple(int(p) for p in args.pops.split(","))

    rows = run_grid(pops, total_steps=args.steps, n_envs=args.n_envs)
    parity = check_member0_parity()
    eval_row = check_eval_protocol()
    doc = write_bench(rows, parity, eval_row, total_steps=args.steps,
                      path=args.json)
    if args.against:
        compare_against(doc, args.against)
    if args.smoke:
        check_smoke(doc)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
