"""Paper Tables 2-4: learning performance per (task, algorithm) with the
three encoder conditions (MiniConv K=4, K=16, Full-CNN) — now with
learning THROUGHPUT (env-steps/sec) per condition, written to
``BENCH_learning.json`` so the perf trajectory tracks training speed too.

The pure-JAX environments are simplified (DESIGN.md §4), so absolute
returns are not comparable to the paper; the benchmark reproduces the
comparison STRUCTURE — within-task Best/Mean/Final per encoder — and the
tooling.  Default is smoke scale; pass ``--full`` for long runs.

Throughput modes
----------------
``--smoke``   one encoder per task (all three algorithms), gated on finite
              Best/Mean/Final and nonzero steps/sec — the CI learning gate.
``--compare`` additionally measures the off-policy engines against the
              pre-refactor per-step Python loop (single env, numpy replay,
              one jitted call per step — reimplemented here as the
              throughput baseline) and reports the speedup.  Both sides
              exclude compile time (steady-state steps/sec).
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
from repro.rl.agent import make_agent
from repro.rl.buffers import ReplayBuffer
from repro.rl.rollout import make_engine
from repro.rl.train import TASK_ALGO, _pipeline_encoder, train

ENCODERS = ("miniconv4", "miniconv16", "full_cnn")
TASKS = ("walker", "hopper", "pendulum")     # PPO / SAC / DDPG per paper
BENCH_PATH = "BENCH_learning.json"


def _smoke_cfgs():
    """Bounded algorithm configs for the CI smoke gate: same algorithms,
    same engines, smaller XLA programs (the default PPO iteration —
    128 steps x 8 envs x 4 epochs — compiles for minutes on CPU hosts).
    learning_starts is pulled below the 256-step smoke budget so the gate
    actually executes interleaved SAC/DDPG gradient updates, not just
    random-action warmup (batch 32 keeps those updates cheap).

    DDPG runs ONE env: pendulum episodes are a fixed 200 steps, so at
    n_envs=4 a 256-step budget is 64 steps per env and every episode is
    truncated (the episodes_completed=0 cell this gate now rejects); one
    env completes a full episode inside the budget."""
    from repro.rl.ddpg import DDPGConfig
    from repro.rl.ppo import PPOConfig
    from repro.rl.sac import SACConfig
    return {"ppo": PPOConfig(n_envs=4, n_steps=32, n_epochs=2,
                             n_minibatches=4),
            "sac": SACConfig(n_envs=4, learning_starts=192, batch_size=32),
            "ddpg": DDPGConfig(n_envs=1, learning_starts=192,
                               batch_size=32)}


def run(*, total_steps: int = 512, tasks=TASKS, encoders=ENCODERS,
        seed: int = 0, verbose: bool = False, cfgs=None):
    rows = []
    for task in tasks:
        for enc in encoders:
            cfg = (cfgs or {}).get(TASK_ALGO[task])
            res = train(task, enc, total_steps=total_steps, seed=seed,
                        verbose=verbose, cfg=cfg)
            rows.append(res)
            s = res.summary()
            steady = s["steady_steps_per_sec"]
            print(f"  {task:<10} {res.algo:<5} {enc:<11} "
                  f"best={res.best:8.1f} final={res.final:8.1f} "
                  f"mean={res.mean:8.1f} episodes={s['episodes']} "
                  f"({s['episodes_truncated']} truncated) "
                  f"steps/s={res.steps_per_sec:7.1f} "
                  f"compile_s={res.compile_s:6.1f} "
                  f"steady/s={steady if steady is None else round(steady, 1)}")
    return rows


# ---------------------------------------------------------------------------
# Throughput: compiled engine (steady state) vs the legacy per-step loop
# ---------------------------------------------------------------------------

def measure_engine_throughput(task: str, encoder_name: str, *,
                              total_steps: int, seed: int = 0,
                              n_envs=None) -> float:
    """Steady-state env-steps/sec of the compiled engine.

    Runs the training plan once to compile every chunk shape, then
    re-initialises and times a second, cache-warm pass — the number a
    long run converges to (compile cost amortises away at paper scale).
    """
    algo = TASK_ALGO[task]
    from repro.envs import make_pixel_env
    env = make_pixel_env(task, train=True)
    encoder = _pipeline_encoder(encoder_name, env.obs_shape[-1])
    agent = make_agent(algo, encoder, env.action_dim, n_envs=n_envs)
    engine = make_engine(env, agent, total_steps)
    phases = engine.plan()

    def one_pass(key):
        # init (params, env resets, ring allocation) happens OUTSIDE the
        # timed window — the legacy baseline's timer also starts after
        # its setup, so the two sides measure the same thing: the loop
        carry = engine.init(key)
        jax.block_until_ready(carry.obs)
        t0 = time.perf_counter()
        steps = 0
        for phase in phases:
            key, sub = jax.random.split(key)
            carry, rewards, dones, _ = engine.run(carry, sub, phase)
            steps += int(np.asarray(rewards).size)
        jax.block_until_ready(dones)
        return steps / (time.perf_counter() - t0)

    one_pass(jax.random.PRNGKey(seed))              # compile pass
    return one_pass(jax.random.PRNGKey(seed + 1))   # timed, cache-warm


def measure_legacy_throughput(task: str, encoder_name: str, *,
                              total_steps: int, seed: int = 0) -> float:
    """env-steps/sec of the PRE-REFACTOR off-policy loop (the baseline).

    Faithful to the seed trainer: ONE env, one jitted env-step and one
    jitted act call per step, host-side numpy replay buffer, a fresh
    ``np.random.default_rng(seed + t)`` per warmup step, and one gradient
    update per step once past ``learning_starts``.  Compile time is
    excluded (every jitted piece is warmed before the timed loop) so the
    comparison against the engine is steady-state vs steady-state.
    """
    algo = TASK_ALGO[task]
    if algo == "ppo":
        raise ValueError("legacy baseline is the OFF-policy per-step loop")
    from repro.envs import make_pixel_env
    env = make_pixel_env(task, train=True)
    encoder = _pipeline_encoder(encoder_name, env.obs_shape[-1])
    agent = make_agent(algo, encoder, env.action_dim)
    cfg = agent.cfg

    state = agent.init(jax.random.PRNGKey(seed))
    buf = ReplayBuffer(cfg.buffer_size, env.obs_shape, env.action_dim, seed)
    reset_jit = jax.jit(env.reset)
    step_jit = jax.jit(env.step)
    act_jit = jax.jit(agent.act)

    def update_step(state, batch, key):
        state, m = agent.update(state, batch, key)
        return agent.target_update(state), m
    update_jit = jax.jit(update_step)

    key = jax.random.PRNGKey(seed + 1)
    env_state, obs = reset_jit(jax.random.PRNGKey(seed + 2))

    # warm every jitted piece so the timed loop is steady-state
    a, _ = act_jit(state.params, obs[None], key)
    s2 = step_jit(env_state, a[0])
    buf.add_batch(np.asarray(obs)[None], np.asarray(a), np.zeros(1, np.float32),
                  np.asarray(obs)[None], np.zeros(1, bool))
    if total_steps > cfg.learning_starts:
        batch = jax.tree.map(jnp.asarray, buf.sample(cfg.batch_size))
        jax.block_until_ready(update_jit(state, batch, key)[0])
    jax.block_until_ready(s2)
    buf = ReplayBuffer(cfg.buffer_size, env.obs_shape, env.action_dim, seed)

    t0 = time.perf_counter()
    for t in range(total_steps):
        key, sub = jax.random.split(key)
        if t < cfg.learning_starts:
            action = jnp.asarray(np.random.default_rng(seed + t).uniform(
                -1, 1, env.action_dim).astype(np.float32))
        else:
            action, _ = act_jit(state.params, obs[None], sub)
            action = action[0]
        env_state, next_obs, reward, done = step_jit(env_state, action)
        buf.add_batch(np.asarray(obs)[None], np.asarray(action)[None],
                      np.asarray(reward)[None], np.asarray(next_obs)[None],
                      np.asarray(done)[None])
        obs = next_obs
        if t >= cfg.learning_starts and len(buf) >= cfg.batch_size:
            key, ku = jax.random.split(key)
            batch = jax.tree.map(jnp.asarray, buf.sample(cfg.batch_size))
            state, _ = update_jit(state, batch, ku)
    jax.block_until_ready(obs)
    return total_steps / (time.perf_counter() - t0)


def compare_offpolicy(task: str = "pendulum", encoder: str = "miniconv4", *,
                      total_steps: int = 256, seed: int = 0,
                      n_envs: int = 8, reps: int = 3) -> dict:
    """Engine (vectorised, compiled) vs the legacy loop (single env — it
    HAS no n_envs; that asymmetry is the point of the refactor).

    Measured in the COLLECTION regime (total_steps below learning_starts,
    so neither side runs gradient updates): the update math is identical
    on both sides, so collection isolates exactly what the refactor
    changed — per-step host dispatch, host RNG construction, numpy replay
    traffic — from compute the two loops share.  The JSON row carries
    ``regime: "collection"`` to keep the number honest.

    The two measurements interleave ``reps`` times and the BEST of each
    side is compared (timeit-style: min time == max sustained throughput),
    so throttling windows on a shared host bias neither side.
    """
    engine, legacy = [], []
    for _ in range(reps):
        engine.append(measure_engine_throughput(
            task, encoder, total_steps=total_steps, seed=seed,
            n_envs=n_envs))
        legacy.append(measure_legacy_throughput(
            task, encoder, total_steps=total_steps, seed=seed))
    engine_sps = float(np.max(engine))
    legacy_sps = float(np.max(legacy))
    row = {"task": task, "algo": TASK_ALGO[task], "encoder": encoder,
           "total_steps": total_steps, "n_envs": n_envs,
           "regime": "collection",
           "engine_steps_per_sec": engine_sps,
           "legacy_steps_per_sec": legacy_sps,
           "engine_reps": engine, "legacy_reps": legacy,
           "speedup": engine_sps / legacy_sps}
    print(f"  off-policy COLLECTION throughput [{task}/{encoder}]: "
          f"engine {engine_sps:.1f} (n_envs={n_envs}) vs legacy per-step "
          f"loop {legacy_sps:.1f} env-steps/s -> {row['speedup']:.1f}x")
    return row


def write_bench(rows, *, total_steps: int, compare_row=None,
                path: str = BENCH_PATH) -> dict:
    doc = perfstamp.stamp({
        "benchmark": "learning",
        "host_detail": {"platform": platform.platform(),
                        "backend": jax.default_backend()},
        "total_steps": total_steps,
        "conditions": [r.summary() | {"wall_time_s": r.wall_time_s}
                       for r in rows],
    }, backend=jax.default_backend())
    if compare_row is not None:
        doc["offpolicy_throughput"] = compare_row
    Path(path).write_text(json.dumps(doc, indent=2))
    print(f"  wrote {path}")
    return doc


def check_smoke(doc: dict) -> None:
    """CI gate: every condition finite with nonzero throughput, and at
    least one COMPLETED episode per condition — Best/Mean/Final must be
    real episodic statistics, not truncated-partial fallbacks."""
    for c in doc["conditions"]:
        name = f"{c['task']}/{c['encoder']}"
        for k in ("best", "final", "mean"):
            assert np.isfinite(c[k]), f"{name}: non-finite {k}={c[k]}"
        assert c["episodes"] >= 1, f"{name}: no episodes recorded"
        assert c["episodes_completed"] >= 1, \
            f"{name}: 0 completed episodes — stats fall back to " \
            "truncated partials (bound episode length or raise the budget)"
        assert c["steps_per_sec"] > 0, f"{name}: zero throughput"
        assert np.isfinite(c["compile_s"]) and c["compile_s"] >= 0, \
            f"{name}: bad compile_s={c['compile_s']}"
        steady = c["steady_steps_per_sec"]
        assert steady is None or steady > 0, \
            f"{name}: bad steady_steps_per_sec={steady}"
    thr = doc.get("offpolicy_throughput")
    if thr is not None:
        assert thr["engine_steps_per_sec"] > 0 \
            and thr["legacy_steps_per_sec"] > 0, "zero throughput measured"
    print(f"  smoke gate OK: {len(doc['conditions'])} conditions finite, "
          f"steps/sec > 0")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=512)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (hours on CPU)")
    ap.add_argument("--tasks", default=",".join(TASKS))
    ap.add_argument("--encoders", default=",".join(ENCODERS))
    ap.add_argument("--smoke", action="store_true",
                    help="one encoder per task (all three algorithms) and "
                         "gate on finite returns + nonzero steps/sec")
    ap.add_argument("--compare", action="store_true",
                    help="also measure off-policy engine vs the legacy "
                         "per-step loop (steady-state env-steps/sec)")
    ap.add_argument("--json", default=BENCH_PATH)
    args = ap.parse_args(argv)
    steps = 200_000 if args.full else args.steps
    encoders = ("miniconv4",) if args.smoke else \
        tuple(args.encoders.split(","))
    rows = run(total_steps=steps, tasks=args.tasks.split(","),
               encoders=encoders, cfgs=_smoke_cfgs() if args.smoke else None)
    compare_row = None
    if args.compare:
        compare_row = compare_offpolicy(total_steps=min(steps, 256))
    doc = write_bench(rows, total_steps=steps, compare_row=compare_row,
                      path=args.json)
    if args.smoke:
        check_smoke(doc)
    print("task,algo,encoder,best,final,mean,episodes,steps_per_sec")
    for r in rows:
        s = r.summary()
        print(f"{r.task},{r.algo},{r.encoder},{r.best:.1f},{r.final:.1f},"
              f"{r.mean:.1f},{s['episodes']},{r.steps_per_sec:.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
