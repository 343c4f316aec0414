"""Path-based sharding rules: FSDP ("data") + Megatron TP ("model").

Model code is mesh-agnostic; these rules attach a PartitionSpec to every
parameter / optimizer-state / cache leaf by matching its pytree path and
shape.  The engine is *divisibility-greedy*: each dimension lists candidate
mesh-axis groups in preference order and gets the first group that (a)
divides the dimension and (b) is not already used by another dimension of
the same leaf.  Architectures whose dimensions don't divide the mesh
(e.g. qwen2-moe's 60 experts, mamba2's 50280 vocab) degrade gracefully to
the next candidate or replication instead of failing to lower.

Scheme (single-pod ("data", "model") and multi-pod ("pod", "data", "model")):

* batch            -> ("pod", "data")      (DP across pods and data axis)
* parameters       -> FSDP over "data" on one dim, TP over "model" on the
                      other; the "pod" axis intentionally does NOT shard
                      parameters, so FSDP all-gathers stay on intra-pod ICI
                      and only gradient all-reduce crosses the slow DCN —
                      the paper's principle (small tensors on the slow link)
                      applied to training.
* KV caches        -> batch over ("pod","data"), kv-heads (or head_dim)
                      over "model"; long_500k (batch=1) shards the sequence
                      dimension over "data" instead.
"""
from __future__ import annotations

import fnmatch
import re
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.module import tree_paths

Axes = tuple[str, ...]            # one axis group, e.g. ("pod", "data")
DimPrefs = Sequence[Axes]         # candidates for one dim, in pref. order
Rule = Sequence[DimPrefs]         # one entry per *logical* dim of the leaf

# ---------------------------------------------------------------------------
# Parameter rules, matched right-to-left on the leaf path.  Leaves with more
# dims than the rule (scan-stacked layers, stacked experts) get leading None.
# ---------------------------------------------------------------------------

DATA = (("data",),)
MODEL = (("model",),)
NONE: DimPrefs = ()

PARAM_RULES: list[tuple[str, Rule]] = [
    # embeddings: vocab TP for the logits matmul, d_model FSDP
    ("*embed/embedding", (MODEL, DATA)),
    ("*dec_pos/embedding", (NONE, DATA)),
    ("*lm_head/kernel", (DATA, MODEL)),
    # attention
    ("*/wq/kernel", (DATA, MODEL)),
    ("*/wk/kernel", (DATA, MODEL)),
    ("*/wv/kernel", (DATA, MODEL)),
    ("*/wo/kernel", (MODEL, DATA)),
    ("*/wq/bias", (MODEL,)),
    ("*/wk/bias", (MODEL,)),
    ("*/wv/bias", (MODEL,)),
    # moe (BEFORE the dense-mlp rules: first match wins and the generic
    # "*/gate/kernel" would shadow the expert paths):
    # experts (E, D, F) — default: expert dim FSDP over "data" when E
    # divides, expert FFN width TP over "model".  param_mode="ep_model"
    # (used with moe_expert_parallel for MoE *training*, §Perf A5) flips
    # the expert dim to "model" so each model shard owns E/16 experts and
    # the dispatch einsums compute expert slices locally; left as the
    # default it regresses MoE *decode* (per-token expert-weight motion).
    ("*/experts/gate/kernel", (DATA, DATA, MODEL)),
    ("*/experts/up/kernel", (DATA, DATA, MODEL)),
    ("*/experts/down/kernel", (DATA, MODEL, DATA)),
    ("*/router/kernel", (NONE, NONE)),
    # dense mlp (also matches the fused shared-expert SwiGLU)
    ("*/gate/kernel", (DATA, MODEL)),
    ("*/up/kernel", (DATA, MODEL)),
    ("*/down/kernel", (MODEL, DATA)),
    # ssm
    ("*/ssm/in_proj/kernel", (DATA, MODEL)),
    ("*/ssm/out_proj/kernel", (MODEL, DATA)),
    # rg-lru
    ("*/rglru/in_x/kernel", (DATA, MODEL)),
    ("*/rglru/in_gate/kernel", (DATA, MODEL)),
    ("*/rglru/w_a/kernel", (DATA, MODEL)),
    ("*/rglru/w_i/kernel", (DATA, MODEL)),
    ("*/rglru/out/kernel", (MODEL, DATA)),
]


def _choose(shape: Sequence[int], rule: Rule, mesh: Mesh) -> P:
    """Greedy divisibility-checked assignment of axis groups to dims."""
    extra = len(shape) - len(rule)
    assert extra >= 0, (shape, rule)
    used: set[str] = set()
    parts: list[Any] = [None] * extra
    for dim, prefs in zip(shape[extra:], rule):
        pick = None
        for axes in prefs:
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if dim % size == 0 and not (set(axes) & used):
                pick = axes if len(axes) > 1 else axes[0]
                used.update(axes)
                break
        parts.append(pick)
    return P(*parts)


def _strip_data(rule: Rule) -> Rule:
    """tp_only mode: drop FSDP ("data") candidates — params replicate over
    the data axes.  Right for decode, where a per-step FSDP all-gather of
    the full parameter set dwarfs the one token's compute (§Perf)."""
    return tuple(tuple(axes for axes in prefs
                       if "data" not in axes) for prefs in rule)


def param_spec(path: str, shape: Sequence[int], mesh: Mesh, *,
               mode: str = "fsdp_tp") -> P:
    for pat, rule in PARAM_RULES:
        if fnmatch.fnmatch(path, pat):
            if len(shape) < len(rule):   # e.g. unexpected rank; replicate
                return P()
            if mode == "tp_only":
                rule = _strip_data(rule)
            elif mode == "ep_model" and "/experts/" in path:
                rule = (MODEL,) + tuple(rule[1:])
            return _choose(shape, rule, mesh)
    return P()  # norms, biases, scalars: replicated


def param_shardings(param_shapes: Any, mesh: Mesh, *,
                    mode: str = "fsdp_tp") -> Any:
    """ShapeDtypeStruct (or array) pytree -> NamedSharding pytree."""
    flat = dict(tree_paths(param_shapes))
    specs = {p: param_spec(p, v.shape, mesh, mode=mode)
             for p, v in flat.items()}
    return jax.tree.map_with_path(
        lambda kp, v: NamedSharding(mesh, specs[_path_str(kp)]),
        param_shapes)


def _path_str(key_path) -> str:
    keys = []
    for p in key_path:
        if isinstance(p, jax.tree_util.DictKey):
            keys.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            keys.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            keys.append(str(p.name))
        else:
            keys.append(str(p))
    return "/".join(keys)


# ---------------------------------------------------------------------------
# Batch / cache / state specs
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Axes:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def cache_spec(path: str, shape: Sequence[int], mesh: Mesh,
               batch: int) -> P:
    """KV caches (…, B, S, KV, D), SSM states (…, B, H, P, N), conv
    states, RG-LRU states (…, B, W).

    batch-shardable => dim holding ``batch`` gets the data axes; for
    batch=1 (long_500k) the sequence dim of KV caches gets "data".
    """
    daxes = batch_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]

    shape = tuple(shape)
    parts: list[Any] = [None] * len(shape)
    used: set[str] = set()

    # locate the batch dim: first dim equal to `batch` (skipping stacked
    # leading layer dims which equal n_pattern/L, usually != batch)
    b_dim = None
    for i, d in enumerate(shape):
        if d == batch:
            b_dim = i
            break
    if b_dim is not None and batch % dsize == 0 and batch >= dsize:
        parts[b_dim] = daxes if len(daxes) > 1 else daxes[0]
        used.update(daxes)

    is_kv = path.endswith("/k") or path.endswith("/v") \
        or re.search(r"/(k|v)$", path) is not None
    if is_kv and len(shape) >= 4:
        s_dim, kv_dim, hd_dim = len(shape) - 3, len(shape) - 2, len(shape) - 1
        # sequence over "data" only if batch didn't take it (long_500k)
        if "data" not in used and shape[s_dim] % mesh.shape["data"] == 0:
            parts[s_dim] = "data"
            used.add("data")
        if shape[kv_dim] % mesh.shape["model"] == 0:
            parts[kv_dim] = "model"
        elif parts[s_dim] is None and \
                shape[s_dim] % mesh.shape["model"] == 0:
            # GQA kv-head count doesn't divide the model axis: shard the
            # SEQUENCE over "model" instead.  Sharding head_dim forces a
            # full f32 cache all-gather per decoded token (§Perf: observed
            # 3.6 GB/step on qwen3 decode_32k); with the sequence sharded,
            # scores are computed locally and only the tiny AV partial
            # sum crosses the mesh.
            parts[s_dim] = "model"
        elif shape[hd_dim] % mesh.shape["model"] == 0:
            parts[hd_dim] = "model"
    else:
        # recurrent states: shard the widest trailing dim over "model"
        cand = max(range(1 if b_dim is None else b_dim + 1, len(shape)),
                   key=lambda i: shape[i], default=None) \
            if len(shape) > 1 else None
        if cand is not None and shape[cand] % mesh.shape["model"] == 0 \
                and shape[cand] >= mesh.shape["model"]:
            parts[cand] = "model"
    return P(*parts)


def cache_shardings(cache_shapes: Any, mesh: Mesh, batch: int) -> Any:
    flat = dict(tree_paths(cache_shapes))
    specs = {p: cache_spec(p, v.shape, mesh, batch) for p, v in flat.items()}
    return jax.tree.map_with_path(
        lambda kp, v: NamedSharding(mesh, specs[_path_str(kp)]),
        cache_shapes)


def data_spec(mesh: Mesh, rank: int, batch: Optional[int] = None) -> P:
    """Plain batch-major input: (B, ...), falling back to fewer (or no)
    axes when the batch does not divide (long_500k has batch=1)."""
    candidates: list[Axes] = [batch_axes(mesh), ("data",), ("pod",)]
    for ax in candidates:
        if not all(a in mesh.shape for a in ax):
            continue
        size = 1
        for a in ax:
            size *= mesh.shape[a]
        if batch is None or (batch % size == 0 and batch >= size):
            return P(ax if len(ax) > 1 else ax[0], *([None] * (rank - 1)))
    return P(*([None] * rank))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# Activation-sharding constraint machinery lives in repro.nn.constrain
# (kept import-cycle-free for layer code); re-exported here for launch code.
from repro.nn.constrain import (activation_sharding, constrain,  # noqa: F401
                                constrain_act)
