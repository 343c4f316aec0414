"""Kernel / VMEM budget rules, plus the broad-except sweep.

``kernel-interpret`` (AST): every ``pallas_call`` site must pass
``interpret=`` explicitly — the repo's compiled-vs-interpret stamping
(PR 6) only works because no call site inherits an ambient default.

``kernel-vmem`` (runtime, arithmetic only — nothing is executed): for the
paper's standard encoder configs, every fused pallas backend must admit at
least a batch-1 launch under the ``PassPlan`` VMEM budget.  A backend
whose batch-independent residency alone exceeds VMEM is unlaunchable and
streaming cannot help it.

``broad-except`` (AST): ``except Exception`` / bare ``except`` hides the
exact bug classes the rest of this engine looks for; outside allow-listed
compat probes each site needs a narrow type or a justified suppression.
"""

from __future__ import annotations

import ast
from typing import List

from .core import Context, Finding, Rule, dotted_name, register_rule


# --------------------------------------------------------------------------
# kernel-interpret

def _check_kernel_interpret(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for f in ctx.files:
        if f.tree is None:
            continue
        for n in ast.walk(f.tree):
            if not isinstance(n, ast.Call):
                continue
            # only the pallas_call(...) call itself, not the immediate
            # invocation pl.pallas_call(...)(x) whose func is that Call
            if not isinstance(n.func, (ast.Name, ast.Attribute)):
                continue
            if dotted_name(n.func).rsplit(".", 1)[-1] != "pallas_call":
                continue
            if not any(k.arg == "interpret" for k in n.keywords):
                findings.append(
                    Finding(
                        "kernel-interpret",
                        f.path,
                        n.lineno,
                        "pallas_call without an explicit interpret= kwarg; "
                        "the compiled/interpret mode stamp on BENCH "
                        "artifacts requires every site to choose explicitly",
                    )
                )
    return findings


# --------------------------------------------------------------------------
# kernel-vmem

# (c_in, input size) pairs covering the paper's standard encoder configs
_AUDIT_CONFIGS = ((12, 84), (4, 64), (4, 128), (4, 256), (4, 400))
_AUDIT_HEAD_DIM = 512
_AUDIT_TILE_H = 8


def audit_vmem_budgets(vmem_limit: int = 0) -> List[Finding]:
    """Static VMEM audit: PassPlan arithmetic only, no kernel launches."""
    findings: List[Finding] = []
    try:
        from repro.core.backends import backend_names, get_backend
        from repro.core.miniconv import standard_spec
        from repro.core.passplan import DEFAULT_VMEM_LIMIT, build_pass_plan
    except Exception as e:  # repro: allow(broad-except) -- audit must report, not crash on, an import failure
        return [
            Finding(
                "kernel-vmem",
                "src/repro/analysis/rules_kernel.py",
                1,
                f"cannot import PassPlan machinery for the VMEM audit: {e!r}",
            )
        ]
    limit = vmem_limit or DEFAULT_VMEM_LIMIT
    for c_in, size in _AUDIT_CONFIGS:
        spec = standard_spec(c_in=c_in)
        plan = build_pass_plan(spec, size, size)
        head = plan.head(_AUDIT_HEAD_DIM)
        for name in backend_names():
            b = get_backend(name)
            if not b.is_pallas or b.mode != "fused":
                continue  # per-pass/grouped launch one pass at a time
            safe = plan.max_safe_batch(
                head=head if b.fused_head else None,
                tile_h=_AUDIT_TILE_H,
                vmem_limit=limit,
                streamed=b.streamed,
            )
            if safe < 1:
                findings.append(
                    Finding(
                        "kernel-vmem",
                        "src/repro/core/backends.py",
                        1,
                        f"backend {name!r} cannot launch even batch=1 for "
                        f"c_in={c_in} {size}x{size} under the "
                        f"{limit / 2**20:.1f} MiB VMEM budget "
                        "(batch-independent residency already exceeds it; "
                        "streaming cannot help)",
                    )
                )
    return findings


def _check_kernel_vmem(ctx: Context) -> List[Finding]:
    if not ctx.runtime:
        return []
    return audit_vmem_budgets()


# --------------------------------------------------------------------------
# broad-except

def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Tuple):
        names = [dotted_name(e) for e in t.elts]
    else:
        names = [dotted_name(t)]
    return any(n in ("Exception", "BaseException") for n in names)


def _reraises(handler: ast.ExceptHandler) -> bool:
    return any(
        isinstance(n, ast.Raise) and n.exc is None for n in ast.walk(handler)
    )


def _check_broad_except(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for f in ctx.files:
        if f.tree is None:
            continue
        for n in ast.walk(f.tree):
            if not isinstance(n, ast.ExceptHandler) or not _is_broad(n):
                continue
            if _reraises(n):
                continue  # catch-to-cleanup-and-reraise is fine
            findings.append(
                Finding(
                    "broad-except",
                    f.path,
                    n.lineno,
                    "broad except handler swallows every bug class this "
                    "engine checks for; catch the specific exceptions or "
                    "add '# repro: allow(broad-except) -- <why>'",
                )
            )
    return findings


register_rule(
    Rule(
        name="kernel-interpret",
        family="kernel",
        description="every pallas_call site passes interpret= explicitly",
        check=_check_kernel_interpret,
    )
)

register_rule(
    Rule(
        name="kernel-vmem",
        family="kernel",
        description=(
            "fused pallas backends must admit batch>=1 for the standard "
            "encoder configs under the PassPlan VMEM budget (arithmetic "
            "only, nothing executed)"
        ),
        check=_check_kernel_vmem,
    )
)

register_rule(
    Rule(
        name="broad-except",
        family="kernel",
        description=(
            "no bare/Exception-wide handlers without a justified "
            "suppression (re-raising handlers exempt)"
        ),
        check=_check_broad_except,
    )
)
