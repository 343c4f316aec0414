"""Execution-mode and host stamps for perf artifacts.

Every number this repo records depends on HOW the kernels executed
(Pallas interpret vs compiled) and WHERE (host platform, accelerator,
core count).  Comparing a compiled-TPU artifact against an interpret-CPU
one is meaningless, and before this module nothing in the BENCH files
said which was which — the ROADMAP's standing "all numbers are
interpret-mode" ambiguity.

:func:`stamp` annotates a result dict with ``mode``, ``host`` and
(optionally) ``backend``; :func:`check_comparable` is the gate the CI
compare steps call before diffing two artifacts — it refuses to compare
across mismatched execution modes and warns on host mismatches via the
returned reason list.
"""
from __future__ import annotations

import os
import platform
from typing import Optional


def execution_mode(interpret: Optional[bool] = None) -> str:
    """``"interpret"`` or ``"compiled"`` — resolved by the kernel layer's
    own switch (:func:`repro.kernels.interpret.resolve_interpret`)."""
    from repro.kernels.interpret import resolve_interpret
    return "interpret" if resolve_interpret(interpret) else "compiled"


def host_fingerprint() -> str:
    """``platform/machine/device-kind/cpu-count``, e.g.
    ``linux/x86_64/cpu/2``.  Coarse on purpose: enough to flag
    cross-host comparisons without leaking hostnames into artifacts."""
    try:
        import jax
        device = jax.devices()[0].device_kind.replace("/", "-")
    except (ImportError, IndexError, RuntimeError):
        # no jax, no devices, or backend init failed: stamp coarse-unknown
        device = "unknown"
    return "/".join([platform.system().lower(), platform.machine(),
                     device, str(os.cpu_count() or 0)])


def stamp(entry: dict, *, backend: Optional[str] = None,
          interpret: Optional[bool] = None,
          transport: Optional[str] = None) -> dict:
    """Return a copy of ``entry`` stamped with mode/host (+ backend,
    + transport).  ``transport`` distinguishes HOW a serving number was
    produced: ``"sim"`` (event-time queue simulation) vs ``"socket"``
    (wall-clock measured real fleet) — a sim-vs-real delta is a
    calibration result, never a regression signal, so transport
    mismatches are hard failures for :func:`check_comparable`."""
    out = dict(entry)
    out["mode"] = execution_mode(interpret)
    out["host"] = host_fingerprint()
    if backend is not None:
        out["backend"] = backend
    if transport is not None:
        out["transport"] = transport
    return out


def mismatches(a: dict, b: dict) -> list[str]:
    """Comparability defects between two stamped entries.

    ``mode`` mismatches (or a missing ``mode`` on either side) and
    ``transport`` mismatches (sim-vs-real: differing values, or stamped
    on only one side) are hard failures for :func:`check_comparable`;
    ``host``/``backend`` mismatches are reported so callers can surface
    them, but two runs on different hosts are still a meaningful
    (cross-host) comparison.
    """
    out = []
    ma, mb = a.get("mode"), b.get("mode")
    if ma is None or mb is None:
        out.append(f"mode missing (got {ma!r} vs {mb!r}; artifact predates "
                   "stamping — re-run the benchmark)")
    elif ma != mb:
        out.append(f"mode {ma!r} != {mb!r}")
    ta, tb = a.get("transport"), b.get("transport")
    if (ta is None) != (tb is None):
        out.append(f"transport stamped on one side only ({ta!r} vs {tb!r}; "
                   "sim-vs-real comparisons are calibration, not diffs)")
    elif ta is not None and ta != tb:
        out.append(f"transport {ta!r} != {tb!r}")
    for key in ("host", "backend"):
        va, vb = a.get(key), b.get(key)
        if va is not None and vb is not None and va != vb:
            out.append(f"{key} {va!r} != {vb!r}")
    return out


def check_comparable(a: dict, b: dict, *, what: str = "artifacts") -> None:
    """Raise ValueError when two stamped entries must not be compared
    (different or missing execution modes, or sim-vs-real transports —
    those deltas are noise or calibration, not regression signal)."""
    hard = [m for m in mismatches(a, b)
            if m.startswith(("mode", "transport"))]
    if hard:
        raise ValueError(
            f"refusing to compare {what} across execution modes: "
            + "; ".join(hard))


__all__ = ["execution_mode", "host_fingerprint", "stamp", "mismatches",
           "check_comparable"]
