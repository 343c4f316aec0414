"""Fixture tests for repro.analysis: every rule gets at least one
positive (fires) and one negative (stays quiet) snippet, plus the
baseline machinery, the schema forward-compat contract (satellite of
rule 4), and the repo-level --strict gate."""

import json
from pathlib import Path

import pytest

from repro.analysis import (
    analyze_source,
    baseline_problems,
    diff_against_baseline,
    load_baseline,
    rule_names,
    save_baseline,
)
from repro.analysis.core import Suppression
from repro.analysis.rules_kernel import audit_vmem_budgets
from repro.analysis.rules_schema import check_registries
from repro.schema import SchemaVersionError

REPO_ROOT = Path(__file__).resolve().parent.parent


def rules_of(findings):
    return {f.rule for f in findings}


def unsuppressed(findings):
    return [f for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# timing-warmup
# ---------------------------------------------------------------------------

TIMING_POS = """
import time
import jax

def measure(fn, x, n):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return ts
"""

TIMING_NEG = """
import time
import jax

def measure(fn, x, n):
    for _ in range(3):
        jax.block_until_ready(fn(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return ts
"""


def test_timing_warmup_positive():
    f = analyze_source(TIMING_POS, rules=["timing-warmup"])
    assert rules_of(f) == {"timing-warmup"}


def test_timing_warmup_negative():
    assert analyze_source(TIMING_NEG, rules=["timing-warmup"]) == []


def test_timing_warmup_block_helper_counts():
    # serving/ uses a local _block() helper instead of jax directly
    src = TIMING_NEG.replace("jax.block_until_ready", "_block")
    assert analyze_source(src, rules=["timing-warmup"]) == []


# ---------------------------------------------------------------------------
# timing-monotonic-accum
# ---------------------------------------------------------------------------

ACCUM_POS = """
import time

def run_load(period, n, send):
    t = time.monotonic()
    for _ in range(n):
        t += period
        send(t)
"""

ACCUM_NEG = """
import time

def run_load(period, n, send):
    t_start = time.monotonic()
    for i in range(n):
        send(t_start + i * period)
"""


def test_monotonic_accum_positive():
    f = analyze_source(ACCUM_POS, rules=["timing-monotonic-accum"])
    assert rules_of(f) == {"timing-monotonic-accum"}


def test_monotonic_accum_negative():
    assert analyze_source(ACCUM_NEG, rules=["timing-monotonic-accum"]) == []


# ---------------------------------------------------------------------------
# rng-reset
# ---------------------------------------------------------------------------

RNG_RESET_POS = """
import numpy as np

class Link:
    def __init__(self, seed):
        self.seed = seed
        self._rng = np.random.default_rng(seed)
        self._busy_until = 0.0

    def reset(self):
        self._busy_until = 0.0
"""

RNG_RESET_NEG = RNG_RESET_POS.replace(
    "        self._busy_until = 0.0\n",
    "        self._busy_until = 0.0\n"
    "        self._rng = np.random.default_rng(self.seed)\n",
    1,
).replace(
    "    def reset(self):\n        self._busy_until = 0.0",
    "    def reset(self):\n"
    "        self._busy_until = 0.0\n"
    "        self._rng = np.random.default_rng(self.seed)",
)


def test_rng_reset_positive():
    f = analyze_source(RNG_RESET_POS, rules=["rng-reset"])
    assert rules_of(f) == {"rng-reset"}


def test_rng_reset_negative():
    assert analyze_source(RNG_RESET_NEG, rules=["rng-reset"]) == []


# ---------------------------------------------------------------------------
# rng-unseeded (scoped to src/repro/serving/)
# ---------------------------------------------------------------------------

RNG_UNSEEDED_POS = """
import numpy as np

def jitter():
    rng = np.random.default_rng()
    return np.random.uniform(0.0, 1.0)
"""

RNG_UNSEEDED_NEG = """
import numpy as np

def jitter(seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0)
"""


def test_rng_unseeded_positive():
    f = analyze_source(
        RNG_UNSEEDED_POS,
        path="src/repro/serving/fake_link.py",
        rules=["rng-unseeded"],
    )
    assert len(f) == 2 and rules_of(f) == {"rng-unseeded"}


def test_rng_unseeded_negative():
    assert (
        analyze_source(
            RNG_UNSEEDED_NEG,
            path="src/repro/serving/fake_link.py",
            rules=["rng-unseeded"],
        )
        == []
    )


def test_rng_unseeded_out_of_scope():
    # the rule only polices the seeded-simulation modules
    assert (
        analyze_source(
            RNG_UNSEEDED_POS, path="examples/demo.py", rules=["rng-unseeded"]
        )
        == []
    )


# ---------------------------------------------------------------------------
# socket-shutdown
# ---------------------------------------------------------------------------

SOCKET_POS = """
import socket

def talk(addr):
    s = socket.create_connection(addr)
    s.sendall(b"x")
    s.close()
"""

SOCKET_NEG = """
import socket

def talk(addr):
    s = socket.create_connection(addr)
    s.sendall(b"x")
    s.shutdown(socket.SHUT_RDWR)
    s.close()
"""

SOCKET_LISTENER = """
import socket

def serve():
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen()
    listener.close()
"""


def test_socket_shutdown_positive():
    f = analyze_source(SOCKET_POS, rules=["socket-shutdown"])
    assert rules_of(f) == {"socket-shutdown"}


def test_socket_shutdown_negative():
    assert analyze_source(SOCKET_NEG, rules=["socket-shutdown"]) == []


def test_socket_shutdown_listener_exempt():
    assert analyze_source(SOCKET_LISTENER, rules=["socket-shutdown"]) == []


# ---------------------------------------------------------------------------
# thread-lifecycle
# ---------------------------------------------------------------------------

THREAD_POS = """
import threading

def go(fn):
    t = threading.Thread(target=fn)
    t.start()
    return t
"""

THREAD_JOINED = """
import threading

def go(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join()
"""

THREAD_DAEMON = """
import threading

def go(fn):
    t = threading.Thread(target=fn, daemon=True)
    t.start()
"""

PROCESS_DAEMON = """
import multiprocessing

def go(fn):
    p = multiprocessing.Process(target=fn, daemon=True)
    p.start()
"""


def test_thread_lifecycle_positive():
    f = analyze_source(THREAD_POS, rules=["thread-lifecycle"])
    assert rules_of(f) == {"thread-lifecycle"}


def test_thread_lifecycle_joined_negative():
    assert analyze_source(THREAD_JOINED, rules=["thread-lifecycle"]) == []


def test_thread_lifecycle_daemon_thread_exempt():
    assert analyze_source(THREAD_DAEMON, rules=["thread-lifecycle"]) == []


def test_thread_lifecycle_daemon_process_not_exempt():
    # a SIGKILLed daemon process loses its sockets; it must be reaped
    f = analyze_source(PROCESS_DAEMON, rules=["thread-lifecycle"])
    assert rules_of(f) == {"thread-lifecycle"}


# ---------------------------------------------------------------------------
# schema-version
# ---------------------------------------------------------------------------

SCHEMA_POS = """
import dataclasses

@dataclasses.dataclass(frozen=True)
class Cfg:
    x: int = 1

    def to_dict(self):
        return {"x": self.x}

    @classmethod
    def from_dict(cls, d):
        return cls(**d)
"""

SCHEMA_NEG = """
import dataclasses

CFG_VERSION = 1

@dataclasses.dataclass(frozen=True)
class Cfg:
    x: int = 1

    def to_dict(self):
        return {"version": CFG_VERSION, "x": self.x}

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        version = d.pop("version", CFG_VERSION)
        if version != CFG_VERSION:
            raise ValueError(f"unsupported version {version}")
        return cls(**d)
"""


def test_schema_version_positive():
    f = analyze_source(SCHEMA_POS, rules=["schema-version"])
    assert rules_of(f) == {"schema-version"}


def test_schema_version_negative():
    assert analyze_source(SCHEMA_NEG, rules=["schema-version"]) == []


def test_schema_version_ignores_plain_classes():
    src = SCHEMA_POS.replace("@dataclasses.dataclass(frozen=True)\n", "")
    assert analyze_source(src, rules=["schema-version"]) == []


# ---------------------------------------------------------------------------
# registry-roundtrip
# ---------------------------------------------------------------------------

REGISTRY_POS = """
from repro.serving.fleet import register_router

register_router("definitely-not-a-registered-router", lambda *a: 0)
"""

REGISTRY_NEG = """
from repro.serving.fleet import register_router

register_router("round_robin", lambda *a: 0)
"""


def test_registry_roundtrip_positive():
    f = analyze_source(REGISTRY_POS, rules=["registry-roundtrip"])
    assert rules_of(f) == {"registry-roundtrip"}
    assert "definitely-not-a-registered-router" in f[0].message


def test_registry_roundtrip_negative():
    assert analyze_source(REGISTRY_NEG, rules=["registry-roundtrip"]) == []


def test_live_registries_are_clean():
    # runtime half on the real repo: constructible + JSON-round-trippable
    assert check_registries() == []


# ---------------------------------------------------------------------------
# kernel-interpret / kernel-vmem
# ---------------------------------------------------------------------------

INTERPRET_POS = """
import jax.experimental.pallas as pl

def launch(kernel, x, shape):
    return pl.pallas_call(kernel, out_shape=shape)(x)
"""

INTERPRET_NEG = """
import jax.experimental.pallas as pl

def launch(kernel, x, shape, interpret):
    return pl.pallas_call(kernel, out_shape=shape, interpret=interpret)(x)
"""


def test_kernel_interpret_positive():
    f = analyze_source(INTERPRET_POS, rules=["kernel-interpret"])
    assert rules_of(f) == {"kernel-interpret"}


def test_kernel_interpret_negative():
    assert analyze_source(INTERPRET_NEG, rules=["kernel-interpret"]) == []


def test_vmem_audit_default_budget():
    # under the real budget every audited config launches: c_in=4 at
    # 400x400, which did not before layer 0 was folded space-to-depth,
    # takes 2 frames with the head
    assert audit_vmem_budgets() == []


def test_vmem_audit_tiny_budget_fires():
    findings = audit_vmem_budgets(vmem_limit=1024)
    assert findings and rules_of(findings) == {"kernel-vmem"}


# ---------------------------------------------------------------------------
# broad-except
# ---------------------------------------------------------------------------

EXCEPT_POS = """
def f():
    try:
        g()
    except Exception:
        pass
"""

EXCEPT_NEG_NARROW = """
def f():
    try:
        g()
    except (ValueError, KeyError):
        pass
"""

EXCEPT_NEG_RERAISE = """
def f():
    try:
        g()
    except Exception:
        cleanup()
        raise
"""

EXCEPT_SUPPRESSED = """
def f():
    try:
        g()
    except Exception:  # repro: allow(broad-except) -- probe: any failure means unsupported
        pass
"""

EXCEPT_NO_JUSTIFICATION = """
def f():
    try:
        g()
    except Exception:  # repro: allow(broad-except)
        pass
"""


def test_broad_except_positive():
    f = analyze_source(EXCEPT_POS, rules=["broad-except"])
    assert rules_of(f) == {"broad-except"}


def test_broad_except_narrow_negative():
    assert analyze_source(EXCEPT_NEG_NARROW, rules=["broad-except"]) == []


def test_broad_except_reraise_negative():
    assert analyze_source(EXCEPT_NEG_RERAISE, rules=["broad-except"]) == []


def test_broad_except_suppressed_with_justification():
    f = analyze_source(EXCEPT_SUPPRESSED, rules=["broad-except"])
    assert len(f) == 1 and f[0].suppressed
    assert "unsupported" in f[0].justification


def test_suppression_without_justification_does_not_suppress():
    f = analyze_source(EXCEPT_NO_JUSTIFICATION, rules=["broad-except"])
    assert rules_of(f) == {"broad-except", "suppression-justification"}
    assert all(not fi.suppressed for fi in f)


def test_allow_example_in_docstring_is_not_a_waiver():
    src = '"""# repro: allow(broad-except) -- not a real comment"""\n' + EXCEPT_POS
    f = analyze_source(src, rules=["broad-except"])
    assert len(f) == 1 and not f[0].suppressed


# ---------------------------------------------------------------------------
# syntax
# ---------------------------------------------------------------------------

def test_syntax_positive():
    f = analyze_source("def f(:\n", rules=[])
    assert rules_of(f) == {"syntax"}


def test_syntax_negative():
    assert analyze_source("x = 1\n", rules=[]) == []


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_diff(tmp_path):
    old = analyze_source(EXCEPT_POS, rules=["broad-except"])
    path = tmp_path / "baseline.json"
    save_baseline(path, old, [])
    baseline = load_baseline(path)

    # same findings -> nothing new; a new finding is detected; removing
    # the old one leaves its fingerprint stale
    new_src = EXCEPT_POS + "\n\ndef h():\n    try:\n        g()\n    except Exception:\n        return None\n"
    live = analyze_source(new_src, rules=["broad-except"])
    new, known, stale = diff_against_baseline(live, baseline)
    assert len(known) == 1 and len(new) == 1 and stale == []

    new2, known2, stale2 = diff_against_baseline([], baseline)
    assert new2 == [] and known2 == [] and len(stale2) == 1


def test_baseline_unknown_version_refused(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError, match="version"):
        load_baseline(path)


def test_baseline_unjustified_suppression_is_a_problem(tmp_path):
    path = tmp_path / "baseline.json"
    save_baseline(
        path,
        [],
        [
            Suppression("a.py", 3, ("broad-except",), ""),
            Suppression("b.py", 7, ("rng-reset",), "real reason"),
        ],
    )
    problems = baseline_problems(load_baseline(path))
    assert len(problems) == 1 and "a.py:3" in problems[0]


def test_committed_baseline_has_only_justified_suppressions():
    baseline = load_baseline(REPO_ROOT / "analysis_baseline.json")
    assert baseline_problems(baseline) == []


# ---------------------------------------------------------------------------
# every registered rule is exercised above
# ---------------------------------------------------------------------------

def test_all_rules_have_fixture_coverage():
    covered = {
        "timing-warmup",
        "timing-monotonic-accum",
        "rng-reset",
        "rng-unseeded",
        "socket-shutdown",
        "thread-lifecycle",
        "schema-version",
        "registry-roundtrip",
        "kernel-interpret",
        "kernel-vmem",
        "broad-except",
        "syntax",
        "suppression-justification",
    }
    assert set(rule_names()) == covered


# ---------------------------------------------------------------------------
# schema forward-compat (companion runtime check for rule 4)
# ---------------------------------------------------------------------------

def test_schema_version_error_is_typed_and_a_valueerror():
    assert issubclass(SchemaVersionError, ValueError)


def test_deployment_config_unknown_version_raises():
    from repro.deploy import DeploymentConfig

    d = DeploymentConfig.standard().to_dict()
    d["version"] = 99
    with pytest.raises(SchemaVersionError, match="version"):
        DeploymentConfig.from_dict(d)


def test_scenario_unknown_version_raises():
    from repro.serving.scenario import SCENARIOS, Scenario

    d = next(iter(SCENARIOS.values())).to_dict()
    d["version"] = 99
    with pytest.raises(SchemaVersionError, match="version"):
        Scenario.from_dict(d)


def test_tuned_plan_unknown_version_raises():
    from repro.core.tuning import TunedPlan

    d = TunedPlan(backend="fused", tile_h=8, micro_batch=4).to_dict()
    d["version"] = 99
    with pytest.raises(SchemaVersionError, match="version"):
        TunedPlan.from_dict(d)


def test_shaping_config_unknown_version_raises():
    from repro.serving.realfleet import ShapingConfig

    d = ShapingConfig(rate_mbps=2.0).to_dict()
    assert d["version"] == 1
    d["version"] = 99
    with pytest.raises(SchemaVersionError, match="version"):
        ShapingConfig.from_dict(d)


def test_tuned_plan_unknown_field_still_raises():
    # unknown fields must not silently drop (pre-existing contract)
    from repro.core.tuning import TunedPlan

    d = TunedPlan(backend="fused", tile_h=8, micro_batch=4).to_dict()
    d["mystery"] = 1
    with pytest.raises(ValueError, match="unknown"):
        TunedPlan.from_dict(d)


# ---------------------------------------------------------------------------
# the repo itself passes --strict against the committed baseline
# ---------------------------------------------------------------------------

def test_repo_is_strict_clean(monkeypatch, capsys):
    from repro.analysis.__main__ import main

    monkeypatch.chdir(REPO_ROOT)
    assert main(["--strict"]) == 0
