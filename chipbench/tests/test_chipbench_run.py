"""Whole runs of the harness at a tiny size on the CPU, with the chip
check skipped: the program passes, and the control (the reference at
``bf16_3x`` in the program's place) and an answer altered where it is
produced both come out as not correct.  Also: no TPU, no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import controls, manifest, run

CHECKOUT = manifest.CHECKOUT
BENCH = manifest.load()
TINY = {"encode.tiny": ("b8", {"pool": 2}),
        "serve.tiny": ("split-10hz", {"clients": 4, "payload_pool": 8,
                                      "payloads_per_client": 4,
                                      "warm_requests": 4})}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose cells run the real kinds and limits on a 16x16x9
    copy of ``mc4-84-c9`` with a 32-wide projection."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(CHECKOUT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((root / "chipbench/configs/mc4-84-c9.json").read_text())
    cfg.update(name="tiny-c9", in_h=16, in_w=16, head_dim=32)
    (root / "chipbench/configs/tiny-c9.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-c9", "source": "test",
                             "file": "chipbench/configs/tiny-c9.json",
                             "reduced": [], "why": "test"})
    for name, (base, changes) in TINY.items():
        traffic = json.loads((root / f"chipbench/traffic/{base}.json")
                             .read_text())
        traffic.update(changes)
        (root / f"chipbench/traffic/{name}.json").write_text(
            json.dumps(traffic))
        bench["workloads"].append({"name": name, "config": "tiny-c9",
                                   "traffic": name, "chips": 1,
                                   "why": "test"})
        kind = traffic["kind"]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if any(w.startswith(kind + ".") for w in m.get("workloads", ())):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def in_process_fleet(monkeypatch):
    # on the CPU the fleet spawns worker processes; keep the replica in
    # this process, as on the chip, so that the patches reach it
    import repro.serving.realfleet as realfleet
    monkeypatch.setattr(realfleet, "_spawns_workers", lambda: False)


def tiny_run(root, cell, trace=0):
    return run.run(["--workload", cell, "--seed", "3000000019",
                    "--seconds", "0.5", "--trace", str(trace)],
                   checkout=root, require_chip=False)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_is_correct(tiny, in_process_fleet, cell):
    res = tiny_run(tiny, cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    e2e = manifest.cell(cell, tiny).end_to_end
    assert set(res["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(tiny, in_process_fleet, cell):
    with controls.reference_in_place("bf16_3x"):
        res = tiny_run(tiny, cell)
    assert not res["correct"]
    (check,) = res["checks"].values()
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_altered_answer_is_not_correct(tiny, in_process_fleet, cell):
    with controls.altered_answer(1.0):
        res = tiny_run(tiny, cell)
    assert not res["correct"]


def test_reference_in_place_at_highest_is_correct(tiny, in_process_fleet):
    with controls.reference_in_place("highest"):
        res = tiny_run(tiny, "encode.tiny")
    assert res["correct"]


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_no_result():
    proc = _command(CHECKOUT)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_python_tracer_off_only_where_the_traffic_says():
    import jax
    default = jax.profiler.ProfileOptions()
    assert run.profile_options({"kind": "encode"}) is None
    options = run.profile_options({"kind": "serve",
                                   "python_tracer_level": 0})
    assert options.python_tracer_level == 0 != default.python_tracer_level
    assert options.host_tracer_level == default.host_tracer_level
    for w in BENCH["workloads"]:
        traffic = manifest.cell(w["name"]).traffic
        want = 0 if traffic["kind"] == "serve" else None
        assert traffic.get("python_tracer_level") == want, w["name"]
