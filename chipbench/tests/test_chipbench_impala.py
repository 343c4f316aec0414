"""The IMPALA-CNN cell's files: its operation count at the published
widths, a whole run of its kind at a tiny size on the CPU (the program
passes; an altered answer and the reference at ``bf16_3x`` in the
program's place do not), and its five readers on a recorded traced run
of the cell on a TPU v5e."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from chipbench import controls, impala_count, manifest, run, scopes, trace
from chipbench.kinds import encode_impala

CHECKOUT = manifest.CHECKOUT
BENCH = manifest.load()
CELL = "encode.impala-64-c3.b256"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
TRACE = FIXTURES / "encode-impala64-b256.xplane.pb"
COUNTERS = FIXTURES / "encode-impala64-b256.counters.json"
READERS = ("encode_step_us", "impala_res_us", "impala_pool_us",
           "impala_mfu", "device_idle.encode")


def config():
    return json.loads((manifest.BENCH_DIR / "configs/impala-64-c3.json")
                      .read_text())


def test_published_widths_count():
    cfg = config()
    assert (cfg["depths"], cfg["blocks"], cfg["c_in"], cfg["head_dim"]) \
        == ([16, 32, 32], 2, 3, 256)
    assert impala_count.flops_per_frame(cfg) == 61_210_624
    assert impala_count.parameters(cfg) == 622_144
    assert impala_count.weight_bytes(cfg) == 4 * 622_144
    assert impala_count.feature_shape(cfg) == (8, 8, 32)


def test_count_by_hand():
    """2 * out_h * out_w * 9 * c_in * c_out per conv: each stack's entry
    conv at its input size, four residual convs at the pooled size; the
    head 2 * 2048 * 256."""
    convs = [2 * 64 * 64 * 9 * 3 * 16, 4 * 2 * 32 * 32 * 9 * 16 * 16,
             2 * 32 * 32 * 9 * 16 * 32, 4 * 2 * 16 * 16 * 9 * 32 * 32,
             2 * 16 * 16 * 9 * 32 * 32, 4 * 2 * 8 * 8 * 9 * 32 * 32]
    cfg = config()
    assert impala_count.conv_flops_per_frame(cfg) == sum(convs)
    assert impala_count.head_flops_per_frame(cfg) == 2 * 2048 * 256
    assert impala_count.bytes_per_launch(cfg, 256) == (
        256 * 64 * 64 * 3 * 4 + 4 * 622_144 + 256 * 256 * 4) == 15_333_632


def test_roofline_is_compute_bound_on_v5e():
    peaks = json.loads((manifest.BENCH_DIR / "peaks.json").read_text())
    least, bound = impala_count.roofline_s(config(), 256,
                                           peaks["TPU v5 lite"])
    assert bound == "compute"
    assert least == pytest.approx(256 * 61_210_624 / 197e12)
    assert 79e-6 < least < 80e-6


def test_reference_init_has_the_counted_parameters():
    import jax
    from chipbench.reference import impala as ref
    shapes = jax.eval_shape(lambda k: ref.init_params(config(), k),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 622_144


def test_op_scopes_reads_instruction_metadata():
    text = (
        'ENTRY %main.9 (obs.1: f32[2,4]) -> f32[2,4] {\n'
        '  %obs.1 = f32[2,4]{1,0} parameter(0), metadata={op_name="obs"}\n'
        '  %fusion.3 = f32[2,4]{1,0} fusion(%obs.1), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(encoder_apply)/impala.encode/'
        'impala.res/add" stack_frame_id=2}\n'
        '  %copy.1 = f32[2,4]{0,1} copy(%fusion.3)\n'
        '  ROOT %reduce-window.2 = f32[2,4]{1,0} reduce-window(%copy.1), '
        'metadata={op_name="jit(encoder_apply)/impala.encode/impala.pool/'
        'reduce_window_max"}\n}\n')
    found = encode_impala.op_scopes(text)
    assert found == {
        "obs.1": "obs",
        "fusion.3": "jit(encoder_apply)/impala.encode/impala.res/add",
        "reduce-window.2":
            "jit(encoder_apply)/impala.encode/impala.pool/reduce_window_max"}


# ---------------------------------------------------------------- tiny run

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose cell runs the real kind and limit on a 15x13 copy
    of ``impala-64-c3`` with depths 4, 8, 8 and a 32-wide head."""
    root = tmp_path_factory.mktemp("tiny-impala")
    shutil.copytree(CHECKOUT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = config()
    cfg.update(name="tiny-impala", in_h=15, in_w=13, depths=[4, 8, 8],
               head_dim=32)
    (root / "chipbench/configs/tiny-impala.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "chipbench/traffic/b256.json").read_text())
    traffic.update(batch=4, pool=2)
    (root / "chipbench/traffic/tiny-impala.json").write_text(
        json.dumps(traffic))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-impala", "source": "test",
                             "file": "chipbench/configs/tiny-impala.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "encode.tiny-impala",
                               "config": "tiny-impala",
                               "traffic": "tiny-impala", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("encode.tiny-impala")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def tiny_run(root):
    return run.run(["--workload", "encode.tiny-impala", "--seed",
                    "3000000019", "--seconds", "0.5", "--trace", "0"],
                   checkout=root, require_chip=False)


def test_program_is_correct(tiny):
    res = tiny_run(tiny)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    (check,) = res["checks"].values()
    assert 0 < check["value"] <= check["limit"]


def test_altered_answer_is_not_correct(tiny):
    with controls.altered_answer(1.0):
        res = tiny_run(tiny)
    assert not res["correct"]


def test_reference_at_bf16_3x_in_place_is_not_correct(tiny, monkeypatch):
    from chipbench.reference import impala as ref
    from repro.deploy import Deployment
    cfg = manifest.cell("encode.tiny-impala", tiny).config
    build = Deployment.__dict__["build"]

    def patched(cls, config):
        dep = build.__func__(cls, config)
        return dataclasses.replace(dep, encoder=dataclasses.replace(
            dep.encoder,
            apply=lambda p, x: ref.encode_project(cfg, p, x, "bf16_3x")))

    monkeypatch.setattr(Deployment, "build", classmethod(patched))
    res = tiny_run(tiny)
    assert not res["correct"]
    (check,) = res["checks"].values()
    assert check["value"] > check["limit"]


def test_the_cell_is_entered_as_the_contract_asks():
    cell = manifest.cell(CELL)
    assert cell.kind == "encode_impala" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    assert cell.traffic["batch"] == 256 and cell.traffic["backend"] == "xla"
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "impala-64-c3"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == cell.config["source"]


# ------------------------------------------------ readers on a v5e trace

@pytest.fixture(scope="module")
def recorded():
    """The recorded window, reduced, with the counters the kind gave it
    (its ``op_scopes`` read from the compiled step at set-up)."""
    summary = trace.reduce(TRACE)
    counters = json.loads(COUNTERS.read_text())
    cell = manifest.cell(CELL)
    window = {"metrics": counters.pop("metrics"), "counters": counters}
    peaks = run.peaks_for("TPU v5 lite")
    return run.Context(cell, window, summary, peaks)


def _read(ctx, name):
    return manifest.reader(ctx.cell.reader_path(name))(ctx)


def test_fixture_is_small_and_from_a_v5e():
    assert TRACE.stat().st_size < 300_000
    from jax.profiler import ProfileData
    planes = [p.name for p in ProfileData.from_file(str(TRACE)).planes]
    assert any(p.startswith(trace.DEVICE_PREFIX) for p in planes)


def test_op_scopes_agree_with_the_trace_metadata(recorded):
    """The names the kind read from the compiled step carry the same name
    stacks as the trace's own ``tf_op`` of each operation of the step."""
    summary, mapping = recorded.trace, recorded.counters["op_scopes"]
    found = scopes.stacks(TRACE, summary)
    inside = scopes._in_modules(
        summary, 0, lambda n: n.startswith(scopes.ENCODE_STEP))
    pairs = [(trace.short_name(name), stack) for (name, _, _), stack, ok
             in zip(summary.ops[0], found[0], inside) if ok and stack]
    assert pairs
    for name, stack in pairs:
        assert mapping[name] == stack, name


def test_five_readers_on_the_recorded_trace(recorded):
    values = {name: _read(recorded, name) for name in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    step = values["encode_step_us"]
    assert values["impala_res_us"] < step
    assert values["impala_pool_us"] < step
    assert values["impala_res_us"] + values["impala_pool_us"] < step
    assert 0 < values["device_idle.encode"] < 100
    assert 0 < values["impala_mfu"] < 100


@pytest.mark.parametrize("scope", ["impala.res", "impala.pool",
                                   "impala.conv", "impala.head"])
def test_scope_time_matches_the_trace_read_directly(recorded, scope):
    """A reader's scope time equals ``chipbench.scopes.scope_time`` over
    the trace's own op metadata, per launch of the step."""
    summary = recorded.trace

    def in_step(n):
        return n.startswith(scopes.ENCODE_STEP)

    t, _ = scopes.scope_time(summary, scopes.stacks(TRACE, summary), scope,
                             module=in_step)
    _, launches = scopes.module_ops_time(summary, in_step)
    assert encode_impala.scope_us(recorded, scope) == pytest.approx(
        1e6 * t / launches)


def test_readers_read_nothing_without_a_trace(recorded):
    ctx = run.Context(recorded.cell, {"metrics": {"frames_per_s": 1.0},
                                      "counters": {}}, None, {})
    for name in READERS:
        assert _read(ctx, name) is None
