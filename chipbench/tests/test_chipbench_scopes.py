"""Step and scope times (``chipbench/scopes.py``) and the readers of
``encode_step_us`` and ``encode_prep_us``, on hand-made intervals and on
two small traces recorded on a TPU v5e, each of four blocked launches of
the fused encoder+head kernel (84x84x9, batch 8): the first of the program
before it had named scopes, the second after.  The readings every other
reader gives of the first are pinned, so that nothing added here moves
them."""
import json
from pathlib import Path

import pytest

from chipbench import manifest, scopes, trace

FIXTURES = Path(__file__).parent / "fixtures"
UNSCOPED = FIXTURES / "encode-84c9-4launches.xplane.pb"
SCOPED = FIXTURES / "encode-84c9-scoped.xplane.pb"
KERNEL = 'custom_call_target="tpu_custom_call"'

# Every reading of the unscoped trace as the benchmark gave it before the
# program had scopes.
PINNED = {
    "busy_s": 0.0016812900000000366, "window_s": 0.010983529999999998,
    "clock_shift_s": 0.001367992999999998,
    "top_ops": [["_fused_launch.1", 0.0015733570000000113],
                ["copy", 7.055900000000476e-05],
                ["pad.3", 2.878700000000234e-05],
                ["reshape.0", 2.588000000004753e-06],
                ["copy_bitcast_fusion", 2.0870000000047795e-06],
                ["pad.2", 1.7239999999946187e-06],
                ["copy_bitcast_fusion.1", 1.0049999999983683e-06],
                ["copy.3", 7.320000000032856e-07],
                ["pad.4", 1.029999999943576e-07],
                ["copy.1", 8.899999999478769e-08]],
    "idle_gaps": [["none", 0.002846317000000001],
                  ["none", 0.0027118090000000025],
                  ["none", 0.001825668999999995],
                  ["none", 0.0017599670000000012],
                  ["dispatch", 0.00015836899999999154]],
    "miniconv_fused_roofline": 0.9507127121247687,
    "device_idle.encode": 84.69262614114008,
    "encode_mfu": 0.04179121381360406,
}


def _in_step(name):
    return name.startswith(scopes.ENCODE_STEP)


class _Ctx:
    """What a reader reads, as a traced run of the encode cell gives it."""

    def __init__(self, summary):
        cell = manifest.cell("encode.mc4-84-c9.b8")
        self.cell, self.config, self.trace = cell, cell.config, summary
        self.peaks = json.loads((manifest.BENCH_DIR / "peaks.json")
                                .read_text())["TPU v5 lite"]
        self.counters = {"batch": 8, "launches": 4}
        self.metrics = {"frames_per_s": 7626.1}


def _read(metric, summary):
    path = manifest.BENCH_DIR / "metrics" / f"{metric}.py"
    return manifest.reader(path)(_Ctx(summary))


@pytest.mark.parametrize("reading", sorted(PINNED))
def test_unscoped_trace_readings_are_pinned(reading):
    s = trace.reduce(UNSCOPED)
    want = PINNED[reading]
    if reading in ("top_ops", "idle_gaps"):
        value = s.breakdown()["device_ops" if reading == "top_ops"
                              else reading]
        assert [k for k, _ in value] == [k for k, _ in want]
        assert [v for _, v in value] == pytest.approx(
            [v for _, v in want], rel=1e-12)
        return
    value = (getattr(s, reading) if hasattr(s, reading)
             else _read(reading, s))
    assert value == pytest.approx(want, rel=1e-12)


def test_op_metadata_names_each_operations_stack():
    s = trace.reduce(UNSCOPED)
    found = scopes.stacks(UNSCOPED, s)
    assert len(found) == 1 and len(found[0]) == len(s.ops[0])
    by_op = {trace.short_name(name): stack
             for (name, _, _), stack in zip(s.ops[0], found[0])}
    assert by_op["_fused_launch.1"] == \
        "jit(encoder_apply)/jit(_fused_launch)/pallas_call"
    assert by_op["pad.3"].startswith("jit(encoder_apply)/jit(_fused_launch)/")
    assert by_op["copy"] == "obs"      # XLA's relayout of the argument


def test_scope_and_step_times_by_hand():
    assert scopes.scope_names("jit(f)/vmap(wire.decode)/mul") == [
        "f", "wire.decode", "mul"]
    s = trace.Summary(
        window=(0.0, 10.0),
        ops=[[("copy", 1.0, 1.5), ("k", 1.5, 3.0), ("pad", 4.0, 4.5),
              ("k", 4.5, 6.0), ("other", 8.0, 9.0)]],
        modules=[[("jit_step(1)", 1.0, 3.0), ("jit_step(1)", 4.0, 6.0),
                  ("jit_other(2)", 8.0, 9.0)]],
        spans=[("window", 0.0, 10.0)])
    found = [["obs", "jit(step)/a.kernel/pallas_call",
              "jit(step)/a.input/pad", "jit(step)/a.kernel/pallas_call",
              "jit(other)/a.kernel/dot"]]
    step = lambda name: name.startswith("jit_step(")  # noqa: E731
    assert scopes.module_ops_time(s, step) == (pytest.approx(4.0), 2.0)
    assert scopes.module_ops_time(s, step, op=lambda n: n == "k") == (
        pytest.approx(3.0), 2.0)
    assert scopes.scope_time(s, found, "a.kernel") == (pytest.approx(4.0), 3)
    assert scopes.scope_time(s, found, "a.kernel", module=step) == (
        pytest.approx(3.0), 2)
    assert scopes.scope_time(s, found, "a.input") == (pytest.approx(0.5), 1)
    # a trace whose stacks could not be read reads no scope
    assert scopes.scope_time(s, [[]], "a.kernel") == (0.0, 0)


def test_step_readers_on_a_program_without_scopes():
    """The trace recorded before the program had scopes: the step is the
    whole busy time, and what lies outside its kernel is read as well."""
    s = trace.reduce(UNSCOPED)
    step = _read("encode_step_us", s)
    assert step == pytest.approx(1e6 * s.busy_s / 4, rel=1e-9)
    kernel_us = 1e6 * PINNED["top_ops"][0][1] / 4
    assert _read("encode_prep_us", s) == pytest.approx(step - kernel_us)


def test_scoped_trace_splits_the_step():
    """On the scoped program the step's time is its kernel scope's plus
    the time outside it, the kernel scope holds just the step's custom
    call, and the step is the device's busy time per launch."""
    s = trace.reduce(SCOPED)
    step_s, launches = scopes.module_ops_time(s, _in_step)
    kernel_s, kernels = scopes.scope_time(
        s, scopes.stacks(SCOPED, s), "miniconv.kernel", module=_in_step)
    call_s, _ = scopes.module_ops_time(s, _in_step,
                                       op=lambda name: KERNEL in name)
    assert launches == 4 and kernels == 4
    assert kernel_s == pytest.approx(call_s, rel=1e-12)
    step, prep = _read("encode_step_us", s), _read("encode_prep_us", s)
    assert step == pytest.approx(1e6 * step_s / launches)
    assert prep + 1e6 * kernel_s / launches == pytest.approx(step)
    assert step == pytest.approx(1e6 * s.busy_s / launches, rel=0.10)
    assert 0 < prep < step


@pytest.mark.parametrize("scope", ["miniconv.encode", "miniconv.input",
                                   "miniconv.weights", "miniconv.head_tile",
                                   "miniconv.kernel"])
def test_scoped_trace_has_time_under_each_scope(scope):
    """Each scope but ``miniconv.out`` holds device time: the slice of the
    projection becomes a layout copy XLA names with no op_name."""
    s = trace.reduce(SCOPED)
    t, n = scopes.scope_time(s, scopes.stacks(SCOPED, s), scope,
                             module=_in_step)
    assert n >= 4 and t > 0


def test_by_scope_table(capsys):
    """The command's table: the step, each scope with time in it, and the
    rest; what no scope holds, with the scopes but the outer one, adds up
    to the step."""
    table = scopes.by_scope(SCOPED)
    assert set(table) == {"step", "miniconv.encode", "miniconv.input",
                          "miniconv.weights", "miniconv.head_tile",
                          "miniconv.kernel", "unscoped"}
    inner = sum(v for k, v in table.items()
                if k not in ("step", "miniconv.encode"))
    assert inner == pytest.approx(table["step"])
    assert table["miniconv.encode"] < table["step"]
    scopes.main([str(SCOPED)])
    out = capsys.readouterr().out.splitlines()
    assert [line.split()[0] for line in out] == list(table)
    # a program without scopes: the whole step under no scope
    bare = scopes.by_scope(UNSCOPED)
    assert list(bare) == ["step", "unscoped"]
    assert bare["unscoped"] == pytest.approx(bare["step"])
