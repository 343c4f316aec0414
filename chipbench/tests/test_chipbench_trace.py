"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e: four blocked launches of the fused encoder+head
kernel (84x84x9, batch 8) inside a ``window`` span, each launch in a
``dispatch`` span and its wait in a ``host_sync`` span."""
from pathlib import Path

import pytest

from chipbench import manifest, trace

FIXTURE = Path(__file__).parent / "fixtures" / "encode-84c9-4launches.xplane.pb"


def test_union_and_gaps_by_hand():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (9.0, 12.0)]
    assert trace.union_s(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.union_s(iv, 1.5, 3.2) == pytest.approx(0.7)
    assert trace.gaps(iv, 0.0, 10.0) == [(2.0, 3.0), (4.0, 9.0)]
    assert trace.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_summary_by_hand():
    s = trace.Summary(
        window=(0.0, 10.0),
        ops=[[("k", 1.0, 2.0), ("k", 4.0, 6.0), ("copy", 5.0, 7.0)]],
        modules=[[("jit_f", 1.0, 2.0), ("jit_f", 4.0, 7.0)]],
        spans=[("window", 0.0, 10.0), ("host_sync", 2.0, 3.5),
               ("dispatch", 3.5, 4.0), ("dispatch", 7.0, 7.5)])
    assert s.busy_s == pytest.approx(4.0)
    assert s.idle_s == pytest.approx(6.0)
    assert s.op_time(lambda n: n == "k") == (pytest.approx(3.0), 2)
    assert s.module_time(lambda n: "jit" in n) == (pytest.approx(4.0), 2)
    assert s.top_ops() == [["k", 3.0], ["copy", 2.0]]
    # gaps: (0,1) none, (2,4) mostly host_sync, (7,10) mostly uncovered
    assert s.idle_gaps() == [["none", 3.0], ["host_sync", 2.0],
                             ["none", 1.0]]
    assert s.idle_gaps(least_s=1.5) == [["none", 3.0], ["host_sync", 2.0]]
    assert s.program_spans == [] and s.program_spans_named("serve.batch") \
        == []


def test_program_spans_by_hand():
    s = trace.Summary(
        window=(1.0, 5.0), ops=[[("k", 1.0, 2.0)]], modules=[[]],
        spans=[("window", 1.0, 5.0)],
        program_spans=[("serve.batch", 0.5, 1.5, {"n": 1}),
                       ("serve.batch", 2.0, 2.5, {"n": 2, "wait_us": 30}),
                       ("serve.device", 2.1, 2.2, {}),
                       ("serve.batch", 4.9, 5.2, {"n": 3}),
                       ("serve.batch", 5.0, 5.1, {"n": 4})])
    # a span belongs to the window it starts in; the harness's are apart
    assert s.program_spans_named("serve.batch") == [
        (2.0, 2.5, {"n": 2, "wait_us": 30}), (4.9, 5.2, {"n": 3})]
    assert s.program_spans_named("serve.device") == [(2.1, 2.2, {})]
    assert s.idle_gaps() == [["none", 3.0]]


def _fixture_ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def test_recorded_trace():
    s = trace.reduce(FIXTURE)
    shift = round(s.clock_shift_s * 1e9)
    ops = [(name, a + shift, b + shift) for name, a, b in _fixture_ops()]
    lo, hi = (round(t * 1e9) for t in s.window)
    # busy by counting covered nanoseconds between every boundary
    cuts = sorted({lo, hi} | {t for _, a, b in ops for t in (a, b)
                              if lo < t < hi})
    busy = sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s0 <= a and b <= e0 for _, s0, e0 in ops))
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-6)
    assert 0 < s.busy_s < s.window_s
    reader = manifest.BENCH_DIR / "metrics" / "miniconv_fused_roofline.py"
    kernel = manifest.reader(reader).__globals__["KERNEL"]
    kernel_s, n = s.op_time(lambda name: kernel in name)
    assert n == 4 and s.clock_shift_s != 0.0
    # after the shift every program runs inside the launch that asked for it
    launches = [(a, b) for name, a, b in s.spans if name == "dispatch"]
    syncs = [b for name, a, b in s.spans if name == "host_sync"]
    for (_, start, end), (d0, _), s1 in zip(s.modules[0], launches, syncs):
        assert d0 <= start and end <= s1
    assert kernel_s == pytest.approx(
        sum(b - a for name, a, b in ops if kernel in name) * 1e-9)
    kernel_op = next(name for name, _, _ in ops if kernel in name)
    assert s.top_ops()[0][0] == trace.short_name(kernel_op)
    gaps = s.idle_gaps()
    assert gaps and all(g[1] > 0 for g in gaps)
    assert {g[0] for g in gaps} <= {"dispatch", "host_sync", "none"}
