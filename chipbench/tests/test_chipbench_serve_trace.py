"""The program's spans in the trace reduction, and the serve cell's readers
of them, on a small trace recorded on a TPU v5e: 4 clients at 10 Hz
(``chipbench/loadgen.py``, a process of its own) through the fleet's
sockets to one in-process replica for half a second, inside a ``window``
span, with the Python tracer off as the serve cell traces.  The reduction keeps each span of ``repro.tracing.SPANS`` with
its arguments beside the harness's spans, which, with the window and the
idle gaps' names, read as they did before it kept them (pinned from the
reduction without program spans, on this trace and the scoped encode
trace)."""
import json
from pathlib import Path

import pytest

from chipbench import manifest, trace
from chipbench.common import SPAN_NAMES

FIXTURES = Path(__file__).parent / "fixtures"
SERVE = FIXTURES / "serve-84c9-4clients.xplane.pb"
SCOPED = FIXTURES / "encode-84c9-scoped.xplane.pb"
SERVE_CELL = "serve.mc4-84-c9.split-10hz"

# The harness's readings of each trace by the reduction as it was before
# it kept the program's spans.
PINNED = {
    SERVE: {
        "busy_s": 6.227399999964023e-05, "window_s": 0.72974348,
        "clock_shift_s": 0.001167786000000004, "n_spans": 1,
        "idle_gaps": [["none", 0.252806953], ["none", 0.02604789099999999],
                      ["none", 0.02545101999999999],
                      ["none", 0.02537634700000002],
                      ["none", 0.025305889000000026]]},
    SCOPED: {
        "busy_s": 0.0016815310000000208, "window_s": 0.004889309000000001,
        "clock_shift_s": 0.0014185410000000023, "n_spans": 9,
        "idle_gaps": [["host_sync", 0.0009756130000000002],
                      ["host_sync", 0.0007186159999999983],
                      ["host_sync", 0.000700684],
                      ["host_sync", 0.0006327329999999964],
                      ["dispatch", 0.00018002899999999822]]},
}


@pytest.mark.parametrize("path", sorted(PINNED), ids=lambda p: p.stem)
def test_harness_readings_unchanged(path):
    s = trace.reduce(path)
    want = PINNED[path]
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert s.clock_shift_s == pytest.approx(want["clock_shift_s"],
                                            rel=1e-12)
    assert len(s.spans) == want["n_spans"]
    assert {name for name, _, _ in s.spans} <= set(SPAN_NAMES)
    gaps = s.idle_gaps(n=len(want["idle_gaps"]))
    assert [k for k, _ in gaps] == [k for k, _ in want["idle_gaps"]]
    assert [v for _, v in gaps] == pytest.approx(
        [v for _, v in want["idle_gaps"]], rel=1e-12)


def test_encode_traces_hold_no_program_span():
    for path in (SCOPED, FIXTURES / "encode-84c9-4launches.xplane.pb"):
        assert trace.reduce(path).program_spans == []


def _host_events(name):
    """``(start, end, stats)`` of the host events named ``name`` that start
    inside the harness's ``window`` event, read straight from the trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(SERVE))
    events = [(e.name, e.start_ns * 1e-9,
               (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events]
    (lo, hi), = [(s, e) for n, s, e, _ in events if n == "window"]
    return [(s, e, a) for n, s, e, a in events if n == name and lo <= s < hi]


def test_program_spans_kept_with_their_arguments():
    from repro.tracing import SPANS
    s = trace.reduce(SERVE)
    names = {name for name, *_ in s.program_spans}
    # the clients run in a process of their own: no fleet.request here
    assert names == set(SPANS) - {"fleet.request"}
    assert not names & {name for name, _, _ in s.spans}
    batches = s.program_spans_named("serve.batch")
    assert batches == _host_events("serve.batch")
    answered = [int(i) for _, _, a in batches
                for i in str(a["req_ids"]).split()]
    assert sum(a["n"] for _, _, a in batches) == len(answered) == 20
    assert len(set(answered)) == 20
    assert all(a["wait_us"] >= 0 for _, _, a in batches)
    # each call into the server half lies inside its micro-batch
    calls = s.program_spans_named("serve.device")
    assert len(calls) == len(batches)
    for (b0, b1, _), (c0, c1, _) in zip(batches, calls):
        assert b0 <= c0 <= c1 <= b1


class _Ctx:
    """What a reader reads, as a traced run of the serve cell gives it."""

    def __init__(self, summary, batches):
        cell = manifest.cell(SERVE_CELL)
        self.cell, self.config, self.trace = cell, cell.config, summary
        self.peaks = json.loads((manifest.BENCH_DIR / "peaks.json")
                                .read_text())["TPU v5 lite"]
        self.counters = {"batches": batches}
        self.metrics = {}


def _read(metric, ctx):
    return manifest.reader(manifest.BENCH_DIR / "metrics"
                           / f"{metric}.py")(ctx)


def _mean(values):
    return sum(values) / len(values)


@pytest.mark.parametrize("metric,want", [
    ("serve_wait_us", lambda: _mean([a["wait_us"] for _, _, a
                                     in _host_events("serve.batch")])),
    ("serve_batch_us", lambda: 1e6 * _mean([e - s for s, e, _
                                            in _host_events("serve.batch")])),
    ("serve_call_us", lambda: 1e6 * _mean([e - s for s, e, _
                                           in _host_events("serve.device")])),
])
def test_serve_span_readers(metric, want):
    ctx = _Ctx(trace.reduce(SERVE), [1] * 20)
    value = _read(metric, ctx)
    assert value == pytest.approx(want(), rel=1e-12)
    assert 0 < value < 1e6 * ctx.trace.window_s


@pytest.mark.parametrize("metric", ["serve_wait_us", "serve_batch_us",
                                    "serve_call_us"])
def test_serve_span_readers_find_nothing_in_an_encode_trace(metric):
    assert _read(metric, _Ctx(trace.reduce(SCOPED), [])) is None
    assert _read(metric, _Ctx(None, [])) is None


def test_serve_device_ms_reads_the_server_half_alone():
    s = trace.reduce(SERVE)
    modules = {name for dev in s.modules for name, _, _ in dev}
    assert len(modules) == 1 and next(iter(modules)).startswith("jit_fn(")
    module_s, n = s.module_time(lambda name: True)
    assert n == 20
    assert _read("serve_device_ms", _Ctx(s, [1] * 20)) == pytest.approx(
        1e3 * module_s / 20, rel=1e-12)
