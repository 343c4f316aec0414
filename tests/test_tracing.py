"""The program's trace names (repro.tracing): the device scopes land in
the compiled program's ``op_name`` metadata, the serve path's host spans
land in a ``jax.profiler`` trace with the ids of the requests they served,
and nothing outside the registry names a scope or a span."""
import re
import threading
import time
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import tracing  # noqa: E402
from repro.deploy import Deployment, DeploymentConfig  # noqa: E402
from repro.kernels.miniconv_pass import miniconv_encoder_stream  # noqa: E402
from repro.serving.realfleet import FleetClient, WorkerServer  # noqa: E402

SRC = Path(__file__).resolve().parents[1] / "src"
LAYERS = [
    {"kernel": 4, "stride": 2, "c_in": 9, "c_out": 16, "activation": "relu"},
    {"kernel": 3, "stride": 2, "c_in": 16, "c_out": 16, "activation": "relu"},
    {"kernel": 3, "stride": 2, "c_in": 16, "c_out": 4,
     "activation": "sigmoid"}]
ENCODE_SCOPES = ("miniconv.input", "miniconv.weights", "miniconv.s2d",
                 "miniconv.head_tile", "miniconv.kernel", "miniconv.out")


def _deployment(backend="fused+head", **kw):
    return Deployment.build(DeploymentConfig.from_dict({
        "spec": {"layers": LAYERS}, "in_h": 16, "in_w": 16, "head_dim": 32,
        "head_act": "relu", "codec": "uint8", "max_batch": 2,
        "backend": backend, "interpret": True, **kw}))


def _op_names(fn, *args) -> set:
    """The ``op_name`` of every instruction of ``fn``'s compiled HLO."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _under(names, *stack) -> bool:
    """Whether some op_name holds the scopes of ``stack`` in that order."""
    pattern = re.compile(".*".join(re.escape(s + "/") for s in stack))
    return any(pattern.search(n) for n in names)


@pytest.fixture(scope="module")
def dep():
    return _deployment()


@pytest.fixture(scope="module")
def params(dep):
    return dep.init(jax.random.PRNGKey(0))


def test_encode_step_carries_its_scopes(dep, params):
    names = _op_names(dep.encoder.apply, params, jnp.zeros((2, 16, 16, 9)))
    assert _under(names, "jit(encoder_apply)", "miniconv.encode",
                  "jit(_fused_launch)", "miniconv.kernel")
    for name in ENCODE_SCOPES:
        assert _under(names, "miniconv.encode", name), name


def test_folded_step_carries_the_s2d_scope():
    """At 84x84 the stride-2 first layer is folded space-to-depth: its
    input's fold sits under miniconv.input and its weights' under
    miniconv.weights, both as miniconv.s2d."""
    dep = _deployment(in_h=84, in_w=84)
    assert dep.plan.fold == 2
    params = jax.eval_shape(dep.init, jax.random.PRNGKey(0))
    names = _op_names(dep.encoder.apply, params,
                      jax.ShapeDtypeStruct((8, 84, 84, 9), jnp.float32))
    assert _under(names, "miniconv.encode", "miniconv.input",
                  "miniconv.s2d")
    assert _under(names, "miniconv.encode", "miniconv.weights",
                  "miniconv.s2d")


def test_split_encode_step_carries_its_scopes():
    dep = _deployment(backend="fused", head_placement="server")
    params = dep.init(jax.random.PRNGKey(1))
    names = _op_names(dep.encoder.apply, params, jnp.zeros((2, 16, 16, 9)))
    for name in ("miniconv.input", "miniconv.kernel", "split.project"):
        assert _under(names, "miniconv.encode", name), name


def test_streamed_launch_carries_its_scopes(dep, params):
    edge = params["edge"]
    ws = [edge[f"layer{i}"]["kernel"] for i in range(3)]
    bs = [edge[f"layer{i}"]["bias"] for i in range(3)]
    head = params["server"]["proj"]

    def stream(x):
        return miniconv_encoder_stream(
            x, ws, bs, dep.plan, chunk_b=2, head_w=head["kernel"],
            head_b=head["bias"], interpret=True, pipelined=True)

    names = _op_names(stream, jnp.zeros((3, 16, 16, 9)))
    for name in ENCODE_SCOPES:
        assert _under(names, "jit(_fused_launch)", name), name


def test_server_half_carries_its_scopes(dep, params):
    edge = dep.split.edge_step(params["edge"], jnp.zeros((1, 16, 16, 9)))
    batch = {k: jnp.stack([v, v]) for k, v in edge.items()}
    names = _op_names(dep.server_batch_fn(params), batch)
    assert _under(names, "vmap(wire.decode)")
    assert _under(names, "split.project")


def test_unregistered_names_are_refused():
    assert "miniconv.s2d" in tracing.SCOPES
    with tracing.scope("miniconv.s2d"):
        pass
    with pytest.raises(ValueError, match="unregistered scope"):
        tracing.scope("miniconv.layer0")
    with pytest.raises(ValueError, match="unregistered span"):
        tracing.span("serve.everything", req_id=1)
    with tracing.scope("miniconv.kernel"), tracing.span("serve.batch", n=1):
        pass


def test_every_scope_and_span_goes_through_the_registry():
    """No module but repro.tracing calls jax.named_scope or
    TraceAnnotation, and every literal name passed to scope() or span()
    is registered."""
    used = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        if path.name != "tracing.py" or path.parent.name != "repro":
            assert not re.search(r"named_scope\(|TraceAnnotation\(", text), \
                path
        used |= {(kind, name) for kind, name in re.findall(
            r'\b(scope|span)\("([^"]+)"', text)}
    assert used, "no scope or span found in src/"
    for kind, name in used:
        registry = tracing.SCOPES if kind == "scope" else tracing.SPANS
        assert name in registry, (kind, name)
    assert {name for kind, name in used if kind == "scope"} == set(
        tracing.SCOPES)
    assert {name for kind, name in used if kind == "span"} == set(
        tracing.SPANS)


def _host_events(trace_dir: Path) -> list:
    from jax.profiler import ProfileData
    (xplane,) = trace_dir.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(xplane))
    return [(e.name, dict(e.stats), e.start_ns, e.end_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_worker_server_spans_name_the_requests_they_served(tmp_path):
    """Under the profiler, each micro-batch writes ``serve.batch`` with
    the ids of the requests it answered, around its stack, device, fetch
    and send spans; each client attempt writes ``fleet.request`` with its
    id and client."""
    in_service, release = threading.Event(), threading.Event()

    def slow_double(stacked):
        in_service.set()
        release.wait(5.0)
        return stacked["data"] * 2.0

    ws = WorkerServer(slow_double, max_batch=8)
    fc = FleetClient([ws.start()], timeout_s=10.0, retries=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        threads = [threading.Thread(target=fc.request, args=(
            {"data": np.full((2,), float(c), np.float32)},),
            kwargs={"client": c}) for c in range(4)]
        threads[0].start()
        assert in_service.wait(5.0)
        for t in threads[1:]:          # queued behind the first batch
            t.start()
        deadline = time.monotonic() + 5.0
        while ws._q.qsize() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        release.set()
        for t in threads:
            t.join(10.0)
    finally:
        # the serve loop exits only after its last ``serve.batch`` has
        # closed, which the clients' answers precede
        fc.shutdown()
        ws.join(5.0)
        jax.profiler.stop_trace()
    assert not ws.is_alive()
    assert ws.batch_sizes == [1, 3]
    events = _host_events(tmp_path)
    batches = [(st, s, e) for name, st, s, e in events
               if name == "serve.batch"]
    assert sorted(st["n"] for st, _, _ in batches) == [1, 3]
    served = sorted(int(i) for st, _, _ in batches
                    for i in str(st["req_ids"]).split())
    assert served == [0, 1, 2, 3]
    assert all(st["wait_us"] >= 0 for st, _, _ in batches)
    for child in ("serve.stack", "serve.device", "serve.fetch",
                  "serve.send"):
        inside = [(s, e) for name, _, s, e in events if name == child]
        assert len(inside) == 2, child
        assert all(any(b0 <= s and e <= b1 for _, b0, b1 in batches)
                   for s, e in inside), child
    requests = {(st["req_id"], st["client"]) for name, st, _, _ in events
                if name == "fleet.request"}
    assert {i for i, _ in requests} == {0, 1, 2, 3}
    assert {c for _, c in requests} == {0, 1, 2, 3}
    assert any(name == "serve.admit" for name, _, _, _ in events)


def test_serve_device_span_counts_the_buffers(tmp_path):
    """``serve.device`` carries ``buffers``, the host arrays a micro-batch
    copies to the device: 1 for a fleet worker, which packs the uint8
    codec's three leaves into one block, and one per leaf for a plain
    ``serve_batch_fn`` handed the stacked dict."""
    from repro.serving.realfleet import _build_worker
    dep = _deployment("xla")
    params = jax.tree.map(np.asarray, dep.init(jax.random.PRNGKey(0)))
    payload = {k: np.asarray(v) for k, v in dep.split.edge_step(
        params["edge"], jnp.zeros((1, 16, 16, 9))).items()}
    assert len(payload) == 3
    block = _build_worker(dep.config.to_dict(), params, 2)
    per_leaf = WorkerServer(dep.server_batch_fn(params), max_batch=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for ws in (block, per_leaf):
            fc = FleetClient([ws.start()], timeout_s=10.0, retries=0)
            fc.request(payload)
            fc.shutdown()
            ws.join(5.0)
    finally:
        jax.profiler.stop_trace()
    buffers = [st["buffers"] for name, st, _, _ in
               sorted(_host_events(tmp_path), key=lambda e: e[2])
               if name == "serve.device"]
    assert buffers == [1, 3]
