"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (JAX start, weights and inputs made on the device from the seed,
the program built and every shape of the cell warmed) is timed from
process start as ``setup_s``.  The window then runs for ``--seconds``;
nothing compiles inside it, and the count of compilations there is
printed.  With ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are read from its trace; with ``--trace 0`` the
end-to-end metrics are reported.  Once the window has closed, the peak
device memory is read, the program's state is freed, and what the window
produced is compared with the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  Where JAX finds no TPU, or fewer chips than the cell needs, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import common, manifest, trace  # noqa: E402
from chipbench.common import log  # noqa: E402

# events JAX records when it lowers or compiles a program
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def find_chips(n: int):
    """The first ``n`` TPU devices; exits without a result otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); no result")
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}; no result")
    return devices[:n]


def enable_compile_cache(checkout: Path) -> str:
    """The persistent compilation cache, with every program in it:
    ``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``
    (a fixed path: the path is part of an entry's key)."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(checkout / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileCounter:
    """Counts lowerings and compilations while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, window: dict, summary, peaks: dict):
        self.cell = cell
        self.config = cell.config
        self.metrics = window["metrics"]      # end-to-end, this traced run
        self.counters = window["counters"]
        self.trace = summary
        self.peaks = peaks


def peaks_for(kind: str) -> dict:
    table = json.loads((manifest.BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}; "
                         f"the table has {', '.join(table)}")
    return table[kind]


def profile_options(traffic: dict):
    """The profiler's options for a traced run: JAX's defaults, unless the
    traffic file sets ``python_tracer_level`` (0 leaves Python function
    calls out of the trace, whose cost on every call would change a
    host-bound path's timing)."""
    if "python_tracer_level" not in traffic:
        return None
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(traffic["python_tracer_level"])
    return options


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(argv=None, *, checkout: Path = CHECKOUT,
        require_chip: bool = True) -> dict:
    """One run; returns the result object.  ``require_chip=False`` lets a
    test drive a run on the CPU: it keeps no compile cache, takes no trace
    and reads no peaks."""
    args = parse(argv)
    cell = manifest.cell(args.workload, checkout)
    import jax
    before = jax.config.jax_default_matmul_precision
    devices = configure(cell, checkout, require_chip)
    try:
        return _run(args, cell, devices, checkout, require_chip)
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def configure(cell, checkout: Path = CHECKOUT, require_chip: bool = True):
    """The cell's devices, the compile cache (on the chip only) and the
    configuration's matmul precision; returns the devices."""
    import jax
    devices = find_chips(cell.chips) if require_chip else \
        jax.devices()[:cell.chips]
    if require_chip:
        log(f"compile cache: {enable_compile_cache(checkout)}")
    # the program leaves the server half's matmuls to JAX's default; set
    # process-wide, since the fleet serves from threads of its own
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    return devices


def _run(args, cell, devices, checkout: Path, require_chip: bool) -> dict:
    import jax
    log(f"cell {cell.name}: {cell.chips} x {devices[0].device_kind}, "
        f"seed {args.seed}")
    kind = manifest.kind_module(cell.kind)
    spans = common.Spans() if require_chip else common.NoSpans()
    counter = CompileCounter()
    work = kind.build(cell, args.seed, spans)
    setup_s = time.monotonic() - T0
    log(f"set-up {setup_s:.3f} s")

    trace_dir = checkout / ".chipbench_trace"
    traced = bool(args.trace) and require_chip
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=profile_options(
                                     cell.traffic))
    counter.armed = True
    try:
        with spans.span("window"):
            window = work.window(args.seconds)
    finally:
        counter.armed = False
        if traced:
            jax.profiler.stop_trace()
    log(f"compilations inside the window: {counter.count}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    work.release()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"attempted": int(window["attempted"]),
              "failed": int(window["failed"])}
    if args.trace:
        summary = None
        if traced:
            summary = trace.reduce(trace.find_xplane(trace_dir),
                                   n_devices=cell.chips)
            shutil.rmtree(trace_dir, ignore_errors=True)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
        ctx = Context(cell, window, summary,
                      peaks_for(devices[0].device_kind) if require_chip
                      else {})
        metrics = {}
        for m in cell.per_layer:
            value = manifest.reader(cell.reader_path(m["name"]))(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and traced:
        result["breakdown"] = summary.breakdown()

    limits = cell.traffic["limits"]
    checks = {}
    for c in work.check():
        checks[c["name"]] = {"value": c["value"],
                             "limit": limits[c["name"]]}
    correct = result["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    log(f"failed {result['failed']} of {result['attempted']}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return {"correct": correct, **result, "checks": checks}


def main(argv=None) -> None:
    print(json.dumps(run(argv)), flush=True)


if __name__ == "__main__":
    main()
