"""Device time of a program's steps, and of the named scopes inside them,
from a JAX profiler trace (``.xplane.pb``).

* A step's time is that of every operation that starts inside one of
  its programs (``XLA Modules`` events), scoped or not: the layout copies
  XLA puts in carry no scope.  It is read from a
  :class:`chipbench.trace.Summary` alone (:func:`module_ops_time`), which
  is all a per-layer metric's reader is given.
* Each device operation carries its HLO ``op_name``, the JAX name stack
  it was traced under, with the program's scopes
  (``repro.tracing.SCOPES``) in it.  The trace keeps it in the ``tf_op``
  stat of the event's metadata, which ``jax.profiler.ProfileData`` does
  not show, so it is read from the file itself (:func:`op_stacks`).

Print each registered scope's device time per launch of a step::

    python3 -m chipbench.scopes TRACE.xplane.pb [MODULE_PREFIX]

``MODULE_PREFIX`` defaults to the encode step's, ``jit_encoder_apply(``.
"""
from __future__ import annotations

import bisect
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace  # noqa: E402
from chipbench.trace import DEVICE_PREFIX, OPS_LINE, Summary  # noqa: E402

ENCODE_STEP = "jit_encoder_apply("


def scope_names(stack: str) -> list[str]:
    """The names in a JAX name stack, each taken out of the transforms
    around it: ``jit(f)/vmap(wire.decode)/mul`` gives ``f``,
    ``wire.decode`` and ``mul``."""
    out = []
    for part in stack.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        out.append(part)
    return out


def _in_modules(summary: Summary, d: int, match) -> list[bool]:
    """Whether each operation of device ``d`` starts inside a program
    whose name ``match`` accepts."""
    mods = sorted((s, e) for name, s, e in summary.modules[d] if match(name))
    starts = [s for s, _ in mods]
    inside = []
    for _, s, _ in summary.ops[d]:
        k = bisect.bisect_right(starts, s) - 1
        inside.append(k >= 0 and s <= mods[k][1])
    return inside


def module_ops_time(summary: Summary, match, op=None) -> tuple[float, float]:
    """Device seconds of the operations that run inside the programs whose
    name ``match`` accepts (with ``op``, only those whose name ``op``
    accepts), and the number of those programs, both per device, within
    the window."""
    lo, hi = summary.window
    total = programs = 0.0
    for d, dev in enumerate(summary.ops):
        programs += sum(1 for name, s, e in summary.modules[d]
                        if match(name) and s < hi and e > lo)
        total += sum(e - s for (name, s, e), ok
                     in zip(dev, _in_modules(summary, d, match))
                     if ok and s < hi and e > lo and (op is None or op(name)))
    return total / len(summary.ops), programs / len(summary.ops)


def scope_time(summary: Summary, stacks: list, name: str,
               module=None) -> tuple[float, int]:
    """Device seconds (averaged over devices) and count of the operations
    whose name stack (``stacks``, per device beside ``summary.ops``) holds
    the scope ``name``, under any transform, within the window; with
    ``module``, only those that run inside a program whose name ``module``
    accepts."""
    lo, hi = summary.window
    total, n = 0.0, 0
    for d, dev in enumerate(summary.ops):
        found = stacks[d] if d < len(stacks) else []
        if len(found) != len(dev):
            continue
        inside = (_in_modules(summary, d, module) if module is not None
                  else [True] * len(dev))
        for (_, s, e), stack, ok in zip(dev, found, inside):
            if ok and s < hi and e > lo and name in scope_names(stack):
                total += e - s
                n += 1
    return total / len(summary.ops), n


# A few fields of the XSpace protobuf (tsl/profiler/protobuf/xplane.proto),
# read by hand: XSpace.planes 1; XPlane.name 2, .lines 3, .event_metadata 4
# and .stat_metadata 5 (maps: key 1, value 2); XLine.name 2, .events 4;
# XEvent.metadata_id 1; XEventMetadata.id 1, .name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .str_value 5,
# .ref_value 7 (a stat metadata whose name is the string).

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a slice of ``buf`` for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _op_name(tf_op: str) -> str:
    """The name stack of a ``tf_op`` stat (``<op_name>:<op_type>``)."""
    return tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op


def op_stacks(path: Path, want) -> dict:
    """For each plane whose name ``want`` accepts: the ``(name, name
    stack)`` of every event of its ``XLA Ops`` line, in the trace's order
    (``ProfileData``'s order too)."""
    out = {}
    for field, plane in _fields(memoryview(Path(path).read_bytes())):
        if field != 1:
            continue
        parts: dict = {}
        for f, v in _fields(plane):
            parts.setdefault(f, []).append(v)
        name = _text(parts[2][0]) if 2 in parts else ""
        if not want(name):
            continue
        stat_names = {}
        for entry in parts.get(5, ()):
            md = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[md.get(1, 0)] = _text(md.get(2, b""))
        meta = {}
        for entry in parts.get(4, ()):
            md = list(_fields(dict(_fields(entry)).get(2, b"")))
            tf_op = ""
            for f, v in md:
                if f != 5:
                    continue
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1, 0)) == "tf_op":
                    tf_op = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            fields = dict(md)
            meta[fields.get(1, 0)] = (_text(fields.get(2, b"")),
                                      _op_name(tf_op))
        events = []
        for line in parts.get(3, ()):
            lf = list(_fields(line))
            if any(f == 2 and _text(v) == OPS_LINE for f, v in lf):
                for f, ev in lf:
                    if f == 4:
                        _, first = next(_fields(ev), (1, 0))
                        mid = first if isinstance(first, int) else 0
                        events.append(meta.get(mid, ("", "")))
        out[name] = events
    return out


def stacks(path: Path, summary: Summary) -> list:
    """Each operation's name stack, per device beside ``summary.ops``
    (reduced from ``path``, whose device planes it takes in the same
    order); a device whose events do not line up with the trace's own list
    of them gets none."""
    by_plane = op_stacks(path, lambda name: name.startswith(DEVICE_PREFIX))
    out = []
    for name, dev in zip(sorted(by_plane), summary.ops):
        found = by_plane[name]
        out.append([st for _, st in found]
                   if [n for n, _ in found] == [n for n, _, _ in dev]
                   else [])
    return out


def by_scope(path: Path, module: str = ENCODE_STEP) -> dict:
    """Device µs per launch of the step whose programs' names start with
    ``module``: the whole step, each registered scope that holds time in
    it, and the operations under none of them."""
    from repro.tracing import SCOPES
    summary = trace.reduce(path)
    found = stacks(path, summary)
    if len(found[0]) != len(summary.ops[0]):
        raise ValueError(f"{path}: the op metadata does not line up with "
                         "the device's events")

    def in_step(name):
        return name.startswith(module)

    step_s, launches = module_ops_time(summary, in_step)
    if launches == 0:
        return {}
    out = {"step": 1e6 * step_s / launches}
    for name in SCOPES:
        t, n = scope_time(summary, found, name, module=in_step)
        if n:
            out[name] = 1e6 * t / launches
    inside = _in_modules(summary, 0, in_step)
    lo, hi = summary.window
    bare = sum(e - s for (_, s, e), stack, ok
               in zip(summary.ops[0], found[0], inside)
               if ok and s < hi and e > lo
               and not set(scope_names(stack)) & set(SCOPES))
    out["unscoped"] = 1e6 * bare / launches
    return out


def main(argv=None) -> None:
    args = sys.argv[1:] if argv is None else argv
    if not 1 <= len(args) <= 2:
        raise SystemExit(__doc__)
    for name, us in by_scope(Path(args[0]), *args[1:]).items():
        print(f"{name:24s} {us:10.3f} us")


if __name__ == "__main__":
    main()
