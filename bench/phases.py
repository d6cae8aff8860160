"""The program's own spans and scopes in a profiler trace.

The program marks its host work with ``gson.*`` spans
(``repro.utils.timing.span``: profiler annotations with ids, such as
``gson.superstep``, ``gson.step.wait``, ``gson.readback``) and the
phases of its device programs with ``gson.*`` named scopes
(``gson.sample``, ``gson.find_winners``, ``gson.update``, ``gson.tail``,
``gson.refresh``, ``gson.check``). This module extends
:mod:`bench.trace` without changing it:

- :func:`load` keeps each device operation's scope path (the op_name
  XLA gives it) as a fourth field, and the program's ``gson.*`` host
  spans, with their ids as a fourth field, beside the harness's
  ``bench.*`` ones. :func:`plain` gives the three-field view that
  :func:`bench.trace.reduce` reads; :func:`bench.trace.read` reads
  either.
- :func:`reduce` adds, to what :func:`bench.trace.reduce` gives, the
  device self time per innermost scope, and the host numbers of the
  supersteps in the window. Idle gaps are labelled by the innermost
  span of either prefix.

A fused operation takes the scope of its root: XLA fuses across scope
boundaries and gives the fusion the op_name of the instruction at its
root, so a fusion that ends a phase counts wholly in that phase. An
operation XLA gives no op_name (on a TPU v5e: scatter fusions, sorts,
copies, loop control) counts in no phase.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field

from bench import trace

SPAN_PREFIX = "gson."
SCOPE = re.compile(r"gson\.\w+")
# the stat of an operation's event metadata that holds its op_name
SCOPE_STAT = "tf_op"


def scope_of(path: str | None) -> str | None:
    """The innermost ``gson.*`` scope of an operation's scope path."""
    found = SCOPE.findall(path or "")
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# the scope paths: ``jax.profiler.ProfileData`` gives an event's own stats
# but not those of its metadata, where a TPU operation's op_name is kept,
# so the few message types needed are read from the protobuf wire format
# (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5, maps of key = 1 to value = 2; XEventMetadata.name =
# 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7)

def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; a length-delimited value
    is a view of its bytes, a varint an int, a fixed one skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, v


def _map(entries) -> dict:
    out = {}
    for e in entries:
        f = dict(_fields(e))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(path: str) -> dict:
    """{device plane: {operation event name: its scope path}} of an
    ``.xplane.pb`` (the ``tf_op`` stat of each operation's metadata)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not trace._device_plane(name):
            continue
        stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                      for k, v in _map(stats).items()}
        want = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        ops = out.setdefault(name, {})
        for meta in _map(events).values():
            fields = list(_fields(meta))
            ev = next((_text(v) for g, v in fields if g == 2), "")
            for g, stat in fields:
                st = dict(_fields(stat)) if g == 5 else {}
                if st.get(1) in want:
                    ops.setdefault(ev, _text(st[5]) if 5 in st
                                   else stat_names.get(st.get(7), ""))
    return out


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` -> events:
    ``{"devices": {plane: [[name, start_ns, end_ns, scope_path], ...]},
    "host": [[name, start_ns, end_ns, ids], ...]}`` with the host spans
    of both prefixes (``ids`` is ``{}`` where a span has none)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    scopes = op_scopes(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if trace._device_plane(plane.name):
            paths = scopes.get(plane.name, {})
            devices[plane.name] = [
                [trace.op_name(e.name), e.start_ns, e.end_ns,
                 paths.get(e.name, "")]
                for line in plane.lines if line.name == trace.OP_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend([e.name, e.start_ns, e.end_ns, dict(e.stats)]
                        for line in plane.lines for e in line.events
                        if e.name.startswith((trace.HOST_PREFIX,
                                              SPAN_PREFIX)))
    return {"devices": devices, "host": host}


def plain(events: dict) -> dict:
    """Three-field events, as :mod:`bench.trace` reads them."""
    return {"devices": {k: [ev[:3] for ev in v]
                        for k, v in events["devices"].items()},
            "host": [ev[:3] for ev in events["host"]]}


@dataclass
class Scoped(trace.Reduced):
    """:class:`bench.trace.Reduced`, and the program's phases: device
    seconds per innermost scope and, over the ``gson.superstep`` spans in
    the window, their count, their host seconds on the critical path,
    the ``gson.readback`` spans, and the seconds in which the device
    was idle while the host was not in ``gson.step.wait``."""

    scope_s: dict = field(default_factory=dict)
    supersteps: int = 0
    session_host_s: float = 0.0
    readbacks: int = 0
    host_idle_s: float = 0.0


def _overlap(a, b) -> float:
    """Length common to two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _length(spans) -> float:
    return sum(e - s for s, e in spans)


def reduce(events: dict, window: tuple[float, float],
           kernels: dict | None = None, top: int = 10) -> Scoped:
    """:func:`bench.trace.reduce` of the three-field view (its idle gaps
    labelled by the innermost span of either prefix), plus the phases:
    ``scope_s`` maps each innermost ``gson.*`` scope to its operations'
    self time; operations in no scope, and those XLA gives no op_name,
    are left out.

    A superstep's host seconds are those of its ``gson.superstep`` span
    in which the host is not in ``gson.step.wait`` and the device is
    idle: the host's own work on the job's critical path, wherever in
    the step the host blocks on the device."""
    lo, hi = window
    red = trace.reduce(plain(events), window, kernels, top)
    devices = {k: v for k, v in events["devices"].items() if v}
    scope_s: dict[str, float] = {}
    spans: dict[str, list] = {}
    for name, s, e, *_ in events["host"]:
        if lo <= 0.5 * (s + e) <= hi:
            spans.setdefault(name, []).append((s, e))
    waits = trace.union(trace._clip(spans.get("gson.step.wait", []),
                                    lo, hi))
    host_idle, busy_any = 0.0, []
    for evs in devices.values():
        for scope, d in trace.self_times(
                [(scope_of(ev[3] if len(ev) > 3 else None), ev[1], ev[2])
                 for ev in evs], lo, hi):
            if scope is not None:
                scope_s[scope] = scope_s.get(scope, 0.0) + d
        busy = trace.union(trace._clip([ev[1:3] for ev in evs], lo, hi))
        busy_any += busy
        idle = (hi - lo) - _length(busy)
        host_idle += idle - (_length(waits) - _overlap(waits, busy))
    steps = trace.union(trace._clip(spans.get("gson.superstep", []),
                                    lo, hi))
    held = trace.union(busy_any + waits)
    n = 1e9 * len(devices)
    return Scoped(
        **vars(red),
        scope_s={g: v / n for g, v in scope_s.items()},
        supersteps=len(spans.get("gson.superstep", [])),
        session_host_s=(_length(steps) - _overlap(steps, held)) / 1e9,
        readbacks=len(spans.get("gson.readback", [])),
        host_idle_s=host_idle / n)


# ---------------------------------------------------------------------------
# for the readers of bench/metrics

@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float) -> Scoped | None:
    events = load(os.path.dirname(path))
    try:
        window = trace.window_of(plain(events), trace.WINDOW)
    except ValueError:
        return None
    return reduce(events, window)


def of(ctx) -> Scoped | None:
    """The phases of the run a reader's ``ctx`` describes: ``ctx.phases``
    where the caller reduced them, else the newest trace the harness
    left under ``.bench_out/trace`` whose traced window is the one
    ``ctx.trace`` was reduced over; None where there is no such trace."""
    if getattr(ctx, "phases", None) is not None:
        return ctx.phases
    from bench.harness import OUT
    files = sorted(glob.glob(os.path.join(OUT, "trace", "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    got = _reduced(files[-1], os.path.getmtime(files[-1]))
    if got is None or abs(got.window_s - ctx.trace.window_s) > 1e-9:
        return None
    return got


def scope_ms_per_iteration(ctx, scopes: dict) -> float | None:
    """Device milliseconds per traced iteration under the scopes of the
    one group of ``scopes`` ({group: scope names}, as a reader declares
    it), or None where the trace has no operation in them."""
    (_, names), = scopes.items()
    got = of(ctx)
    iters = sum(s["iterations"] for s in ctx.supersteps)
    if got is None or not iters:
        return None
    t = sum(got.scope_s.get(n, 0.0) for n in names)
    return 1e3 * t / iters if t > 0 else None
