"""From a profiler trace to device time: busy union, idle share, time by
kernel name, the top device operations and the idle gaps by what the
host was doing.

Two stages, so that the second can be checked on a small recorded
trace: :func:`load` reads the ``.xplane.pb`` that ``jax.profiler``
writes into plain event lists (:func:`save` / :func:`read` keep them as
compressed JSON), and :func:`reduce` turns event lists into numbers.
Times are in nanoseconds on the trace's own clock, which the host
annotations (``bench.*``) and the device operations share.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass, field

# the line of a device plane that holds one event per operation run
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = HOST_PREFIX + "traced_window"


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU")


def op_name(event_name: str) -> str:
    """An operation event's HLO instruction name: its text up to " = "
    (the rest lists operands, which may name other operations)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` -> events:
    ``{"devices": {plane: [[name, start_ns, end_ns], ...]},
    "host": [[name, start_ns, end_ns], ...]}`` where host spans are the
    harness's own ``bench.*`` annotations."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if _device_plane(plane.name):
            ops = [[op_name(e.name), e.start_ns, e.end_ns]
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend([e.name, e.start_ns, e.end_ns]
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(spans) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(spans, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def self_times(events, lo, hi):
    """(name, seconds in ``[lo, hi]`` not covered by operations nested
    in it) per event: a loop or conditional that encloses other
    operations keeps only its own time."""
    evs = sorted(((max(s, lo), min(e, hi), n) for n, s, e in events
                  if e > lo and s < hi), key=lambda t: (t[0], -t[1]))
    own = [e - s for s, e, _ in evs]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    return [(n, max(d, 0.0)) for (_, _, n), d in zip(evs, own)]


@dataclass
class Reduced:
    """Device time over one traced window, averaged over the devices."""

    window_s: float
    busy_s: float
    kernel_s: dict = field(default_factory=dict)   # group -> seconds
    top_ops: list = field(default_factory=list)    # [[name, s], ...]
    idle_gaps: list = field(default_factory=list)  # [[host span, s], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(events: dict, name: str) -> tuple[float, float]:
    """(start, end) of the host span ``name`` (the last one recorded)."""
    spans = [(s, e) for n, s, e in events["host"] if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[-1]


def reduce(events: dict, window: tuple[float, float],
           kernels: dict[str, tuple[str, ...]] | None = None,
           top: int = 10) -> Reduced:
    """Busy union, kernel time per group (events whose name contains one
    of the group's patterns), the ``top`` device operations by their own
    time (:func:`self_times`) and
    the ``top`` idle gaps by the innermost ``bench.*`` host span around
    each gap's middle (the traced window's own span aside); all inside
    ``window`` and averaged over the devices that ran anything."""
    lo, hi = window
    kernels = kernels or {}
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        raise ValueError("no device operation in the trace")
    n = len(devices)
    busy = 0.0
    ksum = {g: 0.0 for g in kernels}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    host = sorted(((s, e, name) for name, s, e in events["host"]
                   if name != WINDOW), key=lambda t: t[1] - t[0])
    for evs in devices.values():
        spans = _clip([(s, e) for _, s, e in evs], lo, hi)
        merged = union(spans)
        busy += sum(e - s for s, e in merged)
        for name, d in self_times(evs, lo, hi):
            ops[name] = ops.get(name, 0.0) + d
            for g, pats in kernels.items():
                if any(p in name for p in pats):
                    ksum[g] += d
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                label = next((nm for s, e, nm in host if s <= mid <= e),
                             "outside any bench span")
                gaps[label] = gaps.get(label, 0.0) + (b - a)
    ns = 1e9 * n
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / ns,
        kernel_s={g: v / ns for g, v in ksum.items()},
        top_ops=[[k, v / ns] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / ns] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]])
