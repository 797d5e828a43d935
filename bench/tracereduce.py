"""Reduce a JAX profiler trace (``.xplane.pb``) of the window to numbers.

The device planes are ``/device:TPU:<n>``; the operations that ran on a
device are the events of their ``XLA Ops`` line.  The host planes carry
the benchmark's ``jax.profiler.TraceAnnotation`` spans (the window, each
unit of work) and JAX's own host events, on the same clock.

    busy_s     union of the device-op intervals inside the window, averaged
               over the devices that ran any
    window_s   length of the window annotation
    top_ops    the ten device operations (by name) that took most time
    idle_gaps  idle device time inside the window, summed by what the host
               was doing in the middle of each gap (its innermost span)
"""
from __future__ import annotations

import collections
import re

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10
#: idle gaps shorter than this are summed under one label
SHORT_GAP_NS = 10_000
#: host lines whose events label idle gaps: the Python thread
HOST_LINES = ("python",)


def _load(path):
    """A ``ProfileData`` of a trace file, or ``path`` itself where it
    already is one."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path) if isinstance(path, str) else path


def merge_intervals(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


_NAME = re.compile(r"%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9\-]*)\(")


def op_label(text: str) -> str:
    """``name:opcode`` of an event named by its HLO instruction text
    (``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12:fusion``);
    other names as they are."""
    name, op = _NAME.match(text), _OPCODE.search(text)
    return f"{name.group(1)}:{op.group(1)}" if name and op else text


def reduce(path, window_name: str) -> dict:
    """Numbers of the window annotated ``window_name``."""
    pd = _load(path)
    window = None
    host = []                                    # (start, end, name)
    device_ops = {}                              # plane -> [(s, e, name)]
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    s = ev.start_ns
                    evs.append((s, s + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                labels = line.name.startswith(HOST_LINES)
                for ev in line.events:
                    s = ev.start_ns
                    if ev.name == window_name and window is None:
                        window = (s, s + ev.duration_ns)
                    elif labels and ev.duration_ns > 0:
                        host.append((s, s + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {window_name!r} annotation in {path}")
    w0, w1 = window
    busy_per_device, gaps_of_first, by_name = [], None, collections.Counter()
    for plane in sorted(device_ops):
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in device_ops[plane]
                   if e > w0 and s < w1]
        if not clipped:
            continue
        merged = merge_intervals((s, e) for s, e, _ in clipped)
        busy_per_device.append(sum(e - s for s, e in merged))
        for s, e, n in clipped:
            by_name[op_label(n)] += e - s
        if gaps_of_first is None:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps_of_first = [(edges[i], edges[i + 1])
                             for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]
    busy = (sum(busy_per_device) / len(busy_per_device)
            if busy_per_device else 0.0)
    idle = collections.Counter()
    host.sort()
    active, i = [], 0
    for s, e in sorted(gaps_of_first or [(w0, w1)], key=lambda g: g[0] + g[1]):
        if e - s < SHORT_GAP_NS:
            idle["gaps under 10 us"] += e - s
            continue
        mid = (s + e) / 2
        while i < len(host) and host[i][0] <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] >= mid]
        label = (min(active, key=lambda h: h[1] - h[0])[2] if active
                 else "no host span")
        idle[label] += e - s
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / 1e9,
        "devices": len(busy_per_device),
        "top_ops": [[n, t / 1e9] for n, t in by_name.most_common(TOP)],
        "idle_gaps": [[n, t / 1e9] for n, t in idle.most_common(TOP)],
    }
