"""Reduce a profiler trace to busy time, op counts and idle gaps.

The device's ops line nests events: a `while` (and a module wrapper)
spans the fusions it runs.  Summing every event counts the same time
two or three times; so busy time is the UNION of the innermost op
intervals on each device's ops line, clipped to the benchmark's
``bench.window`` span, and op counts count the same innermost events.
An idle gap (window time no innermost op covers) is attributed to the
innermost ``bench.*`` host annotation that covers its middle: what the
benchmark was doing while the device waited.

`reduce` works on plain tuples, so a test can feed it a small recorded
trace; `reduce_dir` reads the ``*.xplane.pb`` a `jax.profiler` trace
wrote.
"""

from __future__ import annotations

import glob
import os
import re

#: the line of a device plane whose events are op executions
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
TOP = 10


def innermost(events):
    """The events of one line that contain no other event of the line.
    `events`: iterable of ``(name, start_ns, end_ns)``; zero-length
    events are dropped."""
    ev = sorted((e for e in events if e[2] > e[1]),
                key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[2]]


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _short(name: str) -> str:
    """An op event's name is its HLO text on the TPU; keep the
    instruction's name and result type (``fusion.12 s32[3145728]``)."""
    m = re.match(r"%?([^\s=]+) = (\S+)", name)
    return f"{m[1]} {m[2]}" if m else name


def reduce(devices, host_spans):
    """`devices`: per chip, the events of its ops line; `host_spans`:
    ``(name, start_ns, end_ns)`` host annotations, ``bench.window``
    among them.  Returns busy and window seconds (busy averaged over
    the chips), innermost op executions per chip, the top ops by time
    and the longest idle gaps with what the host was doing."""
    win = [s for s in host_spans if s[0] == WINDOW]
    if not win or not devices:
        return None
    w0, w1 = win[0][1], win[0][2]
    busy, n_ops, per_op, gaps = [], [], {}, []
    spans = sorted((s for s in host_spans if s[0] != WINDOW),
                   key=lambda s: s[2] - s[1])
    for events in devices:
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in innermost(events)
               if e > w0 and s < w1]
        n_ops.append(len(ops))
        for n, s, e in ops:
            per_op[_short(n)] = per_op.get(_short(n), 0) + (e - s)
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                who = next((s[0] for s in spans if s[1] <= mid < s[2]),
                           "unattributed")
                gaps.append((who, (b - a) / len(devices)))
    k = len(devices)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / k / 1e9, "window_s": (w1 - w0) / 1e9,
            "ops": sum(n_ops) / k,
            "device_ops": [[n, t / k / 1e9] for n, t in top],
            "idle_gaps": [[n, t / 1e9] for n, t in
                          sorted(gaps, key=lambda g: -g[1])[:TOP]]}


def reduce_dir(trace_dir):
    """`reduce` over the xplane file under `trace_dir`: the ops line of
    each accelerator plane, and the ``bench.*`` host annotations."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return reduce(devices, host)
