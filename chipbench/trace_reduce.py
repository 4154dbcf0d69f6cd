"""
From a profiler trace to busy time, idle gaps and top operations.

The reduction works on plain data — ``[(plane, [(line, [(name,
start_ns, duration_ns), ...]), ...]), ...]`` — so a test can hand it a
trace built by hand; :func:`load` turns an ``.xplane.pb`` file into
that form with nothing but JAX.

Busy time of a device is the UNION of the intervals of its operation
line (``XLA Ops`` on a TPU plane), so operations that enclose others (a
``while`` around its body) or overlap count once. Idle is the rest of
the traced span. Each idle gap is labelled by the host span (one of
``span_names``, written into the trace by the program's
``TraceAnnotation`` passthrough) that covers most of it, or ``none``.
"""

import glob
import os
import re

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [
        (plane.name, [
            (line.name, [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                         for ev in line.events])
            for line in plane.lines])
        for plane in data.planes
    ]


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping or
    nested intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def covered(intervals):
    return sum(end - start for start, end in union(intervals))


def gaps(intervals, lo, hi):
    """What ``[lo, hi]`` holds beside the union of ``intervals``."""
    out, at = [], lo
    for start, end in union(intervals):
        start, end = max(start, lo), min(end, hi)
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events):
    """``{name: seconds}`` of each event's duration less what the
    events it encloses cover (a ``while`` op is charged its own
    overhead, its body's operations theirs)."""
    total = {}
    stack = []  # (end, name, start, child_cover)

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, child = stack.pop()
            total[name] = total.get(name, 0.0) + max(end - start - child, 0.0)
            if stack:
                stack[-1][3] += end - start

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([start + dur, name, start, 0.0])
    close(float("inf"))
    return {name: ns / 1e9 for name, ns in total.items()}


def op_name(text):
    """``%fusion.141 = f32[60,20]{1,0} fusion(...)`` -> ``fusion.141
    f32[60,20]``: the trace names an operation by its whole HLO
    instruction; its name and the shape it produces (layouts dropped)
    say what it is where the program names no kernel."""
    name, _, rest = text.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)
    end = rest.find(")") + 1 if rest.startswith("(") else rest.find(" ")
    shape = rest[:end] if end > 0 else rest
    return (name.lstrip("%") + " " + shape).strip()[:80]


def _device_planes(planes):
    out = []
    for name, lines in planes:
        if not name.startswith(DEVICE_PREFIX):
            continue
        ops = [ev for line, evs in lines if line == OP_LINE for ev in evs
               if ev[2] > 0]
        if ops:
            out.append((name, ops))
    return sorted(out)


def _host_spans(planes, span_names):
    return [ev for name, lines in planes
            if not name.startswith(DEVICE_PREFIX)
            for _, evs in lines for ev in evs
            if ev[0] in span_names and ev[2] > 0]


def _label(gap, spans):
    best, best_cover = "none", 0.0
    for name, start, dur in spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(planes, span_names=(), top=10):
    """Busy seconds per device, the traced span, the operations that
    took most device time (self time, summed over devices) and the idle
    gaps of the first device by host span. Returns None when no
    operation ran on a device."""
    devices = _device_planes(planes)
    if not devices:
        return None
    spans = _host_spans(planes, set(span_names))
    starts = [ev[1] for _, ops in devices for ev in ops]
    ends = [ev[1] + ev[2] for _, ops in devices for ev in ops]
    starts += [s[1] for s in spans]
    ends += [s[1] + s[2] for s in spans]
    lo, hi = min(starts), max(ends)
    busy = {name: covered([(s, s + d) for _, s, d in ops]) / 1e9
            for name, ops in devices}
    ops_time = {}
    for _, ops in devices:
        for name, secs in self_times(ops).items():
            name = op_name(name)
            ops_time[name] = ops_time.get(name, 0.0) + secs
    first_ops = devices[0][1]
    by_label = {}
    for gap in gaps([(s, s + d) for _, s, d in first_ops], lo, hi):
        label = _label(gap, spans)
        by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0]) / 1e9

    def ranked(table):
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s_per_device": busy,
        "busy_s": sum(busy.values()) / len(busy),
        "device_ops": ranked(ops_time),
        "idle_gaps": ranked(by_label),
    }
