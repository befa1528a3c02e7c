"""From a profiler trace (``.xplane.pb``) to busy/idle time, spans and top operations.

A trace is reduced to plain lists first (``load``), so that every function below
works on ``(start_ns, end_ns, name)`` tuples and can be checked on a hand-built
trace without a device.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|collective-broadcast"
    r"|ppermute|psum|^send|^recv")


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: [(t0, t1, op)]}, "spans": [(t0, t1, name)]}`` of the newest
    trace under ``trace_dir``; spans are the benchmark's own ``bench.*`` annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def op_name(text: str) -> str:
    """``%fusion.3 = f32[8,64]{...} fusion(...)`` -> ``fusion.3 f32[8,64]``: the trace names
    an operation by its whole HLO line."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + (" " + shape if shape and "(" not in shape else ""))[:96]


def from_profile(profile) -> dict:
    devices, spans = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans)}


def union(intervals) -> list:
    """Sorted, disjoint ``(t0, t1)`` covering the same time as ``intervals``."""
    out = []
    for t0, t1 in sorted((iv[0], iv[1]) for iv in intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        elif t1 > t0:
            out.append([t0, t1])
    return [tuple(iv) for iv in out]


def clip(intervals, windows) -> list:
    """The parts of disjoint sorted ``intervals`` that lie inside ``windows``."""
    out = []
    for w0, w1 in union(windows):
        out += [(max(t0, w0), min(t1, w1)) for t0, t1 in intervals
                if t1 > w0 and t0 < w1]
    return out


def total_s(intervals) -> float:
    return sum(t1 - t0 for t0, t1 in intervals) / 1e9


def window_of(trace: dict):
    """The ``bench.window`` span: the traced, measured window."""
    for t0, t1, name in trace["spans"]:
        if name == "bench.window":
            return t0, t1
    raise RuntimeError("the trace holds no bench.window span")


def spans_named(trace: dict, prefix: str) -> list:
    return [(t0, t1) for t0, t1, name in trace["spans"] if name.startswith(prefix)]


def busy_s(trace: dict, windows) -> float:
    """Seconds in which an operation ran on the device inside ``windows``, averaged
    over the devices of the trace."""
    per_device = [total_s(clip(union(events), windows))
                  for events in trace["devices"].values()]
    if not per_device:
        raise RuntimeError("the trace holds no device plane with an 'XLA Ops' line")
    return sum(per_device) / len(per_device)


def self_times(events) -> list:
    """``(name, self_ns)`` per event: its duration less what events nested in it cover
    (a ``while`` encloses its body's operations on the same line)."""
    out, stack = [], []  # stack of [end, name, self_ns]

    def pop():
        end, name, self_ns = stack.pop()
        out.append((name, self_ns))

    for t0, t1, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= t0:
            pop()
        if stack:
            stack[-1][2] -= min(t1, stack[-1][0]) - t0
        stack.append([t1, name, t1 - t0])
    while stack:
        pop()
    return out


def top_ops(trace: dict, window, k: int = 10) -> list:
    """The ``k`` device operations with most self time inside ``window``, in seconds
    summed over devices."""
    w0, w1 = window
    by_name = {}
    for events in trace["devices"].values():
        inside = [e for e in events if e[1] > w0 and e[0] < w1]
        for name, self_ns in self_times(inside):
            by_name[name] = by_name.get(name, 0) + self_ns
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: dict, window, k: int = 10) -> list:
    """Idle time of the first device inside ``window``, by the benchmark span the host
    was in when each gap began (innermost span; ``none`` outside every span)."""
    w0, w1 = window
    events = next(iter(trace["devices"].values()))
    busy = clip(union(events), [window])
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in trace["spans"] if s[2] != "bench.window"]  # sorted by start
    starts = [s[0] for s in spans]
    by_name = {}
    for g0, g1 in gaps:
        hi = bisect.bisect_right(starts, g0)
        covering = [s for s in spans[max(0, hi - 64):hi] if s[1] > g0]
        name = min(covering, key=lambda s: s[1] - s[0])[2] if covering else "none"
        by_name[name] = by_name.get(name, 0) + (g1 - g0)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def exposed_collective_s(trace: dict, window) -> float:
    """Seconds inside ``window`` in which a device ran a collective and nothing else:
    the self time of collective operations on the operations line (an asynchronous
    pair shows only the time its start and done block), averaged over devices."""
    w0, w1 = window
    per_device = []
    for events in trace["devices"].values():
        inside = [e for e in events if e[1] > w0 and e[0] < w1]
        per_device.append(sum(ns for name, ns in self_times(inside)
                              if COLLECTIVE.search(name)) / 1e9)
    return sum(per_device) / len(per_device)
