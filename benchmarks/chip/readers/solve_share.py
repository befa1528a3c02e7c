"""As ``roofline``, for a configuration that keeps its own counts: the least time the
chip could take for the whole solves of the window, by ``<params["module"]>.<floor>``
(a file beside ``run.py``), over the time the device was busy inside the benchmark's
``bench.solve`` spans. With ``params["kernel"]`` set, over the self time of the device
operations whose name starts with it instead: one kernel's share, and nothing to read
where no such operation ran."""

import importlib

import trace_reduce


def read(ctx: dict, params: dict):
    w0, w1 = ctx["window"]
    solves = [s for s in trace_reduce.spans_named(ctx["trace"], "bench.solve")
              if s[0] >= w0 and s[1] <= w1]
    if not solves:
        return None
    if "kernel" in params:
        per_device = []
        for events in ctx["trace"]["devices"].values():
            inside = [e for e in events if e[1] > w0 and e[0] < w1]
            per_device.append(sum(ns for name, ns in trace_reduce.self_times(inside)
                                  if name.startswith(params["kernel"])) / 1e9)
        busy = sum(per_device) / len(per_device) if per_device else 0.0
    else:
        busy = trace_reduce.busy_s(ctx["trace"], solves)
    if busy <= 0:
        return None
    floor = getattr(importlib.import_module(params["module"]), params["floor"])
    return 100.0 * floor(ctx["config"], ctx["peak"], ctx["chips"]) * len(solves) / busy
