"""One kernel's share of its roofline where the work is counted by the program and not
fixed by the shapes: the least time the chip could take for ``params["counter"]`` units of
work counted over the window, by ``<params["module"]>.<floor>(config, peak, chips, count)``
(a file beside ``run.py``), over the self time inside the window of the device operations
whose name starts with ``params["kernel"]``. Nothing counted or no such operation: nothing
to read."""

import importlib

import trace_reduce


def read(ctx: dict, params: dict):
    count = ctx["counters"].get(params["counter"], 0)
    if not count or count <= 0:
        return None
    w0, w1 = ctx["window"]
    per_device = []
    for events in ctx["trace"]["devices"].values():
        inside = [e for e in events if e[1] > w0 and e[0] < w1]
        per_device.append(sum(ns for name, ns in trace_reduce.self_times(inside)
                              if name.startswith(params["kernel"])) / 1e9)
    busy = sum(per_device) / len(per_device) if per_device else 0.0
    if busy <= 0:
        return None
    floor = getattr(importlib.import_module(params["module"]), params["floor"])
    return 100.0 * floor(ctx["config"], ctx["peak"], ctx["chips"], count) / busy
