"""The least time the chip could take for the whole solves of the window, as
``rooflines.<floor>`` reckons it from the configuration's shapes, over the time the device
was busy inside the benchmark's own ``bench.solve`` spans."""

import rooflines
import trace_reduce


def read(ctx: dict, params: dict):
    w0, w1 = ctx["window"]
    solves = [s for s in trace_reduce.spans_named(ctx["trace"], "bench.solve")
              if s[0] >= w0 and s[1] <= w1]
    busy = trace_reduce.busy_s(ctx["trace"], solves) if solves else 0.0
    if busy <= 0:
        return None
    floor = getattr(rooflines, params["floor"])(ctx["config"], ctx["peak"], ctx["chips"])
    return 100.0 * floor * len(solves) / busy
