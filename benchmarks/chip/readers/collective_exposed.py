"""Device time in collectives with no compute running, as a share of the window."""

import trace_reduce


def read(ctx: dict, params: dict):
    w0, w1 = ctx["window"]
    return 100.0 * trace_reduce.exposed_collective_s(ctx["trace"], (w0, w1)) / ((w1 - w0) / 1e9)
