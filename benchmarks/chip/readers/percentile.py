"""Nearest-rank percentile ``q`` of the load generator's samples ``params["samples"]``
(milliseconds; a failed request is slower than any), by the generator's own arithmetic."""

from serve_loop import nearest_rank


def read(ctx: dict, params: dict):
    samples = ctx["samples"].get(params["samples"], [])
    return nearest_rank(samples, params["q"]) if samples else None
