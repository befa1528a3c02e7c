"""A share of counts in percent: the counters named in ``num`` over every counter whose
name starts with ``den_prefix``. Nothing counted, nothing read."""


def read(ctx: dict, params: dict):
    counters = ctx["counters"]
    den = sum(v for k, v in counters.items() if k.startswith(params["den_prefix"]))
    if den <= 0:
        return None
    return 100.0 * sum(counters.get(k, 0) for k in params["num"]) / den
