"""One window count over another, times the configuration's number
``params["times_config"]`` where given: ``num`` / ``den`` of the run's counters.
Nothing counted, nothing read."""


def read(ctx: dict, params: dict):
    counters = ctx["counters"]
    den = counters.get(params["den"], 0)
    if den <= 0 or params["num"] not in counters:
        return None
    scale = ctx["config"][params["times_config"]] if "times_config" in params else 1.0
    return scale * counters[params["num"]] / den
