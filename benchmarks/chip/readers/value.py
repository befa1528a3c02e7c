"""One count or reading as it stands: ``params["counter"]`` of the run's counters."""


def read(ctx: dict, params: dict):
    return ctx["counters"].get(params["counter"])
