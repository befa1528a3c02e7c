"""Milliseconds per occurrence of one host span of the program (``diagnostics.span``),
from the window deltas of its flat counters ``span_n`` / ``span_s`` / ``span_self_s``:
``params["part"]`` ``"all"`` is the span's whole duration, ``"children"`` the part of it
that its child spans cover. A program without the span counts nothing: nothing read."""


def read(ctx: dict, params: dict):
    counters, span = ctx["counters"], params["span"]
    n = counters.get(f"diagnostics.span_n.{span}", 0)
    if n <= 0:
        return None
    seconds = counters.get(f"diagnostics.span_s.{span}", 0.0)
    if params["part"] == "children":
        seconds -= counters.get(f"diagnostics.span_self_s.{span}", 0.0)
    return 1e3 * seconds / n
