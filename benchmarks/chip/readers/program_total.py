"""What the program counted of itself since its process started, asked of the program
(``ht.diagnostics.report()``) once the window has closed: the number at
``params["path"]`` of the report (``["startup", "import_s"]``), or the counter
``params["counter"]`` (one name, or a list that is summed). ``"less_window": true``
takes the window's own delta off, which leaves what set-up counted. A program without
the record or the counter: nothing read."""


def read(ctx: dict, params: dict):
    import heat_tpu as ht

    report = ht.diagnostics.report()
    if "path" in params:
        node = report
        for key in params["path"]:
            node = node.get(key) if isinstance(node, dict) else None
        return node if isinstance(node, (int, float)) else None
    names = params["counter"]
    names = [names] if isinstance(names, str) else names
    if not any(name in report["counters"] for name in names):
        return None
    total = sum(report["counters"].get(name, 0) for name in names)
    if params.get("less_window"):
        total -= sum(ctx["counters"].get(f"diagnostics.{name}", 0) for name in names)
    return total
