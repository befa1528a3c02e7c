"""Run one cell of BENCHMARK.json once and print its result as the last line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric is a file
found by its name in BENCHMARK.json: ``configs/<config>.json`` (sizes, the driver, the
problem or request types as ``"<file>:<callable>"``, any ``jax_config`` options the
deployment sets, and the limits of the output comparison), ``traffic/<traffic>.json``
(the mix's parameters), ``metrics/<metric>.json`` (its reader and the reader's
parameters), ``drivers/<driver>.py`` and ``readers/<reader>.py``. A new one is a new file;
this file needs no edit.

Set-up (data from the seed on the device, the cell's own shapes warmed) is timed from
process start; the window is then measured for ``--seconds``; the plain reference runs
after the window has closed, the memory peak has been read and the program's state is
freed. ``--trace 1`` wraps the window in a profiler trace and reports the per-layer
metrics instead of the end-to-end ones; a mix may cap the traced window
(``trace_seconds``), and the seconds asked for and measured are in the result
(``info``). ``--control <precision>`` (not used by the driver) puts the reference,
computed at that lower precision, in the program's place in the comparison: the run
must then come out as not correct.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, by file: a new file is found unedited."""
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def resolve(ref: str):
    """``"<file>:<attribute>"`` as a configuration names a callable: a file under
    ``drivers/`` or, as ``reference.py`` is, beside this one."""
    module, attr = ref.split(":")
    kind = "drivers" if os.path.exists(os.path.join(HERE, "drivers", f"{module}.py")) else "."
    return getattr(load_module(kind, module), attr)


def load_cell(name: str) -> dict:
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])

    def ours(metric):
        return name in metric.get("workloads", [name])

    return {"name": name, "chips": cell["chips"],
            "config": load_json(ROOT, entry["file"]),
            "traffic": load_json(HERE, "traffic", f"{cell['traffic']}.json"),
            "end_to_end": [m for m in manifest["end_to_end"] if ours(m)],
            "per_layer": [m for m in manifest["per_layer"] if ours(m)]}


class CompileMeter:
    """Backend compiles of this process, by phase (``setup`` until the window opens)."""

    def __init__(self):
        import jax.monitoring

        self.phase = "setup"
        self.count = {"setup": 0, "window": 0, "after": 0}
        self.seconds = {"setup": 0.0, "window": 0.0, "after": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count[self.phase] += 1
            self.seconds[self.phase] += secs


def numeric_counters() -> dict:
    """The program's own counts, flat: executor statistics and diagnostics counters."""
    import heat_tpu as ht

    out = {f"executor.{k}": v for k, v in ht.executor_stats().items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
    out.update({f"diagnostics.{k}": v
                for k, v in ht.diagnostics.report()["counters"].items()})
    return out


def device_gate(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX found {len(devices)} "
                         f"device(s) of platform {devices[0].platform!r}. Nothing ran.")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(jax) -> tuple:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return fullest.get("peak_bytes_in_use", 0), fullest.get("bytes_limit", 0)


def resize(config: dict, traffic: dict, sizes: dict) -> None:
    """The tests' sizes: ``"rows"``, ``"requests.mlp_infer.batch"``, ``"traffic.workers"``."""
    for key, value in sizes.items():
        node, parts = config, key.split(".")
        if parts[0] == "traffic":
            node, parts = traffic, parts[1:]
        for part in parts[:-1]:
            node = node[part]
        node[parts[-1]] = value


def read_per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader (``metrics/<name>.json``
    names it); a reader that finds nothing to read returns nothing and is left out."""
    metrics = {}
    for m in cell["per_layer"]:
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        value = load_module("readers", spec["reader"]).read(ctx, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             control: str = "float32", on_chip: bool = True, sizes: dict = None,
             tamper=None) -> dict:
    """One run; returns the result object. ``on_chip=False``, ``sizes`` (keys of the
    configuration or ``traffic.<key>`` to override) and ``tamper`` (called with the
    driver's state after set-up, to break the timed path) are the tests' arguments."""
    cell = load_cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    resize(config, traffic, sizes or {})

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    for path in (ROOT, HERE, os.path.join(HERE, "drivers")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    # every program of the cell goes to the persistent cache, also the short compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    for option, value in config.get("jax_config", {}).items():
        jax.config.update(option, value)  # options of JAX that the deployment states
    if on_chip:
        device = device_gate(cell["chips"])
    else:
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    import rooflines

    peak = rooflines.peaks(device["kind"]) if on_chip else None
    meter = CompileMeter()
    import heat_tpu as ht

    if trace:
        ht.diagnostics.enable()  # counters such as linalg.plan.ring record only then
    driver = load_module("drivers", config["driver"])
    imported_s = time.perf_counter() - _PROCESS_START
    state = driver.setup(config, traffic, seed, resolve)
    if tamper is not None:
        tamper(state)
    before = numeric_counters()
    asked_seconds = seconds
    if trace:
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        trace_dir = os.path.join(ROOT, ".bench_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # the bench.* annotations, no more
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - _PROCESS_START
    meter.phase = "window"
    with jax.profiler.TraceAnnotation("bench.window"):
        result = driver.window(state, traffic, seconds, seed)
    meter.phase = "after"
    if trace:
        jax.profiler.stop_trace()
    after = numeric_counters()
    peak_bytes, limit_bytes = memory_peak(jax)
    device["memory_peak_bytes"] = peak_bytes

    values = dict(result["values"], setup_s=setup_s)
    if not trace:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    else:
        import trace_reduce

        reduced = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = trace_reduce.window_of(reduced)
        device["busy_s"] = trace_reduce.busy_s(reduced, [w])
        device["window_s"] = (w[1] - w[0]) / 1e9
        counters = {k: after[k] - before.get(k, 0) for k in after}
        counters.update({
            "compile.setup_s": meter.seconds["setup"],
            "compile.window_count": meter.count["window"],
            "memory.peak_share": 100.0 * peak_bytes / limit_bytes if limit_bytes else None})
        ctx = {"config": config, "traffic": traffic, "chips": cell["chips"], "peak": peak,
               "trace": reduced, "window": w, "counters": counters,
               "samples": result["samples"]}
        metrics = read_per_layer(cell, ctx)
        breakdown = {"device_ops": trace_reduce.top_ops(reduced, w),
                     "idle_gaps": trace_reduce.idle_gaps(reduced, w)}

    driver.release(state)
    readings = driver.compare(state, result, control)
    limits = config["limits"]
    compared = {k: {"value": v, "limit": limits[k]} for k, v in readings.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values()) \
        and result["failed"] == 0
    for metric in metrics.values():
        if not math.isfinite(metric["value"]):
            metric["value"] = 1e12  # a failed request is slower than any
    out = {"correct": bool(correct), "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["info"] = {k: result[k] for k in ("wall_s", "by_type", "errors") if k in result}
    out["info"].update(imported_s=imported_s, compiles=meter.count, compile_s=meter.seconds,
                       seconds_asked=asked_seconds, seconds_measured=seconds)
    out["compared"] = compared
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="float32")
    args = ap.parse_args()
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), args.control)
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {out['correct']} failed: {out['failed']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
