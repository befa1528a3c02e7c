"""Requests through ``profiler.request`` into the estimators' own entry points, open or
closed loop from one worker loop (``benchmarks/serving/harness._load_loop``, copied).

A request type is an entry of the configuration's ``requests``: its sizes, its
``builder`` and its ``reference`` (``"<file>:<callable>"``, as a solve cell names its
``problem``), so a new type is a new entry and new files.

The seed chooses data and order, never how much work a window holds. The schedule is
ONE pattern (``PATTERN_SEED``): permutations of the block that holds every (type, staged
slot) pair once, so each block has the types in equal numbers, and for the open loop
exponential gaps, the same set in every block of ``GAP_BLOCK`` arrivals. A run's seed
turns that pattern by a whole number of blocks and renames the staged slots, so every
seed offers the same requests at the same gaps in another order: the tail of an open
loop follows the order of long and short requests, and free orders spread it by 10%.
Latency counts from when a request was due. Nothing here has a latency limit inside a
count.
"""

import gc
import itertools
import math
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

SAMPLED_EXTRA = 24  # answers kept for the comparison, besides the whole first block
PATTERN_SEED = 24  # the one pattern of request blocks and gaps that every seed turns
GAP_BLOCK = 240  # arrivals after which the open loop's set of gaps repeats
CLOSED_MAX_RPS = 2000  # a closed loop's list of requests is longer than any window drains


def nearest_rank(values, q: float) -> float:
    """Exact nearest-rank percentile ``q`` of ``values``."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, round(q * (len(ordered) - 1))))]


def percentile_ms(latencies_s, q: float) -> float:
    """Nearest-rank percentile of a latency list, in milliseconds."""
    return nearest_rank(latencies_s, q) * 1e3


def request_pattern(types, pool: int, n: int) -> list:
    """At least ``n`` requests as ``(type, slot)`` in whole blocks: seeded permutations of
    the block of all pairs."""
    rng = random.Random(PATTERN_SEED)
    block = [(t, s) for t in types for s in range(pool)]
    out = []
    while len(out) < n:
        out += rng.sample(block, len(block))
    return out


def gap_pattern(rate_rps: float, n: int) -> list:
    """At least ``n`` gaps between Poisson arrivals at ``rate_rps``: every block of
    ``GAP_BLOCK`` gaps is the same set, the exponential's quantiles, in seeded order, so
    a block always lasts ``GAP_BLOCK / rate``."""
    rng = random.Random(PATTERN_SEED + 1)
    quantiles = [-math.log(1.0 - (j + 0.5) / GAP_BLOCK) for j in range(GAP_BLOCK)]
    norm = GAP_BLOCK / (rate_rps * sum(quantiles))
    out = []
    while len(out) < n:
        out += [g * norm for g in rng.sample(quantiles, GAP_BLOCK)]
    return out


def schedule(types, pool: int, n: int, seed: int, rate_rps=None):
    """``n`` requests and, for an open loop, their due times: the pattern turned by a
    whole number of request blocks drawn from ``seed``, its slots renamed by ``seed``."""
    rng = random.Random(seed)
    block = len(types) * pool
    pattern = request_pattern(types, pool, n)
    turn = block * rng.randrange(len(pattern) // block)
    rename = {t: rng.sample(range(pool), pool) for t in types}
    requests = [(t, rename[t][s]) for t, s in (pattern[turn:] + pattern[:turn])[:n]]
    if rate_rps is None:
        return requests, None
    gaps = gap_pattern(rate_rps, len(pattern))[:len(pattern)]
    due, t = [], 0.0
    for g in (gaps[turn:] + gaps[:turn])[:n]:
        t += g
        due.append(t)
    return requests, due


def load_loop(profiler, requests, run_one, workers: int, seconds: float, due=None,
              keep=frozenset()):
    """``workers`` threads drain ``requests`` (a list of ``(type, slot)``). With ``due``
    None this is the closed loop: a client sends its next request when the last one is
    answered, until ``seconds`` have passed. With ``due`` it is the open loop: request
    ``i`` waits for ``due[i]`` and its latency counts FROM then, so queueing while all
    workers are busy is in the number. Returns per-request records
    ``(i, type, latency_s or inf, lateness_s)``, kept answers, and the wall seconds."""
    counter = itertools.count()
    records = [[] for _ in range(workers)]
    answers = {}
    start = time.perf_counter()

    def worker(slot_id: int) -> None:
        while True:
            i = next(counter)
            if i >= len(requests):
                return
            if due is None:
                t0 = time.perf_counter()
                if t0 - start >= seconds:
                    return
                late = 0.0
            else:
                t0 = start + due[i]
                now = time.perf_counter()
                if now < t0:
                    time.sleep(t0 - now)
                late = max(0.0, time.perf_counter() - t0)
            name, slot = requests[i]
            try:
                with profiler.request(f"bench.{name}"), \
                        jax.profiler.TraceAnnotation(f"bench.request:{name}"):
                    answer = run_one(name, slot)
                latency = time.perf_counter() - t0
                if i in keep:
                    answers[i] = answer
            except Exception as exc:  # shed, expired or raised: failed, slower than any
                latency = math.inf
                answers.setdefault("errors", []).append(repr(exc))
            records[slot_id].append((i, name, latency, late))

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - start
    return sorted(r for rs in records for r in rs), answers, wall


def setup(config: dict, traffic: dict, seed: int, resolve) -> dict:
    import heat_tpu as ht
    from heat_tpu.core import profiler

    profiler.enable()
    key = jax.random.key(seed, impl="rbg")
    workloads = {}
    for j, name in enumerate(config["mix"]):
        request = config["requests"][name]
        workloads[name] = resolve(request["builder"])(
            ht, jax, jnp, request, jax.random.fold_in(key, j), config["pool"])
    for w in workloads.values():  # every type's programs compile here, not in the window
        for slot in range(config["warmup_requests"]):
            w.request(slot % config["pool"])
    return {"workloads": workloads, "config": config, "profiler": profiler,
            "resolve": resolve}


def window(state: dict, traffic: dict, seconds: float, seed: int) -> dict:
    cfg, workloads = state["config"], state["workloads"]
    if traffic["loop"] == "open":
        n, rate = round(traffic["rate_rps"] * seconds), traffic["rate_rps"]
    else:
        n, rate = int(CLOSED_MAX_RPS * seconds), None
    requests, due = schedule(cfg["mix"], cfg["pool"], n, seed, rate)
    block = len(cfg["mix"]) * cfg["pool"]
    rng = random.Random(seed + 2)
    keep = frozenset(range(block)) | frozenset(
        rng.sample(range(block, max(block + SAMPLED_EXTRA, n // 2)), SAMPLED_EXTRA))

    gc.collect()
    gc.freeze()
    gc.disable()  # no cyclic collection inside the window
    try:
        records, answers, wall = load_loop(
            state["profiler"], requests, lambda name, slot: workloads[name].request(slot),
            traffic["workers"], seconds, due, keep)
    finally:
        gc.enable()
        gc.unfreeze()

    latencies = [r[2] for r in records]
    done = [r for r in records if r[2] != math.inf]
    state["answers"] = {i: (requests[i], np.asarray(a)) for i, a in answers.items()
                        if i != "errors"}
    return {
        "attempted": len(records), "failed": len(records) - len(done), "wall_s": wall,
        "errors": answers.get("errors", [])[:3],
        "values": {"lat_p50_ms": percentile_ms(latencies, 0.50),
                   "lat_p95_ms": percentile_ms(latencies, 0.95),
                   "goodput_rps": len(done) / wall},
        "samples": {"latency_ms": [x * 1e3 for x in latencies],
                    "lateness_ms": [r[3] * 1e3 for r in records]},
        "by_type": {t: sum(r[1] == t for r in done) for t in cfg["mix"]},
    }


def release(state: dict) -> None:
    state["inputs"] = {name: w.inputs for name, w in state["workloads"].items()}
    state["workloads"] = None


def compare(state: dict, result: dict, precision: str) -> dict:
    """Every kept answer against its type's plain reference (``reference`` of the type in
    the configuration); the worst of each type. Under the control the reference computes
    the answers itself at ``precision``, below what the configuration states."""
    cfg, inputs = state["config"], state["inputs"]
    control = None if precision == "float32" else precision
    worst = {}
    for (name, slot), answer in state["answers"].values():
        request = cfg["requests"][name]
        gap = state["resolve"](request["reference"])(inputs[name], request, slot, answer,
                                                     control)
        worst[f"{name}_gap"] = max(worst.get(f"{name}_gap", 0.0), gap)
    missing = [t for t in cfg["mix"] if f"{t}_gap" not in worst]
    if missing:
        raise RuntimeError(f"no answer of {missing} was kept to compare")
    return worst
