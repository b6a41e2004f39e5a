#!/usr/bin/env python3
"""chipbench — one run of one cell of BENCHMARK.json, timed from the
client's side of the served path.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell's configuration, traffic mix, entry point
and per-layer readers by name (configs/<config>.json, worlds/<world>.py,
traffic/<mix>.json, entries/<entry>.py, layers/<metric>.py), makes the world
and the requests from ``--seed``, loads the program through its public
client (the world's own ``load_edges`` where it has one, else its ``SHAPES``
as id columns), warms the cell's own shapes, drives the window, has the entry
compare every answer the window returned with the world's plain reference,
and prints the contract's result line last.  Anything but a TPU with enough
chips exits non-zero with no result; ``--rehearse-cpu`` is the only way onto
the CPU (1 % of the scale, every line says ``platform: "cpu"``, never a
result).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before the heavy imports: they are set-up

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: the manifest that names the cells; a cell's mix and world are looked for
#: under its ``paths`` (the tests point this at a fixture manifest of theirs)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
for _p in (ROOT, os.path.join(HERE, "layers"), os.path.join(HERE, "entries"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import _checks  # entries/_checks.py: the first answer of every cell is a check
import trace_reduce

REHEARSAL_PEAK = "TPU v5 lite"  # a CPU rehearsal borrows this row; it reports nothing
MIN_DEADLINE_S = 180.0
FIRST_CALL_DEADLINE_S = 900.0  # includes the device prepare
ANSWER_WAIT_S = 60.0  # how long past the close an answer is waited for


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(dirs, kind: str, file: str) -> str:
    """The first <dir>/<kind>/<file> that exists."""
    for d in dirs:
        path = os.path.join(d, kind, file)
        if os.path.isfile(path):
            return path
    raise SystemExit(f"chipbench: no {kind}/{file} under {list(dirs)}")


def load_module(kind: str, name: str, dirs=(HERE,)):
    """<kind>/<name>.py as a module (names may hold dots)."""
    path = find(dirs, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(name: str):
    """chipbench/entries/<name>.py: what differs between entry points (the
    requests, the one timed call, what an answer counts for, the judging)."""
    found = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, "entries"))
                   if f.endswith(".py") and not f.startswith("_"))
    if name not in found:
        raise SystemExit(f"chipbench: no entry point {name!r}; chipbench/entries/"
                         f" has {found}")
    return load_module("entries", name)


def load_cell(workload: str, rehearse: bool) -> dict:
    """The cell with its configuration, traffic mix, entry point and metric
    lists."""
    root = os.path.dirname(MANIFEST)
    manifest = load_json(MANIFEST)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in {MANIFEST};"
                         f" it has {sorted(cells)}")
    cell = cells[workload]
    # a cell's data (mix, world): under the manifest's ``paths``, else here
    dirs = [os.path.join(root, p) for p in manifest["paths"]] + [HERE]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(root, entry["file"])
    traffic = load_json(find(dirs, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    reports = lambda m: workload in m.get("workloads", [workload])
    return {
        "name": workload, "chips": cell["chips"], "config": config,
        "traffic": traffic, "entry": load_entry(traffic["entry"]),
        "sizes": config["rehearsal_sizes" if rehearse else "sizes"],
        "world": load_module("worlds", config["world"], dirs),
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


# ---------------------------------------------------------------------------
# the loop: closed, for every mix
# ---------------------------------------------------------------------------


def drive(entry, pool: list, callers: int, seconds: float, annotate: bool,
          passes: int = 0):
    """The closed loop: each caller sends its next request when the last
    has returned, and starts none after ``seconds`` — or, where ``passes``
    is given (a warm-up), once it has sent its share of the pool that many
    times.  Returns (window start, per-request (pool index, sent, answered,
    answer or exception), callers still hung)."""
    logs = [[] for _ in range(callers)]
    gate = threading.Barrier(callers + 1)
    t_end = [0.0]
    if annotate:
        import jax

        span = lambda: jax.profiler.TraceAnnotation(trace_reduce.OWN_SPAN)
    else:
        span = contextlib.nullcontext

    def caller(c: int) -> None:
        mine, log, i = pool[c::callers], logs[c], 0
        gate.wait()
        stop = t_end[0]
        while True:
            sent = time.perf_counter()
            if sent >= stop or (passes and i >= passes * len(mine)):
                return
            req = mine[i % len(mine)]
            i += 1
            try:
                with span():
                    out = entry(req)
            except Exception as e:  # a failed request is a result, not a crash
                out = e
            log.append((req.index, sent, time.perf_counter(), out))

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(callers)]
    for t in threads:
        t.start()
    if passes:
        seconds = len(pool) * MIN_DEADLINE_S  # the count ends it, not the clock
    t_start = time.perf_counter()
    t_end[0] = t_start + seconds
    gate.wait()
    for t in threads:
        t.join(seconds + ANSWER_WAIT_S + MIN_DEADLINE_S)
    hung = sum(t.is_alive() for t in threads)
    return t_start, [e for log in logs for e in log], hung


# ---------------------------------------------------------------------------
# the program behind the cell's entry point
# ---------------------------------------------------------------------------


class CompileWatch:
    """Counts what JAX itself reports: backend compiles, their seconds,
    persistent-cache hits (copied from chip_smoke.py)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests, self.seconds, self.cache_hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def import_shapes(client, ctx, ids: dict, mod, w: dict) -> int:
    """The loader of a world that has none of its own: each edge list of
    ``SHAPES`` is a pair of id columns and nothing else (no caveat, no
    stored context, no expiration).  Returns the edges imported."""
    edges = 0
    for key, rtype, relation, stype, srel in mod.SHAPES:
        r, s = w[key]
        client.import_relationship_id_columns(
            ctx(), resource_ids=ids[rtype][r], resource_relation=relation,
            subject_ids=ids[stype][s], subject_relation=srel)
        edges += int(r.shape[0])
    return edges


class Program:
    """The system under test: a client with the cell's world imported,
    behind the entry point the traffic mix names.  How the edges go in is
    the world's: ``load_edges(client, ctx, ids, w, sizes) -> edges imported``
    (``ctx()`` makes a context, ``ids[type][i]`` is the interned node of
    object i) where the module has one, else ``import_shapes``."""

    def __init__(self, cell: dict, w: dict, say) -> None:
        import gochugaru_tpu.client as gclient
        from gochugaru_tpu import consistency, native, new_tpu_evaluator
        from gochugaru_tpu.utils import metrics
        from gochugaru_tpu.utils.context import background

        traffic, mod, sizes = cell["traffic"], cell["world"], cell["sizes"]
        self.world = mod
        #: the program's counters, gauges and timers, as the readers get them
        self.snapshot = metrics.default.snapshot
        self._background = background
        self.deadline_s = MIN_DEADLINE_S
        self.client = new_tpu_evaluator(
            *[getattr(gclient, name)() for name in traffic["client_options"]])
        self.cs = getattr(consistency, traffic["consistency"])()
        t0 = time.perf_counter()
        self.client.write_schema(self.ctx(), mod.SCHEMA)
        itn = self.client.store.interner
        ids = {t: itn.node_batch(t, [f"{p}{i}" for i in range(sizes[k])])
               for t, p, k in mod.TYPES}
        t1 = time.perf_counter()
        if hasattr(mod, "load_edges"):
            loader = "world.load_edges"
            edges = mod.load_edges(self.client, self.ctx, ids, w, sizes)
        else:
            loader = "run.import_shapes"
            edges = import_shapes(self.client, self.ctx, ids, mod, w)
        if edges != sizes["edges"]:
            raise AssertionError(f"imported {edges} edges, the"
                                 f" configuration states {sizes['edges']}")
        say("loaded", edges=edges, loader=loader, native_ingest=native.available(),
            intern_s=t1 - t0, import_s=time.perf_counter() - t1)
        self.handle = None  # an entry that serves through a handle opens it
        self._call = cell["entry"].bind(self)

    def ctx(self, seconds: float = 0.0):
        return self._background().with_timeout(seconds or self.deadline_s)

    def first_answer(self, rels: list) -> float:
        """The first check pays the device prepare.  Later deadlines stay
        above twice its time: admission control learns it as a dispatch
        cost and sheds a request whose deadline is under the estimate."""
        t0 = time.perf_counter()
        self.client.check(self.ctx(FIRST_CALL_DEADLINE_S), self.cs, *rels)
        took = time.perf_counter() - t0
        self.deadline_s = max(self.deadline_s, 2 * took)
        return took

    def entry(self, req):
        return self._call(self.ctx(), req)

    def close(self) -> None:
        if self.handle is not None:
            self.handle.close()


def resident_bytes(devices) -> int:
    """Bytes of live arrays on the fullest device: what the deployment
    holds there, taken from JAX and not from the program."""
    import jax

    held = {d.id: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device.id in held:
                held[s.device.id] += int(s.data.nbytes)
    return max(held.values())


# ---------------------------------------------------------------------------
# deciding ``correct``
# ---------------------------------------------------------------------------


def is_correct(checked: dict) -> bool:
    return (checked["wrong_answers"]["value"] <= checked["wrong_answers"]["limit"]
            and checked["unanswered_requests"]["value"]
            <= checked["unanswered_requests"]["limit"]
            and checked["answers_compared"]["value"]
            >= checked["answers_compared"]["at_least"])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_cell(args, make_program=Program) -> int:
    """One run.  ``make_program(cell, world, say)`` builds what answers the
    requests: the program, or in the tests and the control something put in
    its place."""
    cell = load_cell(args.workload, args.sizes == "rehearsal")
    traffic = cell["traffic"]
    if args.rehearse_cpu:
        from gochugaru_tpu.utils.platform import force_cpu_platform

        force_cpu_platform(cell["chips"])
    import jax

    from gochugaru_tpu.utils.platform import configure_compile_cache

    platform = jax.default_backend()
    devices = jax.devices()[:cell["chips"]]
    if platform != ("cpu" if args.rehearse_cpu else "tpu") or (
            len(jax.devices()) < cell["chips"]):
        # nothing on stdout: a run without the chip prints no result
        print(f"chipbench: JAX found {len(jax.devices())} device(s) of backend"
              f" {platform!r}; {args.workload} needs {cell['chips']} TPU"
              " chip(s); nothing was run", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = load_json(HERE, "peaks.json")
    kind = REHEARSAL_PEAK if args.rehearse_cpu else device["kind"]
    if kind not in peaks:
        raise SystemExit(f"chipbench: no published peaks for device kind"
                         f" {kind!r} in chipbench/peaks.json")

    def say(event: str, **fields) -> None:
        print(json.dumps({"platform": device["platform"],
                          "device_kind": device["kind"],
                          "device_count": device["count"],
                          "event": event, **fields}), flush=True)

    cache_dir = configure_compile_cache()
    # the program caches only what took a second to compile; its tier
    # programs compile in less and would recompile in every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileWatch()
    stages = {"imports_s": time.perf_counter() - T_START}
    say("start", workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, sizes=args.sizes, jax=jax.__version__,
        compile_cache_dir=cache_dir)

    def stage(name: str, t0: float) -> float:
        stages[name] = time.perf_counter() - t0
        return time.perf_counter()

    # -- set-up: world, program, requests, warm-up ---------------------------
    t = time.perf_counter()
    w = cell["world"].build_world(cell["sizes"], args.seed)
    t = stage("world_s", t)
    program = make_program(cell, w, say)
    t = stage("load_s", t)
    entry = cell["entry"]
    pool = entry.requests(cell, w, np.random.default_rng([args.seed, 1]))
    t = stage("requests_s", t)
    rng = np.random.default_rng([args.seed, 2])
    # whatever the entry: a check of four of the world's own probes, with
    # whatever request context the cell's checks carry
    first = _checks.probe_rels(cell["world"], cell["world"].make_probes(
        w, cell["sizes"], rng, 4))
    warm = entry.warm_requests(cell, w, rng)
    program.first_answer(first)
    t = stage("first_answer_s", t)
    for req in warm:
        program.entry(req)
    # the warm loop: by the clock (``warm_loop_s``), or the whole pool
    # ``warm_passes`` times where each request has shapes of its own
    _, warm_log, _ = drive(program.entry, pool, traffic["callers"],
                           traffic.get("warm_loop_s", 0.0), False,
                           traffic.get("warm_passes", 0))
    for _i, _s, _a, out in warm_log:
        if isinstance(out, Exception):
            raise out
    t = stage("warm_up_s", t)
    snapshot = getattr(program, "snapshot", dict)  # a control has no registry
    say("set_up", **stages, warm_requests=len(warm_log),
        compile_requests=compiles.requests, compile_s=compiles.seconds,
        compile_cache_hits=compiles.cache_hits,
        prepare_stages={k[len("prepare."):-len(".total_s")]: v
                        for k, v in sorted(snapshot().items())
                        if k.startswith("prepare.") and k.endswith(".total_s")})
    held = resident_bytes(devices)
    gc.collect()
    gc.freeze()  # the requests are the harness's, not garbage the program made
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        t_traced = time.perf_counter()
    before, compiled_before = snapshot(), compiles.requests

    # -- the window ------------------------------------------------------------
    t_start, log, hung = drive(program.entry, pool, traffic["callers"],
                               seconds, bool(args.trace))
    setup_s = t_start - T_START
    t_close = max([e[2] for e in log], default=t_start + seconds)
    window_s = t_close - t_start
    if args.trace:
        traced_s = time.perf_counter() - t_traced
        jax.profiler.stop_trace()
    after = snapshot()
    window_compiles = compiles.requests - compiled_before
    stats = devices[0].memory_stats() or {}
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices)
    program.close()

    # -- numbers -----------------------------------------------------------------
    done = [e for e in log if not isinstance(e[3], Exception)]
    counts = entry.tally([e[3] for e in done])
    latency_ms = np.array([1000.0 * (e[2] - e[1]) for e in done])
    say("window", requests=len(log), answered=len(done), **counts,
        window_s=window_s, asked_s=seconds,
        request_p50_ms=float(np.median(latency_ms)) if len(done) else None,
        request_max_ms=float(latency_ms.max()) if len(done) else None,
        window_compiles=window_compiles, bytes_in_use=stats.get("bytes_in_use"),
        errors=sorted({repr(e[3])[:200] for e in log
                       if isinstance(e[3], Exception)})[:5])
    if hasattr(entry, "counters"):  # events an entry adds; no metric reads them
        say("counters", **entry.counters(before, after))
    if hasattr(entry, "describe"):
        say("answers", **entry.describe(pool, done))
    if not done:
        print("chipbench: no request was answered in the window",
              file=sys.stderr)
        return 3
    result = {"attempted": len(log) + hung, "failed": len(log) - len(done) + hung}
    if args.trace:
        t0 = time.perf_counter()
        xplane = trace_reduce.find_xplane(TRACE_DIR)
        trace = trace_reduce.reduce_trace(xplane)
        trace["window_s"] = traced_s
        say("trace", xplane_bytes=os.path.getsize(xplane),
            reduce_s=time.perf_counter() - t0, traced_s=traced_s,
            **{k: trace[k] for k in ("busy_s", "devices", "device_events")})
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".", exist_ok=True)
            shutil.copyfile(xplane, args.keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if not args.rehearse_cpu and not trace["busy_s"]:
            print("chipbench: the trace holds no device operation",
                  file=sys.stderr)
            return 3
        context = {"config": cell["config"], "traffic": traffic,
                   "peak": peaks[kind],
                   "window": {**counts, "requests": len(done),
                              "seconds": window_s,
                              **{f"request_p{q}_ms": float(
                                  np.percentile(latency_ms, q))
                                 for q in (50, 95, 99)},
                              "compile_requests": window_compiles}}
        metrics_out = {}
        for m in cell["per_layer"]:
            v = load_module("layers", m["name"]).read(before, after, trace, context)
            if v is not None:  # the reader found nothing to read
                metrics_out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"], device["window_s"] = trace["busy_s"], traced_s
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        values = {
            **{m: counts[k] / window_s for m, k in entry.RATES.items()},
            "request_p95_ms": float(np.percentile(latency_ms, 95)),
            "device_bytes_per_edge": held / cell["sizes"]["edges"],
            "setup_s": setup_s,
        }
        metrics_out = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in cell["end_to_end"]}

    # -- correct: after the window, the peak reading and the close -----------
    t0 = time.perf_counter()
    checked = entry.judge(cell, w, pool, log, hung)
    say("judged", reference_s=time.perf_counter() - t0,
        total_s=time.perf_counter() - T_START)
    line = {"correct": is_correct(checked), **result, "metrics": metrics_out,
            "device": device, "checked": checked}
    print(json.dumps(line), flush=True)
    print(json.dumps({"checked": checked}), file=sys.stderr, flush=True)
    return 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU backend at rehearsal sizes; never a result")
    ap.add_argument("--sizes", choices=("full", "rehearsal"), default=None,
                    help="rehearsal: the configuration's 1 % sizes")
    ap.add_argument("--keep-trace", default="",
                    help="copy the run's .xplane.pb to this path")
    args = ap.parse_args(argv)
    args.seed &= (1 << 63) - 1
    if args.sizes is None:
        args.sizes = "rehearsal" if args.rehearse_cpu else "full"
    return args


if __name__ == "__main__":
    sys.exit(run_cell(parse_args()))
