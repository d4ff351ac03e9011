"""Chip benchmark: one cell of ``BENCHMARK.json`` for one seed.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the graph
and how the program is set up for it) and a traffic mix
(``bench/traffic/<traffic>.json``: its ``driver`` and parameters). Both are
found by name; so is each per-layer metric's reader
(``bench/metrics/<metric>.py``). Two drivers serve every mix:

* ``open_loop``: requests arrive on a fixed schedule through one tenant
  session of ``ServeFrontend``, whatever the server's progress; each is
  timed from its scheduled send to its delivery;
* ``key_sets``: sets of search keys go whole through
  ``BFSServeEngine.submit_many``, back to back, each timed from submit to
  its last answer.

Set-up (graph generation from the seed, partitioning, device placement,
compile or compile-cache load, warm-up) is timed from process start to the
window's start. The window then runs ``--seconds``; with ``--trace 1`` it
is recorded by the JAX profiler and the per-layer metrics are read from the
trace, the engine's counters and the serving spans. Afterwards the program
is freed and a sample of the answers, drawn from the seed, is compared
exactly with ``reference.py``.

Earlier lines (standard error) report set-up steps, generator lateness and
counters; the last lines of standard error give each compared number with
its limit; the last line of standard output is the result JSON. Without a
TPU, or with fewer chips than the cell asks for, the run exits 2 and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import gen  # noqa: E402
import reference  # noqa: E402


def _load(name: str, path: str):
    """Import a file by path (``trace`` would otherwise find the standard
    library's module of that name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tr = _load("bench_trace", os.path.join(HERE, "trace.py"))

TRACE_DIR = os.path.join(HERE, ".trace")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def clock() -> float:
    return time.perf_counter()


def note(name: str):
    """A host annotation on the profiler's trace (free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` %
    of the population at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if not v.size:
        raise ValueError("percentile of an empty population")
    k = max(int(np.ceil(q / 100.0 * v.size)), 1)
    return float(v[k - 1])


def open_loop_metrics(due, done, seconds: float, end: float) -> dict:
    """End-to-end metrics of an open-loop window over every request sent
    in it: ``due`` and ``done`` are each request's scheduled send and
    delivery time (NaN if never delivered) from the window's start, which
    lasted ``seconds``; the drain after it ended at ``end``. Requests never
    delivered count with the latency they had reached at ``end``."""
    done = np.asarray(done, dtype=np.float64)
    lat = np.where(np.isnan(done), end, done) - np.asarray(due)
    return {"served_qps": int((done < seconds).sum()) / seconds,
            "latency_p50_s": percentile(lat, 50),
            "latency_p90_s": percentile(lat, 90)}


# -- the cell, found by name -------------------------------------------------
def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


SPECS = ("BENCHMARK.json", os.path.join("bench", "pending.json"))


def load_cell(workload: str) -> dict:
    """The workload's entry, configuration, traffic mix and the metrics it
    reports (end-to-end and per-layer), all by name. A cell is looked up in
    ``BENCHMARK.json``, then in ``bench/pending.json``: cells written in
    the same form that wait for their chip measurements before they join
    the benchmark."""
    for bench_file in SPECS:
        if not os.path.exists(os.path.join(ROOT, bench_file)):
            continue
        spec = load_json(bench_file)
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload in cells:
            break
    else:
        raise SystemExit(f"no workload {workload!r} in {' or '.join(SPECS)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
    return {
        "cell": cell,
        "config": load_json(configs[cell["config"]]["file"]),
        "traffic": load_json(os.path.join("bench", "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return _load("bench_metric_" + metric.replace(".", "_"),
                 os.path.join(HERE, "metrics", metric + ".py")).read


# -- the system under test ---------------------------------------------------
def program():
    """The program's serving API (imported only once the chip is known)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    from repro.core.msbfs import MSBFSConfig, nn_scan_steps
    from repro.core.partition import partition_graph
    from repro.core.types import COOGraph
    from repro.obs import Observability
    from repro.serve import BFSServeEngine, Query, QueryKind, ServeFrontend

    return {"compile_cache": compile_cache, "MSBFSConfig": MSBFSConfig,
            "nn_scan_steps": nn_scan_steps,
            "partition_graph": partition_graph, "COOGraph": COOGraph,
            "Observability": Observability, "BFSServeEngine": BFSServeEngine,
            "Query": Query, "QueryKind": QueryKind,
            "ServeFrontend": ServeFrontend}


def engine_kwargs(P, cfg: dict) -> dict:
    e = cfg["engine"]
    return {"cfg": P["MSBFSConfig"](n_queries=e["lanes"]),
            "refill": e["refill"], "overlap": e["overlap"],
            "sweep_block": e["sweep_block"], "edge_chunk": e["edge_chunk"],
            "cache_capacity": e["cache_capacity"]}


def log_plan(P, eng) -> None:
    """The nn slot scan's static shape: the plan's longest slot run and the
    segmented OR's doubling steps (set-up log only, no metric)."""
    run_len = eng.plan.max_run
    log(f"plan: msbfs.nn.max_run={run_len} "
        f"msbfs.nn.scan_steps={P['nn_scan_steps'](run_len)}")


def partition(P, cfg: dict, n: int, src, dst):
    p = cfg["partition"]
    return P["partition_graph"](P["COOGraph"](n, src, dst), th=p["th"],
                                p_rank=p["p_rank"], p_gpu=p["p_gpu"])


class CompileWatch:
    """Counts compiles and compile-cache loads (JAX's own events)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __enter__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._hit)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._hit)
        return False

    def _hit(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1


class Window:
    """The measured window, optionally under the JAX profiler."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.path = None

    def __enter__(self):
        import jax

        if self.traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._note = note(tr.WINDOW)
        self._note.__enter__()
        self.t0 = clock()
        return self

    def close(self) -> None:
        import jax

        self.t1 = clock()
        self._note.__exit__(None, None, None)
        if self.traced:
            jax.profiler.stop_trace()
            self.path = tr.find_xplane(TRACE_DIR)

    def __exit__(self, *exc):
        return False


def stats_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a if isinstance(a[k], (int, float))}


# -- drivers -----------------------------------------------------------------
def point_requests(cfg: dict, traffic: dict, n: int, src, seed: int,
                   count: int, block: int) -> list:
    """Block ``block`` of the open-loop stream as plain descriptors."""
    kinds, srank, trank = gen.point_requests(traffic, count, n, block)
    names = gen.popularity_order(n, src, gen.label_seed(cfg, seed),
                                 traffic["base_seed"])
    out = []
    for k, s, t in zip(kinds, srank, trank):
        kind = traffic["kinds"][k]
        out.append({"kind": kind["kind"],
                    "source": int(names[int(s) % names.size]),
                    "max_depth": kind.get("max_depth"),
                    "targets": ((int(names[int(t) % names.size]),)
                                if kind["kind"] == "multi_target" else ())})
    return out


def key_sets(cfg: dict, traffic: dict, n: int, src, seed: int) -> list:
    """The run's key sets: distinct search keys, ``keys_per_set`` a set.
    With a ``base_seed`` the traffic fixes its keys once per graph (the
    same base vertices for every seed) and the seed orders each set's
    lanes; without one the seed draws them."""
    size = traffic["keys_per_set"]
    count = size * traffic["max_sets"]
    if "base_seed" not in traffic:
        pool = gen.search_keys(n, src, count, seed)
        return [pool[i:i + size]
                for i in range(0, pool.size - size + 1, size)]
    pool = gen.fixed_search_keys(n, src, count, gen.label_seed(cfg, seed),
                                 traffic["base_seed"])
    return [gen.lane_order(pool[i:i + size], seed, i // size)
            for i in range(0, pool.size - size + 1, size)]


def make_query(P, d: dict):
    """A plain descriptor as the program's typed query."""
    K = P["QueryKind"]
    if d["kind"] == "distance_limited":
        return P["Query"](d["source"], K.DISTANCE_LIMITED,
                          max_depth=d["max_depth"])
    if d["kind"] == "multi_target":
        return P["Query"](d["source"], K.MULTI_TARGET, targets=d["targets"])
    return P["Query"](d["source"], K(d["kind"]))


def levels_request(key) -> dict:
    return {"kind": "levels", "source": int(key), "max_depth": None,
            "targets": ()}


class KeySetChecks:
    """The answers a key-set run compares: every answer of the first
    ``check_whole_sets`` sets, so that a fault in any one lane shows, and a
    sample of ``check_sample`` answers of the later sets, drawn from the
    seed. However many sets a window holds, at most ``check_whole_sets *
    keys_per_set + check_sample`` answers are kept and compared."""

    def __init__(self, traffic: dict, seed: int):
        self.whole = traffic["check_whole_sets"]
        self.checks: list = []
        self._later = gen.Reservoir(traffic["check_sample"], seed)

    def add(self, i: int, keys, answers) -> None:
        for key, ans in zip(keys, answers):
            c = levels_request(key) | {"answer": ans}
            if i < self.whole:
                self.checks.append(c)
            else:
                self._later.offer(c)

    def all(self) -> list:
        return self.checks + self._later.items


def run_open_loop(P, run: dict, pg, n: int, src) -> dict:
    """Serve the open-loop stream through one latency-class session."""
    cfg, traffic, seed = run["config"], run["traffic"], run["seed"]
    seconds, traced = run["seconds"], run["trace"]
    obs = P["Observability"](trace_capacity=1 << 21) if traced else None
    fe = P["ServeFrontend"](obs=obs)
    eng = fe.register_graph("graph", pg=pg, **engine_kwargs(P, cfg))
    log_plan(P, eng)
    sess = fe.open_session("bench", "graph", slo="latency")
    rate = run.get("rate") or traffic["rate_qps"]
    count = max(int(round(rate * seconds)), 1)
    prefix = [make_query(P, d) for d in point_requests(
        cfg, traffic, n, src, seed, traffic["warmup_requests"], block=0)]
    plain = point_requests(cfg, traffic, n, src, seed, count, block=1)
    stream = [make_query(P, d) for d in plain]
    due = gen.arrival_times(count, seconds, traffic["base_seed"])
    t = clock()
    eng.warmup(queries=prefix)
    log(f"compile_or_load_s={clock() - t:.3f}")
    t = clock()
    fe.submit(sess, prefix)
    fe.drain()
    fe.results(sess)
    log(f"warmup_prefix: requests={len(prefix)} s={clock() - t:.3f} "
        f"sweeps={eng.stats.sweeps}")

    check = set(gen.sample(count, traffic["check_requests"], seed).tolist())
    waiting: dict = {}
    done = np.full(count, np.nan)
    sent = np.full(count, np.nan)
    answers: dict = {}

    def deliver(now: float) -> None:
        for q, res in fe.results(sess).items():
            for i in waiting.pop(q, ()):
                done[i] = now
                if i in check:
                    answers[i] = res

    no_lane = [0]

    def send(i: int, now: float) -> None:
        q = stream[i]
        queued = eng.stream_status()["pending"]
        with note("bench.submit"):
            fe.submit(sess, [q])
        no_lane[0] += eng.stream_status()["pending"] == queued
        sent[i] = now
        waiting.setdefault(q, []).append(i)

    s0 = eng.stats.as_dict()
    f0 = fe.tenant_stats("bench").as_dict()
    backlog = []
    nxt = 0
    with CompileWatch() as watch, Window(traced) as win:
        while True:
            now = clock() - win.t0
            if now >= seconds:
                break
            while nxt < count and due[nxt] <= now:
                send(nxt, clock() - win.t0)
                nxt += 1
            with note("bench.poll"):
                fe.poll(wait=False)
            deliver(clock() - win.t0)
            if nxt < count:
                wait = due[nxt] - (clock() - win.t0)
                if wait > 0:
                    with note("bench.generator"):
                        time.sleep(min(wait, 0.002))
            if len(backlog) < int(now) + 1:
                st = eng.stream_status()
                backlog.append(st["busy"] + st["pending"])
        while nxt < count:            # due inside the window, sent late
            send(nxt, clock() - win.t0)
            nxt += 1
        win.close()
    compiles = watch.count
    s1 = eng.stats.as_dict()
    f1 = fe.tenant_stats("bench").as_dict()
    spans = ([(e.name, e.ts - win.t0, e.dur) for e in obs.trace.events()
              if e.is_span and win.t0 <= e.ts < win.t1] if traced else [])
    with note("bench.drain"):
        limit = clock() + traffic["drain_s"]
        while waiting and clock() < limit:
            fe.poll(wait=True)
            deliver(clock() - win.t0)
    end = clock() - win.t0
    e2e = open_loop_metrics(due, done, seconds, end)
    failed = int(np.isnan(done).sum())
    late = sent - due
    log(f"window: offered_qps={count / seconds:.4f} sent={count} "
        f"answered_in_window={int((done < seconds).sum())} failed={failed} "
        f"drain_end_s={end:.3f} compiles_in_window={compiles}")
    log(f"generator_lateness_s: p50={percentile(late, 50):.6f} "
        f"p90={percentile(late, 90):.6f} max={float(late.max()):.6f}")
    log(f"backlog_per_s={backlog}")
    ds, dt = stats_delta(s0, s1), stats_delta(f0, f1)
    log("window_stats=" + json.dumps({**ds, **{"frontend_" + k: v for k, v
                                              in dt.items()}}))
    ctx = {"seconds": seconds, "window_s": win.t1 - win.t0, "sent": count,
           "setup_s": win.t0 - T0, "no_lane": no_lane[0], "backlog": backlog,
           "answered": int((done < seconds).sum()), "stats": ds,
           "tenant": dt, "spans": spans, "trace": win.path}
    checks = [plain[i] | {"answer": answers.get(i)} for i in sorted(check)]
    return {"e2e": e2e, "ctx": ctx, "attempted": count, "failed": failed,
            "checks": checks, "keys": None, "_free": (fe, eng)}


def run_key_sets(P, run: dict, pg, n: int, src) -> dict:
    """Serve back-to-back key sets of LEVELS queries for the window."""
    cfg, traffic, seed = run["config"], run["traffic"], run["seed"]
    seconds, traced = run["seconds"], run["trace"]
    obs = P["Observability"](trace_capacity=1 << 21) if traced else None
    eng = P["BFSServeEngine"](pg=pg, obs=obs, **engine_kwargs(P, cfg))
    log_plan(P, eng)
    size = traffic["keys_per_set"]
    sets = key_sets(cfg, traffic, n, src, seed)
    query = lambda k: make_query(P, levels_request(k))  # noqa: E731
    t = clock()
    eng.warmup(queries=[query(k) for k in sets[0]])
    log(f"compile_or_load_s={clock() - t:.3f}")

    s0 = eng.stats.as_dict()
    timed, checks, failed = [], KeySetChecks(traffic, seed), 0
    with CompileWatch() as watch, Window(traced) as win:
        for i, keys in enumerate(sets):
            if clock() - win.t0 >= seconds:
                break
            qs = [query(k) for k in keys]
            sweeps, blocks = eng.stats.sweeps, eng.stats.sweep_blocks
            cpu = time.process_time()
            t0 = clock()
            with note("bench.submit"):
                res = eng.submit_many(qs)
            t1 = clock()
            timed.append((keys, t1 - t0))
            failed += sum(r is None for r in res)
            checks.add(i, keys, res)
            del res
            log(f"set {i}: keys={size} s={t1 - t0:.6f} "
                f"sweeps={eng.stats.sweeps - sweeps} "
                f"blocks={eng.stats.sweep_blocks - blocks} "
                f"cpu_s={time.process_time() - cpu:.3f} "
                f"load1={os.getloadavg()[0]:.2f}")
        win.close()
    compiles = watch.count
    s1 = eng.stats.as_dict()
    spans = ([(e.name, e.ts - win.t0, e.dur) for e in obs.trace.events()
              if e.is_span and win.t0 <= e.ts < win.t1] if traced else [])
    ds = stats_delta(s0, s1)
    log(f"window: sets={len(timed)} window_s={win.t1 - win.t0:.6f} "
        f"compiles_in_window={compiles}")
    log("window_stats=" + json.dumps(ds))
    ctx = {"seconds": seconds, "window_s": win.t1 - win.t0,
           "setup_s": win.t0 - T0, "sent": size * len(timed), "no_lane": 0,
           "stats": ds, "tenant": {}, "spans": spans, "trace": win.path}
    return {"e2e": {}, "ctx": ctx, "attempted": size * len(timed),
            "failed": failed, "checks": checks.all(), "keys": timed,
            "_free": (eng,)}


DRIVERS = {"open_loop": run_open_loop, "key_sets": run_key_sets}


# -- the comparison that decides ``correct`` ---------------------------------
def compare(g: reference.Graph, checks: list, answer_of=None) -> dict:
    """Exact comparison of the sampled answers with the reference.
    ``answer_of(check, levels)`` may stand in for the served answer (the
    control does)."""
    levels = g.levels(c["source"] for c in checks)
    wrong = unanswered = 0
    for c in checks:
        src = c["source"]
        want = reference.answer(c["kind"], levels[src], c["max_depth"],
                                c["targets"])
        got = (answer_of(c, levels[src]) if answer_of is not None
               else c["answer"])
        if got is None:
            unanswered += 1
        elif not reference.same(got, want):
            wrong += 1
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "compared": len(checks)}


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(run: dict, require_tpu: bool = True) -> dict | None:
    """One run of one cell; returns the result object, or None (after
    logging why) when the chip the cell needs is missing."""
    import jax

    chips = run["cell"]["chips"]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    P = program()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device: {devs[0].platform} {devs[0].device_kind} count={len(devs)} "
        f"jax={jax.__version__} "
        f"compile_cache={P['compile_cache'].enable_compile_cache()}")
    cfg, seed = run["config"], run["seed"]
    t = clock()
    n, src, dst = gen.make_graph(cfg, seed)
    log(f"graph: {cfg['generator']} scale={cfg['scale']} n={n} "
        f"stored_edges={src.size} gen_s={clock() - t:.3f}")
    t = clock()
    pg = partition(P, cfg, n, src, dst)
    log(f"partition: p={pg.p} n_local={pg.n_local} delegates={pg.d} "
        f"s={clock() - t:.3f}")
    out = DRIVERS[run["traffic"]["driver"]](P, run, pg, n, src)
    device = device_info(jax, chips)
    setup_s = out["ctx"]["setup_s"]
    del pg
    out.pop("_free")
    gc.collect()

    t = clock()
    g = reference.Graph(n, src, dst)
    res = compare(g, out["checks"])
    metrics_e2e = dict(out["e2e"], setup_s=setup_s)
    if out["keys"] is not None:
        edges = sum(int(g.teps_edges(k).sum()) for k, _ in out["keys"])
        wall = sum(s for _, s in out["keys"])
        metrics_e2e["gteps"] = edges / wall / 1e9 if wall else 0.0
        log(f"teps: edges={edges} wall_s={wall:.6f}")
    log(f"reference_s={clock() - t:.3f}")
    correct = (res["wrong_answers"] == 0 and out["failed"] == 0
               and res["compared"] > 0)

    want = run["per_layer"] if run["trace"] else run["end_to_end"]
    metrics = {}
    breakdown = None
    ctx = out["ctx"]
    if run["trace"]:
        red = tr.reduce_file(ctx["trace"]) if ctx["trace"] else None
        ctx["reduced"] = red
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        for m in want:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in want:
            if m["name"] in metrics_e2e:
                metrics[m["name"]] = {"value": metrics_e2e[m["name"]],
                                      "unit": m["unit"]}
    checks = {"wrong_answers": {"value": res["wrong_answers"], "limit": 0},
              "unanswered": {"value": out["failed"], "limit": 0}}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    log(f"compared={res['compared']}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = load_cell(args.workload)
    run.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    result = run_cell(run)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
