"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

Set-up makes the configuration's corpus and the query pool of ``--seed``
(``corpus.py``), builds the index (``FusionANNSIndex.build``; a checkout's
first run, which also fills the compile cache) or loads its snapshot, and
starts the serving stack with the program's own default knobs
(``make_serving_stack``).  It then warms up on the cell's traffic: bursts
of every size the stack can batch, then ``warmup_s`` of the mix itself.
The window sends the mix for ``--seconds`` through
``ReplicaRouter.submit``; afterwards the stack is stopped and freed, and
the reference answers the window's queries.
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

import spec as spec_mod
from spec import BENCH_DIR, CHECKOUT

POOL = 32768            # distinct queries: warm-up and window draw in order
PROBES = 512            # pool queries fill_compile_cache ranks
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
SNAPSHOT_DIR = os.path.join(BENCH_DIR, ".snapshots")
TRACE_DIR = os.path.join(BENCH_DIR, ".traces")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Compilations and persistent-cache hits, from JAX's monitoring
    events (as ``chip_smoke.py`` counts them)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits


class GcClock:
    """Pauses of Python's garbage collector, from ``gc.callbacks``."""

    def __init__(self):
        self.pauses: List[tuple] = []       # (generation, seconds)
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def report(self) -> str:
        secs = [s for _, s in self.pauses]
        full = sum(g == 2 for g, _ in self.pauses)
        return (f"{len(secs)} collections ({full} of generation 2), longest "
                f"{1e3 * max(secs, default=0):.1f} ms, total "
                f"{1e3 * sum(secs):.1f} ms")


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader gets (``bench/metrics/*.py``)."""

    config: Dict
    serving: Dict
    answers: List             # SearchResponse of each answered window request
    answers_in_window: List   # those answered before the window closed
    latencies_s: np.ndarray   # each window request's, as latency_p99_ms's
    trace: Optional[object]   # devtrace.Summary, with --trace 1
    peaks: Optional[Dict]

    @property
    def n_in_window(self) -> int:
        return len(self.answers_in_window)


def say(*parts) -> None:
    print(*parts, flush=True)


def use_compile_cache() -> str:
    """The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout.  Set before JAX is
    imported, so the program takes the same directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def find_program() -> None:
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(
            f"no program under {src}: run from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator, only the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def anns_config(cfg: Dict):
    from repro.configs.base import ANNSConfig
    fields = {f.name for f in dataclasses.fields(ANNSConfig)}
    return ANNSConfig(**{k: v for k, v in cfg.items() if k in fields})


def percentile_ms(lat_s: np.ndarray, q: float) -> float:
    return 1e3 * float(np.percentile(lat_s, q))


def end_to_end(names: List[str], led, correct_checks: Dict,
               setup_s: float) -> Dict[str, float]:
    lat = led.latencies_s()
    window = led.t_close - led.t_open
    done = np.asarray(led.done)
    values = {
        "qps": lambda: float(np.sum((done >= led.t_open)
                                    & (done < led.t_close))) / window,
        "latency_p50_ms": lambda: percentile_ms(lat, 50),
        "latency_p99_ms": lambda: percentile_ms(lat, 99),
        "recall_at_10": lambda: 1.0 - correct_checks["recall_miss"]["value"],
        "setup_s": lambda: setup_s,
    }
    return {n: values[n]() for n in names}


@dataclasses.dataclass
class Setup:
    """The chip, the data and the built index of one run."""

    devs: list
    peaks: Optional[Dict]
    clock: CompileClock
    data: np.ndarray
    pool: np.ndarray
    index: object
    index_how: str            # "built" or "loaded"
    knobs: Dict


def prepare(cfg: Dict, seed: int, chips: int,
            require_chip: bool = True) -> Setup:
    """Everything before the serving stack: the chip, the corpus and query
    pool from ``seed``, and ``FusionANNSIndex.build``."""
    cache = use_compile_cache()
    find_program()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = devices(chips, require_chip)
    import roofline
    try:
        peaks = roofline.peaks(devs[0].device_kind)
    except roofline.UnknownDevice:
        if require_chip:
            raise
        peaks = None
    clock = CompileClock(jax)
    say(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
        f"compile cache {cache}")

    import corpus
    from repro.serve.stack import ServingStackConfig

    t = time.perf_counter()
    data, pool = corpus.make(cfg["corpus"], cfg["n_vectors"], cfg["dim"],
                             POOL, seed)
    say(f"data: corpus {data.shape}, query pool {pool.shape} (no query "
        f"repeats below {POOL} requests), {time.perf_counter() - t:.2f} s")
    index, index_how = built_index(cfg, data)
    knobs = {k: v for k, v in dataclasses.asdict(ServingStackConfig()).items()
             if k not in ("mesh", "snapshot_dir")}
    say(f"serving knobs (program defaults): {json.dumps(knobs)}")
    return Setup(devs, peaks, clock, data, pool, index, index_how, knobs)


def program_digest() -> str:
    """A hash of every source file of the program, so that an index built
    by other code is never loaded."""
    root = os.path.join(CHECKOUT, "src", "repro")
    digest = hashlib.sha256()
    for dirpath, dirnames, files in os.walk(root):
        dirnames.sort()
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                digest.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as fh:
                    digest.update(fh.read())
    return digest.hexdigest()


def built_index(cfg: Dict, data: np.ndarray):
    """The configuration's index and how it came: ``FusionANNSIndex.build``
    on the first run in a checkout, which saves it with ``save_snapshot``
    ("built"); every later run loads it (``load_snapshot``, "loaded"), as a
    restarted replica would.  The corpus is the configuration's, so one
    snapshot serves every seed; it is keyed by the configuration's content
    and the program's source."""
    from repro.core.engine import FusionANNSIndex
    digest = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    digest.update(program_digest().encode())
    path = os.path.join(SNAPSHOT_DIR,
                        f"{cfg['name']}-{digest.hexdigest()[:16]}")
    t = time.perf_counter()
    if os.path.isdir(path):
        index = FusionANNSIndex.load_snapshot(path)
        how = "loaded"
    else:
        # the program's own build seed: the benchmark hands it only data
        index = FusionANNSIndex.build(data, anns_config(cfg))
        partial = path + ".partial"
        shutil.rmtree(partial, ignore_errors=True)
        index.save_snapshot(partial)
        os.replace(partial, path)
        how = "built"
    index.codes.block_until_ready()
    # a query collects no candidate only where every list the graph search
    # returns is empty, so fewer than top_m empty lists rule it out
    empty = sum(len(m) == 0 for m in index.posting.members)
    say(f"index: {how} in {time.perf_counter() - t:.2f} s ({path}), "
        f"{index.posting.n_clusters} posting lists, {empty} of them empty "
        f"(top_m {cfg['top_m']})")
    return index, how


def fill_compile_cache(st: Setup, top_m: int) -> None:
    """Runs scan windows of every size the stack forms over candidate
    unions from one query's smallest to ``scan_window`` queries' largest,
    so that the persistent cache holds each program a later run's queries
    can need.  Without it a run whose windows reached a union size no
    earlier run had reached compiled that program in its set-up, for
    about 17 s.

    A window's programs follow its size and the power of two above its
    candidate union, so the windows take probes at five quantiles of the
    candidate count, ``d`` of them distinct and the rest repeats."""
    view = st.index.view()
    probes = st.pool[:PROBES]
    counts = [len(view.collect_candidates(q, top_m)[0]) for q in probes]
    ranked = probes[np.argsort(counts, kind="stable")]
    k = st.knobs
    for b in range(1, k["scan_window"] + 1):
        plan = st.index.plan(window=b, fused=k["fused"],
                             lut_int8=k["lut_int8"],
                             inflight_depth=k["inflight_depth"])
        for q in (0.0, 0.25, 0.5, 0.75, 1.0):
            at = min(int(q * len(ranked)), len(ranked) - b)
            for d in range(1, b + 1):
                window = np.concatenate([ranked[at:at + d],
                                         ranked[at:at + 1].repeat(b - d, 0)])
                st.index.executor.run(window, plan)


def warm_up(router, st: Setup, mix: Dict, seed: int) -> int:
    """Bursts of every size the stack can batch, then ``warmup_s`` of the
    mix itself.  Returns the next unused pool index."""
    import drive
    t = time.perf_counter()
    nxt = drive.shape_sweep(router, st.pool, 0, mix,
                            st.knobs["n_replicas"] * st.knobs["max_batch"])
    warm = drive.run_phase(router, st.pool, nxt, mix,
                           float(mix["warmup_s"]), seed + 1)
    nxt += len(warm)
    say(f"warm-up: {time.perf_counter() - t:.2f} s, {nxt} requests; "
        f"compile so far {st.clock.programs} programs "
        f"({st.clock.seconds:.2f} s), {st.clock.cache_hits} cache hits")
    return nxt


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = CHECKOUT,
             require_chip: bool = True) -> Dict:
    """Runs one cell once and returns the result line's object."""
    spec = spec_mod.load(root)
    wl = spec_mod.workload(spec, cell)
    cfg = spec_mod.config(spec, wl["config"], root)
    mix = spec_mod.traffic(wl["traffic"], root)
    wanted = spec_mod.metrics_for(spec, cell, trace)
    readers = ({m["name"]: spec_mod.metric_reader(m["name"], root)
                for m in wanted} if trace else {})

    st = prepare(cfg, seed, int(wl["chips"]), require_chip)
    if st.index_how == "built":         # a checkout's first run
        t = time.perf_counter()
        fill_compile_cache(st, cfg["top_m"])
        say(f"compile cache filled: {time.perf_counter() - t:.2f} s, "
            f"{st.clock.programs} programs so far")
    import jax

    import drive
    from repro.serve.stack import make_serving_stack
    devs, peaks, clock, data, pool, knobs = (st.devs, st.peaks, st.clock,
                                             st.data, st.pool, st.knobs)
    router = make_serving_stack(st.index)
    try:
        nxt = warm_up(router, st, mix, seed)
        if trace:
            shutil.rmtree(os.path.join(TRACE_DIR, cell), ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(TRACE_DIR, cell),
                                     profiler_options=opts)
        before = clock.snapshot()
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        setup_s = time.perf_counter() - t_start
        try:
            led = drive.run_phase(router, pool, nxt, mix, seconds, seed,
                                  span=True)
        finally:
            gc.callbacks.remove(gc_clock)
        after = clock.snapshot()
        if trace:
            jax.profiler.stop_trace()
    finally:
        router.stop()
    say(f"compiles in window: {after[1] - before[1]} programs "
        f"({after[0] - before[0]:.3f} s), {after[2] - before[2]} cache hits")
    say(f"garbage collection in window: {gc_clock.report()}")
    done = np.sort(np.asarray(led.done))
    done = done[(done >= led.t_open) & (done < led.t_close)]
    if len(done) > 1:
        say(f"longest wait between answers in window: "
            f"{1e3 * np.diff(done).max():.1f} ms")
    late_ms = 1e3 * (np.asarray(led.sent) - np.asarray(led.due))
    if len(late_ms):
        say(f"window: {len(led)} requests, {led.n_open()} unanswered "
            f"{drive.LATE_WAIT_S:.0f} s past the close; generator late by "
            f"p50 {np.median(late_ms):.3f} ms, p99 "
            f"{np.percentile(late_ms, 99):.3f} ms, max {late_ms.max():.3f} ms")
    mem = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    serving = dict(knobs)
    del router
    st.index = None
    gc.collect()

    import check
    import reference
    queries = pool[np.asarray(led.query_idx, np.int64) % POOL]
    t = time.perf_counter()
    exact_ids, _ = reference.topk(data, queries, cfg["top_k"])
    say(f"reference: {len(queries)} queries, "
        f"{time.perf_counter() - t:.2f} s")
    answers = [(a.ids, a.dists) if a is not None else None
               for a in led.answer]
    g = cfg["guarantees"]
    checks = check.compare(data, queries, answers, exact_ids, cfg["top_k"], {
        "unanswered": 0, "malformed": 0, "dist_gap": g["dist_gap_limit"],
        "recall_miss": 1.0 - g["recall_at_10_floor"]})
    shown = 0
    for qi, ans in enumerate(answers):
        why = ans is not None and check.malformed_reason(
            np.asarray(ans[0]).ravel(), np.asarray(ans[1]).ravel(),
            cfg["top_k"], len(data))
        if why and shown < 5:
            shown += 1
            say(f"malformed answer to window request {qi} ({why}): ids "
                f"{np.asarray(ans[0]).tolist()} dists "
                f"{np.asarray(ans[1]).tolist()}")
    for err in [e for e in led.error if e][:3]:
        say(f"failed request: {err}")
    answered = [a for a in led.answer if a is not None]
    lat = led.latencies_s()
    say(f"latency samples: {len(lat)}; beyond p99: "
        f"{int(np.sum(lat > np.percentile(lat, 99))) if len(lat) else 0}")

    out: Dict = {"correct": check.passed(checks), "attempted": len(led),
                 "failed": len(led) - len(answered)}
    if trace:
        import devtrace
        from jax.profiler import ProfileData
        summary = devtrace.summarize(ProfileData.from_file(
            devtrace.latest_xplane(os.path.join(TRACE_DIR, cell))))
        done = np.asarray(led.done)
        in_win = [a for a, d in zip(led.answer, done) if a is not None
                  and led.t_open <= d < led.t_close]
        rec = RunRecord(config=cfg, serving=serving, answers=answered,
                        answers_in_window=in_win, latencies_s=lat,
                        trace=summary,
                        peaks=peaks)
        metrics = {}
        for m in wanted:
            v = readers[m["name"]](rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = end_to_end([m["name"] for m in wanted], led, checks, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in wanted}
    out["metrics"] = metrics
    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs),
                     "memory_peak_bytes": peak_bytes}
    if trace:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["index"] = st.index_how
    out["checks"] = checks
    return out
