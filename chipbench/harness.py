"""One run of one cell: build the session from the cell's configuration
file, warm up, measure a window, check, and hand back the result.

The session is built as `chip_smoke.py:run_session` and
`examples/insitu_grayscott.py` build it: `FrameworkConfig().with_overrides(
*overrides)` -> `InSituSession(cfg, sinks=[sink])` -> `.run(n)`. From the
program the harness takes the session, its recorder's spans and counters,
its fallback ledger and the names of its XLA programs; everything that
measures or compares lives in this directory.

Where the rendered field comes from is not the harness's to know: the
configuration's field source (`sources/<name>.py`, found by name like
`layers/*.py`) builds the session, keeps the part of the field a run is
compared by, and owns that part's reference, checks and limits. Warm-up,
window, sink, viewer, the end-to-end metrics, the guarantees, the decoded
frames against the `reference_overrides` session and the traced readers
are the same for every source.
"""

import gc
import glob
import importlib.util
import json
import os
import shutil
import time
import types

import numpy as np

from chipbench import arith, reference, sources, xplane
from chipbench.traffic import Replay, Sink, Viewer

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot give a result; the message is the one-line reason."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_files(cell: dict, home: str = HERE, bench: dict = None) -> dict:
    """The cell with its configuration and traffic files, found by the
    names in its entry: `<home>/configs/<config>.json` (`home`: this
    directory, or `rehearsal/` for a rehearsal size) and
    `traffic/<traffic>.json`."""
    cell = dict(cell, home=home)
    cell["config_file"] = load_json(home, "configs", cell["config"] + ".json")
    cell["traffic_file"] = load_json(HERE, "traffic",
                                     cell["traffic"] + ".json")
    cell["bench"] = bench or load_json(ROOT, "BENCHMARK.json")
    return cell


def load_cell(workload: str) -> dict:
    """The cell's entry in BENCHMARK.json with its files."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
    return find_files(cells[workload], bench=bench)


def load_file(kind: str, path: str):
    """One file of the benchmark that is code (a per-layer reader, a field
    source) as a module of its own."""
    stem = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layers() -> list:
    """Every per-layer metric: one reader module per file of `layers/`,
    found by listing the directory."""
    return [load_file("layer", path) for path in
            sorted(glob.glob(os.path.join(HERE, "layers", "*.py")))]


def load_source(cell: dict):
    """The cell's field source: `sources/<name>.py` by the configuration's
    `field_source` (absent: `sources.DEFAULT`), looked for beside the
    configuration first (`rehearsal/sources/`), then here."""
    name = cell["config_file"].get("field_source", sources.DEFAULT)
    for home in (cell.get("home", HERE), HERE):
        path = os.path.join(home, "sources", name + ".py")
        if os.path.exists(path):
            return load_file("source", path)
    raise BenchFailure(f"no field source {name!r} under sources/")


def overrides_of(cell: dict) -> list:
    """The overrides the cell's session is built from: the configuration's,
    then the traffic file's."""
    return (cell["config_file"]["overrides"]
            + cell["traffic_file"].get("overrides", []))


class CompileMeter:
    """What XLA compiled in this process (jax.monitoring): every backend
    compile request with its seconds (a persistent-cache hit still issues
    the request and spends its retrieval time there) and the
    persistent-cache hits. Copied from chip_smoke.py."""

    def __init__(self):
        from jax import monitoring

        self.requests, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.requests += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "seconds": self.seconds,
                "cache_hits": self.cache_hits}

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


def find_device(chips: int, on_chip: bool) -> dict:
    """The device as JAX reports it. Without a TPU, with fewer chips than
    the cell asks for, or with a kind that is not in the peak table, there
    is no result. `on_chip` false (the rehearsal and the tests) skips the
    look for a chip, and nothing else."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if on_chip:
        if dev.platform != "tpu":
            raise BenchFailure(f"needs a TPU; JAX found {info}")
        arith.peaks_for(dev.device_kind)
    if len(devs) < chips:
        raise BenchFailure(f"the cell asks for {chips} chip(s); JAX found "
                           f"{info}")
    return info


def enable_cache() -> str:
    """The persistent compilation cache, at the directory the program's
    `utils/backend.enable_compile_cache` uses (JAX_COMPILATION_CACHE_DIR,
    else <checkout>/.jax_cache), with the thresholds off: JAX caches only
    compiles of >= 1 s by default, and of a warm run's ~29 requests only
    2 hit (PERF.md, PR 21)."""
    import jax

    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return enable_compile_cache()


def peak_bytes(n_devices: int) -> list:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:n_devices]]


def build_session(overrides, seed: int, amplitude: float, sink=None,
                  viewer=None):
    """The default source's session from bare overrides and the seed's
    amplitude, for callers that have no cell (tests/test_sortlast.py)."""
    cell = {"config_file": {}, "traffic_file": {"field_perturbation":
                                                amplitude}}
    return load_source(cell).build_session(cell, overrides, seed, sink,
                                           viewer)


def end_session(source, sess) -> None:
    """A source that started something with its session (a producer
    process, a channel) ends it here; most have nothing to end."""
    end = getattr(source, "end_session", None)
    if end is not None:
        end(sess)


def process_start() -> float:
    """`time.perf_counter()` as it stood when this process began (the
    interpreter's own start-up counts as set-up), from /proc; now, where
    /proc does not say."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


# ---------------------------------------------------------------- one run
#
# A run is `open_run` -> `run_window` (warm-up, window, its checks, the
# traced readers, session freed) -> `references` -> `compare` -> `result`,
# and `run_cell` is exactly that chain. control.py and the rehearsal (with
# the tests) compose the same functions with something else put between
# two of them: a lower precision in the program's place, the timed path
# broken underneath.


def open_run(cell: dict, seed: int, trace: bool, on_chip: bool = True,
             verbose: bool = True):
    """Find the device, switch the cache on and build the cell's session.
    The run's state travels in the namespace this returns."""
    run = types.SimpleNamespace(
        cell=cell, seed=seed, trace=trace, on_chip=on_chip,
        conf=cell["config_file"], traf=cell["traffic_file"],
        log=print if verbose else (lambda s: None), checks=[])
    t_start = process_start()
    run.phases = [("process start to harness", time.perf_counter() - t_start)]
    t0 = time.perf_counter()
    run.device = find_device(cell["chips"], on_chip)
    run.cache_dir = enable_cache()
    from scenery_insitu_tpu import obs
    run.phases.append(("import jax and the program, find the device",
                       time.perf_counter() - t0))
    t0 = time.perf_counter()
    run.meter = CompileMeter()
    obs.clear_ledger()
    run.viewer = Viewer(run.traf["steering"], seed)
    run.sink = Sink(run.viewer, keep=[run.traf["reference_frame"]])
    run.source = load_source(cell)
    run.sess = run.source.build_session(
        cell, overrides_of(cell) + (["obs.enabled=true"] if trace else []),
        seed, run.sink, run.viewer)
    run.phases.append(("build the session", time.perf_counter() - t0))
    run.t_start = t_start
    return run


def check(run, name, value, limit, ok) -> None:
    run.checks.append((name, value, limit, bool(ok)))
    run.log(f"[chipbench] check {name}: {value} (limit {limit}) "
            f"{'ok' if ok else 'FAILED'}")


def warm_up(run, seconds: float) -> None:
    """Every shape the window uses, steering included; then the window's
    frame count from the steered warm-up frames' interval, and the frames
    of the window that the checks will look at, drawn from the seed."""
    sess, sink, traf = run.sess, run.sink, run.traf
    t0 = time.perf_counter()
    sess.run(1)
    run.phases.append(("frame 0 (compile or cache retrieval)",
                       time.perf_counter() - t0))
    t0 = time.perf_counter()
    run.kept = run.source.keep(sess)        # of the field after frame 0
    sess.run(traf["steer_from_frame"] - 1)
    run.viewer.active = True
    sess.run(max(traf["warmup_frames"] - traf["steer_from_frame"], 2))
    # mean interval of the steered warm-up frames (the intervals alternate
    # with the camera messages, so a median would fall on either value)
    k = traf["steer_from_frame"] + 2
    mean_gap = lambda: (sink.stamps[-1] - sink.stamps[k]) / (
        len(sink.stamps) - 1 - k)
    gap = mean_gap()
    t_steady = sink.stamps[-1] - sink.stamps[k]
    if t_steady < traf["warmup_min_seconds"]:
        sess.run(int((traf["warmup_min_seconds"] - t_steady) / gap) + 1)
        gap = mean_gap()
    span = (min(seconds, traf["trace_max_seconds"]) if run.trace
            else seconds)
    n = max(traf["min_window_frames"], int(round(span / gap)))
    if run.trace:
        n = min(n, traf["trace_max_frames"])
    run.phases.append(("fetch field 0 and the other warm-up frames",
                       time.perf_counter() - t0))
    rng = np.random.default_rng(run.seed)
    run.first, run.n_frames = len(sink.frames), n
    run.sampled = sorted({run.first + n - 1, *(
        run.first + int(i)
        for i in rng.integers(0, n, traf["sampled_frames"]))})
    # the window frame compared with the reference: among the first few,
    # because the reference replays every frame up to it
    run.compared = run.first + int(rng.integers(
        0, min(n, traf["compared_among_first"])))
    sink.keep |= {*run.sampled, run.compared}
    run.compiles0 = run.meter.snapshot()
    run.steps0 = sess.obs.counters.get("compile_step", 0)
    run.answered0 = len(run.viewer.answered)
    run.events0 = len(sess.obs.events)
    run.log(f"[chipbench] warm-up: {run.first} frames, {gap * 1e3:.3f} "
            f"ms/frame, compile {run.compiles0}, cache {run.cache_dir}; "
            f"window: {n} frames")
    run.log("[chipbench] set-up by phase (s): "
            + ", ".join(f"{n} {s:.2f}" for n, s in run.phases))


def measure(run) -> None:
    """The window: one `sess.run(n)`, traced or not; then the end-to-end
    metrics, all on the sink's clock but the memory reading."""
    import jax

    sess, sink = run.sess, run.sink
    run.trace_dir = os.path.join(ROOT, ".chipbench", "trace",
                                 run.cell["name"])
    setup_s = time.perf_counter() - run.t_start

    def window():
        t0 = time.perf_counter()
        sess.run(run.n_frames)
        run.source.wait(sess)
        return t0, time.perf_counter()

    if run.trace:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.ANCHOR):
                run.t_w0, t_w1 = window()
        finally:
            jax.profiler.stop_trace()
    else:
        run.t_w0, t_w1 = window()
    run.window_s = t_w1 - run.t_w0
    run.compiles1 = run.meter.snapshot()
    run.peaks = peak_bytes(run.cell["chips"])

    run.stamps = stamps = np.asarray(sink.stamps[run.first:])
    gaps_ms = np.diff(stamps) * 1e3
    run.steers = run.viewer.answered[run.answered0:]
    steer_ms = [(t1 - t0) * 1e3 for t0, t1, _ in run.steers
                if t0 >= run.t_w0]
    e2e = {"setup_s": (setup_s, "s")}
    if gaps_ms.size:
        # all the window's frames over all its time, first delivery to last
        e2e["fps"] = (gaps_ms.size / float(stamps[-1] - stamps[0]),
                      "frames/s")
    if gaps_ms.size >= 200:
        e2e["frame_p95_ms"] = (float(np.percentile(gaps_ms, 95)), "ms")
    if steer_ms:
        e2e["steer_to_pixel_ms"] = (float(np.median(steer_ms)), "ms")
    if run.peaks[0] is not None:
        e2e["peak_hbm_GB"] = (run.peaks[0] / 1e9, "GB")
    run.end_to_end, run.steer_answers = e2e, len(steer_ms)
    if gaps_ms.size:
        longest = np.argsort(gaps_ms)[-3:][::-1]
        quarters = [round(float(np.median(q)), 4)
                    for q in np.array_split(gaps_ms, 4) if q.size]
        run.log(
            f"[chipbench] window {run.window_s:.3f} s, {len(stamps)} "
            f"deliveries, {e2e['fps'][0]:.4f} frames over time; median "
            f"interval {np.median(gaps_ms):.4f} ms (per-layer "
            f"frame_median_ms in a traced run); longest intervals (ms at "
            f"frame) {[(round(float(gaps_ms[i]), 1), int(i)) for i in longest]}"
            f"; {len(steer_ms)} steering answers; peak_bytes_in_use per "
            f"device {run.peaks}; median interval of each quarter of the "
            f"window {quarters}")


def check_ledger(run, name: str) -> None:
    """The fallback ledger holds no row but those the configuration's
    guarantees admit by component (`fallback_ledger_admits`, each with its
    reason); every row is printed."""
    from scenery_insitu_tpu import obs

    admits = run.conf["guarantees"].get("fallback_ledger_admits", {})
    ledger = obs.ledger()
    rows = [r for r in ledger if r["component"] not in admits]
    check(run, name, len(rows), 0, not rows)
    for row in ledger:
        run.log(f"[chipbench] ledger: {row['component']}: {row['from']} -> "
                f"{row['to']} ({row['reason']})" + (
                    f" [admitted: {admits[row['component']]}]"
                    if row["component"] in admits else ""))


def window_checks(run) -> int:
    """What the window delivered, against the configuration's guarantees.
    Returns the number of failed frames."""
    sess, sink, shape = run.sess, run.sink, run.conf["shape"]
    first, n = run.first, run.n_frames
    want = list(range(first, first + n))
    got = sink.frames[first:]
    in_order = sum(1 for a, b in zip(got, want) if a == b)
    failed = (n - in_order) + len(sink.faults)
    for f in run.sampled:
        faults = (reference.payload_faults(
            sink.kept[f], shape["k"], run.conf["limits"]["covered_share_min"])
            if f in sink.kept else ["not delivered"])
        if faults:
            failed += 1
            run.log(f"[chipbench] frame {f}: {'; '.join(faults)}")
    check(run, "frames_delivered_once_in_order", f"{in_order}/{n}", n,
          got == want and not sink.faults)
    check(run, "frames_failed", failed, 0, failed == 0)
    nbytes = arith.vdi_bytes_per_frame(shape)
    check(run, "vdi_bytes_per_frame", int(sink.nbytes[-1]), nbytes,
          set(sink.nbytes[first:]) == {nbytes})
    check_ledger(run, "fallback_ledger_rows")
    new_compiles = run.compiles1["requests"] - run.compiles0["requests"]
    new_steps = sess.obs.counters.get("compile_step", 0) - run.steps0
    check(run, "compile_requests_in_window", new_compiles + new_steps, 0,
          new_compiles == 0 and new_steps == 0)
    for c in run.source.window_checks(run.cell, run.kept):
        check(run, *c)
    check(run, "steering_answers_in_window", run.steer_answers, ">= 1",
          run.steer_answers >= 1)
    return failed


def read_layers(run):
    """The traced run's per-layer metrics, breakdown and device times:
    ({name: (value, unit)}, breakdown or None, {busy_s, window_s} or {})."""
    if not run.trace:
        return {}, None, {}
    sess, conf = run.sess, run.conf
    spans = [e for e in sess.obs.events[run.events0:] if e["type"] == "span"]
    paths = glob.glob(os.path.join(run.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise BenchFailure(f"the profiler wrote no trace to {run.trace_dir}")
    events = xplane.compact(paths[0])
    if not events["devices"] and not run.on_chip:
        tr = None           # a rehearsal on the CPU: no device plane
        run.log("[chipbench] rehearsal: no device plane in the trace; "
                "device_trace readers are left out")
    else:
        tr = xplane.Trace(events)
    anchor0 = tr.window[0] if tr else 0
    epoch = sess.obs.epoch
    to_ns = lambda t: int((t - run.t_w0) * 1e9) + anchor0
    host_spans = [[e["name"], to_ns(epoch + e["ts"]),
                   to_ns(epoch + e["ts"] + e["dur"])] for e in spans]
    ctx = {"trace": tr, "spans": spans, "frames": run.n_frames,
           "window_s": run.window_s, "shape": conf["shape"], "config": conf,
           "peaks": (arith.peaks_for(run.device["kind"]) if run.on_chip
                     else None),
           "nbytes": run.sink.nbytes[run.first:], "steers": run.steers,
           "workload": run.cell["name"], "stamps": run.stamps}
    per_layer, breakdown, dev_extra = {}, None, {}
    for mod in load_layers():
        if mod.CELLS != "all" and run.cell["name"] not in mod.CELLS:
            continue
        if tr is None and mod.SOURCE == "device_trace":
            continue
        value = mod.read(ctx)
        if value is not None:
            per_layer[mod.NAME] = (float(value), mod.UNIT)
    if tr is not None:
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(host_spans, 10)}
        dev_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
        run.log(f"[chipbench] programs on device 0: {tr.program_names()}")
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    return per_layer, breakdown, dev_extra


def produced_by(run) -> dict:
    """What the timed path produced for the comparison: what the source
    kept of the field after frame 0, the reference frame of the warm-up
    and the compared frame of the window, and the pose every frame up to
    that one was rendered from."""
    sink = run.sink
    return {
        "kept": run.kept,
        "frames": {f: sink.kept[f] for f in (run.traf["reference_frame"],
                                             run.compared)
                   if f in sink.kept},
        "compared": run.compared,
        "poses": sink.poses[:run.compared + 1]}


def close_run(run) -> None:
    """End what the source started and free the session, whether the run
    got to its end or not."""
    end_session(run.source, run.sess)
    del run.sess, run.sink
    run.kept = None
    gc.collect()


def references(cell: dict, seed: int, produced: dict) -> dict:
    """The plain references of what `produced_by` handed back: the source's
    own of what it kept of the field, and the session rebuilt with the
    configuration's `reference_overrides` (the XLA schedules named), fed
    the same field by the source and replaying the run's cameras up to the
    compared frame of the window."""
    conf, traf = cell["config_file"], cell["traffic_file"]
    source = load_source(cell)
    kept = source.plain_reference(cell, seed)
    wanted = sorted({traf["reference_frame"], produced["compared"]})
    viewer = Viewer(traf["steering"], seed)
    sink = Sink(viewer, keep=wanted)
    sess = source.build_session(
        cell, overrides_of(cell) + conf["reference_overrides"], seed, sink,
        Replay(viewer, produced["poses"]), fed=kept)
    try:
        for f in range(wanted[-1] + 1):
            if f in wanted:
                sess.run(1)
            else:   # not fetched; wait, so that dispatch cannot run ahead
                sess.run(1, fetch=False)
                source.wait(sess)
    finally:
        end_session(source, sess)
    frames = dict(sink.kept)
    del sess, sink
    gc.collect()
    return {"kept": kept, "frames": frames}


def compare(run, produced: dict, refs: dict) -> None:
    """Each number compared, beside its limit."""
    limits = run.conf["limits"]
    for c in run.source.compare(run.cell, produced["kept"], refs["kept"]):
        check(run, *c)
    for f, ref in sorted(refs["frames"].items()):
        where = "window" if f == produced["compared"] else "warmup"
        if f not in produced["frames"]:
            check(run, f"decoded_psnr_dB_{where}_frame", "not delivered",
                  limits["psnr_floor_db"], False)
            continue
        got = produced["frames"][f]
        q = reference.psnr(
            reference.decode(ref["vdi_color"], ref["vdi_depth"]),
            reference.decode(got["vdi_color"], got["vdi_depth"]))
        run.log(f"[chipbench] frame {f} ({where}) decoded against the "
                f"reference session's frame {f}")
        check(run, f"decoded_psnr_dB_{where}_frame", q,
              limits["psnr_floor_db"], q >= limits["psnr_floor_db"])
    check_ledger(run, "fallback_ledger_rows_reference")


def result(run, failed: int, layers) -> dict:
    per_layer, breakdown, dev_extra = layers
    run.meter.close()
    device = dict(run.device, memory_peak_bytes=max(
        (p for p in run.peaks if p is not None), default=None), **dev_extra)
    return {"correct": all(ok for *_, ok in run.checks),
            "attempted": run.n_frames, "failed": failed,
            "end_to_end": run.end_to_end, "per_layer": per_layer,
            "device": device, "breakdown": breakdown, "checks": run.checks,
            "window_s": run.window_s}


def run_window(run, seconds: float):
    """Warm-up, window, the window's checks and the traced readers; the
    session is ended and freed, also where one of them raises. Returns
    (failed frames, layers, produced)."""
    try:
        warm_up(run, seconds)
        measure(run)
        failed = window_checks(run)
        layers = read_layers(run)
        produced = produced_by(run)
    finally:
        close_run(run)
    return failed, layers, produced


def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell. Returns {"correct", "attempted", "failed",
    "end_to_end", "per_layer", "device", "breakdown", "checks"}; raises
    BenchFailure where there is no result to give."""
    run = open_run(cell, seed, trace)
    failed, layers, produced = run_window(run, seconds)
    t0 = time.perf_counter()
    compare(run, produced, references(cell, seed, produced))
    run.log(f"[chipbench] reference and comparisons: "
            f"{time.perf_counter() - t0:.2f} s (in neither setup_s nor the "
            f"window); compile totals {run.meter.snapshot()}")
    return result(run, failed, layers)
