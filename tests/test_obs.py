"""Observability layer (ISSUE 3): structured spans, the fallback ledger,
Chrome-trace/JSONL export, the disabled-recorder no-op path, and the
Timers windowed-dump reset."""

import json
import warnings

import pytest

from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.obs.recorder import Recorder
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.runtime.session import InSituSession
from scenery_insitu_tpu.runtime.timers import Timers


@pytest.fixture(autouse=True)
def _isolate_global_obs():
    """Sessions with obs enabled install themselves as the process
    recorder and degradations land in a process-global ledger — restore
    both around every test."""
    prev = obs.get_recorder()
    obs.clear_ledger()
    yield
    obs.set_recorder(prev)
    obs.clear_ledger()


def _session_cfg(**kw):
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=8", "composite.adaptive_iters=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        "runtime.stats_window=2")
    return cfg.with_overrides(*[f"{k}={v}" for k, v in kw.items()])


# ------------------------------------------------------------ recorder core

def test_span_nesting_and_attribution():
    rec = Recorder(enabled=True, rank=3)
    with rec.span("frame", frame=7):
        with rec.span("sim", frame=7, kind="gray_scott"):
            pass
        with rec.span("dispatch", frame=7):
            pass
    spans = [e for e in rec.events if e["type"] == "span"]
    assert [s["name"] for s in spans] == ["sim", "dispatch", "frame"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["frame"]["depth"] == 0 and "parent" not in by_name["frame"]
    assert by_name["sim"]["depth"] == 1
    assert by_name["sim"]["parent"] == "frame"
    assert by_name["sim"]["attrs"] == {"kind": "gray_scott"}
    for s in spans:
        assert s["frame"] == 7
        assert s["rank"] == 3
        assert s["dur"] >= 0.0
    # spans feed the wrapped Timers' PhaseStats too (one sink among several)
    assert rec.timers.stats["sim"].n == 1


def test_counters_and_summary():
    rec = Recorder(enabled=True)
    rec.count("compile_step")
    rec.count("compile_step")
    rec.count("build_steps", 8)
    s = rec.summary()
    assert s["counters"]["compile_step"] == 2
    assert s["counters"]["build_steps"] == 8
    assert s["enabled"] is True
    assert isinstance(s["degradations"], list)


# ------------------------------------------------------------------- ledger

def test_forced_codec_degrade_in_ledger(monkeypatch):
    from scenery_insitu_tpu.io import vdi_io

    monkeypatch.setattr(vdi_io, "have_zstd", lambda: False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert vdi_io.resolve_codec("zstd") == "zlib"
        assert vdi_io.resolve_codec("zstd") == "zlib"
    entries = [e for e in obs.ledger() if e["component"] == "io.vdi_codec"]
    assert len(entries) == 1, entries
    assert entries[0]["from"] == "zstd" and entries[0]["to"] == "zlib"
    assert entries[0]["count"] == 2          # deduped, counted
    # the warning the inline site used to emit still fires (once)
    assert sum("zstandard" in str(x.message) for x in w) == 1


# ---------------------------------------------------------------- exporters

def test_chrome_trace_schema(tmp_path):
    rec = Recorder(enabled=True, rank=1)
    with rec.span("sim", frame=0):
        pass
    rec.count("compile_step")
    rec.event("compile", frame=0, what="vdi_step")
    obs.degrade("test.component", "a", "b", "because", warn=False)
    path = rec.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    xs = [e for e in evs if e.get("ph") == "X"]
    assert xs, "no complete (X) span events"
    for e in xs:
        for key in ("ph", "ts", "dur", "pid", "name", "tid"):
            assert key in e, (key, e)
        assert e["pid"] == 1
        assert e["args"]["frame"] == 0
    assert any(e.get("ph") == "C" for e in evs)          # counter
    assert any(e.get("cat") == "degrade" for e in evs)   # ledger instants
    assert any(e.get("ph") == "M" for e in evs)          # process name


def test_metrics_jsonl(tmp_path):
    rec = Recorder(enabled=True)
    with rec.span("sim", frame=0):
        pass
    path = rec.export_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines[0]["type"] == "span" and lines[0]["name"] == "sim"
    assert lines[-1]["type"] == "summary"
    assert "degradations" in lines[-1]


def test_disabled_recorder_noop(tmp_path):
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.jsonl"
    rec = Recorder(enabled=False, trace_path=str(trace),
                   metrics_path=str(metrics))
    for i in range(5):
        with rec.span("sim", frame=i):
            pass
    rec.flush()
    assert rec.events == []                  # zero events recorded
    assert not trace.exists() and not metrics.exists()   # no sink writes
    # ...but the PR-1 timer behavior is fully preserved
    assert rec.timers.stats["sim"].n == 5


# ------------------------------------------------------- session integration

def test_session_run_writes_trace_and_metrics(tmp_path):
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.jsonl"
    cfg = _session_cfg(**{
        "obs.enabled": "true",
        "obs.trace_path": str(trace),
        "obs.metrics_path": str(metrics)})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    sess.run(3)
    with open(trace) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in xs}
    # every host-visible render phase is covered
    assert {"sim", "dispatch", "fetch", "sinks"} <= names, names
    frames = {e["args"].get("frame") for e in xs if e["name"] == "sim"}
    assert frames == {0, 1, 2}
    assert all(e["pid"] == 0 for e in xs)     # rank attribution
    lines = [json.loads(l) for l in open(metrics) if l.strip()]
    assert lines and lines[-1]["type"] == "summary"
    assert lines[-1]["frames"] == 3
    assert lines[-1]["counters"].get("build_steps") == 1


def test_session_disabled_obs_zero_events():
    sess = InSituSession(_session_cfg(), mesh=make_mesh(2))
    sess.run(2)
    assert sess.obs.events == []
    assert sess.obs.enabled is False
    assert sess.timers.stats["sim"].n == 2   # PR-1 behavior intact


def test_session_device_snapshot():
    sess = InSituSession(_session_cfg(), mesh=make_mesh(2))
    sess.run(1)
    snaps = sess.device_snapshot()
    assert "gather" in snaps
    snap = snaps["gather"]
    assert snap is None or "source" in snap


def test_gather_obs_events_single_process():
    from scenery_insitu_tpu.parallel.multihost import gather_obs_events

    rec = Recorder(enabled=True, rank=0)
    with rec.span("sim", frame=0):
        pass
    merged = gather_obs_events(rec)
    assert merged is not None
    assert merged[0]["name"] == "sim"
    assert merged[-1]["type"] == "summary"


# ------------------------------------------------------------------- timers

def test_window_stats_reset_between_dumps():
    """Regression: each windowed dump must average ONLY its own window —
    never accumulate over the whole run."""
    lines = []
    t = Timers(window=2, log=lines.append)
    for _ in range(2):
        t.record("sim", 1.0)
        t.frame_done()
    assert any("window of 2" in l for l in lines)
    # reset happened: the window accumulator is empty after the dump
    assert all(st.n == 0 for st in t.window_stats.values())
    for _ in range(2):
        t.record("sim", 3.0)
        t.frame_done()
    # second window dump shows the second window's average (3000 ms),
    # not the accumulated 2000 ms
    second = [l for l in lines if "sim" in l][-1]
    assert "3000.000 ms" in second, second
    assert t.stats["sim"].n == 4             # totals still cover the run


def test_dump_totals_flushes_partial_window():
    lines = []
    t = Timers(window=100, log=lines.append)
    for _ in range(3):                        # never reaches a boundary
        t.record("sim", 0.5)
        t.frame_done()
    assert not any("window" in l for l in lines)
    t.dump_totals()
    assert any("final partial window" in l for l in lines)
    assert any("totals over 3 frames" in l for l in lines)
    # idempotent on the window part
    n = len(lines)
    t.close()
    assert not any("final partial window" in l for l in lines[n:])


def test_degrade_dedup_and_warning_once():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        obs.degrade("x.y", "fast", "slow", "why")
        obs.degrade("x.y", "fast", "slow", "why")
        obs.degrade("x.y", "fast", "slow", "other reason")
    entries = [e for e in obs.ledger() if e["component"] == "x.y"]
    assert len(entries) == 2
    assert entries[0]["count"] == 2 and entries[1]["count"] == 1
    assert len(w) == 2                        # one warning per distinct entry


def test_obs_config_roundtrip():
    cfg = FrameworkConfig().with_overrides(
        "obs.enabled=true", "obs.trace_path=/tmp/t.json", "obs.window=7")
    assert cfg.obs.enabled is True
    assert cfg.obs.trace_path == "/tmp/t.json"
    assert cfg.obs.window == 7
    d = cfg.to_dict()
    assert d["obs"]["enabled"] is True
    cfg2 = FrameworkConfig.from_dict(d)
    assert cfg2.obs == cfg.obs


# ---------------------------------------------------------------- SLO engine

def _slo_cfg(**kw):
    from scenery_insitu_tpu.config import SLOConfig
    kw.setdefault("window", 8)
    kw.setdefault("min_samples", 2)
    return SLOConfig(enabled=True, **kw)


def test_slo_disabled_noop():
    from scenery_insitu_tpu.config import SLOConfig
    from scenery_insitu_tpu.obs.slo import SLOEngine

    rec = Recorder(enabled=True)
    slo = SLOEngine(SLOConfig(enabled=False, frame_p99_ms=0.001), rec)
    for i in range(50):
        slo.observe("frame_ms", 1e9, frame=i)
    snap = slo.snapshot()
    assert snap["enabled"] is False
    assert snap["metrics"] == {}
    assert snap["healthy"] is True
    assert rec.counters.get("slo_breaches") is None


def test_slo_breach_fires_on_transition_and_rearms():
    from scenery_insitu_tpu.obs.slo import SLOEngine

    rec = Recorder(enabled=True)
    slo = SLOEngine(_slo_cfg(frame_p99_ms=10.0), rec)
    for i in range(8):                     # comfortably under budget
        slo.observe("frame_ms", 1.0, frame=i)
    assert not slo.breached("frame_ms")
    for i in range(4):                     # p99 over budget: ONE episode
        slo.observe("frame_ms", 100.0, frame=8 + i)
    assert slo.breached("frame_ms")
    assert rec.counters.get("slo_breaches") == 1
    events = [e for e in rec.events if e["name"] == "slo_breach"]
    assert len(events) == 1
    assert events[0]["attrs"]["metric"] == "frame_ms"
    assert events[0]["attrs"]["budget"] == 10.0
    assert [e["component"] for e in obs.ledger()].count("slo.breach") == 1
    # flush the window back under budget -> the gate re-arms ...
    for i in range(8):
        slo.observe("frame_ms", 1.0, frame=12 + i)
    assert not slo.breached("frame_ms")
    # ... and the next excursion is a SECOND counted episode
    for i in range(4):
        slo.observe("frame_ms", 100.0, frame=20 + i)
    assert rec.counters.get("slo_breaches") == 2
    assert slo.snapshot()["metrics"]["frame_ms"]["breaches"] == 2


def test_slo_min_samples_gates_the_check():
    from scenery_insitu_tpu.obs.slo import SLOEngine

    rec = Recorder(enabled=True)
    slo = SLOEngine(_slo_cfg(min_samples=5, frame_p99_ms=1.0), rec)
    for i in range(4):                     # wildly over budget, too few
        slo.observe("frame_ms", 1e6, frame=i)
    assert not slo.breached()
    slo.observe("frame_ms", 1e6, frame=4)  # 5th sample arms the gate
    assert slo.breached("frame_ms")


def test_slo_untracked_metric_is_gate_free():
    from scenery_insitu_tpu.obs.slo import SLOEngine

    slo = SLOEngine(_slo_cfg(), Recorder(enabled=True))
    for i in range(20):
        slo.observe("made_up_metric", 1e9, frame=i)
    m = slo.snapshot()["metrics"]["made_up_metric"]
    assert m["budget"] == 0.0 and m["breaches"] == 0
    assert slo.snapshot()["healthy"] is True


def test_slo_observe_phase_and_quantiles():
    from scenery_insitu_tpu.obs.slo import SLOEngine

    slo = SLOEngine(_slo_cfg(phase_p99_ms=1e9), Recorder(enabled=True))
    for ms in (1.0, 2.0, 3.0, 4.0):
        slo.observe_phase("composite", ms / 1e3)   # seconds, like Timers
    m = slo.snapshot()["metrics"]["phase:composite_ms"]
    assert m["n"] == 4 and m["last"] == 4.0
    assert slo.quantile("phase:composite_ms", 0.50) == 2.0
    assert slo.quantile("phase:composite_ms", 0.99) == 4.0


def test_slo_snapshot_schema():
    from scenery_insitu_tpu.obs.slo import SLOEngine

    slo = SLOEngine(_slo_cfg(frame_p99_ms=5.0), Recorder(enabled=True))
    slo.observe("frame_ms", 2.0, frame=0)
    snap = slo.snapshot()
    assert snap["type"] == "slo_report"
    assert set(snap) == {"type", "enabled", "window", "min_samples",
                         "metrics", "total_breaches", "healthy"}
    assert set(snap["metrics"]["frame_ms"]) == {
        "n", "window_n", "last", "p50", "p99", "budget", "breached",
        "breaches"}
    json.dumps(snap)                       # machine-readable for real


# ------------------------------------------------- fleet telemetry collector

def test_lineage_instants_and_age():
    from scenery_insitu_tpu.obs.collector import lineage, trace_ctx

    rec = Recorder(enabled=True)
    obs.set_recorder(rec)
    lineage("publish", "send", 3)
    ctx = trace_ctx(3, src=1)
    lineage("publish", "recv", None, ctx=ctx)
    send, recv = [e for e in rec.events if e["name"] == "lineage"]
    assert send["attrs"]["stage"] == "publish"
    assert send["attrs"]["role"] == "send" and send["frame"] == 3
    # the recv side decodes the wire trace context: frame comes from the
    # ctx, and the origin stamp yields the measured age
    assert recv["frame"] == 3 and recv["attrs"]["src"] == 1
    assert recv["attrs"]["t_origin"] == ctx["t"]
    assert recv["attrs"]["age_ms"] >= 0.0


def test_publisher_collector_roundtrip():
    zmq = pytest.importorskip("zmq")  # noqa: F841
    from scenery_insitu_tpu.obs.collector import Collector, ObsPublisher

    col = Collector()
    pub = ObsPublisher(col.endpoint, col.hb_endpoint, rank=2,
                       interval_s=0.0)
    try:
        # prove the PUB path first (the channel is legally lossy while
        # the zmq subscription handshake is in flight)
        deadline = __import__("time").monotonic() + 10.0
        while not pub.linked and __import__("time").monotonic() < deadline:
            pub.probe()
            col.poll(10)
        assert pub.linked
        assert col.batches == 0            # probes carry no payload
        rec = Recorder(enabled=True, rank=2)
        with rec.span("frame", frame=0):
            pass
        assert pub.pump(rec, force=True)
        for _ in range(100):
            # poll() also counts probes still queued from the handshake
            # loop above — wait for the payload batch itself
            col.poll(20)
            if col.batches:
                break
        assert col.batches == 1
        merged = col.merged_events()
        assert any(e["name"] == "frame" and e["rank"] == 2
                   for e in merged)
        # the pong-driven clock model has a sane bound on loopback
        assert pub.rtt > 0.0
        assert abs(pub.clock_offset) < 5.0
    finally:
        pub.close()
        col.close()


def test_publisher_to_dead_collector_drops_are_ledgered():
    pytest.importorskip("zmq")
    from scenery_insitu_tpu.obs.collector import Collector, ObsPublisher

    col = Collector()
    ep, hb = col.endpoint, col.hb_endpoint
    col.close()                            # collector is GONE
    pub = ObsPublisher(ep, hb, rank=0, interval_s=0.0)
    rec = Recorder(enabled=True)
    try:
        for i in range(5):
            with rec.span("frame", frame=i):
                pass
            pub.pump(rec, force=True)      # never raises, never blocks
        # a PUB socket discards silently, so the verdict comes from the
        # heartbeat liveness: >= 3 unanswered pings = presumed lost
        assert pub.drops > 0
        assert rec.counters.get("obs_batch_drops", 0) > 0
        assert any(e["component"] == "obs.collector"
                   for e in obs.ledger())
    finally:
        pub.close()


# ---------------------------------------------------------- flight recorder

def test_flight_recorder_dumps_partial_artifacts_on_crash(tmp_path):
    """Kill the session mid-run (sim raises at frame 2 of 5): the crash
    path must flush WELL-FORMED partial trace/metrics artifacts before
    the exception propagates — the window that explains the crash is
    exactly the one a normal flush would have lost."""
    from scenery_insitu_tpu.runtime.session import VolumeSimAdapter

    class DyingSim:
        def __init__(self, inner, die_at):
            self._inner = inner
            self._die_at = die_at
            self._calls = 0
            self.kind = inner.kind

        def advance(self, n):
            if self._calls >= self._die_at:
                raise RuntimeError("sim exploded mid-run")
            self._calls += 1
            self._inner.advance(n)

        @property
        def field(self):
            return self._inner.field

    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.jsonl"
    cfg = _session_cfg(**{"obs.enabled": "true",
                          "obs.trace_path": str(trace),
                          "obs.metrics_path": str(metrics)})
    sess = InSituSession(cfg, mesh=make_mesh(2),
                         sim=DyingSim(VolumeSimAdapter(cfg), die_at=2))
    with pytest.raises(RuntimeError, match="sim exploded"):
        sess.run(5)
    # both artifacts exist, parse, and hold the pre-crash frames (the
    # dying frame's sim span still closes, so it may be the last one)
    doc = json.load(open(trace))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    frames = {e["args"].get("frame") for e in xs if e["name"] == "sim"}
    assert {0, 1} <= frames and max(frames) <= 2
    lines = [json.loads(l) for l in open(metrics) if l.strip()]
    assert lines[-1]["type"] == "summary"
    assert sess.obs.counters.get("flight_dumps") == 1
    assert any(e["name"] == "flight_dump" for e in sess.obs.events)
    assert any(e["component"] == "obs.flight_recorder"
               for e in obs.ledger())


# ------------------------------------------- session x fleet side-channel

def test_session_pumps_configured_collector(tmp_path):
    pytest.importorskip("zmq")
    from scenery_insitu_tpu.obs.collector import Collector

    col = Collector()
    try:
        cfg = _session_cfg(**{
            "obs.enabled": "true",
            "obs.collector": col.endpoint,
            "obs.collector_hb": col.hb_endpoint,
            "obs.collector_interval_s": 0.001})
        sess = InSituSession(cfg, mesh=make_mesh(2))
        # settle the PUB path before the frames (the channel is legally
        # lossy during the zmq subscription handshake)
        deadline = __import__("time").monotonic() + 10.0
        while (not sess._obs_pub.linked
               and __import__("time").monotonic() < deadline):
            sess._obs_pub.probe()
            col.poll(10)
        assert sess._obs_pub.linked
        sess.run(3)
        for _ in range(100):
            col.poll(20)
            if col.batches > 0 and any(
                    e["name"] == "sim" for e in col.merged_events()):
                break
        assert col.batches > 0
        names = {e["name"] for e in col.merged_events()}
        assert "sim" in names              # real session phases arrived
        assert sess.obs.counters.get("obs_batches_published", 0) > 0
    finally:
        col.close()


def test_session_slo_breach_end_to_end():
    # min_samples first: overrides validate one at a time, and the
    # default min_samples (16) would not fit the shrunken window
    cfg = _session_cfg(**{"slo.enabled": "true", "slo.min_samples": "1",
                          "slo.window": "8",
                          "slo.frame_p99_ms": "0.000001"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    sess.run(2)                            # any real frame breaches that
    snap = sess.slo.snapshot()
    assert snap["total_breaches"] >= 1
    assert snap["metrics"]["frame_ms"]["n"] == 2
    assert not snap["healthy"]
    assert sess.obs.counters.get("slo_breaches", 0) >= 1
    assert any(e["component"] == "slo.breach" for e in obs.ledger())


# ------------------------------------------- one trace, one clock (ISSUE 24)

_MXU = {"slicer.engine": "mxu", "vdi.adaptive_mode": "temporal"}
_CHILDREN = {"fetch.ready": "fetch", "fetch.copy": "fetch",
             "fetch.concat": "fetch", "camera_readback": None}


class _Steer:
    """An in-process steering source: a camera message on every second
    drain, eye only. With the session's orbit on, the camera such a
    message meets was computed on the device, so `steer_camera` reads its
    target back; so does the frame between two messages, at dispatch."""

    def __init__(self):
        self.drains = 0

    def drain(self):
        self.drains += 1
        return ([{"type": "camera", "eye": [0.1, 0.6, 3.0]}]
                if self.drains % 2 else [])


def _mxu_session(ranks, enabled, **kw):
    sess = InSituSession(
        _session_cfg(**{"obs.enabled": str(enabled).lower(), **_MXU, **kw}),
        mesh=make_mesh(ranks), sinks=[lambda i, p: None])
    sess.steering = _Steer()
    sess.orbit_rate = 0.01
    return sess


@pytest.mark.parametrize("ranks", [1, 2])
def test_session_child_spans_nest_and_carry_frames(ranks):
    sess = _mxu_session(ranks, True)
    sess.run(3)
    spans = [e for e in sess.obs.events if e["type"] == "span"]
    assert all("frame" in s for s in spans), \
        [s["name"] for s in spans if "frame" not in s]
    nest = {(s["name"], s.get("parent")) for s in spans}
    assert {("fetch.ready", "fetch"), ("fetch.copy", "fetch"),
            ("camera_readback", "dispatch"),
            ("camera_readback", "steer")} <= nest, nest
    sites = {s["attrs"]["site"] for s in spans
             if s["name"] == "camera_readback"}
    assert sites == {"mxu_step", "steer_defaults"}
    copies = [s for s in spans if s["name"] == "fetch.copy"]
    assert all(s["attrs"]["bytes"] > 0 for s in copies)
    if ranks == 1:
        assert len(copies) == 3                 # one per frame
    else:
        # one per addressable shard of each of the VDI's two leaves
        assert len(copies) == 3 * 2 * ranks
        assert {s["attrs"]["shard"] for s in copies} == set(range(ranks))
        assert ("fetch.concat", "fetch") in nest
    # a child closes before its parent: the gap after it is its own
    by_frame = {}
    for s in spans:
        by_frame.setdefault((s["name"], s["frame"]), s)
    for s in spans:
        if s["name"].startswith("fetch."):
            par = by_frame[("fetch", s["frame"])]
            assert par["ts"] <= s["ts"]
            assert s["ts"] + s["dur"] <= par["ts"] + par["dur"]
    # the step executable's scope table, taken once, for the trace join
    table = sess.obs.hlo_scopes
    assert "jit_step" in table
    assert {"march", "fold"} <= set(table["jit_step"].values())


@pytest.mark.parametrize("ranks,enabled", [(1, True), (4, True), (8, True),
                                           (4, False)])
def test_to_host_is_bitwise_np_asarray(ranks, enabled):
    """The recorded fetch copies a sharded frame shard by shard and
    assembles it on the host: the same bytes as np.asarray's."""
    import jax
    import numpy as np

    sess = _mxu_session(ranks, enabled)
    sess.run(1)
    out = sess.render_frame()
    n0 = len(sess.obs.events)
    host = sess._to_host(1, out)
    for got, dev in zip(jax.tree_util.tree_leaves(host),
                        jax.tree_util.tree_leaves(out)):
        want = np.asarray(dev)
        assert isinstance(got, np.ndarray)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    names = [e["name"] for e in sess.obs.events[n0:]]
    if not enabled:
        assert sess.obs.events == [] and sess.obs.hlo_scopes == {}
    elif ranks == 1:
        assert names == ["fetch.ready", "fetch.copy"]
    else:
        assert names.count("fetch.copy") == 2 * ranks
        assert names.count("fetch.concat") == 2


@pytest.mark.parametrize("ranks", [1, 2, 4])
def test_obs_enabled_payload_is_bitwise_the_disabled_one(ranks):
    payloads = []
    for enabled in (False, True):
        sess = _mxu_session(ranks, enabled)
        payloads.append(sess.run(3))
        assert bool(sess.obs.events) is enabled
    off, on = payloads
    for key in ("vdi_color", "vdi_depth"):
        assert off[key].tobytes() == on[key].tobytes()
    assert off["frame"] == on["frame"] == 2


@pytest.fixture(scope="module")
def profiled_annotations(tmp_path_factory):
    """One profile of a 3-frame session taken with run(profile_dir=...)
    and obs.enabled: {span name: [stats of each annotation of that name
    on the /host:CPU plane]} and the recorder's own spans."""
    import glob

    from jax.profiler import ProfileData

    prev = obs.get_recorder()
    out = str(tmp_path_factory.mktemp("profile"))
    sess = _mxu_session(1, True)
    sess.run(1)
    n0 = len(sess.obs.events)
    sess.run(3, profile_dir=out)
    spans = [e for e in sess.obs.events[n0:] if e["type"] == "span"]
    obs.set_recorder(prev)
    obs.clear_ledger()
    path, = glob.glob(out + "/plugins/profile/*/*.xplane.pb")
    names = {s["name"] for s in spans}
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    return found, spans


@pytest.mark.parametrize("name", ["steer", "sim", "dispatch", "fetch",
                                  "sinks", "fetch.ready", "fetch.copy",
                                  "camera_readback"])
def test_profile_holds_spans_as_annotations_with_frame(
        profiled_annotations, name):
    found, spans = profiled_annotations
    mine = [s for s in spans if s["name"] == name]
    assert mine and len(found.get(name, [])) == len(mine)
    assert sorted(st["frame"] for st in found[name]) == \
        sorted(s["frame"] for s in mine)
    if name == "camera_readback":
        assert {st["site"] for st in found[name]} == \
            {"mxu_step", "steer_defaults"}


def test_disabled_span_makes_no_annotation(monkeypatch):
    from scenery_insitu_tpu.obs import recorder as rec_mod

    made = []
    monkeypatch.setattr(rec_mod, "_annotation",
                        lambda: made.append(1) or None)
    rec = Recorder(enabled=False)
    with rec.span("sim", frame=0, kind="x"):
        pass
    assert made == [] and rec.events == []
    rec = Recorder(enabled=True)
    with rec.span("sim", frame=0, kind="x", arr=object()):
        pass
    assert made == [1] and rec.events[0]["frame"] == 0


# ------------------------------------- the host's frame from inside (PR 39)

_LOOP_SPANS = {"steer", "sim", "dispatch", "upkeep", "host_copy.start",
               "release", "fetch", "sinks"}


class _Mailbox:
    """A steering source that hands over what the test put in it."""

    def __init__(self):
        self.msgs = []

    def drain(self):
        msgs, self.msgs = self.msgs, []
        return msgs


def _steered_session(enabled, ranks=1, sink=None, **kw):
    sess = InSituSession(
        _session_cfg(**{"obs.enabled": str(enabled).lower(), **_MXU, **kw}),
        mesh=make_mesh(ranks), sinks=[sink or (lambda i, p: None)])
    sess.steering = _Mailbox()
    return sess


def test_loop_thread_spans_cover_the_run():
    """With the recorder on, the loop thread's depth-0 spans are leaf
    spans side by side (none covers an iteration) and leave under 5 % of
    `run`'s wall time under no span."""
    import time

    sess = _steered_session(True, **{"sim.grid": "[48,48,48]",
                                     "render.width": "96",
                                     "render.height": "64"})
    sess.run(2)                             # compiles
    n0 = len(sess.obs.events)
    t0 = time.perf_counter()
    sess.run(6)
    wall = time.perf_counter() - t0
    spans = [e for e in sess.obs.events[n0:] if e["type"] == "span"]
    top = sorted((e for e in spans if e["depth"] == 0
                  and e["thread"] == "MainThread"), key=lambda e: e["ts"])
    assert {e["name"] for e in top} == _LOOP_SPANS
    for a, b in zip(top, top[1:]):          # side by side, in order
        assert a["ts"] + a["dur"] <= b["ts"]
    covered = sum(e["dur"] for e in top)
    assert covered >= 0.95 * wall, (covered, wall)
    assert max(e["dur"] for e in top) < 0.5 * wall      # no root span
    # two `upkeep` spans a frame (after the dispatch, after the retire),
    # two `release` for every fetch: before it the payload of the frame
    # before, with its bytes, after it the retired frame's device arrays
    per_frame = lambda name: [e for e in top if e["name"] == name]
    assert len(per_frame("upkeep")) == 12 and len(per_frame("fetch")) == 6
    nbytes = per_frame("host_copy.start")[0]["attrs"]["bytes"]
    assert nbytes > 0
    named = lambda attrs: {k: v for k, v in attrs.items()
                           if k not in ("rss_pages", "minflt", "majflt")}
    assert [named(e["attrs"]) for e in per_frame("release")] == [
        a for n in [0] + [nbytes] * 5 for a in ({"bytes": n},
                                                {"device": True})]
    assert all(isinstance(e["thread"], str) for e in spans)


def test_unrecorded_run_opens_no_new_span_and_reads_nothing(monkeypatch):
    """With the recorder off: no span of the new names is made, no
    `is_ready()` is asked of a frame in flight, the sim's `upload_busy` is
    not read, no thread name is looked up, and `events` stays empty."""
    import types

    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.obs import recorder as rec_mod

    def boom(*_a, **_k):
        raise AssertionError("touched in a run that records nothing")

    class _Sim:
        """A sim facade whose `upload_busy` may not be read."""

        kind = "external"
        upload_busy = property(boom)

        def __init__(self):
            self.field = jnp.zeros((16, 16, 16), jnp.float32)

        def advance(self, n):
            pass

    made = []
    init = rec_mod._Span.__init__
    monkeypatch.setattr(
        rec_mod._Span, "__init__",
        lambda self, rec, name, *a: (made.append(name),
                                     init(self, rec, name, *a))[1])
    sess = InSituSession(_session_cfg(**_MXU), sim=_Sim(),
                         mesh=make_mesh(1), sinks=[lambda i, p: None])
    sess.steering = _Mailbox()
    sess.run(1)
    monkeypatch.setattr(type(jnp.zeros(1)), "is_ready", boom)
    proxy = types.SimpleNamespace(**{
        k: getattr(rec_mod.threading, k) for k in dir(rec_mod.threading)
        if not k.startswith("__")})
    proxy.current_thread = boom
    monkeypatch.setattr(rec_mod, "threading", proxy)
    sess.steering.msgs = [{"type": "camera", "eye": [0.2, 0.6, 3.0]}]
    made.clear()
    payload = sess.run(3)
    assert payload["frame"] == 3 and sess._steer_seq == 1
    assert sess.obs.events == []
    assert made and not set(made) & {"upkeep", "host_copy.start", "release"}
    assert not {"upkeep", "host_copy.start", "release"} & set(
        sess.timers.stats)
    # the same reads are made, once per launch, in a recorded run
    rec = InSituSession(_session_cfg(**{"obs.enabled": "true", **_MXU}),
                        sim=_Sim(), mesh=make_mesh(1),
                        sinks=[lambda i, p: None])
    with pytest.raises(AssertionError, match="records nothing"):
        rec.run(1)


def test_a_camera_message_is_followed_from_drain_to_sinks():
    """A message drained before frame 2 is number 1: that frame's `steer`
    span says so (with how many it drained and when), its `dispatch`,
    `fetch` and `sinks` spans carry the number, the frames before carry 0,
    and the frame's own view matrix is the message's camera."""
    import time

    import numpy as np

    views = {}
    sess = _steered_session(
        True, sink=lambda i, p: views.__setitem__(
            i, np.asarray(p["meta"].view, np.float64)))
    eye_of = lambda v: -v[:3, :3].T @ v[:3, 3]
    sess.run(2)
    eye = [0.3, 0.5, 2.9]
    t_sent = time.perf_counter()
    sess.steering.msgs = [{"type": "record", "on": True},
                          {"type": "camera", "eye": eye}]
    sess.run(2)
    sess.steering.msgs = [{"type": "camera", "eye": [0.0, 0.6, 3.0]},
                          {"type": "camera", "eye": [0.1, 0.6, 3.0]}]
    sess.run(1)
    spans = [e for e in sess.obs.events if e["type"] == "span"]
    of = lambda name: {e["frame"]: e.get("attrs") or {} for e in spans
                       if e["name"] == name}
    steer = of("steer")
    assert [steer[f]["msgs"] for f in range(5)] == [0, 0, 2, 0, 2]
    assert [steer[f].get("seq") for f in range(5)] == [None, None, 1,
                                                       None, 3]
    assert t_sent < steer[2]["t_drain"] < time.perf_counter()
    assert "t_drain" not in steer[1]
    for name in ("dispatch", "fetch", "sinks"):
        assert [of(name)[f]["steer_seq"] for f in range(5)] == \
            [0, 0, 1, 1, 3], name
    assert np.allclose(eye_of(views[2]), eye, atol=1e-5)
    assert np.allclose(eye_of(views[3]), eye, atol=1e-5)
    assert not np.allclose(eye_of(views[1]), eye, atol=1e-3)
    assert np.allclose(eye_of(views[4]), [0.1, 0.6, 3.0], atol=1e-5)


def test_a_launch_says_what_was_in_flight():
    """`prev_ready` and `upload_busy` on every recorded `dispatch` span:
    nothing is in flight at the first launch of a run, and a sim with no
    uploader is never busy; a frame the loop has waited for is ready."""
    import jax

    sess = _steered_session(True, **{"runtime.pipeline_depth": "2"})
    sess.run(1)
    ready = []
    orig = sess.render_frame

    def waited():
        if sess._pending:                   # the frame before, finished
            jax.block_until_ready(sess._pending[-1][1])
            ready.append(sess._prev_ready())
        return orig()

    sess.render_frame = waited
    sess.run(3)
    launches = [e["attrs"] for e in sess.obs.events
                if e["type"] == "span" and e["name"] == "dispatch"]
    assert [a["prev_ready"] for a in launches] == [False, False, True, True]
    assert ready == [True, True]
    assert {a["upload_busy"] for a in launches} == {False}
    assert not sess._pending                # nothing held after a run


def test_chrome_trace_gives_each_thread_a_row():
    import threading

    rec = Recorder(enabled=True)
    with rec.span("sim", frame=0):
        worker = threading.Thread(
            target=lambda: rec.span("ingest.upload").__enter__()
            .__exit__(None, None, None), name="shm-uploader")
        worker.start()
        worker.join(10)
    rec.count("compile_step")
    evs = rec.chrome_trace_events()
    rows = {e["args"]["name"]: e["tid"] for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert set(rows) == {"MainThread", "shm-uploader"}
    assert len(set(rows.values())) == 2 and 0 not in rows.values()
    tid = {e["name"]: e["tid"] for e in evs if e.get("ph") == "X"}
    assert tid == {"sim": rows["MainThread"],
                   "ingest.upload": rows["shm-uploader"]}
    assert {e["tid"] for e in evs if e.get("ph") == "C"} == {0}
    assert {e["thread"] for e in rec.events if e["type"] == "span"} == \
        set(rows)


# ------------------------------------------- the transfers' account (PR 43)

@pytest.fixture(scope="module")
def transfer_spans():
    """{ranks: (spans, counters)} of a recorded, steered run of four frames
    after a frame that compiled: on one device and on a 4-device mesh."""
    found = {}
    prev = obs.get_recorder()
    for ranks in (1, 4):
        sess = _steered_session(True, ranks=ranks)
        sess.run(1)
        n0, c0 = len(sess.obs.events), dict(sess.obs.counters)
        sess.steering.msgs = [{"type": "camera", "eye": [0.2, 0.6, 3.0]}]
        sess.run(4)
        found[ranks] = (
            [e for e in sess.obs.events[n0:] if e["type"] == "span"],
            {k: v - c0.get(k, 0) for k, v in sess.obs.counters.items()})
    obs.set_recorder(prev)
    return found


def _account_fetch_copy(spans, counters, ranks):
    copies = [e for e in spans if e["name"] == "fetch.copy"]
    assert len(copies) == 4 * (1 if ranks == 1 else 2 * ranks)
    for e in copies:
        assert {type(e["attrs"][k]) for k in ("waited", "beside0",
                                              "beside1")} == {bool}
    for frame in {e["frame"] for e in copies}:      # one `waited` a frame
        assert len({e["attrs"]["waited"] for e in copies
                    if e["frame"] == frame}) == 1
    # the run's last frame is fetched with nothing newer in flight
    last = [e["attrs"] for e in copies if e["frame"] == 4]
    assert not any(a["beside0"] or a["beside1"] for a in last)


def _account_minflt(spans, counters, ranks):
    from scenery_insitu_tpu.obs.hostmem import host_pages

    counted = host_pages().counts_faults        # gVisor's kernel: none
    for name in ("fetch", "release") + (("fetch.concat",) if ranks > 1
                                        else ()):
        named = [e for e in spans if e["name"] == name]
        assert named, name
        for e in named:
            assert isinstance(e["attrs"]["rss_pages"], int), name
            assert ("minflt" in e["attrs"]) == counted
            assert e["attrs"].get("minflt", 0) >= 0
            assert e["attrs"].get("majflt", 1) > 0      # only where non-zero
    assert len([e for e in spans if e["name"] == "release"]) == 8
    concat = [e for e in spans if e["name"] == "fetch.concat"]
    assert len(concat) == (0 if ranks == 1 else 8)
    for e in concat:                    # beside what it already said
        assert {"bytes", "fresh", "kmajor", "rss_pages"} <= set(e["attrs"])


def _account_minflt_frame(spans, counters, ranks):
    from scenery_insitu_tpu.obs.hostmem import PAGE, host_pages

    upkeep = sorted((e for e in spans if e["name"] == "upkeep"),
                    key=lambda e: e["ts"])
    assert len(upkeep) == 8
    first, second = upkeep[0::2], upkeep[1::2]
    assert not any("touched_frame" in (e.get("attrs") or {}) for e in first)
    touched = [e["attrs"]["touched_frame"] for e in second]
    assert all(isinstance(n, int) and n >= 0 for n in touched)
    assert {e["attrs"]["page"] for e in second} == {PAGE}
    assert counters["host_pages_touched"] == sum(touched)
    # the growth of the reading intervals that grew is never under the
    # iteration's net growth, nor under that of a span inside it
    for e, n in zip(second, touched):
        assert n >= e["attrs"]["rss_pages_frame"]
        assert n >= max((s["attrs"]["rss_pages"] for s in spans
                         if s["name"] in ("fetch", "release")
                         and s["frame"] == e["frame"] - 1), default=0)
    if host_pages().counts_faults:
        faults = [e["attrs"]["minflt_frame"] for e in second]
        assert counters["host_minor_faults"] == sum(faults)
        for e, total in zip(second, faults):
            assert total >= sum(
                s["attrs"]["minflt"] for s in spans
                if s["name"] in ("fetch", "release")
                and s["frame"] == e["frame"] - 1)
    else:
        assert "host_minor_faults" not in counters
        assert not any("minflt_frame" in e["attrs"] for e in second)


def _account_prev_ready(spans, counters, ranks):
    for name in ("steer", "sim", "dispatch"):
        by_frame = {}
        for e in spans:
            if e["name"] == name:
                by_frame.setdefault(e["frame"], []).append(
                    e["attrs"]["prev_ready"])
        assert sorted(by_frame) == [1, 2, 3, 4], name
        assert all(len(v) == 1 and type(v[0]) is bool
                   for v in by_frame.values()), name
    # a run's first launch has nothing in flight before it
    assert not any(e["attrs"]["prev_ready"] for e in spans
                   if e["frame"] == 1 and e["name"] in ("steer", "sim",
                                                        "dispatch"))


@pytest.mark.parametrize("ranks", [1, 4])
@pytest.mark.parametrize("what", ["fetch_copy", "minflt", "minflt_frame",
                                  "prev_ready"])
def test_the_transfers_account_is_on_the_spans_that_exist(
        transfer_spans, what, ranks):
    """A recorded `run`: `waited` / `beside0` / `beside1` on every
    `fetch.copy`; `rss_pages` (and `minflt` where the kernel counts
    faults) on `fetch`, `fetch.concat` and both `release` spans;
    `touched_frame` with the page size (counter `host_pages_touched`) and
    `minflt_frame` (counter `host_minor_faults`) on the second `upkeep`;
    `prev_ready` on `steer`, `sim` and `dispatch`, one value a frame."""
    globals()["_account_" + what](*transfer_spans[ranks], ranks)


@pytest.mark.parametrize("ranks", [1, 4])
def test_fetch_copy_says_whether_it_waited_and_beside_what(ranks):
    """Whatever the device answers is what the spans say: a frame (and
    every newer one) the loop has waited for was not `waited` for and had
    nothing `beside` it; one that never answers ready was, beside the
    newer frame in flight for all but the run's last fetch."""
    import jax

    sess = _steered_session(True, ranks=ranks)
    sess.run(1)
    fetch = sess._fetch

    def all_done(index, out):
        jax.block_until_ready((out, [p[1] for p in sess._pending]))
        return fetch(index, out)

    copies = lambda n0: [e["attrs"] for e in sess.obs.events[n0:]
                         if e.get("name") == "fetch.copy"]
    n0, sess._fetch = len(sess.obs.events), all_done
    sess.run(3)
    assert copies(n0) and not any(
        a["waited"] or a["beside0"] or a["beside1"] for a in copies(n0))
    n0, sess._fetch, sess._ready = len(sess.obs.events), fetch, \
        lambda out: False
    sess.run(3)
    per_frame = len(copies(n0)) // 3
    assert all(a["waited"] for a in copies(n0))
    assert [a["beside0"] and a["beside1"] for a in copies(n0)] == \
        [True] * 2 * per_frame + [False] * per_frame
    launches = [e["attrs"]["prev_ready"] for e in sess.obs.events[n0:]
                if e.get("name") in ("steer", "sim", "dispatch")]
    assert len(launches) == 9 and not any(launches)


@pytest.mark.parametrize("ranks", [1, 4])
def test_unrecorded_run_reads_no_fault_count_and_sweeps_nothing(
        ranks, monkeypatch):
    """With the recorder off the process's memory is never read
    (`HostPages.read`: `/proc/self/statm` and `resource.getrusage`) and the
    `is_ready()` sweep (`_ready`: `waited`, `beside`, `prev_ready`) is
    never made; a recorded run does both every frame; and what the sinks
    get is the same bytes either way."""
    import resource

    from scenery_insitu_tpu.obs import hostmem

    hostmem.host_pages()            # its own first readings are not a run's
    calls = {"pages": 0, "getrusage": 0, "ready": 0}
    wrapped = {"pages": hostmem.HostPages.read,
               "getrusage": resource.getrusage,
               "ready": InSituSession._ready}

    def counted(name):
        def call(*args):
            calls[name] += 1
            return wrapped[name](*args)
        return call

    payloads = {}
    for enabled in (False, True):
        sess = _steered_session(enabled, ranks=ranks)
        sess.run(1)
        monkeypatch.setattr(hostmem.HostPages, "read", counted("pages"))
        monkeypatch.setattr(hostmem.resource, "getrusage",
                            counted("getrusage"))
        monkeypatch.setattr(InSituSession, "_ready",
                            staticmethod(counted("ready")))
        calls.update(pages=0, getrusage=0, ready=0)
        payloads[enabled] = sess.run(3)
        monkeypatch.undo()
        if enabled:
            # a frame: the iteration's reading, two a span on `fetch` and
            # both `release` (and each `fetch.concat`); a sweep for
            # `waited`, and with a frame in flight those of the three
            # launch spans and of the copies' `beside`
            assert calls["pages"] >= 3 * 7 and calls["ready"] >= 3 * 3
            assert calls["getrusage"] == (
                calls["pages"] if hostmem.host_pages().counts_faults else 0)
        else:
            assert calls == {"pages": 0, "getrusage": 0, "ready": 0}
            assert sess.obs.events == []
            assert not {"host_minor_faults", "host_pages_touched"} & set(
                sess.obs.counters)
    for key in ("vdi_color", "vdi_depth"):
        assert payloads[False][key].tobytes() == payloads[True][key].tobytes()


def test_host_pages_reads_what_a_first_touch_grows():
    """`HostPages`: a fresh 8 MB mapping, touched, grows the resident set
    by its pages (net of nothing: `rss_pages` and the summed growth
    agree), unmapping it shrinks it and grows nothing; a kernel that
    counts no fault (asked once) is never asked again and leaves `minflt`
    out."""
    import mmap

    import numpy as np

    from scenery_insitu_tpu.obs.hostmem import PAGE, HostPages

    pages = HostPages()
    n = 8 << 20
    before = pages.read()
    pages.take_grown()
    fresh = mmap.mmap(-1, n)
    np.frombuffer(fresh, np.uint8).fill(1)
    found = pages.since(before)
    assert n // PAGE <= found["rss_pages"] <= n // PAGE + 512
    assert found["rss_pages"] <= pages.take_grown() <= n // PAGE + 512
    assert ("minflt" in found) == pages.counts_faults
    before = pages.read()
    fresh.close()
    assert pages.since(before)["rss_pages"] <= -(n // PAGE) + 512
    assert pages.take_grown() <= 512
    blind = HostPages()
    blind.counts_faults = False
    assert blind.read()[1:] == (None, None)
    assert set(blind.since(blind.read())) == {"rss_pages"}


@pytest.mark.parametrize("fold,share", [("pallas_fused", 1.0),
                                        ("pallas_seg", 0.0)])
def test_fold_chunk_counters_say_how_often_the_kernel_shades(fold, share):
    """PR 46: a recorded step notes, while its first call traces it, how
    many chunks its write march folds and how many of them the fold
    kernel shades itself, and adds both on every call:
    `fold_chunks_fused / fold_chunks` reads 1.0 where the march hands the
    kernel its value plane, 0.0 where it hands over shaded rgba. An
    unrecorded step is the jitted function itself and counts nothing."""
    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.obs.profiler import scoped_step
    from scenery_insitu_tpu.ops import slicer

    vol = procedural_volume(24, kind="blobs", seed=3)
    tf = for_dataset("procedural")
    cam = Camera.create((0.2, 0.4, 3.0), fov_y_deg=45.0, near=0.3, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, SliceMarchConfig(
        matmul_dtype="f32", scale=1.0, fold=fold, chunk=8))
    cfg = VDIConfig(max_supersegments=4, adaptive_mode="temporal")
    thr = slicer.initial_threshold(vol, tf, cam, spec, cfg)

    def build():
        @jax.jit
        def step(data, thr):
            v = vol._replace(data=data)
            vdi, _, _, nxt = slicer.generate_vdi_mxu_temporal(
                v, tf, cam, spec, thr, cfg)
            return vdi.color, nxt
        return step

    rec = Recorder(enabled=True)
    step = scoped_step(build(), rec)
    for _ in range(3):
        color, thr = step(vol.data, thr)
    assert float(jnp.max(color[:, 3])) > 0.0
    assert rec.counters["fold_chunks"] == 3 * 3         # 24 planes, chunk 8
    assert rec.counters["fold_chunks_fused"] \
        / rec.counters["fold_chunks"] == share

    off = Recorder(enabled=False)
    plain = build()
    assert scoped_step(plain, off) is plain
    plain(vol.data, thr)
    assert "fold_chunks" not in off.counters
    assert "fold_chunks_fused" not in off.counters
    assert rec.counters["fold_chunks"] == 9             # nobody listening
