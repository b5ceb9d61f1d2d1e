"""The attribution plane (ISSUE 18; docs/OBSERVABILITY.md "Phase
attribution"): named-scope presence in the lowered HLO of every
distributed step builder across schedules, ProfileCapture accounting
(the per-phase sum IS the step wall-clock by construction), roofline
verdict classification on synthetic attributions, the divergence engine
against a perturbed modeled stack, and the disabled-capture
zero-overhead path."""

import jax
import jax.numpy as jnp
import pytest

from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                       TopologyConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.obs.profiler import (EXTRA_PHASES, PHASES,
                                             ProfileCapture,
                                             parse_hlo_scopes, phase,
                                             scope_names, scope_of)
from scenery_insitu_tpu.obs.roofline import (COMM_PHASES, peaks_for,
                                             roofline_verdicts)
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  distributed_vdi_step_mxu,
                                                  shard_volume)
from scenery_insitu_tpu.parallel.topology import make_topology_mesh

W = H = 16
STEPS = 48
N = 8


def _cam():
    return Camera.create((0.0, 0.2, 4.0), fov_y_deg=50.0, near=0.5,
                         far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _vol():
    return procedural_volume(16, kind="blobs")


def _mxu_spec(cam, vol):
    from scenery_insitu_tpu.ops import slicer

    return slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             scale=2.0),
                            multiple_of=N)


def _vcfg():
    return VDIConfig(max_supersegments=6, adaptive_iters=2)


def _compiled_scopes(step, vol, mesh, cam):
    # named scopes survive into compiled-HLO op_name metadata (the join
    # key ProfileCapture uses); the StableHLO dump strips its locs
    fn = step if hasattr(step, "lower") else jax.jit(step)
    data = shard_volume(vol.data, mesh)
    text = fn.lower(data, vol.origin, vol.spacing,
                    cam).compile().as_text()
    return scope_names(text) & set(PHASES)


# --------------------------------------------- scope-name mechanics

def test_scope_of_innermost_wins():
    assert scope_of("jit(step)/sitpu_wave/while/sitpu_march/dot") == \
        "march"
    assert scope_of("jit(step)/transpose") is None
    assert scope_of("sitpu_exchange/ppermute") == "exchange"


def test_phase_scope_lands_in_compiled_hlo():
    @jax.jit
    def f(x):
        with phase("march"):
            y = x @ x
        with phase("merge"):
            return y + 1.0

    x = jnp.ones((8, 8), jnp.float32)
    text = f.lower(x).compile().as_text()
    assert {"march", "merge"} <= scope_names(text)
    module, ops, inherited = parse_hlo_scopes(text)
    assert module
    assert set(ops.values()) >= {"march"}, ops
    assert inherited <= set(ops)


# ------------------------------------- per-builder scope presence

def test_scopes_vdi_mxu_frame_schedule():
    vol, cam = _vol(), _cam()
    mesh = make_mesh(N)
    step = distributed_vdi_step_mxu(
        mesh, _tf(), _mxu_spec(cam, vol), _vcfg(),
        CompositeConfig(max_output_supersegments=8, adaptive_iters=2))
    got = _compiled_scopes(step, vol, mesh, cam)
    assert {"march", "exchange", "merge", "resegment"} <= got, got


def test_scopes_vdi_mxu_waves_schedule():
    vol, cam = _vol(), _cam()
    mesh = make_mesh(N)
    step = distributed_vdi_step_mxu(
        mesh, _tf(), _mxu_spec(cam, vol), _vcfg(),
        CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                        schedule="waves", wave_tiles=2, exchange="ring"))
    got = _compiled_scopes(step, vol, mesh, cam)
    assert {"wave", "march", "merge"} <= got, got
    # the ring hop scope rides inside the wave pipeline
    assert "exchange" in got or "wire_encode" in got, got


def test_scopes_vdi_gather_ring_exchange():
    vol, cam = _vol(), _cam()
    mesh = make_mesh(N)
    step = distributed_vdi_step(
        mesh, _tf(), W, H, _vcfg(),
        CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                        exchange="ring"),
        max_steps=STEPS)
    got = _compiled_scopes(step, vol, mesh, cam)
    assert {"march", "exchange", "merge", "resegment"} <= got, got


def test_scopes_hier_dcn_hop():
    """The two-level composite tags its inter-host hops dcn_hop so the
    attribution can split ICI from DCN time."""
    vol, cam = _vol(), _cam()
    tcfg = TopologyConfig(num_hosts=2)
    mesh, _ = make_topology_mesh(tcfg)
    step = distributed_vdi_step(
        mesh, _tf(), W, H, _vcfg(),
        CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                        exchange="ring"),
        max_steps=STEPS, topology=tcfg)
    got = _compiled_scopes(step, vol, mesh, cam)
    assert "dcn_hop" in got, got
    assert {"march", "merge", "resegment"} <= got, got


def test_scopes_plain_step():
    from scenery_insitu_tpu.config import RenderConfig

    vol, cam = _vol(), _cam()
    mesh = make_mesh(N)
    step = distributed_plain_step(
        mesh, _tf(), W, H, RenderConfig(max_steps=STEPS))
    got = _compiled_scopes(step, vol, mesh, cam)
    assert "march" in got, got
    assert "merge" in got or "exchange" in got, got


# ------------------------------------------- capture accounting

def test_capture_sum_matches_wall():
    """The acceptance gate: per-phase ms (scoped + unattributed + host)
    sums to the measured wall-clock — exact by construction (host-gap +
    thread-pool normalization), asserted within rounding."""
    @jax.jit
    def f(x):
        with phase("march"):
            y = x @ x
        with phase("merge"):
            return jnp.tanh(y).sum()

    x = jnp.ones((256, 256), jnp.float32)
    attr = ProfileCapture(frames=3, warmup=1, devices=1).capture(f, x)
    assert attr is not None, "trace backend absent on CPU?"
    assert attr["type"] == "phase_attribution"
    total = sum(p["ms"] for p in attr["phases"].values())
    wall = attr["wall_ms_per_frame"]
    assert abs(total - wall) <= max(0.15 * wall, 0.05), (total, wall)
    for name in attr["phases"]:
        assert name in PHASES or name in EXTRA_PHASES, name
    assert attr["coverage"] is not None and attr["coverage"] <= 1.0
    assert attr["phases"]["host"]["ms"] >= 0.0


def test_capture_joins_scoped_ops():
    @jax.jit
    def f(x):
        with phase("march"):
            return (x @ x).sum()

    x = jnp.ones((512, 512), jnp.float32)
    attr = ProfileCapture(frames=2, devices=1).capture(f, x)
    assert attr is not None
    assert attr["scoped_ops"] > 0
    assert attr["events_joined"] > 0
    assert "march" in attr["phases"], attr["phases"]
    assert attr["phases"]["march"]["events"] > 0


def test_capture_disabled_is_inert():
    calls = []

    class Boom:
        def lower(self, *a):            # must never be touched
            calls.append("lower")
            raise AssertionError

    out = ProfileCapture(enabled=False).capture(Boom())
    assert out is None and not calls


def test_capture_failure_degrades_not_raises():
    class NotJitted:
        pass

    obs.clear_ledger()
    out = ProfileCapture().capture(NotJitted())
    assert out is None
    assert any(e["component"] == "obs.profiler" for e in obs.ledger())


# ------------------------------------------------ roofline verdicts

def _attr(phases, wall=None, devices=1):
    total = sum(phases.values())
    wall = wall if wall is not None else total
    return {"type": "phase_attribution", "backend": "cpu",
            "device_kind": "cpu", "frames": 1, "devices": devices,
            "wall_ms_per_frame": wall, "device_ms_per_frame": total,
            "coverage": min(1.0, total / wall),
            "phases": {k: {"ms": v, "events": 1}
                       for k, v in phases.items()}}


def test_roofline_hbm_bound_classification():
    """march moving 82 GB/s against a 100 GB/s peak with negligible
    flops must classify hbm."""
    peaks = {"tflops": 100.0, "hbm_gbps": 100.0, "ici_gbps": 45.0,
             "dcn_gbps": 3.125, "device_kind": "synthetic",
             "platform": "tpu", "peaks_source": "test"}
    cost = {"source": "xla_cost_analysis",
            "bytes_accessed": 8.2e9, "flops": 1e9}
    v = roofline_verdicts(_attr({"march": 100.0}), cost, peaks)
    verdict = v["verdicts"]["march"]
    assert verdict["bound"] == "hbm", verdict
    assert verdict["hbm_frac_peak"] > verdict["mxu_frac_peak"]


def test_roofline_mxu_bound_classification():
    peaks = {"tflops": 100.0, "hbm_gbps": 1000.0, "ici_gbps": 45.0,
             "dcn_gbps": 3.125, "device_kind": "synthetic",
             "platform": "tpu", "peaks_source": "test"}
    cost = {"source": "xla_cost_analysis",
            "bytes_accessed": 1e9, "flops": 9e13}
    v = roofline_verdicts(_attr({"march": 1000.0}), cost, peaks)
    assert v["verdicts"]["march"]["bound"] == "mxu"


def test_roofline_comm_and_host_bounds():
    """exchange/dcn_hop classify on their link; a phase under the host
    floor classifies host regardless of its compute fractions."""
    peaks = {"tflops": 100.0, "hbm_gbps": 100.0, "ici_gbps": 45.0,
             "dcn_gbps": 3.125, "device_kind": "synthetic",
             "platform": "tpu", "peaks_source": "test"}
    cost = {"source": "xla_cost_analysis",
            "bytes_accessed": 1e6, "flops": 1e6}
    attr = _attr({"march": 1.0, "exchange": 5.0, "dcn_hop": 5.0,
                  "host": 10.0})
    v = roofline_verdicts(
        attr, cost, peaks,
        modeled={"ici_bytes_per_frame": 200e6,
                 "dcn_bytes_per_frame": 10e6})
    assert v["verdicts"]["exchange"]["bound"] in ("ici", "ici-dcn")
    assert v["verdicts"]["dcn_hop"]["bound"] in ("dcn", "ici-dcn")
    assert v["verdicts"]["host"]["bound"] == "host"
    # tiny compute fractions → below the floor → host-bound
    assert v["verdicts"]["march"]["bound"] == "host"
    assert set(COMM_PHASES) == {"exchange", "dcn_hop"}


def test_roofline_cpu_peaks_are_relative_only():
    peaks = peaks_for("cpu", "cpu")
    assert peaks["device_kind"] is None or peaks["platform"] == "cpu"
    assert "relative" in peaks["peaks_source"]
    v = roofline_verdicts(_attr({"march": 1.0}),
                          {"source": "xla_cost_analysis",
                           "bytes_accessed": 1e6, "flops": 1e6}, peaks)
    assert "march" in v["verdicts"]
    assert v["assumptions"]["peaks_source"] == peaks["peaks_source"]


# ------------------------------------------------ divergence engine

def _modeled_doc():
    return {
        "type": "modeled_projection",
        "assumptions": {"ranks": 8, "grid": 512, "hbm_gbps": 819,
                        "ici_gbps_effective": 45.0},
        "stack": [
            {"lever": "baseline", "config": {},
             "ms": {"sim": 3.0, "march": 1.0, "composite_stream": 0.5,
                    "exchange_exposed": 3.0}},
            {"lever": "ring", "config": {"exchange": "ring"},
             "ms": {"sim": 3.0, "march": 1.0, "composite_stream": 0.5,
                    "exchange_exposed": 1.0}},
        ],
    }


def test_divergence_ranks_the_perturbed_lever():
    """Measured march share triple the model's → march must top the
    next-perf-PR ranking with a positive share delta."""
    from benchmarks.divergence import divergence_report

    attr = _attr({"sim_step": 3.0, "march": 9.0, "merge": 0.3,
                  "resegment": 0.2, "exchange": 3.0})
    rep = divergence_report(attr, _modeled_doc())
    assert rep["type"] == "divergence_report"
    assert rep["modeled_row"] == "baseline"
    top = rep["next_perf_pr"][0]
    assert top["lever"] == "march", rep["next_perf_pr"]
    assert top["share_delta"] > 0
    assert "attack" in top["verdict"]


def test_divergence_selects_config_matched_row():
    from benchmarks.divergence import divergence_report

    attr = _attr({"sim_step": 3.0, "march": 1.0, "merge": 0.5,
                  "exchange": 1.0})
    rep = divergence_report(attr, _modeled_doc(),
                            measured_config={"exchange": "ring"})
    assert rep["modeled_row"] == "ring"
    # matching scale and shares → exchange ratio ≈ 1
    assert rep["levers"]["exchange_exposed"]["ratio"] == 1.0


def test_divergence_unmodeled_residual_accounted():
    """Only `unattributed` is unmodeled now: PR 19's host-delivery term
    moved the measured `host` phase under the `host_delivery` lever, so
    host time diverges against the model instead of hiding in the
    residual."""
    from benchmarks.divergence import divergence_report

    attr = _attr({"sim_step": 1.0, "march": 1.0, "unattributed": 2.0,
                  "host": 6.0})
    rep = divergence_report(attr, _modeled_doc())
    assert rep["unmodeled_ms"] == 2.0
    assert rep["unmodeled_share"] == 0.2
    assert rep["levers"]["host_delivery"]["measured_ms"] == 6.0
    total = sum(e["measured_ms"] for e in rep["levers"].values()) \
        + rep["unmodeled_ms"]
    assert abs(total - rep["measured_total_ms"]) < 1e-6


def test_divergence_self_check_on_committed_artifacts():
    """CI's gate: every committed attribution artifact must produce a
    schema-complete report against the committed modeled projection."""
    from benchmarks.divergence import self_check

    assert self_check() == 0


def test_divergence_roundtrip_from_bench_artifact(tmp_path):
    """report_from_files accepts a bench artifact embedding the capture
    (the SITPU_BENCH_PROFILE=1 shape)."""
    import json

    from benchmarks.divergence import report_from_files

    doc = {"metric": "x", "config": {"exchange": "ring"},
           "phase_attribution": _attr({"sim_step": 2.0, "march": 1.0,
                                       "exchange": 1.0})}
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(doc))
    m = tmp_path / "modeled_projection_r0.json"
    m.write_text(json.dumps(_modeled_doc()))
    rep = report_from_files(str(p), str(m))
    assert rep["modeled_row"] == "ring"
    assert rep["levers"]["sim"]["measured_ms"] == 2.0


# ------------------------------------------------ fleet-trace export

def test_publish_attribution_is_an_instant_event():
    from scenery_insitu_tpu.obs.profiler import publish_attribution

    rec = obs.Recorder(enabled=True)
    publish_attribution(_attr({"march": 2.0, "exchange": 1.0}), rec=rec,
                        frame=4)
    ev, = [e for e in rec.events if e["name"] == "phase_attribution"]
    assert ev["type"] == "instant" and ev["frame"] == 4
    assert ev["attrs"]["ms_march"] == 2.0
    assert ev["attrs"]["ms_exchange"] == 1.0


# ------------------------------- scopes that reach the chip (ISSUE 24)

@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/sitpu_march/sitpu_fold/sitpu_fold_seg_compact/pallas_call",
     "fold"),
    ("jit(step)/sitpu_merge/sitpu_resegment_sorted/pallas_call",
     "resegment"),
    ("jit(f)/sitpu_sim_step_fused/pallas_call", "sim_step"),
    ("jit(step)/sitpu_march/sitpu_fold/reshape", "fold"),
    ("jit(step)/sitpu_foldable/add", "foldable"),
    # the vortex sim program's phases are in the catalog under their own
    # names: none begins `sim_step_`, so none collapses into `sim_step`
    ("jit(vortex_frame)/sitpu_sim_advect/shard_map/gather", "sim_advect"),
    ("jit(vortex_frame)/sitpu_sim_project/jit(fft)/dot.9", "sim_project"),
    ("jit(vortex_frame)/sitpu_sim_field/sqrt", "sim_field"),
    ("jit(f)/sitpu_sim_step/sitpu_sim_advect/gather", "sim_advect"),
])
def test_scope_of_reads_a_kernel_name_as_its_phase(op_name, want):
    assert scope_of(op_name) == want


@pytest.mark.parametrize("fold", ["xla", "pallas_seg", "pallas_fused"])
def test_scopes_temporal_mxu_step_divides_march_and_fold(fold):
    """The slicer's own scopes: resampling and shading stay `march`, what
    the consumer does with a chunk (and the finish of its state) is
    `fold`, whatever the fold's schedule."""
    import dataclasses

    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu,
        distributed_vdi_step_mxu_temporal)

    cam, vol, mesh = _cam(), _vol(), make_mesh(2)
    spec = dataclasses.replace(_mxu_spec(cam, vol), fold=fold)
    vcfg = dataclasses.replace(_vcfg(), adaptive_mode="temporal")
    step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, vcfg,
                                             CompositeConfig())
    seed = distributed_initial_threshold_mxu(mesh, _tf(), spec, vcfg)
    data = shard_volume(vol.data, mesh)
    thr = seed(data, vol.origin, vol.spacing, cam)
    lowered = step.lower(data, vol.origin, vol.spacing, cam, thr)
    assert {"march", "fold"} <= scope_names(
        lowered.as_text(debug_info=True))
    _, ops, inherited = parse_hlo_scopes(lowered.compile().as_text())
    assert {"march", "fold"} <= {ops[i] for i in set(ops) - inherited}


def test_scoped_step_keeps_the_table_once():
    from scenery_insitu_tpu.obs.profiler import scoped_step

    @jax.jit
    def step(x):
        with phase("march"):
            y = x @ x
        with phase("fold"):
            return jnp.cumsum(jnp.tanh(y), 0)

    x = jnp.ones((64, 64), jnp.float32)
    assert scoped_step(step, obs.Recorder(enabled=False)) is step
    rec = obs.Recorder(enabled=True)
    wrapped = scoped_step(step, rec)
    assert rec.hlo_scopes == {}
    assert jnp.array_equal(wrapped(x), step(x))
    table = dict(rec.hlo_scopes)
    assert set(table) == {"jit_step"} == set(rec.hlo_inherited)
    assert {"march", "fold"} <= set(table["jit_step"].values())
    assert rec.hlo_inherited["jit_step"] <= set(table["jit_step"])
    rec.hlo_scopes.clear()
    wrapped(x)                              # read once, not per call
    assert rec.hlo_scopes == {}
    assert wrapped.lower(x).compile() is not None


def test_self_times_leave_a_while_only_what_its_body_leaves():
    from scenery_insitu_tpu.obs.profiler import _self_times

    ops = [("fusion.1", 10, 10), ("while.2", 30, 20), ("fusion.3", 32, 6),
           ("cond.4", 40, 8), ("fold.5", 41, 5), ("copy.6", 52, 6)]
    got = {name: self_ns for name, _, self_ns in _self_times(ops)}
    assert got == {"fusion.1": 10, "while.2": 6, "fusion.3": 6,
                   "cond.4": 3, "fold.5": 5, "copy.6": 6}


def test_capture_joins_from_the_xplane(tmp_path):
    """The join runs from the .xplane.pb: op events only (what a TPU
    needs), each phase's time split into its own and what it inherited."""
    from scenery_insitu_tpu.obs import profiler

    @jax.jit
    def f(x):
        with phase("march"):
            return (x @ x).sum()

    x = jnp.ones((512, 512), jnp.float32)
    attr = ProfileCapture(frames=2, devices=1,
                          trace_dir=str(tmp_path)).capture(f, x)
    assert attr is not None, obs.ledger()
    assert attr["events_joined"] > 0
    march = attr["phases"]["march"]
    assert march["events"] > 0
    assert 0.0 <= march["inherited_ms"] <= march["ms"]
    evs = list(profiler._trace_events(str(tmp_path)))
    assert evs and all(e["ph"] == "X" and "hlo_op" in e["args"]
                       for e in evs)
    assert attr["events_total"] == len(evs)


@pytest.mark.parametrize("left", ["nothing", "trace.json.gz"])
def test_no_xplane_is_no_trace(tmp_path, left):
    """There is one reader: a directory the profiler left no .xplane.pb
    in has no trace, whatever else lies there."""
    from scenery_insitu_tpu.obs import profiler

    if left != "nothing":
        run = tmp_path / "plugins" / "profile" / "2026_01_01"
        run.mkdir(parents=True)
        (run / f"host.{left}").write_bytes(b"")
    with pytest.raises(FileNotFoundError):
        list(profiler._trace_events(str(tmp_path)))


_HLO_INHERIT = """HloModule jit_step, entry_computation_layout={()->f32[]}

%body.1 (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %copy.2 = f32[4] copy(%p)
  %fusion.3 = f32[4] fusion(%copy.2), kind=kLoop, calls=%fused.9, metadata={op_name="jit(step)/sitpu_march/while/body/sitpu_fold/add"}
  ROOT %dus.4 = f32[4] dynamic-update-slice(%fusion.3, %p)
}

%shared.5 (q: f32[4]) -> f32[4] {
  %q = f32[4] parameter(0)
  ROOT %copy.6 = f32[4] copy(%q)
}

ENTRY %main.7 () -> f32[] {
  %while.8 = f32[4] while(%init), condition=%cond.0, body=%body.1, metadata={op_name="jit(step)/sitpu_march/while"}
  %call.10 = f32[4] call(%while.8), to_apply=%shared.5, metadata={op_name="jit(step)/sitpu_merge/call"}
  %call.11 = f32[4] call(%call.10), to_apply=%shared.5, metadata={op_name="jit(step)/sitpu_resegment/call"}
  ROOT %copy.12 = f32[4] copy(%call.11)
}
"""


@pytest.mark.parametrize("inst,want,marked", [
    ("while.8", "march", False),        # its own op_name
    ("fusion.3", "fold", False),        # innermost of its own path
    ("copy.2", "march", True),          # none of its own: the while's
    ("dus.4", "march", True),
    ("copy.6", None, False),            # two callers that disagree
    ("copy.12", None, False),           # the entry has no caller
])
def test_an_instruction_without_a_scope_inherits_and_is_marked(
        inst, want, marked):
    module, ops, inherited = parse_hlo_scopes(_HLO_INHERIT)
    assert module == "jit_step"
    assert ops.get(inst) == want
    assert (inst in inherited) == marked
