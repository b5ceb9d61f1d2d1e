"""A camera message stays on the host (ISSUE 26): the session keeps the
message's own eye and target beside the camera it puts on the device, and
the march regime is decided from them. The device copy is bit-equal to what
the parent's `apply_steering` made, and a camera that only lives on the
device (orbit, restore, a caller's assignment) is still read back."""

import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.core.camera import Camera, HostPose
from scenery_insitu_tpu.obs.recorder import Recorder
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.runtime.checkpoint import load_session, save_session
from scenery_insitu_tpu.runtime.session import (InSituSession, camera_regime,
                                                host_pose, steer_session)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parent_apply_steering(cam, msg):
    """`apply_steering`'s camera branch as the parent commit had it: both
    defaults read back, three puts."""
    target = msg.get("target", np.asarray(cam.target))
    up = msg.get("up", np.asarray(cam.up))
    cam = cam._replace(eye=jnp.asarray(msg["eye"], jnp.float32),
                       target=jnp.asarray(target, jnp.float32),
                       up=jnp.asarray(up, jnp.float32))
    if "fov_y" in msg:
        cam = cam._replace(fov_y=jnp.float32(msg["fov_y"]))
    return cam


def _holder(camera=None, enabled=True):
    """What `steer_session` and `camera_regime` touch of a session."""
    rec = Recorder(enabled=enabled)
    obs.set_recorder(rec)       # as a session does with its own
    return types.SimpleNamespace(
        camera=camera or Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0,
                                       near=0.3, far=20.0),
        _host_pose=None, frame_index=0, _slicer=slicer, obs=rec)


def _readbacks(rec):
    return [e for e in rec.events
            if e["type"] == "span" and e["name"] == "camera_readback"]


# ------------------------------------------------------- (a) regime agreement

def _traffic_poses():
    from chipbench.traffic import camera_poses

    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "insitu10-steer.json")) as f:
        steering = json.load(f)["steering"]
    return [([float(x) for x in eye], steering["target"])
            for eye in camera_poses(steering, seed=0)]


_D = 2.5
_SIX = [([s * _D if a == i else 0.3 for i in range(3)], [0.0, 0.1, -0.2])
        for a in range(3) for s in (1, -1)]
# within 1e-6 of a 45-degree boundary, on both sides of it, as the f32 the
# device holds and as float64 that f32 rounds onto the boundary itself
_EDGE = [([_D, 0.1, _D * (1 + e)], [0.0, 0.0, 0.0])
         for e in (1e-6, -1e-6, 1e-7, -1e-7, 1e-9, 0.0)]
_EDGE += [([-_D * (1 + e), _D, 0.2], [0.0, 0.0, 0.0])
          for e in (1e-6, -1e-6, 1e-9)]
_EDGE += [([1.0, 1.0, 1.0], [1.0, 1.0, 1.0]),      # eye == target
          ([0.5 + 1e-7, 0.25, 0.5], [0.5, 3.0 + 1e-7, 0.5 - 1e-7])]
_CASES = ([("ring%02d" % i, *c) for i, c in enumerate(_traffic_poses())]
          + [("six%d" % i, *c) for i, c in enumerate(_SIX)]
          + [("edge%02d" % i, *c) for i, c in enumerate(_EDGE)])


@pytest.mark.parametrize("eye,target", [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_host_regime_is_choose_axis_of_the_device_camera(eye, target):
    sess = _holder()
    steer_session(sess, {"type": "camera", "eye": eye, "target": target,
                         "up": [0.0, 1.0, 0.0]})
    regime = camera_regime(sess, "mxu_step")
    assert regime == slicer.choose_axis(sess.camera)
    assert sess.obs.counters["regime_host"] == 1
    assert not _readbacks(sess.obs)


def test_six_regimes_are_six():
    got = set()
    for eye, target in _SIX:
        sess = _holder()
        steer_session(sess, {"type": "camera", "eye": eye,
                             "target": target})
        got.add(camera_regime(sess, "mxu_step"))
    assert got == {(a, s) for a in range(3) for s in (1, -1)}


# ----------------------------------------------------- (b) bit-equal leaves

_FULL = {"type": "camera", "eye": [0.31, 0.62, 2.93],
         "target": [0.01, -0.02, 0.03], "up": [0.1, 0.9, 0.0],
         "fov_y": 0.7853981633974483}
_MESSAGES = {"full": _FULL,
             "no_fov_y": {k: v for k, v in _FULL.items() if k != "fov_y"},
             "no_up": {k: v for k, v in _FULL.items() if k != "up"},
             "no_target": {k: v for k, v in _FULL.items() if k != "target"},
             "eye_only": {"type": "camera", "eye": _FULL["eye"]},
             "arrays": {"type": "camera",
                        "eye": np.array([1 / 3, 2 / 3, 3.0]),
                        "target": (0, 0, 0), "up": np.float32([0, 1, 0])}}


def _bits(cam):
    out = []
    for leaf in cam:
        assert leaf.dtype == jnp.float32 and not leaf.weak_type
        out.append((leaf.shape, np.asarray(leaf).view(np.uint32).tolist()))
    return out


@pytest.mark.parametrize("known", [False, True],
                         ids=["device_camera", "steered_camera"])
@pytest.mark.parametrize("name", list(_MESSAGES))
def test_steered_leaves_are_bit_equal_to_the_parents(name, known):
    sess = _holder()
    want = sess.camera
    if known:       # a first message: the session then knows its target
        first = {"type": "camera", "eye": [0.2, 0.5, -2.0],
                 "target": [0.125, 1e-3, 0.3]}
        steer_session(sess, first)
        want = _parent_apply_steering(want, first)
    msg = _MESSAGES[name]
    steer_session(sess, msg)
    assert _bits(sess.camera) == _bits(_parent_apply_steering(want, msg))
    pose = host_pose(sess)
    assert pose.camera is sess.camera
    for host, leaf in ((pose.eye, sess.camera.eye),
                       (pose.target, sess.camera.target)):
        assert host.dtype == np.float32
        np.testing.assert_array_equal(host, np.asarray(leaf))
    # only a target the message lacks, of a camera the session has no host
    # values for, is read back
    reads = _readbacks(sess.obs)
    if "target" in msg or known:
        assert not reads
    else:
        assert [r["attrs"]["site"] for r in reads] == ["steer_defaults"]


def test_other_cameras_have_no_host_values():
    sess = _holder()
    steer_session(sess, _FULL)
    steered = sess.camera
    assert host_pose(sess) is not None
    sess.camera = steered._replace(eye=steered.eye + 1.0)   # a caller's own
    assert host_pose(sess) is None
    assert camera_regime(sess, "plain") == slicer.choose_axis(sess.camera)
    assert [r["attrs"]["site"] for r in _readbacks(sess.obs)] == ["plain"]
    assert "regime_host" not in sess.obs.counters
    sess.camera = steered               # prewarm puts the same object back
    assert isinstance(host_pose(sess), HostPose)


def test_non_camera_message_reaches_on_steer_only():
    seen = []
    sess = _holder()
    sess.on_steer = [seen.append]
    sess._sink_guard = types.SimpleNamespace(
        run=lambda fns, *a, **kw: [f(*a) for f in fns])
    cam = sess.camera
    steer_session(sess, {"type": "record", "on": True})
    assert seen == [{"type": "record", "on": True}]
    assert sess.camera is cam and sess._host_pose is None


# ------------------------------------------------------- session-level runs

_MXU = {"slicer.engine": "mxu"}
_MODES = {"vdi_temporal": {**_MXU, "vdi.adaptive_mode": "temporal"},
          "plain": {**_MXU, "runtime.generate_vdis": "false"}}


def _session(mode="vdi_temporal", enabled=True, sinks=()):
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=8", "composite.adaptive_iters=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        "runtime.stats_window=2", f"obs.enabled={str(enabled).lower()}",
        *[f"{k}={v}" for k, v in _MODES[mode].items()])
    return InSituSession(cfg, mesh=make_mesh(1), sinks=list(sinks))


class _EverySecond:
    """An in-process steering source: message i before frame 2 i."""

    def __init__(self, messages):
        self.messages, self.drains = list(messages), 0

    def drain(self):
        i, self.drains = self.drains, self.drains + 1
        if i % 2 or i // 2 >= len(self.messages):
            return []
        return [self.messages[i // 2]]


_RING = [{"type": "camera", "eye": e, "target": t, "up": [0.0, 1.0, 0.0]}
         for e, t in _traffic_poses()[:3]]


def test_steered_run_reads_nothing_back():
    """(c) six frames, a message before every second one: no read-back
    span at all, every frame's regime decided on the host."""
    sess = _session()
    sess.steering = _EverySecond(_RING)
    sess.run(6)
    assert not _readbacks(sess.obs)
    assert sess.obs.counters["regime_host"] == 6
    assert sess.obs.counters.get("compile_step") == 1


@pytest.mark.parametrize("steered", [False, True])
def test_orbit_camera_is_still_read_back(steered):
    """(d) the benchmark orbit computes the camera on the device: every
    frame rendered from such a camera opens `camera_readback` at the
    dispatch site, as it did."""
    sess = _session()
    sess.orbit_rate = 0.01
    if steered:
        sess.steering = _EverySecond(_RING)
    sess.run(6)
    reads = _readbacks(sess.obs)
    assert {r["attrs"]["site"] for r in reads} == {"mxu_step"}
    assert {r["parent"] for r in reads} == {"dispatch"}
    # a message's own frame is decided on the host, the orbited ones not
    assert [r["frame"] for r in reads] == ([1, 3, 5] if steered
                                           else list(range(6)))
    assert sess.obs.counters.get("regime_host", 0) == (3 if steered else 0)


def _payloads(mode, path, messages, orbit=0.0):
    """Frame payloads of a run steered by `messages` (one before every
    second frame), through the session's steering path or through the
    parent's: its `apply_steering`, a camera without host values."""
    got = []
    sess = _session(mode, enabled=False,
                    sinks=[lambda i, p: got.append(p)])
    sess.orbit_rate = orbit
    source = _EverySecond(messages)
    if path == "session":
        sess.steering = source
    for _ in range(2 * len(messages)):
        if path == "parent":
            for msg in source.drain():
                sess.camera = _parent_apply_steering(sess.camera, msg)
        sess.run(1)
    return sess, got


_CROSSING = [_RING[0],
             {"type": "camera", "eye": [2.9, 0.5, 0.3]},       # regime (0, -1)
             {"type": "camera", "eye": [0.1, 0.4, 3.0], "fov_y": 0.8}]


@pytest.mark.parametrize("orbit", [0.0, 0.01], ids=["still", "orbit"])
@pytest.mark.parametrize("mode", list(_MODES))
def test_steered_payloads_are_bitwise_the_parents(mode, orbit):
    a, new = _payloads(mode, "session", _CROSSING, orbit)
    b, old = _payloads(mode, "parent", _CROSSING, orbit)
    assert len(new) == len(old) == 6
    assert a.obs.counters["regime_host"] == (3 if orbit else 6)
    assert "regime_host" not in b.obs.counters
    assert sorted(a._steps.steps) == sorted(b._steps.steps)
    assert len(a._steps.steps) == 2               # the run crossed regimes
    for p, q in zip(new, old):
        assert p["frame"] == q["frame"]
        keys = [k for k in ("vdi_color", "vdi_depth", "image") if k in p]
        assert keys
        for k in keys:
            np.testing.assert_array_equal(p[k], q[k])
        np.testing.assert_array_equal(np.asarray(p["meta"].view),
                                      np.asarray(q["meta"].view))
        np.testing.assert_array_equal(np.asarray(p["meta"].projection),
                                      np.asarray(q["meta"].projection))


def test_checkpoint_between_two_messages_keeps_the_regime(tmp_path):
    first, second = _CROSSING[1], {"type": "camera", "eye": [0.2, -3.0, 0.4]}
    a = _session()
    steer_session(a, first)
    a.run(1)
    path = str(tmp_path / "ckpt.npz")
    save_session(a, path)
    b = _session()
    load_session(b, path)
    # the restored camera lives on the device: read back, same regime
    assert host_pose(a) is not None and host_pose(b) is None
    assert camera_regime(b, "mxu_step") == camera_regime(a, "mxu_step") \
        == (0, -1)
    assert [r["attrs"]["site"] for r in _readbacks(b.obs)] == ["mxu_step"]
    for sess in (a, b):
        steer_session(sess, second)         # lacks target: a's from its
        assert host_pose(sess) is not None  # host values, b's read back
    assert camera_regime(a, "mxu_step") == camera_regime(b, "mxu_step") \
        == (1, 1)
    assert _bits(a.camera) == _bits(b.camera)
