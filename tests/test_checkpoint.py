"""Session checkpoint/resume tests: a resumed session must continue
bit-exactly where the checkpointed one stopped (the aux subsystem the
reference lacks — it could only replay render-product dumps)."""

import numpy as np
import pytest

from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.runtime.checkpoint import (checkpoint_sink,
                                                   load_session,
                                                   save_session)
from scenery_insitu_tpu.runtime.session import InSituSession


def _cfg(**over):
    base = dict([
        ("slicer.engine", "mxu"), ("slicer.scale", "1.0"),
        ("sim.grid", "[16,16,16]"), ("sim.steps_per_frame", "2"),
        ("vdi.max_supersegments", "6"), ("vdi.adaptive_mode", "temporal"),
        ("composite.max_output_supersegments", "8"),
        ("mesh.num_devices", "4"),
    ])
    base.update(over)
    return FrameworkConfig().with_overrides(
        *(f"{k}={v}" for k, v in base.items()))


def test_resume_is_bit_exact(tmp_path):
    path = str(tmp_path / "ckpt.npz")

    # uninterrupted 5-frame run (orbiting camera, temporal thresholds)
    a = InSituSession(_cfg())
    a.orbit_rate = 0.05
    ref = a.run(5)

    # 3 frames -> checkpoint -> fresh session -> resume -> 2 more
    b = InSituSession(_cfg())
    b.orbit_rate = 0.05
    b.run(3)
    save_session(b, path)

    c = InSituSession(_cfg())
    c.orbit_rate = 0.123   # overwritten by the checkpoint
    load_session(c, path)
    assert c.frame_index == b.frame_index
    assert c.orbit_rate == 0.05
    assert len(c._steps.thr) == len(b._steps.thr)
    got = c.run(2)

    assert got["frame"] == ref["frame"]
    np.testing.assert_array_equal(ref["vdi_color"], got["vdi_color"])
    np.testing.assert_array_equal(ref["vdi_depth"], got["vdi_depth"])


def test_resume_particle_session(tmp_path):
    path = str(tmp_path / "p.npz")
    cfg = _cfg(**{"sim.kind": "sho", "sim.num_particles": "500",
                  "vdi.adaptive_mode": "histogram",
                  "render.width": "32", "render.height": "24"})
    a = InSituSession(cfg)
    ref = a.run(4)

    b = InSituSession(cfg)
    b.run(2)
    save_session(b, path)
    c = InSituSession(cfg)
    load_session(c, path)
    got = c.run(2)
    np.testing.assert_array_equal(ref["image"], got["image"])


def test_mismatched_checkpoint_rejected(tmp_path):
    path = str(tmp_path / "m.npz")
    a = InSituSession(_cfg())
    a.run(1)
    save_session(a, path)

    wrong_kind = InSituSession(_cfg(**{"sim.kind": "vortex"}))
    with pytest.raises(ValueError, match="sim kind"):
        load_session(wrong_kind, path)

    wrong_shape = InSituSession(_cfg(**{"sim.grid": "[32,32,32]"}))
    with pytest.raises(ValueError, match="shape"):
        load_session(wrong_shape, path)


def test_checkpoint_sink(tmp_path):
    sess = InSituSession(_cfg(**{"vdi.adaptive_mode": "histogram"}))
    sess.sinks.append(checkpoint_sink(str(tmp_path), every=2).bind(sess))
    sess.run(4)
    import glob
    files = sorted(glob.glob(str(tmp_path / "ckpt_*.npz")))
    assert len(files) >= 1
    # the dump must load back into a fresh same-config session
    c = InSituSession(_cfg(**{"vdi.adaptive_mode": "histogram"}))
    load_session(c, files[-1])


def test_resume_bit_exact_across_regime_switches(tmp_path):
    """Checkpoint taken mid-orbit with several march regimes' threshold
    state in flight: the resumed run must reproduce the uninterrupted one
    bit-exactly, including the regime tracker's drop/keep decisions."""
    path = str(tmp_path / "r.npz")

    def mk():
        s = InSituSession(_cfg(**{"sim.grid": "[12,12,12]",
                                  "mesh.num_devices": "2"}))
        s.orbit_rate = 0.35      # ~18 frames per revolution
        return s

    a = mk()
    ref = a.run(20)
    assert len(a._steps.thr) >= 2          # the orbit crossed regimes

    b = mk()
    b.run(12)
    assert len(b._steps.thr) >= 2   # the checkpoint itself is multi-regime
    save_session(b, path)
    c = mk()
    load_session(c, path)
    # the drop/keep tracker must survive the round trip — without it the
    # first post-resume frame makes a different drop decision than the
    # uninterrupted run whenever the boundary lands on a regime switch
    assert c._steps.last_key == b._steps.last_key
    got = c.run(8)

    assert got["frame"] == ref["frame"]
    np.testing.assert_array_equal(ref["vdi_color"], got["vdi_color"])
    np.testing.assert_array_equal(ref["vdi_depth"], got["vdi_depth"])


def test_hybrid_temporal_checkpoint_roundtrip(tmp_path):
    """Hybrid-mode temporal keys are ('hybrid', axis, sign) 3-tuples: both
    signs of an axis must checkpoint under DISTINCT tags and restore
    without cross-contamination."""
    import jax.numpy as jnp

    from scenery_insitu_tpu.ops.supersegments import ThresholdState

    path = str(tmp_path / "h.npz")
    cfg = _cfg(**{"sim.kind": "hybrid", "sim.num_particles": "32",
                  "sim.particle_radius": "0.8",
                  "sim.grid": "[12,12,12]", "mesh.num_devices": "2"})
    a = InSituSession(cfg)
    assert a._temporal
    a.run(2)
    (key,) = list(a._steps.thr)
    assert key[0] == "hybrid" and len(key) == 3
    # fabricate the opposite-sign regime with distinct values: a tag
    # collision would make one of the two restore as the other
    other = (key[0], key[1], -key[2])
    a._steps.thr[other] = ThresholdState(
        *(jnp.asarray(x) + 0.125 for x in a._steps.thr[key]))
    save_session(a, path)

    b = InSituSession(cfg)
    b.run(2)
    load_session(b, path)
    assert set(b._steps.thr) == {key, other}
    np.testing.assert_array_equal(np.asarray(a._steps.thr[key].thr),
                                  np.asarray(b._steps.thr[key].thr))
    np.testing.assert_array_equal(np.asarray(a._steps.thr[other].thr),
                                  np.asarray(b._steps.thr[other].thr))
    assert not np.array_equal(np.asarray(b._steps.thr[key].thr),
                              np.asarray(b._steps.thr[other].thr))


def test_steered_tf_survives_checkpoint(tmp_path):
    """A session whose TF was changed by steering must resume with THAT
    TF, not the constructor's — bit-exact across the round trip."""
    from scenery_insitu_tpu.runtime.streaming import make_tf_message

    path = str(tmp_path / "tf.npz")

    def mk():
        s = InSituSession(_cfg(**{"sim.grid": "[12,12,12]",
                                  "mesh.num_devices": "2"}))
        return s

    a = mk()
    a.run(2)
    msg = make_tf_message([(0.0, 0.85), (1.0, 0.85)], colormap="jet")
    for cb in a.on_steer:
        cb(msg)
    a.run(1)
    save_session(a, path)
    ref = a.run(2)

    b = mk()
    load_session(b, path)
    np.testing.assert_array_equal(np.asarray(b.tf.alpha_m),
                                  np.asarray(a.tf.alpha_m))
    got = b.run(2)
    np.testing.assert_array_equal(ref["vdi_color"], got["vdi_color"])
