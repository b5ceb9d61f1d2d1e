"""Session checkpoint / resume.

The reference checkpoints only the *render product* — VDIDataIO metadata +
raw VDI buffer dumps reloaded by the offline viewers
(DistributedVolumes.kt:910-915; VDICompositingTest.kt:162-163); the
simulation itself cannot be resumed. This framework already matches that
(io/vdi_io.py artifacts + vdi_sink); this module goes further and
checkpoints the *session* — simulation state, frame index, camera pose,
and the carried temporal-threshold controller state — so an in-situ run
can stop and resume bit-exactly.

Format: one ``.npz`` with a JSON header entry. Arrays are fetched to host
(a resumed session re-places them onto its mesh via the normal dispatch
path). For multi-host runs, checkpoint per process or switch the payload
to orbax; the header/state contract here is the same either way.
"""

from __future__ import annotations


import json
from typing import TYPE_CHECKING

import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:   # pragma: no cover
    from scenery_insitu_tpu.runtime.session import InSituSession

_VERSION = 1
_CAMERA_FIELDS = ("eye", "target", "up", "fov_y", "near", "far")


def _sim_arrays(sim) -> dict:
    """kind-specific state arrays of a sim adapter (host numpy)."""
    kind = sim.kind
    if kind in ("gray_scott",):
        return {"u": sim.state.u, "v": sim.state.v}
    if kind == "vortex":
        return {"u": sim.state.u}
    if kind in ("lennard_jones", "sho"):
        return {"pos": sim.state.pos, "vel": sim.state.vel,
                "box": sim.state.box}
    if kind == "hybrid":
        return {"u": sim.flow.u, "tracers": sim.tracers}
    raise ValueError(f"unknown sim kind {kind!r}")


def _restore_sim(sim, arrays: dict) -> None:
    kind = sim.kind
    a = {k: jnp.asarray(v) for k, v in arrays.items()}
    if kind == "gray_scott":
        sim.state = sim.state._replace(u=a["u"], v=a["v"])
    elif kind == "vortex":
        sim.state = sim.state._replace(u=a["u"])
    elif kind in ("lennard_jones", "sho"):
        sim.state = sim.state._replace(pos=a["pos"], vel=a["vel"],
                                       box=a["box"])
    elif kind == "hybrid":
        sim.flow = sim.flow._replace(u=a["u"])
        sim.tracers = a["tracers"]
    else:
        raise ValueError(f"unknown sim kind {kind!r}")


def save_session(sess: "InSituSession", path: str) -> None:
    """Checkpoint a session to ``path`` (.npz)."""
    from scenery_insitu_tpu.ops.supersegments import ThresholdState

    header = {
        "version": _VERSION,
        "sim_kind": sess.sim.kind,
        "mode": sess.mode,
        "engine": sess.engine,
        "temporal": bool(getattr(sess, "_temporal", False)),
        "mesh_devices": int(sess._n_ranks),
        "frame_index": sess.frame_index,
        "orbit_rate": float(sess.orbit_rate),
        "thr_regimes": sorted(sess._steps.thr.keys()),
        "last_regime": sess._steps.last_key,
    }
    arrays = {f"sim/{k}": np.asarray(v)
              for k, v in _sim_arrays(sess.sim).items()}
    for name, val in zip(_CAMERA_FIELDS, sess.camera):
        arrays[f"camera/{name}"] = np.asarray(val)
    # the transfer function is runtime-mutable state since TF steering
    # (apply_tf_steering): without it a resumed session would silently
    # render with the constructor TF
    for name, val in zip(type(sess.tf)._fields, sess.tf):
        arrays[f"tf/{name}"] = np.asarray(val)
    for regime, thr in sess._steps.thr.items():
        # join EVERY key part: hybrid-mode keys are ('hybrid', axis, sign)
        # and both signs of an axis must keep distinct tags
        tag = "thr/" + "_".join(str(p) for p in regime)
        for field in ThresholdState._fields:
            arrays[f"{tag}/{field}"] = np.asarray(getattr(thr, field))
    with open(path, "wb") as f:       # stream; no in-memory zip copy
        np.savez(f, __header__=np.frombuffer(
            json.dumps(header).encode(), np.uint8), **arrays)


def load_session(sess: "InSituSession", path: str) -> None:
    """Restore a checkpoint into a session built from the SAME config
    (grid shapes, sim kind, mesh size must match — loudly checked)."""
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.ops.supersegments import ThresholdState

    with np.load(path) as z:
        header = json.loads(bytes(z["__header__"]).decode())
        if header["version"] != _VERSION:
            raise ValueError(f"checkpoint version {header['version']} != "
                             f"{_VERSION}")
        if header["sim_kind"] != sess.sim.kind:
            raise ValueError(
                f"checkpoint sim kind {header['sim_kind']!r} does not "
                f"match session {sess.sim.kind!r}")
        if header["mode"] != sess.mode:
            raise ValueError(
                f"checkpoint mode {header['mode']!r} does not match "
                f"session {sess.mode!r}")
        # bit-exact resume needs the same compiled step: engine, adaptive
        # regime and mesh size all change what the resumed run computes
        for key, have in (("engine", sess.engine),
                          ("temporal", bool(getattr(sess, "_temporal",
                                                    False))),
                          ("mesh_devices",
                           int(sess._n_ranks))):
            want = header.get(key)
            if want is not None and want != have:
                raise ValueError(
                    f"checkpoint {key}={want!r} does not match session "
                    f"{have!r} — same config required")
        sim_arrays = {k.split("/", 1)[1]: z[k]
                      for k in z.files if k.startswith("sim/")}
        want = _sim_arrays(sess.sim)
        for k, cur in want.items():
            if k not in sim_arrays:
                raise ValueError(f"checkpoint missing sim array {k!r}")
            if tuple(sim_arrays[k].shape) != tuple(np.shape(cur)):
                raise ValueError(
                    f"sim array {k!r} shape {sim_arrays[k].shape} does "
                    f"not match session {np.shape(cur)} — same config "
                    "required")
        _restore_sim(sess.sim, sim_arrays)
        sess.camera = Camera(*(jnp.asarray(z[f"camera/{n}"])
                               for n in _CAMERA_FIELDS))
        tf_fields = type(sess.tf)._fields
        present = [n for n in tf_fields if f"tf/{n}" in z.files]
        if present and len(present) != len(tf_fields):
            # some-but-not-all keys = field-set mismatch (e.g. the TF type
            # evolved without a version bump) — silently falling back to
            # the constructor TF would be exactly the wrong-TF resume this
            # block exists to prevent
            raise ValueError(
                f"checkpoint tf/ keys {present} do not match the session "
                f"TransferFunction fields {list(tf_fields)} — checkpoint "
                "and session versions differ")
        if present:
            new_tf = type(sess.tf)(*(jnp.asarray(z[f"tf/{n}"])
                                     for n in tf_fields))
            changed = any(
                not np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(new_tf, sess.tf))
            sess.tf = new_tf
            if changed:
                # the restored TF differs from the constructor's: rebuild
                # the compiled steps exactly like live TF steering does
                # (AttributeError here is the loud failure the module
                # promises — a session type without _build_steps cannot
                # silently keep steps that baked the old TF in)
                sess._build_steps()
        # (older checkpoints have no tf/ keys: constructor TF applies)
        sess.frame_index = int(header["frame_index"])
        sess.orbit_rate = header["orbit_rate"]
        sess._steps.thr = {}
        for regime in header.get("thr_regimes", []):
            regime = tuple(regime)
            tag = "thr/" + "_".join(str(p) for p in regime)
            state = ThresholdState(
                *(jnp.asarray(z[f"{tag}/{f}"])
                  for f in ThresholdState._fields))
            expect = _thr_shape(sess, regime)
            if expect is not None and tuple(state.thr.shape) != expect:
                raise ValueError(
                    f"threshold state for regime {regime} has shape "
                    f"{tuple(state.thr.shape)}, session expects {expect} "
                    "— same slicer/mesh config required")
            sess._steps.thr[regime] = state
        # restore the regime tracker VERBATIM: StepTable.enter drops the
        # entered regime's carried state on a regime CHANGE, and the
        # resumed run must make the same drop/keep decisions as the
        # uninterrupted one
        last = header.get("last_regime")
        sess._steps.last_key = None if last is None else tuple(last)


def _thr_shape(sess, regime):
    """Expected [n*nj, ni] of a regime's rank-stacked threshold maps under
    this session's config (None for sessions without an mxu VDI pass).
    Hybrid-mode keys are ('hybrid', axis, sign); vdi keys (axis, sign)."""
    if sess.engine != "mxu" or sess.mode not in ("vdi", "hybrid"):
        return None
    axis_sign = tuple(regime[1:]) if regime and regime[0] == "hybrid" \
        else tuple(regime)
    # TOTAL rank count — on a hierarchical (hosts, ranks) mesh the
    # threshold maps stack over the flat axis view (docs/MULTIHOST.md)
    n = sess._n_ranks
    spec = sess._slicer.make_spec(sess.camera, sess.sim.field.shape,
                                  sess.cfg.slicer, axis_sign=axis_sign,
                                  multiple_of=n)
    return (n * spec.nj, spec.ni)


def checkpoint_sink(directory: str, every: int = 50):
    """Session sink: checkpoint every N frames (composable with the other
    sinks, ≅ the reference's periodic VDIDataIO dumps but for the whole
    session). The sink needs the session itself, so bind it:
    ``sess.sinks.append(checkpoint_sink(d).bind(sess))``.

    The file is named by the session's CURRENT frame index (the state the
    checkpoint actually contains) — with the session's one-frame dispatch
    pipelining that is ~2 ahead of the payload index the sink fires on,
    so do not pair ``ckpt_N.npz`` with a same-index VDI dump."""
    import os

    class _Sink:
        def __init__(self):
            self.sess = None

        def bind(self, sess):
            self.sess = sess
            return self

        def __call__(self, index: int, payload: dict) -> None:
            if self.sess is not None and every and index % every == 0:
                os.makedirs(directory, exist_ok=True)
                # zero-padded so lexicographic order == frame order
                save_session(self.sess, os.path.join(
                    directory, f"ckpt_{self.sess.frame_index:05d}.npz"))

    return _Sink()
