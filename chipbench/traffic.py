"""The one general traffic generator: a closed-loop steering viewer and the
sink that takes the end-to-end clock readings, both driven by a traffic file
(`traffic/<name>.json`). The program receives only what they generate: camera
messages through `session.steering`, and a sink callback."""

import math
import time

import numpy as np


def camera_poses(steering: dict, seed: int) -> list:
    """The file's ring of eye positions around `base_eye`, in an order drawn
    from the seed: every seed sends the same cameras, in another order."""
    base = np.asarray(steering["base_eye"], np.float64)
    target = np.asarray(steering["target"], np.float64)
    n, radius = steering["poses"], math.radians(steering["orbit_radius_deg"])
    rel = base - target
    poses = []
    for i in range(n):
        yaw = radius * math.cos(2 * math.pi * i / n)
        pitch = radius * math.sin(2 * math.pi * i / n)
        cy, sy, cp, sp = (math.cos(yaw), math.sin(yaw), math.cos(pitch),
                          math.sin(pitch))
        r = np.array([cy * rel[0] + sy * rel[2], rel[1],
                      -sy * rel[0] + cy * rel[2]])
        r = np.array([r[0], cp * r[1] - sp * r[2], sp * r[1] + cp * r[2]])
        poses.append((target + r).astype(np.float32))
    order = np.random.default_rng(seed).permutation(n)
    return [poses[i] for i in order]


def eye_of_view(view: np.ndarray) -> np.ndarray:
    """The eye position a 4x4 world-to-eye matrix was built for:
    view = [R | -R eye]."""
    v = np.asarray(view, np.float64)
    return -v[:3, :3].T @ v[:3, 3]


class Viewer:
    """One closed-loop viewer with one camera message outstanding. It is
    the session's steering source (`drain()`), and the sink tells it of
    every delivered frame (`on_frame`)."""

    def __init__(self, steering: dict, seed: int):
        self.poses = camera_poses(steering, seed)
        self._eyes = np.stack(self.poses).astype(np.float64)
        self.target = [float(x) for x in steering["target"]]
        self.up = [float(x) for x in steering["up"]]
        self.active = False
        self._next = 0
        self._pending = []
        self._outstanding = None        # (pose, t_sent, deliveries_at_send)
        self.answered = []              # (t_sent, t_answered, deliveries)

    def drain(self) -> list:
        msgs, self._pending = self._pending, []
        return msgs

    def message(self, pose: int) -> dict:
        return {"type": "camera",
                "eye": [float(x) for x in self.poses[pose]],
                "target": self.target, "up": self.up}

    def pose_of(self, view):
        """Which of the poses this 4x4 view matrix was built for; None for
        a frame rendered before the first message (the session's own
        starting camera)."""
        gaps = np.abs(self._eyes - eye_of_view(view)).max(axis=1)
        pose = int(gaps.argmin())
        return pose if gaps[pose] < 1e-4 else None

    def send(self, now: float, delivered: int) -> None:
        pose = self._next % len(self.poses)
        self._next += 1
        self._pending.append(self.message(pose))
        self._outstanding = (pose, now, delivered)

    def on_frame(self, pose, now: float, delivered: int) -> None:
        if self._outstanding is None:
            self.send(now, delivered)
            return
        sent, t_sent, at_send = self._outstanding
        if pose == sent:
            self.answered.append((t_sent, now, delivered - at_send))
            self.send(now, delivered)


class Replay:
    """A steering source that gives a reference session the cameras a
    run's frames were rendered with: before frame i is dispatched it hands
    over the message frame i of the run carried (`poses[i]`, as the sink
    recorded them; None = no message yet)."""

    def __init__(self, viewer: Viewer, poses: list):
        self.viewer, self.poses = viewer, list(poses)
        self._frame = 0

    def drain(self) -> list:
        pose = (self.poses[self._frame] if self._frame < len(self.poses)
                else None)
        self._frame += 1
        return [] if pose is None else [self.viewer.message(pose)]


class Sink:
    """The benchmark's sink: it is called with the fetched payload, which
    is the moment a dump or stream sink would have the bytes. It stamps
    each delivery, counts its bytes, tells the viewer, and keeps the few
    payloads the checks need (`keep`: frame indices)."""

    def __init__(self, viewer: Viewer, keep=()):
        self.viewer = viewer
        self.keep = set(keep)
        self.kept = {}
        self.frames = []                # frame index of each delivery
        self.poses = []                 # the viewer's pose each was rendered
                                        # from (None: the starting camera)
        self.stamps = []                # perf_counter at each delivery
        self.nbytes = []
        self.faults = []                # cheap per-frame findings

    def __call__(self, index: int, payload: dict) -> None:
        now = time.perf_counter()
        c, d = payload["vdi_color"], payload["vdi_depth"]
        self.frames.append(payload["frame"])
        self.stamps.append(now)
        self.nbytes.append(c.nbytes + d.nbytes)
        if payload["frame"] != index:
            self.faults.append(f"sink index {index} carries frame "
                               f"{payload['frame']}")
        if payload["frame"] in self.keep:
            self.kept[payload["frame"]] = {
                "frame": payload["frame"], "vdi_color": c, "vdi_depth": d}
        pose = None
        if self.viewer.active:
            pose = self.viewer.pose_of(np.asarray(payload["meta"].view))
            self.viewer.on_frame(pose, now, len(self.frames))
        self.poses.append(pose)
