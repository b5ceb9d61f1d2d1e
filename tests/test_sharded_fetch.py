"""Delivery of a frame that is sharded over the mesh (ISSUE 27): one
routine, `InSituSession._to_host`, recorded or not. What the sinks get is
byte-equal to `np.asarray(leaf)` and read-only, a frame a sink keeps is
never written again, and the host arrays of frames nobody keeps are used
again (`HostFrames`): fresh pages, not the copy, were nine tenths of the
assembly on a v5e's host."""

import sys
import time

import jax
import numpy as np
import pytest

from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.runtime.session import HostFrames, InSituSession

FRAMES = 3
_PAYLOADS = {
    "vdi": ({"slicer.engine": "mxu", "vdi.adaptive_mode": "temporal"},
            ("vdi_color", "vdi_depth")),
    "image": ({"runtime.generate_vdis": "false"}, ("image",)),
}


def _session(ranks, enabled, payload, sink):
    extra, keys = _PAYLOADS[payload]
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=8", "composite.adaptive_iters=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        f"obs.enabled={str(enabled).lower()}",
        *[f"{k}={v}" for k, v in extra.items()])
    return InSituSession(cfg, mesh=make_mesh(ranks), sinks=[sink]), keys


@pytest.mark.parametrize("payload", ["vdi", "image"])
@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("ranks", [2, 4])
def test_sharded_frame_delivery(ranks, enabled, payload, monkeypatch):
    kept = []                   # the sink keeps every payload it is handed
    sess, keys = _session(ranks, enabled, payload,
                          lambda i, p: kept.append(p))
    device = []                 # each frame as np.asarray reads its leaves
    fetch = sess._fetch

    def spy(index, out):
        device.append([np.asarray(leaf).copy()
                       for leaf in jax.tree_util.tree_leaves(out)])
        split.append([not leaf.is_fully_replicated
                      for leaf in jax.tree_util.tree_leaves(out)])
        return fetch(index, out)

    split, waits = [], []
    sess._fetch = spy
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waits.append(1), ready(x))[1])
    sess.run(FRAMES)
    assert [p["frame"] for p in kept] == list(range(FRAMES))
    assert all(any(s) for s in split), "the frame is not sharded at all"

    # compared only now: two frames were kept while a third was assembled
    for p, want in zip(kept, device):
        for key, w in zip(keys, want):
            got = p[key]
            assert isinstance(got, np.ndarray) and not got.flags.writeable
            assert got.dtype == w.dtype and got.shape == w.shape
            assert got.tobytes() == w.tobytes(), (p["frame"], key)

    assert sess.obs.counters["frames_fetched_sharded"] == FRAMES
    spans = [e for e in sess.obs.events if e["type"] == "span"]
    if not enabled:
        # no span, and no device wait beyond the copies' own
        assert sess.obs.events == [] and waits == []
        return
    assert len(waits) == FRAMES                     # fetch.ready
    for frame in range(FRAMES):
        mine = [s for s in spans if s.get("frame") == frame
                and s.get("parent") == "fetch"]
        n_split = sum(split[frame])
        copies = [s for s in mine if s["name"] == "fetch.copy"]
        sharded = [s for s in copies if "shard" in s["attrs"]]
        assert len(sharded) == n_split * ranks
        assert {s["attrs"]["shard"] for s in sharded} == set(range(ranks))
        assert len(copies) - len(sharded) == len(split[frame]) - n_split
        assert all(s["attrs"]["bytes"] > 0 for s in copies)
        concats = [s for s in mine if s["name"] == "fetch.concat"]
        assert len(concats) == n_split              # one per sharded leaf
        assert [s["name"] for s in mine].count("fetch.ready") == 1


def test_one_device_frame_does_not_come_this_way():
    kept = []
    sess, _ = _session(1, False, "vdi", lambda i, p: kept.append(p))
    sess._to_host = None        # would raise if the fetch called it
    sess.run(2)
    assert len(kept) == 2
    assert "frames_fetched_sharded" not in sess.obs.counters


def test_unkept_frames_share_their_host_arrays():
    """A sink that keeps nothing: the loop lets its last payload go
    before it fetches the next frame (PR 39: by name, inside a recorded
    run's `release` span), so one generation of arrays serves every
    frame, whatever their number."""
    sess, keys = _session(4, False, "vdi", lambda i, p: None)
    payload = sess.run(6)
    bufs = sess._host_frames._bufs
    assert len(bufs) == len(keys)
    assert any(payload["vdi_color"] is b for b in bufs)


def test_host_frames_take_only_what_nobody_holds():
    pool = HostFrames()
    a = pool.take((4, 6), np.float32)
    a[...] = 1.0
    a.flags.writeable = False
    b = pool.take((4, 6), np.float32)               # `a` is held: another
    assert b is not a and b.flags.writeable
    view = a[1:, ::2]                               # a view holds its owner
    ident = id(a)
    del a
    c = pool.take((4, 6), np.float32)
    assert id(c) != ident and (view == 1.0).all()
    del view, c
    d = pool.take((4, 6), np.float32)               # free now: taken again
    assert id(d) == ident and d.flags.writeable
    d[...] = 2.0
    # another size: allocated, and the free arrays of the old size let go
    del d
    e = pool.take((2, 3), np.float64)
    assert e.shape == (2, 3) and e.dtype == np.float64
    assert [x.shape for x in pool._bufs] == [(4, 6), (2, 3)]    # b, e
    assert pool._bufs[0] is b


def test_host_frames_assemble_under_thread_switching():
    """More copy threads than cores and a switch interval a thousand times
    shorter: every assembly still holds exactly the blocks it was given,
    whether the array is fresh, used again, or split unevenly."""
    rng = np.random.default_rng(27)
    pool = HostFrames()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(5e-6)
    try:
        deadline = time.monotonic() + 20.0
        for rounds in range(40):
            k, w = int(rng.integers(1, 24)), 8 * int(rng.integers(1, 5))
            want = rng.random((k, 3, 5, 4 * w)).astype(np.float32)
            parts = [((slice(None),) * 3 + (slice(r * w, (r + 1) * w),),
                      np.ascontiguousarray(want[..., r * w:(r + 1) * w]))
                     for r in range(4)]
            got = pool.assemble(want.shape, want.dtype, parts)
            assert not got.flags.writeable
            assert got.tobytes() == want.tobytes(), rounds
            assert time.monotonic() < deadline
    finally:
        sys.setswitchinterval(interval)
    assert len(pool._bufs) <= 2


def _blocks(want: np.ndarray, cut: str):
    """``want`` as the parts a frame sharded over four ranks arrives in:
    by its minor axis (`w`), by its leading axis (`k`: how a VDI frame
    leaves the mesh, pipeline._frame_out), or whole (`one`)."""
    full = (slice(None),) * want.ndim
    if cut == "one":
        return [(full, want.copy())]
    axis = 0 if cut == "k" else want.ndim - 1
    step = want.shape[axis] // 4
    parts = []
    for r in range(4):
        index = list(full)
        index[axis] = slice(r * step, (r + 1) * step)
        parts.append((tuple(index), np.ascontiguousarray(want[tuple(index)])))
    return parts


@pytest.mark.parametrize("cut", ["w", "k", "one"])
@pytest.mark.parametrize("shape", [(16, 4, 10, 32), (8, 2, 6, 8),
                                   (4, 3, 5, 12)])
def test_host_frames_assemble_any_disjoint_blocks(cut, shape):
    """Column blocks, slot blocks and a single block: each assembly has
    `np.asarray`'s bytes, is read-only, and takes the array of the one
    before once nobody holds it."""
    rng = np.random.default_rng(40)
    pool = HostFrames()
    idents = set()
    for _ in range(3):
        want = rng.random(shape).astype(np.float32)
        got = pool.assemble(want.shape, want.dtype, _blocks(want, cut))
        assert isinstance(got, np.ndarray) and not got.flags.writeable
        assert got.flags.c_contiguous and got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()
        idents.add(id(got))
        del got
    assert len(idents) == 1 and len(pool._bufs) == 1
    held = pool.assemble(want.shape, want.dtype, _blocks(want, cut))
    again = pool.assemble(want.shape, want.dtype, _blocks(2 * want, cut))
    assert again is not held and held.tobytes() == want.tobytes()
    assert again.tobytes() == (2 * want).tobytes()


@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("k_out,payload,kmajor", [
    (8, "vdi", True),           # 8 slots over 4 ranks: slot blocks
    (6, "vdi", False),          # 4 does not divide 6: column blocks
    (8, "image", False)])       # a plain image: 4 channels lead
def test_to_host_says_how_the_frame_left_the_mesh(k_out, payload, kmajor,
                                                  enabled):
    """`kmajor` on a recorded run's `fetch.concat` spans, and the counter
    `frames_fetched_kmajor` recorded or not: true exactly for a frame
    whose every sharded leaf is cut along its leading axis alone."""
    extra, keys = _PAYLOADS[payload]
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        f"composite.max_output_supersegments={k_out}",
        "composite.adaptive_iters=2", "sim.grid=[16,16,16]",
        "sim.steps_per_frame=2", f"obs.enabled={str(enabled).lower()}",
        *[f"{k}={v}" for k, v in extra.items()])
    sess = InSituSession(cfg, mesh=make_mesh(4), sinks=[lambda i, p: None])
    last = sess.run(FRAMES)
    for key in keys:
        assert not last[key].flags.writeable
    assert sess.obs.counters["frames_fetched_sharded"] == FRAMES
    assert sess.obs.counters.get("frames_fetched_kmajor", 0) == \
        (FRAMES if kmajor else 0)
    said = [e["attrs"]["kmajor"] for e in sess.obs.events
            if e["type"] == "span" and e["name"] == "fetch.concat"]
    assert said == ([kmajor] * (FRAMES * len(keys)) if enabled else [])


def test_fetch_concat_says_when_the_pool_allocates():
    """`fresh` on a recorded run's `fetch.concat` spans: true on the first
    frame from the mesh and on the frame after one a sink kept, false on
    every other (the pool handed out an array nobody held)."""
    kept = []
    sess, keys = _session(4, True, "vdi",
                          lambda i, p: kept.append(p) if i == 2 else None)
    sess.run(6)
    fresh = {}
    for e in sess.obs.events:
        if e["type"] == "span" and e["name"] == "fetch.concat":
            assert e["attrs"]["bytes"] > 0 and e["thread"] == "MainThread"
            fresh.setdefault(e["frame"], set()).add(e["attrs"]["fresh"])
    assert fresh == {0: {True}, 1: {False}, 2: {False}, 3: {True},
                     4: {False}, 5: {False}}
    assert len(kept) == 1 and len(sess._host_frames._bufs) == 2 * len(keys)


def test_host_frames_last_fresh():
    pool = HostFrames()
    a = pool.take((4, 6), np.float32)
    assert pool.last_fresh
    del a
    b = pool.take((4, 6), np.float32)
    assert not pool.last_fresh
    c = pool.take((4, 6), np.float32)       # `b` is held
    assert pool.last_fresh and c is not b
