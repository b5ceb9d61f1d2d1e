"""The four-rank sort-last deployment against plain references (ISSUE 27).

(a) Seeded random fragments through the step builders' own exchange +
    composite on a 4-device mesh, against `chipbench/reference_sortlast.py`:
    all R*K supersegments of a pixel sorted and composited, nothing else.
(b) The session at the rehearsal configuration `tiny-4rank` on the normal
    path: the sim field against the plain roll, the delivered frames against
    the session under the configuration's `reference_overrides`, and against
    the one-rank session of the same seed and camera.

Each tolerance stands beside its reason, and `composite.wire=bf16`, the
program's own lower precision, fails where it is switched on."""

import jax
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import harness, reference
from chipbench.reference_sortlast import sortlast_decode
from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import CompositeConfig
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import _composite_exchanged_sched

R, K, H, W = 4, 16, 8, 32

# The program and the reference composite the same supersegments in the
# same order and differ only in how the float32 "over" sum is bracketed
# (resegmenting folds runs of neighbours first): at most R*K = 64 terms of
# magnitude <= 1, each rounded to 2^-24, so well under 64 * 6e-8 = 4e-6.
# bfloat16 on the wire rounds every colour and depth to 2^-9 relative
# (2e-3): two orders of magnitude above the limit.
DECODE_ATOL = 2e-5


def _fragments(seed: int):
    """Per rank K slots per pixel, sorted front to back, a random number of
    them live (0..K, so empty pixels and full ones both occur), all ranks
    drawn over the SAME depth range so that their supersegments interleave
    and overlap. Alpha stays well above the fold's empty-slot cut (1e-4) and
    low enough that the far supersegments still show through the near ones;
    the first column of pixels is empty on every rank."""
    rng = np.random.default_rng(seed)
    start = np.sort(rng.uniform(1.0, 5.0, (R, K, H, W)), axis=1)
    end = start + rng.uniform(0.01, 0.6, (R, K, H, W))
    color = rng.uniform(0.01, 0.12, (R, K, 4, H, W))
    color[:, :, :3] *= color[:, :, 3:4]             # premultiplied
    count = rng.integers(0, K + 1, (R, 1, H, W))
    count[..., 0] = 0
    live = np.arange(K)[None, :, None, None] < count
    depth = np.where(live[:, :, None], np.stack([start, end], axis=2), np.inf)
    color = np.where(live[:, :, None], color, 0.0)
    return color.astype(np.float32), depth.astype(np.float32)


def _composite_on_mesh(color, depth, cfg):
    """[R, K, 4|2, H, W] fragments, rank r's on device r, through the
    exchange + composite every distributed step builder calls."""
    mesh = make_mesh(R)
    axis = mesh.axis_names[0]

    def step(c, d):
        out = _composite_exchanged_sched(c[0], d[0], R, axis, cfg)
        return out.color, out.depth

    spec = P(None, None, None, axis)
    f = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                          out_specs=(spec, spec), check_vma=False))
    put = lambda x: jax.device_put(x, NamedSharding(mesh, P(axis)))
    oc, od = f(put(color), put(depth))
    return np.asarray(oc), np.asarray(od)


@pytest.mark.parametrize("seed", [27, 2_147_483_659])
@pytest.mark.parametrize("backend,exchange,wire,sound", [
    ("xla", "all_to_all", "f32", True),
    ("pallas", "all_to_all", "f32", True),     # the chip's kernel, interpreted
    ("xla", "ring", "f32", True),
    ("xla", "all_to_all", "bf16", False),      # the control
])
def test_exchange_and_composite_against_sortlast_reference(
        seed, backend, exchange, wire, sound):
    color, depth = _fragments(seed)
    cfg = CompositeConfig(max_output_supersegments=K, backend=backend,
                          exchange=exchange, wire=wire)
    oc, od = _composite_on_mesh(color, depth, cfg)
    assert oc.shape == (K, 4, H, W) and od.shape == (K, 2, H, W)
    want = sortlast_decode(color, depth)
    assert want[3].max() > 0.9 and (want[3, :, 0] == 0).all()
    err = float(np.abs(reference.decode(oc, od) - want).max())
    assert (err <= DECODE_ATOL) is sound, err


def test_sortlast_reference_by_hand():
    """Two ranks, one pixel: the far rank's slab lies behind the near
    rank's, an empty slot carries a stale colour, and the ranks are
    handed over in the other order."""
    near = np.array([0.2, 0.1, 0.0, 0.5], np.float32)
    far = np.array([0.0, 0.3, 0.3, 0.6], np.float32)
    colors = np.zeros((2, 2, 4, 1, 1), np.float32)
    depths = np.full((2, 2, 2, 1, 1), np.inf, np.float32)
    colors[0, 0, :, 0, 0], depths[0, 0, :, 0, 0] = far, (3.0, 4.0)
    colors[1, 0, :, 0, 0], depths[1, 0, :, 0, 0] = near, (1.0, 2.0)
    colors[1, 1, :, 0, 0] = 0.7                     # stale, slot empty
    got = sortlast_decode(colors, depths)[:, 0, 0]
    np.testing.assert_allclose(got, near + (1 - near[3]) * far, rtol=1e-6)


# ------------------------------------------------- (b) the tiny session

SEED = 2_147_483_659
FRAMES = 3


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal configuration's sessions, three frames each from the
    same seed and the session's default camera: the normal path on four
    ranks, the same with `reference_overrides`, with the bf16 wire, and on
    one rank. {name: (field after frame 0, [payload per frame])}."""
    conf = harness.load_json(harness.HERE, "rehearsal", "configs",
                             "tiny-4rank.json")
    amplitude = harness.load_json(
        harness.HERE, "traffic", "insitu10-steer.json")["field_perturbation"]
    one_rank = [o.replace("mesh.num_devices=4", "mesh.num_devices=1")
                for o in conf["overrides"]]
    runs = {}
    for name, overrides in [
            ("normal", conf["overrides"]),
            ("reference", conf["overrides"] + conf["reference_overrides"]),
            ("control", conf["overrides"] + conf["control_overrides"]),
            ("one_rank", one_rank)]:
        obs.clear_ledger()
        frames = []
        sess = harness.build_session(
            overrides, SEED, amplitude,
            sink=lambda i, p: frames.append(
                (p["vdi_color"].copy(), p["vdi_depth"].copy())))
        sess.run(1)
        field0 = np.asarray(sess.sim.field)
        sess.run(FRAMES - 1)
        assert len(frames) == FRAMES and not obs.ledger()
        runs[name] = (field0, frames)
    return conf, amplitude, runs


def _psnr(a, b):
    return reference.psnr(reference.decode(*a), reference.decode(*b))


def test_tiny_four_rank_sim_field_against_the_plain_roll(tiny):
    conf, amplitude, runs = tiny
    shape = conf["shape"]
    want = reference.gray_scott_frame0(shape["grid"], SEED,
                                       shape["steps_per_frame"],
                                       amplitude=amplitude)
    err = float(np.abs(runs["normal"][0] - want).max())
    assert err <= conf["limits"]["sim_atol"], err
    # the halo exchange moves values, it computes none: the z-sharded roll
    # and the one-rank sim give the same field
    assert np.abs(runs["normal"][0] - runs["one_rank"][0]).max() \
        <= conf["limits"]["sim_atol"]


def test_tiny_four_rank_frames_against_the_reference_session(tiny):
    """The configuration's own limit: the fold and the composite named as
    their XLA schedules compute the same f32 mathematics."""
    conf, _, runs = tiny
    floor = conf["limits"]["psnr_floor_db"]
    for got, ref in zip(runs["normal"][1], runs["reference"][1]):
        assert _psnr(got, ref) >= floor
    # bf16 on the wire between the ranks falls under that limit
    worst = min(_psnr(got, ref) for got, ref in
                zip(runs["control"][1], runs["reference"][1]))
    assert worst < floor, worst


# One rank marches the whole volume into K supersegments; four ranks march
# a quarter each into K and resegment the 4K that arrive into K. Against
# the reference session the frames are bit-equal (inf dB: the same program
# with two schedules renamed). Against one rank they are not: the default
# camera marches along the sharded axis, so every rank marches exactly the
# slices the one-rank march does (no halo plane enters), but each rank's
# K slots break where its own quarter of the ray says, and the composite
# joins them again, so the "over" sums are bracketed differently. That is
# float32 rounding, not another rendering: 162.7-164.6 dB over these
# frames, and 79-81 dB with bf16 on the wire. The floor is the
# configurations' own 120 dB.
ONE_RANK_FLOOR_DB = 120.0


def test_tiny_four_rank_frames_against_the_one_rank_session(tiny):
    _, _, runs = tiny
    for got, ref in zip(runs["normal"][1], runs["one_rank"][1]):
        assert got[0].shape == ref[0].shape
        assert _psnr(got, ref) >= ONE_RANK_FLOOR_DB
    worst = min(_psnr(got, ref) for got, ref in
                zip(runs["control"][1], runs["one_rank"][1]))
    assert worst < ONE_RANK_FLOOR_DB, worst
