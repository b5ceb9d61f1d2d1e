"""The 1024^3 configuration on four chips (PR 41): its cell finds its files
at the source's widths; the blocked plain reference
(`reference_gs_blocks.py`) equals the whole-grid one bit for bit at 32^3,
for slab sizes that do and do not divide the grid; the seed rule written
twice (reference, field source) is one rule; the program's sharded start
equals the whole one; the rehearsal size (`tiny-gsblocks-4rank`: 32^3 over
four virtual devices, the cell's own field source) is `correct` and its
bf16 control is not; the five `gs1024_*` readers give the hand-computed
answers on the fixtures.

`tiny-gsblocks-4rank` lives in `rehearsal/gs1024/configs/`, not beside the
other rehearsal configurations: `test_files.py` pins the list of those, and
this PR may not edit it (`rehearsal/shm/`, `rehearsal/vortex/` are the
pattern)."""

import ast
import os

import numpy as np
import pytest

from chipbench import arith, control, harness, reference
from chipbench import reference_gs_blocks as blocks
from chipbench import scopes, xplane
from chipbench.rehearse import rehearse

SEED = 3_000_000_019
GRID = (32, 32, 32)
CELL = "gs1024-4rank-insitu"
HOME = os.path.join(harness.HERE, "rehearsal", "gs1024")
VORTEX = harness.load_json(harness.HERE, "fixtures", "scopes_vortex.json")
SMALL = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")
# slabs that divide 32 planes, that do not, the whole grid, thinner than
# the ten halo planes
SLABS = [8, 12, 32, 5]
CHECKS = ["frames_delivered_once_in_order", "frames_failed",
          "vdi_bytes_per_frame", "fallback_ledger_rows",
          "compile_requests_in_window", "sim_state_devices",
          "rank_peak_bytes_max", "steering_answers_in_window",
          "sim_field_frame0_max_abs_diff", "decoded_psnr_dB_warmup_frame",
          "decoded_psnr_dB_window_frame", "fallback_ledger_rows_reference"]
READERS = ["gs1024_sim_device_ms", "gs1024_sim_hbm_share",
           "gs1024_sim_halo_ms", "gs1024_collective_ms",
           "gs1024_composite_hbm_share"]


def cell() -> dict:
    c = harness.find_files(
        {"name": "rehearsal-tiny-gsblocks-4rank",
         "config": "tiny-gsblocks-4rank", "traffic": "insitu10-steer"},
        home=HOME)
    return dict(c, chips=c["config_file"]["chips"])


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in READERS}


def test_the_cell_finds_its_files_at_the_sources_widths():
    c = harness.load_cell(CELL)
    conf, small = c["config_file"], harness.load_json(
        harness.HERE, "configs", "gs512-4rank.json")
    assert (c["chips"], conf["chips"], conf["reduced"]) == (4, 4, ["ranks"])
    assert c["traffic"] == "insitu10-steer"
    assert conf["field_source"] == "sim_gray_scott_blocks"
    assert conf["overrides"] == [o.replace("[512,512,512]",
                                           "[1024,1024,1024]")
                                 for o in small["overrides"]]
    for key in ("control_overrides", "programs"):
        assert conf[key] == small[key], key
    assert set(conf["reference_overrides"]) <= set(
        small["reference_overrides"])
    assert conf["shape"] == dict(small["shape"], grid=[1024, 1024, 1024])
    limits = conf["limits"]
    assert (limits["psnr_floor_db"], limits["sim_atol"],
            limits["covered_share_min"]) == (120.0, 5e-05, 0.01)
    assert limits["rank_peak_bytes_max"] <= 14.4e9
    assert "fallback_ledger_admits" not in conf["guarantees"]
    shape = conf["shape"]
    assert arith.intermediate_grid(shape) == (1280, 1280)
    assert arith.vdi_bytes_per_frame(shape) == 629_145_600
    assert arith.sim_floor_bytes_per_frame(shape) == 16 * 1024 ** 3
    assert readers()["gs1024_composite_hbm_share"].floor_bytes(shape) == \
        (64 + 16) * 24 * 1280 * 320
    tiny = cell()["config_file"]
    for key in ("field_source", "reference_overrides", "control_overrides"):
        assert tiny[key] == (small if key == "reference_overrides"
                             else conf)[key], key
    entries = {m["name"]: m for m in c["bench"]["per_layer"]}
    assert set(readers()) == set(READERS) <= set(entries)
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "fps"


def test_the_blocked_reference_stands_alone():
    with open(blocks.__file__) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if "scenery_insitu_tpu" in n]


@pytest.mark.parametrize("slab", SLABS)
def test_the_blocked_start_is_the_whole_grid_start(slab):
    """Bit for bit: unperturbed against `reference.gray_scott_init`, and
    the seeded start the same whatever the slab it was made in."""
    want_u, want_v = (np.asarray(x) for x in
                      reference.gray_scott_init(GRID))
    u, v = blocks.start(GRID, SEED, 0.0, slab)
    assert np.array_equal(u, want_u) and np.array_equal(v, want_v)
    whole = blocks.start(GRID, SEED, 1e-3, GRID[0])
    made = blocks.start(GRID, SEED, 1e-3, slab)
    assert all(np.array_equal(a, b) for a, b in zip(whole, made))
    # a slab that begins before plane 0 wraps
    u, v = (np.asarray(x) for x in blocks.start_slab(GRID, -3, 7, SEED,
                                                     1e-3))
    assert np.array_equal(v, np.concatenate([whole[1][-3:], whole[1][:4]]))
    assert np.array_equal(u, np.concatenate([whole[0][-3:], whole[0][:4]]))


@pytest.mark.parametrize("slab", SLABS)
@pytest.mark.parametrize("steps", [10, 3])
def test_the_blocked_roll_is_the_whole_grid_roll(slab, steps):
    import jax.numpy as jnp

    u0, v0 = blocks.start(GRID, SEED, 1e-3, GRID[0])
    want = reference.gray_scott_steps(jnp.asarray(u0), jnp.asarray(v0),
                                      steps)
    got = blocks.state_after(GRID, SEED, 1e-3, steps, slab)
    assert all(np.array_equal(g, np.asarray(w)) for g, w in zip(got, want))
    assert np.array_equal(
        blocks.field_after(GRID, SEED, 1e-3, steps, slab), got[1])


def test_the_seed_rule():
    """Per cell from (seed, z, y, x): inside [-1, 1), spread like a
    uniform draw, another for another seed, also past 32 bits; amplitude
    0 is the unperturbed start."""
    v = blocks.start(GRID, SEED, 1e-3)[1]
    inside = v > 0
    assert np.array_equal(inside, np.asarray(
        reference.gray_scott_init(GRID)[1]) > 0)
    n = (v[inside].astype(np.float64) / 0.25 - 1.0) / 1e-3
    assert -1.0 - 1e-4 <= n.min() < -0.99 and 0.99 < n.max() < 1.0 + 1e-4
    assert abs(n.mean()) < 0.03 and abs(n.std() - 3 ** -0.5) < 0.02
    for other in (SEED + 1, SEED + 2 ** 32, 7):
        w = blocks.start(GRID, other, 1e-3)[1]
        assert (w[inside] != v[inside]).mean() > 0.99
    assert blocks.seed_keys(SEED) != blocks.seed_keys(SEED + 2 ** 32)


@pytest.mark.parametrize("devices", [1, 4])
def test_the_sources_draw_is_the_references(devices):
    """The rule is written twice (the session never runs the reference's
    code): the same bits, on one device and on each rank's own planes."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    source = harness.load_source(cell())
    assert np.array_equal(source.seed_keys(SEED),
                          np.asarray(blocks.seed_keys(SEED), np.uint32))
    u0, v0 = blocks.start(GRID, SEED, 0.0)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("ranks",))
    sharding = NamedSharding(mesh, P("ranks", None, None))
    v = jax.jit(source.seeded, out_shardings=sharding)(
        jax.device_put(v0, sharding), source.seed_keys(SEED),
        np.float32(1e-3))
    assert len(v.sharding.device_set) == devices
    assert np.array_equal(source.to_host(v),
                          blocks.start(GRID, SEED, 1e-3)[1])


@pytest.mark.parametrize("devices", [1, 4])
def test_the_programs_sharded_start_is_the_whole_one(devices):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from scenery_insitu_tpu.sim import grayscott as gs

    mesh = Mesh(np.array(jax.devices()[:devices]), ("ranks",))
    st = gs.GrayScott.init(GRID, sharding=NamedSharding(
        mesh, P("ranks", None, None)))
    assert len(st.u.sharding.device_set) == devices
    want = reference.gray_scott_init(GRID)
    assert np.array_equal(np.asarray(st.u), np.asarray(want[0]))
    assert np.array_equal(np.asarray(st.v), np.asarray(want[1]))


def test_the_source_serves_no_set_up_steps():
    c = cell()
    c["traffic_file"] = dict(c["traffic_file"], pre_evolve_steps=10)
    with pytest.raises(ValueError, match="pre_evolve_steps"):
        harness.load_source(c).build_session(c, [], SEED)


def test_a_checkout_that_runs_out_of_memory_gives_no_result(monkeypatch):
    """The benchmark's files laid over a checkout that builds the start
    whole on one device: no result, in one line with the allocator's."""
    import jax

    from scenery_insitu_tpu.runtime import session

    def full(*a, **k):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Error allocating device buffer: "
            "Attempting to allocate 4.00G. That was not possible.")

    monkeypatch.setattr(session.VolumeSimAdapter, "__init__", full)
    with pytest.raises(harness.BenchFailure, match="out of memory in "
                       "InSituSession.*RESOURCE_EXHAUSTED"):
        harness.open_run(cell(), SEED, False, on_chip=False, verbose=False)


def test_the_memory_check():
    source, c = harness.load_source(cell()), cell()
    limit = c["config_file"]["limits"]["rank_peak_bytes_max"]
    kept = {"sim_devices": 4, "platform": "tpu",
            "rank_peaks": [4.3e9, 4.2e9, 4.2e9, 4.2e9],
            "rank_peaks_before": [0, 0, 0, 0]}
    by = {n: (v, lim, ok) for n, v, lim, ok in
          source.window_checks(c, kept)}
    assert by["rank_peak_bytes_max"] == (4.3e9, limit, True)
    ok = lambda k: dict((n, o) for n, *_, o in source.window_checks(c, k))[
        "rank_peak_bytes_max"]
    over = [4.2e9, limit + 1, 4.2e9, 4.2e9]
    assert not ok(dict(kept, rank_peaks=over))
    # a second session of one process under the first one's mark; and one
    # that raised it
    assert ok(dict(kept, rank_peaks=over, rank_peaks_before=over))
    assert not ok(dict(kept, rank_peaks=[9e9] * 4, rank_peaks_before=over))
    # no statistics: a fault on the chip, said and passed off it
    for platform, want in (("tpu", False), ("cpu", True)):
        none = dict(kept, platform=platform, rank_peaks=[None] * 4,
                    rank_peaks_before=[None] * 4)
        assert ok(none) is want


def test_rehearsal_is_correct_and_the_control_is_not():
    c = cell()
    res = rehearse(c, SEED, 0.3, False)
    assert [n for n, *_ in res["checks"]] == CHECKS
    assert res["correct"] and res["failed"] == 0
    by = {n: v for n, v, _, _ in res["checks"]}
    assert by["sim_state_devices"] == 4
    assert by["sim_field_frame0_max_abs_diff"] <= 1e-6
    assert by["vdi_bytes_per_frame"] == arith.vdi_bytes_per_frame(
        c["config_file"]["shape"])
    bad = control.read(c, SEED, 0.3, "rounded", on_chip=False)
    assert not bad["correct"]
    failed = {n for n, _, _, ok in bad["checks"] if not ok}
    assert failed == {"sim_field_frame0_max_abs_diff",
                      "decoded_psnr_dB_warmup_frame",
                      "decoded_psnr_dB_window_frame"}
    by = {n: v for n, v, _, _ in bad["checks"]}
    assert by["sim_field_frame0_max_abs_diff"] > 5 * c["config_file"][
        "limits"]["sim_atol"]


def test_a_traced_rehearsal_records_the_build(monkeypatch):
    """With the recorder on, the session's `sim.build` span says what was
    placed, and the counter counts the shards of u and v."""
    seen = {}
    read_layers = harness.read_layers

    def spy(run):
        seen["build"] = [e for e in run.sess.obs.events
                         if e.get("name") == "sim.build"]
        seen["shards"] = run.sess.obs.counters.get("sim_state_shards_built")
        return read_layers(run)

    monkeypatch.setattr(harness, "read_layers", spy)
    res = rehearse(cell(), SEED, 0.3, True)
    assert res["correct"]
    assert not [n for n in res["per_layer"] if n.startswith("gs1024_")]
    (build,) = seen["build"]
    assert build["attrs"] == {"devices": 4,
                              "bytes_per_device": 2 * 8 * 32 * 32 * 4 + 5 * 4}
    assert seen["shards"] == 8


def _ctx(fix, programs, monkeypatch):
    monkeypatch.setattr(scopes, "table", lambda: (fix["hlo_scopes"],
                                                  fix["hlo_inherited"]))
    return {"trace": xplane.Trace(fix["events"]), "spans": [],
            "frames": fix["frames"], "config": {"programs": programs},
            "shape": harness.load_cell(CELL)["config_file"]["shape"],
            "peaks": arith.peaks_for("TPU v5 lite")}


# on `scopes_vortex.json` with the vortex program standing for the sim (it
# ran once, 60 ms; collectives in flight inside it [12, 20) and [63, 65);
# the step's all-to-all 5 ms), and `scopes_small.json` (merge 4 +
# resegment 10 ms)
WANT = {
    "gs1024_sim_device_ms": (VORTEX, 60.0),
    "gs1024_sim_hbm_share": (VORTEX, 16 * 1024 ** 3 / 4 / 0.060 / 819e9
                             * 100.0),
    "gs1024_sim_halo_ms": (VORTEX, 10.0),
    "gs1024_collective_ms": (VORTEX, 5.0),
    "gs1024_composite_hbm_share": (SMALL, 80 * 24 * 1280 * 320 / 0.014
                                   / 819e9 * 100.0),
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_fixture(name, monkeypatch):
    fix, want = WANT[name]
    programs = ({"sim": "^jit_vortex_frame", "step": r"^jit_step\("}
                if fix is VORTEX else
                {"sim": "^jit_multi_step", "step": r"^jit_step\("})
    got = readers()[name].read(_ctx(fix, programs, monkeypatch))
    assert got == pytest.approx(want)
    absent = {"sim": "^jit_absent", "step": "^jit_absent"}
    assert readers()[name].read(_ctx(fix, absent, monkeypatch)) is None
