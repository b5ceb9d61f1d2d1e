"""The session's step table (runtime/steps.StepTable) over the modes
that compile one step per march regime: what a compile counts, when
carried state is seeded, carried and dropped, and what a prewarm and a
steered transfer function leave behind."""

import numpy as np
import pytest

from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.runtime.session import (InSituSession,
                                                regime_camera, steer_session)

BASE = ("render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1",
        "slicer.engine=mxu", "slicer.scale=1.0", "slicer.matmul_dtype=f32")
TEMPORAL = ("vdi.adaptive_mode=temporal",)
REUSE = ("composite.temporal_reuse=ranges",)
HYBRID = ("sim.kind=hybrid", "sim.num_particles=32",
          "sim.particle_radius=0.8")
PLAIN = ("runtime.generate_vdis=false",)

# id: (overrides, ranks, carries thresholds, carries reuse fragments)
CASES = {
    "vdi-temporal-1rank": (TEMPORAL, 1, True, False),   # the one-chip cells
    "vdi-temporal-4rank": (TEMPORAL, 4, True, False),   # the four-chip cell
    "vdi-temporal-reuse": (TEMPORAL + REUSE, 2, True, True),
    "vdi-reuse": (REUSE, 2, False, True),
    "vdi-stateless": ((), 2, False, False),
    "plain": (PLAIN, 2, False, False),
    "hybrid-temporal": (HYBRID + TEMPORAL, 2, True, False),
    "hybrid": (HYBRID, 2, False, False),
}


def _refuse(*_):
    raise AssertionError("carried state was seeded a second time")


@pytest.mark.parametrize("case", list(CASES))
def test_step_table(case):
    overrides, ranks, has_thr, has_reuse = CASES[case]
    sess = InSituSession(FrameworkConfig().with_overrides(*BASE, *overrides),
                         mesh=make_mesh(ranks))
    table, count = sess._steps, sess.obs.counters

    def stores():       # the table replaces its dicts: look them up anew
        return [store for store, on in ((table.thr, has_thr),
                                        (table.reuse, has_reuse)) if on]

    cam_a = sess.camera
    cam_b = regime_camera(cam_a, (0, 1), sess._slicer)
    assert sess._temporal == has_thr and sess._reuse == has_reuse

    # one compile per regime, none on a hit; state seeded once, then carried
    sess.run(1)
    (key_a,) = table.steps
    assert count["compile_step"] == 1
    assert all(set(store) == {key_a} for store in stores())
    seeded = table.steps[key_a]
    table.steps[key_a] = seeded._replace(
        seed_thr=seeded.seed_thr and _refuse,
        seed_reuse=seeded.seed_reuse and _refuse)
    first = [store[key_a] for store in stores()]
    sess.run(2)
    assert count["compile_step"] == 1 and len(table.steps) == 1
    assert all(store[key_a] is not was
               for store, was in zip(stores(), first))
    table.steps[key_a] = seeded

    # another regime compiles its own step; coming back finds the first
    # regime's step, and a session that carries state drops what it had
    # carried there (it went stale meanwhile) and seeds it again
    sess.camera = cam_b
    sess.run(1)
    assert count["compile_step"] == 2 and len(table.steps) == 2
    seeds = []
    table.steps[key_a] = seeded._replace(
        seed_thr=seeded.seed_thr and (
            lambda *a: seeds.append("thr") or seeded.seed_thr(*a)),
        seed_reuse=seeded.seed_reuse and (
            lambda *a: seeds.append("reuse") or seeded.seed_reuse(*a)))
    sess.camera = cam_a
    sess.run(2)
    assert count["compile_step"] == 2
    assert sorted(seeds) == ["reuse"] * has_reuse + ["thr"] * has_thr
    assert count.get("regime_switches", 0) == (2 if stores() else 0)
    table.steps[key_a] = seeded

    # a prewarm compiles what is missing and leaves the loop's state alone
    carried = [dict(store) for store in stores()]
    last, frame = table.last_key, sess.frame_index
    times = sess.prewarm_regimes([(1, -1), (0, 1)])
    assert set(times) == {(1, -1), (0, 1)}
    assert count["compile_step"] == 3 and len(table.steps) == 3
    assert sess.camera is cam_a and sess.frame_index == frame
    assert table.last_key == last
    for store, was in zip(stores(), carried):
        assert store.keys() == was.keys()
        assert all(store[k] is was[k] for k in was)

    # a steered TF not seen before builds anew; one seen before takes its
    # steps back, with fresh carried state
    steps_a = table.steps
    steer_session(sess, {"type": "tf", "points": [(0.0, 0.0), (1.0, 0.9)]})
    assert table.steps == {} and all(store == {} for store in
                                     (table.thr, table.reuse))
    sess.run(1)
    assert count["compile_step"] == 4
    assert count.get("tf_steps_reused", 0) == 0
    steer_session(sess, {"type": "tf", "points": [(0.0, 0.0), (1.0, 0.5)]})
    steer_session(sess, {"type": "tf", "points": [(0.0, 0.0), (1.0, 0.9)]})
    assert count["tf_steps_reused"] == 1 and count["build_steps"] == 3
    assert list(table.steps) == [key_a]
    assert table.thr == {} and table.reuse == {}
    assert table.steps is not steps_a
    payload = sess.run(1)
    assert count["compile_step"] == 4
    assert all(set(store) == {key_a} for store in stores())
    assert all(np.isfinite(v).all() for k, v in payload.items()
               if k in ("vdi_color", "image"))
