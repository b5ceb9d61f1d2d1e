"""The trace -> metrics reduction, on a hand-made event list whose answers
are known and on two event lists recorded on the chip."""

import gzip
import json
import os

import pytest

from chipbench import xplane

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
MS = 1_000_000


def hand_made() -> dict:
    """Window 0..100 ms on one device. Two programs; a `while` that
    encloses two fusions; a synchronous all-to-all; an asynchronous
    collective-permute in flight 50..70 ms, of which 58..60 ms and
    68..70 ms run beside no other op."""
    ops = [
        ["fusion.1", 10 * MS, 10 * MS, "fusion"],
        ["while.2", 30 * MS, 20 * MS, "while"],
        ["fusion.3", 32 * MS, 6 * MS, "fusion"],
        ["fusion.4", 40 * MS, 8 * MS, "fusion"],
        ["all_to_all.5", 50 * MS, 2 * MS, "all-to-all"],
        ["copy.6", 52 * MS, 6 * MS, "copy"],
        ["fusion.7", 60 * MS, 8 * MS, "fusion"],
    ]
    return {"anchor": [0, 100 * MS], "devices": {"0": {
        "modules": [["jit_multi_step(1)", 10 * MS, 10 * MS],
                    ["jit_step(2)", 30 * MS, 38 * MS]],
        "ops": ops,
        "async": [["collective-permute-start.8", 50 * MS, 20 * MS,
                   "collective-permute-start"]]}}}


def test_opcode_and_names():
    text = ("%all_to_all.1 = (f32[16,4]{1,0:T(8,128)}, f32[5]{0:T(128)S(1)})"
            " all-to-all(f32[1]{0} %x), dimensions={0}")
    assert xplane.op_name(text) == "all_to_all.1"
    assert xplane.op_code(text) == "all-to-all"
    assert xplane.op_kind("copy-start.17") == "copy-start"
    assert xplane.op_kind("cond.61.clone") == "cond"
    assert xplane.op_kind("gray_scott_fused_t4") == "gray_scott_fused_t4"


def test_hand_made_trace():
    tr = xplane.Trace(hand_made())
    assert tr.window_s == pytest.approx(0.1)
    # busy: 10..20, 30..50, 50..58, 60..68 = 46 ms
    assert tr.busy_s() == pytest.approx(0.046)
    assert tr.idle_share() == pytest.approx(0.54)
    assert tr.program_runs(r"^jit_step\(") == 1
    assert tr.program_s("^jit_multi_step") == pytest.approx(0.010)
    assert tr.program_s(r"^jit_step\(") == pytest.approx(0.038)
    # the while owns only what its body leaves: 20 - 6 - 8 = 6 ms
    top = dict(tr.top_ops(10))
    assert top["fusion"] == pytest.approx(0.032)
    assert top["while"] == pytest.approx(0.006)
    total, exposed = tr.collective_s()
    assert total == pytest.approx(0.020)          # 50..70 ms
    assert exposed == pytest.approx(0.006)        # 50..52, 58..60, 68..70
    gaps = tr.idle_gaps([["fetch", 68 * MS, 100 * MS],
                         ["dispatch", 20 * MS, 25 * MS]], 3)
    assert gaps[0] == ["host:fetch", pytest.approx(0.032)]
    assert gaps[1][0] == "host:between_spans"     # 0..10 ms
    assert gaps[2] == ["host:dispatch", pytest.approx(0.010)]


def test_a_trace_without_anchor_or_device_is_refused():
    with pytest.raises(ValueError, match="annotation"):
        xplane.Trace({"anchor": None, "devices": {"0": {}}})
    with pytest.raises(ValueError, match="device"):
        xplane.Trace({"anchor": [0, 1], "devices": {}})


def load(name: str) -> dict:
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        return json.load(f)


def test_recorded_one_chip_trace():
    """Two frames of gs128 on one v5e: the numbers this reduction gave when
    the fixture was recorded, and what must hold of any trace."""
    tr = xplane.Trace(load("trace_gs128.json.gz"))
    assert tr.program_runs(r"^jit_step\(") == 2
    assert tr.program_s("^jit_multi_step") == pytest.approx(0.000771728)
    assert tr.program_s(r"^jit_step\(") == pytest.approx(0.004647866)
    assert tr.busy_s() == pytest.approx(0.005365605)
    assert tr.idle_share() == pytest.approx(0.4269494152)
    assert tr.collective_s() == (0.0, 0.0)
    assert tr.top_ops(1)[0][0] == "branch_1_fun"
    # self times partition the busy union
    segs = tr.segments("0")
    assert sum(e - s for s, e, *_ in segs) / 1e9 == \
        pytest.approx(tr.busy_s())
    assert tr.busy_s() <= tr.window_s


def test_recorded_four_chip_trace():
    """One frame of gs512 on four ranks: halo permutes in flight beside
    the roll sim, the all-to-all exposed."""
    tr = xplane.Trace(load("trace_gs512x4.json.gz"))
    assert sorted(tr.devices) == ["0", "1", "2", "3"]
    assert tr.program_runs(r"^jit_step\(") == 1
    assert tr.program_s("^jit_multi_step") == pytest.approx(0.0937846085)
    assert tr.program_s(r"^jit_step\(") == pytest.approx(0.15739395225)
    total, exposed = tr.collective_s()
    assert total == pytest.approx(0.074950191)
    assert exposed == pytest.approx(0.0039675)
    assert 0 < exposed < total < tr.window_s
