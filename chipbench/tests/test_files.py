"""BENCHMARK.json against the files it names: every cell's configuration,
traffic and field-source file is there, every per-layer entry is a reader
file saying the same, every `workloads` list names cells that exist, and
the arithmetic gives the published sizes."""

import os
import subprocess
import sys

import pytest

from chipbench import arith, harness

ROOT = harness.ROOT


def bench() -> dict:
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_cells_find_their_files():
    b = bench()
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config_file"]["name"] == w["config"]
        assert cell["config_file"]["chips"] == w["chips"]
        assert cell["traffic_file"]["name"] == w["traffic"]
        source = harness.load_source(cell)
        for part in ("build_session", "keep", "wait", "window_checks",
                     "plain_reference", "compare", "rounded"):
            assert callable(getattr(source, part)), (w["name"], part)
    for c in b["configs"]:
        f = harness.load_json(ROOT, c["file"])
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]


def test_layer_entries_are_reader_files():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    readers = {m.NAME: m for m in harness.load_layers()}
    cells = {w["name"] for w in bench()["workloads"]}
    e2e = {m["name"] for m in bench()["end_to_end"]}
    assert set(entries) <= set(readers)
    for name, m in readers.items():
        if name not in entries:     # a reader waiting for its cell
            assert m.CELLS != "all" and not cells & set(m.CELLS)
            continue
        e = entries[name]
        assert (e["unit"], e["layer"], e["moves"], e["source"]) == \
            (m.UNIT, m.LAYER, m.MOVES, m.SOURCE)
        assert e.get("workloads", "all") == m.CELLS
        assert m.MOVES in e2e


def test_workloads_lists_name_cells_that_report_what_they_move():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    cells_of = lambda m: set(m.get("workloads", cells))
    e2e = {m["name"]: cells_of(m) for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert cells_of(m) and cells_of(m) <= cells, m["name"]
    for m in b["per_layer"]:
        assert cells_of(m) <= e2e[m["moves"]], m["name"]
    for cell in cells:      # set-up, one more end to end, one per layer
        assert sum(cell in c for c in e2e.values()) >= 2 and cell in e2e[
            "setup_s"]
        assert any(cell in cells_of(m) for m in b["per_layer"])


def test_every_rehearsal_configuration_finds_its_source():
    import glob

    from chipbench.rehearse import rehearsal_cell

    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(os.path.join(
        harness.HERE, "rehearsal", "configs", "*.json")))
    assert names == ["tiny-1rank", "tiny-4rank", "tiny-hostring"]
    for name in names:
        cell = rehearsal_cell(config=name)
        assert cell["config_file"]["name"] == name
        assert cell["chips"] == cell["config_file"]["shape"]["ranks"]
        assert callable(harness.load_source(cell).build_session)
    with pytest.raises(harness.BenchFailure, match="no field source"):
        harness.load_source({"config_file": {"field_source": "nowhere"}})


def test_shape_arithmetic():
    shape = harness.load_json(harness.HERE, "configs",
                              "gs512-1chip.json")["shape"]
    assert arith.intermediate_grid(shape) == (640, 640)
    assert arith.vdi_bytes_per_frame(shape) == 16 * 24 * 640 * 640
    assert arith.sim_floor_bytes_per_frame(shape) == 16 * 512 ** 3
    assert arith.march_dense_flops_per_frame(shape) == \
        512 * (2 * 640 * 512 * 512 + 2 * 640 * 512 * 640)
    four = dict(shape, ranks=4)
    assert arith.intermediate_grid(four) == (640, 640)
    assert arith.peaks_for("TPU v5 lite")["hbm_gbps"] == 819.0
    try:
        arith.peaks_for("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind got peaks")


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "gs128-insitu", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_no_result_where_the_program_is_missing(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: non-zero, no result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gs128-insitu",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
