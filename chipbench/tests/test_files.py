"""BENCHMARK.json against the files it names: every cell's configuration
and traffic file is there, every per-layer entry is a reader file saying
the same, and the arithmetic gives the published sizes."""

import os
import subprocess
import sys

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
