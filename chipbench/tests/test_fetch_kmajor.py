"""`fetch_kmajor_share` (PR 40) on hand-made spans: the per cent of the
window's frames fetched from a mesh whose every `fetch.concat` span says
`kmajor` true. A frame with one leaf cut any other way is not counted;
spans from before the attribute read as nothing, with the reason."""

import pytest

from chipbench import harness

OLD = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")


def concat(frame, **attrs):
    return {"type": "span", "name": "fetch.concat", "rank": 0,
            "ts": 0.01 * frame, "dur": 0.003, "depth": 1, "parent": "fetch",
            "thread": "MainThread", "frame": frame,
            "attrs": dict(bytes=4096, **attrs)}


def reader():
    (mod,) = [m for m in harness.load_layers()
              if m.NAME == "fetch_kmajor_share"]
    return mod


def read(spans):
    return reader().read({"spans": spans, "frames": 4, "window_s": 0.1})


def test_the_reader_and_its_entry_agree():
    (entry,) = [m for m in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["per_layer"]
        if m["name"] == "fetch_kmajor_share"]
    mod = reader()
    assert entry == {"name": mod.NAME, "unit": mod.UNIT, "better": "higher",
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": mod.CELLS}
    assert mod.CELLS == ["gs512-4rank-insitu", "vortex256-4rank-insitu"]


@pytest.mark.parametrize("flags,want", [
    # per frame, the `kmajor` of its colour and of its depth leaf
    ([(True, True)] * 4, 100.0),
    ([(False, False)] * 4, 0.0),
    ([(True, True), (False, False), (True, True), (True, True)], 75.0),
    # a mixed frame is not a slot-major frame
    ([(True, False), (True, True)], 50.0),
    ([(False, True)], 0.0)])
def test_share_of_frames_whose_every_leaf_is_kmajor(flags, want, capsys):
    spans = [concat(f, fresh=False, kmajor=k)
             for f, leaves in enumerate(flags) for k in leaves]
    assert read(spans) == pytest.approx(want)
    assert "MISSING SOURCE" not in capsys.readouterr().err


def test_spans_without_the_attribute_read_as_nothing(capsys):
    """The parent's program under this reader: `fetch.concat` spans with
    `fresh` and no `kmajor` give nothing and say why; an older span list
    the same; no span at all, nothing and not a word."""
    assert read([concat(0, fresh=True), concat(1, fresh=False)]) is None
    assert "MISSING SOURCE" in capsys.readouterr().err
    assert read(OLD["spans"]) is None
    assert "MISSING SOURCE" in capsys.readouterr().err
    assert read([]) is None
    assert capsys.readouterr().err == ""


def test_only_marked_spans_are_counted():
    """A frame's unmarked span beside its marked one (a leaf that was not
    sharded opens no `fetch.concat`; a span from elsewhere has no say)."""
    spans = [concat(0, fresh=False, kmajor=True), concat(0, fresh=False),
             concat(1, fresh=False, kmajor=False)]
    assert read(spans) == pytest.approx(50.0)
