"""Seconds of the session's `dataset.load` span: the raw file read in
z-slabs and put on the device at its own dtype (attrs `read_s` / `put_s`
apart, on stderr). The span closes before the window opens, so it is not
among the window's spans the harness hands over: the reader takes it from
the program's recorder, where the session left it. Nothing from a program
that has no such span."""

import sys

NAME = "dataset_load_s"
UNIT = "s"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "setup_s"
CELLS = ["kingsnake-u8-view"]


def read(ctx):
    from scenery_insitu_tpu import obs

    loads = [e for e in obs.get_recorder().events
             if e.get("type") == "span" and e["name"] == "dataset.load"]
    if not loads:
        print("[chipbench] MISSING SOURCE: no `dataset.load` span on the "
              "recorder", file=sys.stderr)
        return None
    a = loads[-1].get("attrs", {})
    print(f"[chipbench] dataset.load: {a.get('bytes')} B of {a.get('dtype')} "
          f"in {a.get('parts')} parts, read {a.get('read_s', 0.0):.3f} s, "
          f"put {a.get('put_s', 0.0):.3f} s", file=sys.stderr)
    return loads[-1]["dur"]
