"""Memory the process touched for the first time, per frame (PR 43): the
pages its resident set grew by over each iteration of the frame loop
(`touched_frame` on the iteration's second `upkeep` span: the growth of
`/proc/self/statm`'s resident pages summed over the reading intervals that
grew, so the 157 MB that land and the 157 MB let go in another span do not
cancel; the runtime's transfer threads and `HostFrames`' copy threads count
too) times the run's own page size (`page` on the same span), in MB, mean
over the window. Beside `d2h_MB_per_frame` it says what share of a frame's
bytes lands on pages nobody had touched: what a first touch costs is the
kernel's page faults.

The faults themselves (`minflt_frame`, `getrusage`) are printed beside it
where the kernel counts them: the sandboxed kernel of this repo's chip
machines counts none, and under transparent huge pages one fault maps 2 MB,
so they are no metric. To stderr also the split by what the spans' own
`rss_pages` grew: inside the `fetch` spans (less their `fetch.concat`),
inside `fetch.concat`, inside both `release` spans, and the rest (what was
touched while the loop was in another span or in none). Nothing from a
program whose `upkeep` spans carry no `touched_frame`."""

import sys

NAME = "host_fault_MB_per_frame"
UNIT = "MB"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    frames = [e["attrs"] for e in ctx["spans"] if e["name"] == "upkeep"
              and "touched_frame" in (e.get("attrs") or {})]
    if not frames:
        if ctx["spans"]:
            scopes._missing("no `upkeep` span carries `touched_frame`")
        return None
    page = frames[0]["page"]
    mb = lambda pages: pages * page / 1e6 / len(frames)
    total = sum(a["touched_frame"] * a["page"] for a in frames) / 1e6 / len(
        frames)
    grew = {name: sum(max(0, (e.get("attrs") or {}).get("rss_pages", 0))
                      for e in ctx["spans"] if e["name"] == name)
            for name in ("fetch", "fetch.concat", "release")}
    parts = {"fetch": mb(max(0, grew["fetch"] - grew["fetch.concat"])),
             "fetch.concat": mb(grew["fetch.concat"]),
             "release": mb(grew["release"])}
    parts["rest"] = total - sum(parts.values())
    counted = [a["minflt_frame"] for a in frames if "minflt_frame" in a]
    faults = sum(counted) / len(counted) if counted else None
    major = sum(a.get("majflt_frame", 0) for a in frames)
    print(f"[chipbench] host_fault_MB_per_frame {total:.3f} over "
          f"{len(frames)} iterations (page {page} B) = "
          + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
          + (f"; minor faults a frame {faults:.1f} (x page = "
             f"{faults * page / 1e6:.3f} MB)"
             if counted else "; this kernel counts no page fault")
          + (f"; {major} major faults" if major else ""),
          file=sys.stderr, flush=True)
    return total
