"""Host time per frame inside `InSituSession.run` outside its `fetch` and
`sinks` spans (host clock): steering drain, sim and step dispatch, loop
bookkeeping."""

NAME = "dispatch_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    inside = sum(e["dur"] for e in ctx["spans"]
                 if e["name"] in ("fetch", "sinks"))
    return (ctx["window_s"] - inside) / ctx["frames"] * 1e3
