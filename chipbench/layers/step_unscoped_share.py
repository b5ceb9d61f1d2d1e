"""The part of the frame-step program's device time that no `sitpu_*` scope
explains: its time less the self time of the ops that carry a scope, over
its time (averaged over the devices). Ops without a scope and time inside
the program in which no op ran both count as unexplained. An op whose phase
is inherited from the `while` or `conditional` around it counts as
explained (the run's log gives the inherited ms of each phase). Nothing
from a program that keeps no scope table."""

NAME = "step_unscoped_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    got = scopes.step(ctx)
    if not got["program"] or not got["table"]:
        return None
    return (1.0 - sum(got["scopes"].values()) / got["program"]) * 100.0
