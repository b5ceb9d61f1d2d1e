# SITPU-COUNTER good fixture: the same shapes done right — registered
# literals, names threaded through *_counter parameters. Parsed by the
# linter only.
import itertools


def render(rec, data):
    rec.count("build_steps")
    return data


def exchange_ring(rec, hops, hop_counter="ring_steps_built"):
    # dynamic name is fine when it arrives via a *_counter-suffixed
    # parameter whose default (and every literal override) is registered
    rec.count(hop_counter, hops)
    return hops


def relabel(rec, hops):
    return exchange_ring(rec, hops, hop_counter="dcn_hops_built")


def suppressed(rec, metric):
    rec.count(metric)  # sitpu-lint: disable=SITPU-COUNTER
    return metric


def fine(rec):
    seq = itertools.count(1)
    return next(seq)
