# SITPU-THREAD bad fixture: distributed step builders that lose
# CompositeConfig fields. Parsed by the linter only.
import dataclasses


def distributed_bad_step(mesh, tf, width, height, comp_cfg=None):
    """Takes the config whole and then REBUILDS it from the fields it
    remembers — ``wire`` is not among them (what the plain builders'
    ``knob_cfg`` did until PR 29)."""
    knob_cfg = CompositeConfig(schedule=comp_cfg.schedule,
                               wave_tiles=comp_cfg.wave_tiles)

    def step(data, cam):
        return composite_cfg(march(data, cam), knob_cfg)
    return step


def distributed_replaced_step(mesh, tf, comp_cfg=None):
    """Forwards the config, but a copy with a field overwritten."""
    comp_cfg = comp_cfg or CompositeConfig()    # the default fill is fine
    quiet = dataclasses.replace(comp_cfg, temporal_reuse="off")

    def step(data, cam):
        return composite_cfg(march(data, cam), quiet)
    return step


def distributed_missing_step(mesh, tf, width, height,
                             exchange="all_to_all"):
    """Takes one field by name instead of the config — every other field
    is invisible to callers and silently pinned to the composite
    default."""
    def step(data, cam):
        return composite(march(data, cam), exchange=exchange)
    return step


def distributed_dropped_obj_step(mesh, tf, comp_cfg=None):
    """Takes the whole config object and then never threads it."""
    def step(data, cam):
        return composite_default(march(data, cam))
    return step


def march(data, cam):
    return data


def composite(frag, **kw):
    return frag


def composite_cfg(frag, cfg):
    return frag


def composite_default(frag):
    return frag
