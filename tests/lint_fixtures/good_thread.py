# SITPU-THREAD good fixture: the compliant builder shapes. Parsed by the
# linter only.


def distributed_obj_step(mesh, tf, vdi_cfg=None, comp_cfg=None,
                         topology=None):
    """Whole-object threading: comp_cfg flows into the composite call —
    every current and future field rides along — and the mesh topology
    is resolved, not dropped."""
    topo = resolve_topology(mesh, topology)

    def step(data, cam):
        return composite_cfg(march(data, cam), comp_cfg, topo)
    return step


def distributed_plain_like_step(mesh, tf, width, height, comp_cfg=None,
                                topology=None):
    """The plain builders' shape: the config is taken whole, filled with
    the default when absent, forwarded to the resolvers, and the fields
    this step consumes are read off it."""
    comp_cfg = comp_cfg or CompositeConfig()
    topo = resolve_topology(mesh, topology)
    waves = resolve_waves(comp_cfg, width)

    def step(data, cam):
        return composite(march(data, cam), exchange=comp_cfg.exchange,
                         wire=comp_cfg.wire, waves=waves, topo=topo)
    return step


def march(data, cam):
    return data


def composite(frag, **kw):
    return frag


def composite_cfg(frag, cfg, topo):
    return frag


def resolve_topology(mesh, topology):
    return topology


def resolve_waves(cfg, width):
    return False
