"""SITPU-THREAD — CompositeConfig threading through the distributed step
builders.

PRs 4, 5, 6 and 8 each added a ``CompositeConfig`` field (``exchange``,
``wire``, ``k_budget``, ``schedule``/``wave_tiles``) and each had to
hand-audit that EVERY distributed step builder forwarded it — a
mechanical invariant that rots silently: a builder that drops a field
still renders, it just quietly ignores the configuration (exactly the
reference's three-tier config failure mode the config module docstring
complains about). Since PR 29 every builder takes the config object
whole, so a new field rides along; what is left to check is that nobody
unpacks it again.

Rules, per builder (top-level ``distributed_*step*`` / ``_build_mxu_step``
in ``parallel/pipeline.py``):

- it takes the config whole: a ``comp_cfg`` parameter. Loose per-field
  parameters in its place are how a field gets lost between the session
  and the composite.
- ``comp_cfg`` must be forwarded — appear as a direct argument of some
  call in the body (including nested defs). Rebuilding it
  (``dataclasses.replace`` / a fresh ``CompositeConfig(...)`` with
  fields) inside a builder is flagged: that is how whole-object
  threading silently drops fields.
- it must accept AND consume ``topology`` (below).
"""

from __future__ import annotations

import ast
import re
from typing import List

from scenery_insitu_tpu.tools.lint.core import (Diagnostic, SourceFile,
                                                func_params, iter_calls)

CODE = "SITPU-THREAD"

BUILDER_RE = re.compile(r"^(distributed_.*step.*|_build_mxu_step)$")
COMPOSITE_CLASS = "CompositeConfig"
COMP_PARAM = "comp_cfg"
# the scale-out plane (docs/MULTIHOST.md): every distributed step builder
# must accept AND forward the TopologyConfig — a builder that drops it
# silently renders the flat single-domain composite on a hierarchical
# mesh, exactly the class of rot this checker exists for (topology is
# its own config object, not a CompositeConfig field).
TOPO_PARAM = "topology"


def _name_used_as_call_arg(fn: ast.AST, name: str) -> bool:
    """Is ``name`` forwarded — a bare-Name argument (positional, keyword
    value, or *args) of any call inside ``fn`` (nested defs included)?"""
    for c in iter_calls(fn):
        for a in c.args:
            if isinstance(a, ast.Starred):
                a = a.value
            if isinstance(a, ast.Name) and a.id == name:
                return True
        for kw in c.keywords:
            if isinstance(kw.value, ast.Name) and kw.value.id == name:
                return True
    return False


def _builders(pipeline_src: SourceFile) -> List[ast.FunctionDef]:
    return [n for n in pipeline_src.tree.body
            if isinstance(n, ast.FunctionDef) and BUILDER_RE.match(n.name)]


def _check_builder(src: SourceFile, fn: ast.FunctionDef) -> List[Diagnostic]:
    diags = []
    params = func_params(fn)
    if TOPO_PARAM not in params:
        diags.append(Diagnostic(
            src.path, fn.lineno, CODE,
            f"does not accept '{TOPO_PARAM}' (TopologyConfig; every "
            f"distributed builder must thread the mesh topology — "
            f"docs/MULTIHOST.md)", fn.name))
    elif not _name_used_as_call_arg(fn, TOPO_PARAM):
        diags.append(Diagnostic(
            src.path, fn.lineno, CODE,
            f"accepts '{TOPO_PARAM}' but never consumes it — the "
            f"hierarchical composite is silently dropped", fn.name))
    if COMP_PARAM not in params:
        diags.append(Diagnostic(
            src.path, fn.lineno, CODE,
            f"does not accept {COMP_PARAM} — a distributed builder takes "
            f"the {COMPOSITE_CLASS} whole, never its fields one by one",
            fn.name))
        return diags
    if not _name_used_as_call_arg(fn, COMP_PARAM):
        diags.append(Diagnostic(
            src.path, fn.lineno, CODE,
            f"accepts {COMP_PARAM} but never forwards it — every "
            f"{COMPOSITE_CLASS} field is dropped", fn.name))
    for c in iter_calls(fn):
        callee = c.func
        # a bare CompositeConfig() is the `comp_cfg or
        # CompositeConfig()` default fill — only a RE-construction
        # with explicit fields (or dataclasses.replace on the
        # threaded object) can drop fields
        rebuilt = (isinstance(callee, ast.Name)
                   and callee.id == COMPOSITE_CLASS
                   and (c.args or c.keywords)) or \
                  (isinstance(callee, ast.Attribute)
                   and callee.attr == "replace"
                   and any(isinstance(a, ast.Name)
                           and a.id == COMP_PARAM for a in c.args))
        if rebuilt:
            diags.append(Diagnostic(
                src.path, c.lineno, CODE,
                f"rebuilds {COMPOSITE_CLASS} inside a builder — fields "
                f"not restated here are silently dropped; forward "
                f"{COMP_PARAM} itself", fn.name))
    return diags


def check(sources: List[SourceFile],
          pipeline_path: str = "scenery_insitu_tpu/parallel/pipeline.py"
          ) -> List[Diagnostic]:
    pipeline_src = {s.path: s for s in sources}.get(pipeline_path)
    if pipeline_src is None:
        return []            # custom path sets without the core file
    diags: List[Diagnostic] = []
    for fn in _builders(pipeline_src):
        diags.extend(_check_builder(pipeline_src, fn))
    return diags
