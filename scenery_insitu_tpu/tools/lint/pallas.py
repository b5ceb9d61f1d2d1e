"""SITPU-PALLAS — the Mosaic kernel contracts, checked instead of recited.

The checkable parts of the kernel contract at every ``pl.pallas_call``
site. (Until PR 21 a third rule demanded a ``*_compile_ok`` probe with
an XLA fallback around every kernel; a refusal of a default-path kernel
now reaches the user with Mosaic's own message, and ``chip_smoke.py`` is
where the kernels meet the compiler.)

**P2 — tile-divisibility declared.** A grid of ``shape // tile`` silently
leaves output tiles unwritten when the division floors; every kernel
entry must either guard (``if h % TILE_H: raise``, the explicit-tz
checks) or pad by a computed remainder (``(-h) % TILE_H`` feeding a
pad) — some ``%``-derived handling must be visible in the entry function.

**P3 — SMEM operands are whole.** Mosaic's lowering refuses a blocked
SMEM operand whose last two block dimensions are not multiples of
(8, 128) or the full extent — the ``(1, 1)`` scalar-output blocks the
occupancy ranges epilogue once used do not compile (libtpu 0.0.34).
Any ``pl.BlockSpec`` carrying ``memory_space=pltpu.SMEM`` must leave the
block shape out: the whole array rides in SMEM and the kernel indexes
it by ``pl.program_id``.
"""

from __future__ import annotations

import ast
from typing import List

from scenery_insitu_tpu.tools.lint.core import (Diagnostic, SourceFile,
                                                dotted_name, iter_calls)

CODE = "SITPU-PALLAS"


def _pallas_call_sites(tree: ast.Module) -> List[ast.Call]:
    return [c for c in iter_calls(tree)
            if dotted_name(c.func).endswith("pallas_call")]


def _top_level_fn_of(tree: ast.Module, node: ast.AST):
    for top in tree.body:
        if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and top.lineno <= node.lineno <= (top.end_lineno
                                                  or top.lineno):
            return top
    return None


def _has_mod_guard(fn) -> bool:
    """An explicit divisibility guard or a %-derived padding in ``fn``."""
    def has_mod(e):
        return any(isinstance(n, ast.Mod) for n in ast.walk(e))

    pads = any(dotted_name(c.func).rsplit(".", 1)[-1] in ("pad", "cdiv")
               for c in iter_calls(fn))
    for n in ast.walk(fn):
        if isinstance(n, ast.If) and has_mod(n.test) \
                and any(isinstance(b, ast.Raise) for b in ast.walk(n)):
            return True
        if isinstance(n, ast.Assert) and has_mod(n.test):
            return True
        if isinstance(n, (ast.Assign, ast.AnnAssign)) \
                and n.value is not None and has_mod(n.value) and pads:
            return True
    return False


def _smem_blockspec_diags(src: SourceFile) -> List[Diagnostic]:
    diags = []
    for c in iter_calls(src.tree):
        if not dotted_name(c.func).endswith("BlockSpec"):
            continue
        kw = {k.arg: k.value for k in c.keywords if k.arg}
        ms = kw.get("memory_space")
        if ms is None or "SMEM" not in ast.dump(ms):
            continue
        shape = c.args[0] if c.args else kw.get("block_shape")
        if shape is None:
            continue                    # whole-operand SMEM ref (inputs)
        diags.append(Diagnostic(
            src.path, c.lineno, CODE,
            "blocked SMEM operand — Mosaic refuses SMEM blocks that "
            "are not (8, 128)-tiled; pass the whole array "
            "(BlockSpec(memory_space=pltpu.SMEM)) and index it by "
            "program_id (see sim/pallas_stencil.py ranges epilogue)"))
    return diags


def check(sources: List[SourceFile]) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for src in sources:
        sites = _pallas_call_sites(src.tree)
        if not sites:
            continue
        seen_fns = set()
        for site in sites:
            fn = _top_level_fn_of(src.tree, site)
            if fn is None:
                diags.append(Diagnostic(
                    src.path, site.lineno, CODE,
                    "module-level pallas_call — no entry function to "
                    "declare its tile-divisibility handling"))
                continue
            if fn.name in seen_fns:
                continue
            seen_fns.add(fn.name)
            if not _has_mod_guard(fn):
                diags.append(Diagnostic(
                    src.path, site.lineno, CODE,
                    f"{fn.name}() declares no tile-divisibility handling "
                    f"(no %-guard raise/assert and no %-derived "
                    f"padding) — a floored grid division silently "
                    f"leaves output tiles unwritten", fn.name))
        diags.extend(_smem_blockspec_diags(src))
    return diags
