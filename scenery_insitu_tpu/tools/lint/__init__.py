"""sitpu-lint — AST-level invariant checkers for this codebase.

Run ``python -m scenery_insitu_tpu.tools.lint`` (docs/STATIC_ANALYSIS.md).

Four project-specific checkers, each born from a hand-audit a landed PR
had to repeat:

- ``SITPU-LEDGER`` (ledger.py): behavior-changing fallback branches must
  mint ``obs.degrade`` entries (PR 3's completeness invariant).
- ``SITPU-THREAD`` (thread.py): every distributed step builder takes the
  CompositeConfig whole, forwards it and never rebuilds it, and consumes
  the topology (the PR 4/5/8 audit).
- ``SITPU-TRACE`` (trace.py): host-sync / retrace hazards inside
  jitted/scanned code (protects the pipelined overlap structure).
- ``SITPU-PALLAS`` (pallas.py): every ``pallas_call`` sits behind a
  Mosaic compile probe, declares divisibility handling, shapes SMEM
  scalar outputs (1, 1) (the PR 1/6 kernel contracts).

Pure stdlib ``ast`` — no jax, no execution of the code under analysis.
"""

from scenery_insitu_tpu.tools.lint.core import (Baseline,  # noqa: F401
                                                Diagnostic, SourceFile,
                                                default_scan_paths,
                                                find_repo_root,
                                                load_sources)
from scenery_insitu_tpu.tools.lint.runner import (run_checks,  # noqa: F401
                                                  run_lint)

__all__ = ["Baseline", "Diagnostic", "SourceFile", "default_scan_paths",
           "find_repo_root", "load_sources", "run_checks", "run_lint"]
