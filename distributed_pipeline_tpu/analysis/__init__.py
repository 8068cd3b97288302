"""graftlint: JAX-aware static analysis for this training stack.

Generic linters cannot see the bug classes that actually burn TPU runs
here — the ones past rounds fixed by hand (CHANGES.md r6): PRNG key
reuse, a hidden step-2 recompile from unpinned
``out_shardings``, donating Orbax-restored buffers into a
cache-deserialized executable. This subpackage is the correctness-
tooling layer production JAX stacks carry for exactly these hazards:

* :mod:`core` — AST module model (import resolution, traced-context
  discovery, donation map), the rule registry, and the file runner.
* :mod:`rules` — the rule catalog (GL001..GL011), one visitor per
  hazard class this repo has hit.
* :mod:`callgraph` — the whole-program pass (ISSUE 15): per-module
  summaries + import resolution + signature-aware fixpoints flow
  tracedness, donation liveness, static-argnum and PRNG-key facts
  across call and module boundaries, turning the r7 audit's blind
  spots into proofs (GL002/GL003/GL005/GL007 graph halves, GL011).
* :mod:`cache` — content-hash parse/summary cache so the lint gate
  stops reparsing unchanged modules as the gated path list grows.
* :mod:`baseline` — committed allowlist store: findings audited as
  unavoidable are fingerprinted into ``graftlint_baseline.json``
  instead of the rule being suppressed.
* :mod:`cli` — ``python -m distributed_pipeline_tpu.analysis
  [--format json|human|github] [--baseline FILE] [--changed FILE...]
  [--no-cache] PATHS``.

The static pass is paired with a runtime "sanitizer mode"
(``--sanitize``, utils/perf.RecompileMonitor + transfer guards in
utils/trainer.TrainLoop) that catches dynamically what the AST pass
cannot prove: actual recompiles and implicit host<->device transfers.
"""

from __future__ import annotations

from .baseline import Baseline
from .cache import AnalysisCache
from .callgraph import CallGraph, ModuleSummary, summarize_module
from .core import Finding, Module, Rule, all_rules, run_paths
from . import rules as _rules  # noqa: F401  (imports register the catalog)

__all__ = ["AnalysisCache", "Baseline", "CallGraph", "Finding", "Module",
           "ModuleSummary", "Rule", "all_rules", "run_paths",
           "summarize_module"]
