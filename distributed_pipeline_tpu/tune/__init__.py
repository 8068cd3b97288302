"""Profile-guided sharding auto-tuner (ISSUE 13, ROADMAP item 4).

Layout became DATA in r11 (``--partition_rules`` regex tables), paired-
interleaved ABBA windows made deltas measurable on a noisy box,
and the footprint gauges made memory a number. This package composes them
into a CONTROL LOOP: enumerate candidate rule tables x mesh-axis splits
for a model/shape (:mod:`.candidates`), statically reject anything that
cannot shard before ever compiling, measure each survivor in a child
process (:mod:`.measure` — steps/s, per-replica state bytes, peak live
bytes, steady recompiles; OOM/timeout folds to a pruned row), drive
successive halving under a wall-clock budget with every trial journaled
for resume (:mod:`.search`), and emit the winner as a
``--partition_rules`` artifact ``run/train.py`` loads verbatim
(Mesh-TensorFlow's layout-as-data, arxiv 1811.02084; the pjit/TPUv4
playbook, arxiv 2204.06514).

Lazy exports (PEP 562): the fleet/launcher style — importing the package
costs nothing until a symbol is touched, so import-light callers
(tests reading journals) never pay the jax import hiding behind
:mod:`.candidates`.
"""

from __future__ import annotations

_LAZY = {
    "Candidate": ".candidates",
    "enumerate_candidates": ".candidates",
    "mesh_splits": ".candidates",
    "param_shapes": ".candidates",
    "rule_variants": ".candidates",
    "validate_candidate": ".candidates",
    "child_env": ".measure",
    "run_child": ".measure",
    "run_search": ".search",
    "write_artifact": ".search",
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(_LAZY[name], __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
