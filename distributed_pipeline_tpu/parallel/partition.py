"""Regex-rule partition engine: param-tree paths -> PartitionSpecs.

The auto-sharding layer (ROADMAP item 2). Models used to get sharded by
flax logical-axis metadata hand-mapped in ``parallel/sharding.py``; new
models therefore meant editing the engine. Here sharding is DECLARED: a
model family ships a rule table — ordered ``(regex, PartitionSpec)``
pairs matched against each parameter's tree path (the
``match_partition_rules`` / ``make_shard_and_gather_fns`` pattern of the
big public JAX LLM trainers; SNIPPETS [2]) — and the engine materializes
NamedShardings from it. ``parallel/sharding.py`` remains as a thin compat
shim (flax-logical-metadata models resolve through the same
:func:`resolve_shardings` fixer).

On top of the engine sits ZeRO-1 (Xu et al. 2020, "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training"):
:func:`zero1_shardings` extends a params-layout sharding tree so each
optimizer-state/EMA leaf is additionally sharded across the ``data`` mesh
axis. Weight-update state is only ever consumed elementwise inside the
train step, so XLA's SPMD partitioner gathers it on use (all-gather of the
updates, not of the 2x-Adam + EMA state), and per-replica weight-update
memory drops by the data-parallel factor — the refactor that unlocks
larger models (utils/trainer.py wires it behind
``--shard_optimizer``).

Three invariants the tests pin (tests/test_partition.py):

* scalar leaves (ndim 0 or size 1) never partition, whatever the rules;
* every leaf must match a rule — tables end with an explicit catch-all
  ``(r".*", P())`` so "replicate the rest" is a decision, not an accident;
* axes whose size a dim does not divide fall back to replication at
  materialization time (:func:`fix_spec` — tiny test models shard cleanly
  on any mesh, same contract as the old hand-wired path).

ELASTIC note (ISSUE 10): :func:`zero1_shardings` is a pure function of
the CURRENT mesh — on a shrink/grow resume the new run's dp may differ
from the one the checkpoint was written at (and the chosen shard dim may
even move when divisibility changes), which is fine by construction: the
trainer hands ``restore_resume_state`` abstract targets built from the
NEW layout and orbax reshards the stored state into it, in either
direction of a ``--shard_optimizer`` flip. dp == 1 degenerates to the
param layout, so shrinking all the way to one replica is just the
trivial case of the same path.
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "Rules", "match_partition_rules", "named_tree_map", "tree_path_name",
    "fix_spec", "resolve_shardings", "make_shard_and_gather_fns",
    "zero1_spec", "zero1_shardings", "parse_partition_rules",
    "rules_from_json", "rules_to_json", "load_partition_artifact",
    "rules_for_workload", "MOE_RULES", "DIFFUSEQ_RULES", "GPT2_RULES",
]

# An ordered rule table: first regex (re.search) matching a leaf's
# '/'-joined tree path wins.
Rules = Tuple[Tuple[str, P], ...]


def tree_path_name(path: Sequence[Any]) -> str:
    """A tree_flatten_with_path key path -> '/'-joined name, e.g.
    ``params/backbone/block_0/attn/qkv``."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any) -> Any:
    """``tree_map(fn, tree)`` where ``fn`` also receives the leaf's
    '/'-joined path (the engine's matching key)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [fn(tree_path_name(p), x) for p, x in leaves])


def match_partition_rules(rules: Sequence[Tuple[str, P]], tree: Any) -> Any:
    """PartitionSpec pytree for ``tree`` (live arrays, ShapeDtypeStructs —
    anything with ``.shape`` leaves) according to ``rules``.

    Scalar leaves (ndim 0 or one element) are never partitioned. Every
    other leaf must match some rule: a table without an explicit catch-all
    ``(r".*", P())`` raises on the first uncovered path instead of
    silently replicating it."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(name: str, leaf: Any) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()  # scalars never partition (snippet [2] contract)
        for pat, spec in compiled:
            if pat.search(name):
                if len(tuple(spec)) > len(shape):
                    raise ValueError(
                        f"partition rule {pat.pattern!r} has "
                        f"{len(tuple(spec))} entries but {name!r} has rank "
                        f"{len(shape)} (shape {shape})")
                return spec
        raise ValueError(
            f"no partition rule matched {name!r} — rule tables must end "
            f"with an explicit catch-all (r'.*', PartitionSpec()) so "
            f"replication is declared, not accidental")

    return named_tree_map(spec_for, tree)


def _axes_size(mesh: Mesh, entry: Any) -> int:
    axes = entry if isinstance(entry, tuple) else (entry,) if entry else ()
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def fix_spec(mesh: Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Materialization fixer: pad the spec to the leaf's rank and drop
    axes whose size the dim does not divide (fall back to replication) —
    the same contract the hand-wired path always had, so tiny test models
    shard cleanly on any mesh."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    fixed = [ax if _axes_size(mesh, ax) > 1 and dim % _axes_size(mesh, ax) == 0
             else None
             for dim, ax in zip(shape, entries)]
    return P(*fixed)


def _shape_of(leaf: Any) -> Tuple[int, ...]:
    if isinstance(leaf, (tuple, list)):
        return tuple(leaf)
    return tuple(leaf.shape)


def resolve_shardings(mesh: Mesh, specs: Any, tree: Any) -> Any:
    """PartitionSpec tree + shape-carrying tree -> NamedSharding tree,
    divisibility-fixed per leaf. ``tree`` leaves may be arrays, abstract
    values, or bare shape tuples."""
    return jax.tree_util.tree_map(
        lambda s, l: NamedSharding(mesh, fix_spec(mesh, s, _shape_of(l))),
        specs, tree)


def make_shard_and_gather_fns(mesh: Mesh, specs: Any) -> Tuple[Any, Any]:
    """Per-leaf ``(shard_fns, gather_fns)`` pytrees from a PartitionSpec
    tree (snippet [2] surface).

    ``shard_fns[leaf](x)`` places ``x`` into its rule sharding (host numpy
    or an already-device array both work — ``device_put`` reshards);
    ``gather_fns[leaf](x)`` brings a sharded leaf back fully replicated,
    the gather-on-use primitive for host-side consumers (export tooling,
    eval code that wants the whole array). Both are explicit transfers,
    legal under the sanitizer's transfer guard."""

    def make_shard(spec: P):
        def fn(x: Any) -> jax.Array:
            return jax.device_put(
                x, NamedSharding(mesh, fix_spec(mesh, spec, np.shape(x))))
        return fn

    def make_gather(spec: P):
        del spec  # gathering is spec-independent: target is replicated

        def fn(x: Any) -> jax.Array:
            return jax.device_put(x, NamedSharding(mesh, P()))
        return fn

    shard_fns = jax.tree_util.tree_map(make_shard, specs,
                                       is_leaf=lambda x: isinstance(x, P))
    gather_fns = jax.tree_util.tree_map(make_gather, specs,
                                        is_leaf=lambda x: isinstance(x, P))
    return shard_fns, gather_fns


# ------------------------------------------------------------------- ZeRO-1


def zero1_spec(mesh: Mesh, spec: P, shape: Tuple[int, ...],
               axis: str = "data") -> P:
    """Extend a (materialized) param spec so the leaf is additionally
    sharded across ``axis`` — the ZeRO-1 layout for weight-update state.

    Placement policy: the first dim the axis divides — an unsharded dim
    first, else an already-sharded dim whose per-shard size still divides
    (mixed FSDP/TP meshes). Leaves nothing divides stay as they are
    (small odd-shaped params; replicating them costs ~nothing)."""
    dp = mesh.shape[axis]
    fixed = tuple(fix_spec(mesh, spec, shape))
    if dp <= 1 or not shape:
        return P(*fixed)
    used = {a for e in fixed if e
            for a in (e if isinstance(e, tuple) else (e,))}
    if axis in used:
        # the param layout already consumes the axis (a rule table that
        # shards some dim over 'data'): the leaf is dp-sharded as-is, and
        # adding it again would build an invalid duplicate-axis spec
        return P(*fixed)
    entries = list(fixed)
    for d, ax in enumerate(entries):
        if ax is None and shape[d] % dp == 0:
            entries[d] = axis
            return P(*entries)
    for d, ax in enumerate(entries):
        if ax is None:
            continue
        if shape[d] % (_axes_size(mesh, ax) * dp) == 0:
            axes = ax if isinstance(ax, tuple) else (ax,)
            entries[d] = tuple(axes) + (axis,)
            return P(*entries)
    return P(*entries)


def zero1_shardings(mesh: Mesh, shardings: Any, tree: Any,
                    axis: str = "data") -> Any:
    """Params-layout NamedSharding tree -> ZeRO-1 NamedSharding tree:
    every leaf additionally sharded across the ``axis`` mesh axis (see
    :func:`zero1_spec`). Applied to optimizer moments and EMA copies —
    state the train step only reads/writes elementwise, so GSPMD gathers
    on use and per-replica bytes drop by ~``mesh.shape[axis]``."""
    return jax.tree_util.tree_map(
        lambda ns, l: NamedSharding(
            mesh, zero1_spec(mesh, ns.spec, _shape_of(l), axis)),
        shardings, tree)


# ------------------------------------------------------- per-model tables
#
# These tables REPRODUCE the flax-logical-metadata shardings the models
# shipped with (tests/test_partition.py pins leaf-for-leaf equivalence
# across mesh shapes), expressed as path rules so the next model declares
# a table instead of threading metadata through every self.param call.
#
# Layout legend (parallel/mesh.py axes):
#   fsdp   — ZeRO-3-style parameter sharding (every weight's "embed" dim)
#   tensor — Megatron TP pairing (wi column-, wo row-parallel; heads split)
#   expert — MoE expert-weight leading dim (GShard)
#   pipe   — stacked-layer leading dim under scan_layers (GPipe stages)

# MoE expert weights — both the named-block layout (moe/...) and the
# scan-stacked layout (blocks/moe_... with a leading layer-group dim).
MOE_RULES: Rules = (
    (r"moe/router$", P("fsdp", None)),
    (r"moe/wi$", P("expert", "fsdp", "tensor")),
    (r"moe/wo$", P("expert", "tensor", "fsdp")),
    (r"blocks/moe_router$", P("pipe", "fsdp", None)),
    (r"blocks/moe_wi$", P("pipe", "expert", "fsdp", "tensor")),
    (r"blocks/moe_wo$", P("pipe", "expert", "tensor", "fsdp")),
)

# The shared transformer trunk: named blocks (block_N/...), the
# scan-stacked dense layout (blocks/...), and the MoE-scan group layout
# (blocks/dense_* carries an extra per-group dense-layer dim, blocks/moe_*
# the attention/LN halves of MoE groups).
_BACKBONE_RULES: Rules = (
    (r"attn/qkv$", P("fsdp", None, "tensor", None)),
    (r"attn/out$", P("tensor", None, "fsdp")),
    (r"mlp/wi$", P("fsdp", "tensor")),
    (r"mlp/wo$", P("tensor", "fsdp")),
    (r"blocks/dense_qkv$", P("pipe", None, "fsdp", None, "tensor", None)),
    (r"blocks/dense_out$", P("pipe", None, "tensor", None, "fsdp")),
    (r"blocks/dense_wi$", P("pipe", None, "fsdp", "tensor")),
    (r"blocks/dense_wo$", P("pipe", None, "tensor", "fsdp")),
    (r"blocks/dense_ln\d_(scale|bias)$", P("pipe", None, None)),
    (r"blocks/(moe_)?qkv$", P("pipe", "fsdp", None, "tensor", None)),
    (r"blocks/(moe_)?out$", P("pipe", "tensor", None, "fsdp")),
    (r"blocks/(moe_)?wi$", P("pipe", "fsdp", "tensor")),
    (r"blocks/(moe_)?wo$", P("pipe", "tensor", "fsdp")),
    (r"blocks/(moe_)?ln\d_(scale|bias)$", P("pipe", None)),
)

# The embedding table shards over vocab only: tensor (Megatron
# vocab-parallel logits) + fsdp (ZeRO for the big table). Its hidden dim
# stays replicated — an fsdp-sharded hidden dim would push fsdp onto every
# [B, L, hidden] activation the table produces and fight the batch
# sharding (see models/diffuseq.py's annotation rationale).
_EMBED_RULE = (r"word_emb/embedding$", P(("tensor", "fsdp"), None))

DIFFUSEQ_RULES: Rules = MOE_RULES + _BACKBONE_RULES + (
    _EMBED_RULE,
    (r"(^|/)pos_emb$", P(None, "fsdp")),
    (r"in_proj/kernel$", P(None, "fsdp")),
    (r"out_proj/kernel$", P("fsdp", None)),
    # LN scales/biases, Dense biases, the time-embedding MLP: replicated
    (r".*", P()),
)

GPT2_RULES: Rules = MOE_RULES + _BACKBONE_RULES + (
    _EMBED_RULE,
    # pos_emb replicated (it adds directly into the activation — sharding
    # its hidden dim would fight the batch sharding, gpt2.py rationale)
    (r".*", P()),
)

_FAMILY_RULES: Dict[str, Rules] = {
    "diffuseq": DIFFUSEQ_RULES,
    "gpt2": GPT2_RULES,
}


def rules_for_workload(workload: Any) -> Optional[Rules]:
    """The rule table a workload declares (``workload.partition_rules``),
    else its family's built-in table, else None (unknown families keep the
    flax logical-metadata compat path in parallel/sharding.py)."""
    declared = getattr(workload, "partition_rules", None)
    if declared:
        return tuple(declared)
    return _FAMILY_RULES.get(getattr(workload, "family", ""))


def rules_from_json(raw: Any) -> Rules:
    """Wire-format rule list -> Rules: an ordered list of
    ``[regex, spec]`` pairs where ``spec`` is a list of entries — ``null``
    (replicate the dim), a mesh-axis name, or a list of axis names
    (several axes on one dim)."""
    rules = []
    for entry in raw:
        if not (isinstance(entry, list) and len(entry) == 2
                and isinstance(entry[0], str) and isinstance(entry[1], list)):
            raise ValueError(
                f"partition rule entries must be [regex, [spec...]] pairs, "
                f"got {entry!r}")
        pat, spec = entry
        rules.append((pat, P(*(tuple(e) if isinstance(e, list) else e
                               for e in spec))))
    return tuple(rules)


def rules_to_json(rules: Rules) -> list:
    """Rules -> the wire format :func:`rules_from_json` reads (the tuner
    artifact writer; round-trips exactly)."""
    out = []
    for pat, spec in rules:
        out.append([pat, [list(e) if isinstance(e, tuple) else e
                          for e in tuple(spec)]])
    return out


def _read_rules_body(text: str) -> str:
    """Shared ``--partition_rules`` input resolution: inline JSON,
    ``@/path.json``, or a bare file path."""
    body = text.strip()
    if body.startswith("@"):
        with open(body[1:]) as f:
            return f.read()
    if not body.startswith(("[", "{")):
        with open(body) as f:
            return f.read()
    return body


def parse_partition_rules(text: str) -> Optional[Rules]:
    """``--partition_rules`` parser: inline JSON, ``@/path.json``, or a
    bare file path. The JSON is either the ordered ``[regex, spec]`` pair
    list (:func:`rules_from_json`), e.g.
    ``[["attn/qkv$", ["fsdp", null, "tensor", null]], [".*", []]]``, or a
    TUNER ARTIFACT object (tune/search.py) whose rules ride the
    ``partition_rules`` key — so the file the auto-tuner emits is loaded
    verbatim. Returns None for empty input."""
    if not text:
        return None
    raw = json.loads(_read_rules_body(text))
    if isinstance(raw, dict):
        if "partition_rules" not in raw:
            raise ValueError(
                "a --partition_rules JSON object must carry the rule "
                "list under 'partition_rules' (the tuner artifact shape)")
        raw = raw["partition_rules"]
    return rules_from_json(raw)


def load_partition_artifact(text: str) -> Optional[Dict[str, Any]]:
    """Full ``--partition_rules`` payload including the tuner's layout
    recommendations: ``{"rules": Rules, "mesh": dict|None,
    "shard_optimizer": bool|None}``. A plain rule list (the pre-tuner
    input shape) yields mesh/shard_optimizer None; empty input None."""
    if not text:
        return None
    raw = json.loads(_read_rules_body(text))
    if isinstance(raw, dict):
        if "partition_rules" not in raw:
            raise ValueError(
                "a --partition_rules JSON object must carry the rule "
                "list under 'partition_rules' (the tuner artifact shape)")
        mesh = raw.get("mesh")
        return {
            "rules": rules_from_json(raw["partition_rules"]),
            "mesh": dict(mesh) if isinstance(mesh, dict) else None,
            "shard_optimizer": (bool(raw["shard_optimizer"])
                                if raw.get("shard_optimizer") is not None
                                else None),
        }
    return {"rules": rules_from_json(raw), "mesh": None,
            "shard_optimizer": None}
