"""Model factory and workload plug-in surface.

Fills the reference's empty model layer (``/root/reference/models/__init__.py``
is 0 bytes; ``utils/initialization.py:18-27`` ``create_model_from_config`` is a
stub) with two concrete families behind the same factory call the reference
entry point makes (``run/train.py:71`` passes ``**args.dict()``):

* ``diffuseq`` — seq2seq embedding diffusion (base/large/xl presets);
* ``gpt2``     — causal LM (base/medium/large/xl presets);
* ``deepseek_v32`` — DeepSeek-V3.2-Exp as one chip's share of an
  expert-parallel deployment (models/deepseek_v32.py): served through
  ``DecodeServer``'s chunked prefill; its ``arch`` flag carries the source's
  ``config.json`` keys and the cut;
* ``dots3_note`` — dots3-note-prev likewise (models/dots3_note.py: the
  configuration; the layers are ``deepseek_v32``'s, told their kind): full
  latent-attention layers with the indexer beside window layers whose cache
  is a ring a slot, headwise output gates;
* ``keye_vl2`` — Keye-VL-2.0's language model as one pipeline stage with
  every expert held (models/keye_vl2.py): grouped-query attention through
  the lightning indexer over a paged K/V pool, rotary positions, head
  norms, an untied head, and a sorted, grouped pass over the experts; on
  the same chunked-prefill seam.

The factory returns a :class:`Workload`: the flax module plus pure
``init_params`` / ``compute_losses`` functions — the reference's user-hook
trio (``compute_losses``/``backward_from_losses``/``log_loss_dict``,
``utils/trainer.py:19-31``) collapsed into one functional object that the
jitted trainer consumes.
"""

from __future__ import annotations

import dataclasses
import random as _random
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backbone import TransformerBackbone
from .deepseek_v32 import DeepseekV32Config, LatentMoEModel
from .diffuseq import DiffuSeqModel, diffuseq_losses
from .diffusion import DiffusionSchedule, make_schedule
from .dots3_note import Dots3NoteConfig
from .gpt2 import GPT2Model, gpt2_losses
from .keye_vl2 import KeyeVL2Config, SparseGQAMoEModel

__all__ = [
    "Workload", "create_model_from_config", "seed_all", "PRESETS",
    "DiffuSeqModel", "GPT2Model", "TransformerBackbone",
    "make_schedule", "DiffusionSchedule",
]

# (hidden, layers, heads) per family/size.
PRESETS: Dict[str, Dict[str, Tuple[int, int, int]]] = {
    "diffuseq": {
        "base": (768, 12, 12),    # BASELINE.md config 1/2
        "large": (1024, 24, 16),  # config 3
        "xl": (1600, 32, 25),     # config 5
    },
    "gpt2": {
        "base": (768, 12, 12),
        "medium": (1024, 24, 16),  # config 4
        "large": (1280, 36, 20),
        "xl": (1600, 48, 25),
    },
    # the published model; a deployment's cut comes through `arch`
    "deepseek_v32": {"base": (7168, 61, 128)},
    "dots3_note": {"base": (5120, 46, 128)},
    "keye_vl2": {"base": (2048, 48, 32)},
}
# the served-only families of plain functions on the chunked-prefill seam:
# (the class that reads their `arch`, the model that runs it)
CHUNKED = {"deepseek_v32": (DeepseekV32Config, LatentMoEModel),
           "dots3_note": (Dots3NoteConfig, LatentMoEModel),
           "keye_vl2": (KeyeVL2Config, SparseGQAMoEModel)}
DIFFUSEQ_EMB_DIM = 128  # DiffuSeq uses a low-dim embedding space


@dataclasses.dataclass(frozen=True)
class Workload:
    """A model family bound to its pure loss function.

    ``compute_losses(params, batch, rng) -> {"loss": scalar, ...metrics}`` is
    jit-safe; the trainer differentiates it directly (the reference's separate
    ``backward_from_losses`` hook disappears — grad is a transform, not a
    method).
    """

    model: Any
    family: str
    seq_len: int
    hidden_size: int
    num_layers: int
    compute_losses: Callable[[Any, Dict[str, jnp.ndarray], jax.Array],
                             Dict[str, jnp.ndarray]]
    example_batch: Callable[[int], Dict[str, np.ndarray]]
    schedule: Optional[DiffusionSchedule] = None
    # Declared sharding (parallel/partition.py): ordered (path-regex,
    # PartitionSpec) rules the trainer resolves into NamedShardings. None
    # falls back to the family's built-in table (rules_for_workload), and
    # unknown families to the flax logical-metadata compat path — a new
    # model declares a table here instead of editing the engine.
    partition_rules: Optional[Tuple[Tuple[str, Any], ...]] = None

    def init_params(self, rng: jax.Array) -> Any:
        """Initialize parameters from a dummy batch (shapes only)."""
        batch = jax.tree_util.tree_map(jnp.asarray, self.example_batch(1))
        if self.family == "diffuseq":
            t = jnp.zeros((1,), jnp.int32)
            variables = self.model.init(rng, batch["input_ids"], t,
                                        batch["pad_mask"],
                                        method=DiffuSeqModel.init_variables)
        else:
            variables = self.model.init(rng, batch["input_ids"],
                                        batch["pad_mask"])
        # init() materializes every collection; only "params" is trainable
        # state ("losses" holds MoE aux sows — per-step outputs, not state).
        return {k: v for k, v in variables.items() if k != "losses"}

    def param_count(self, params: Any) -> int:
        """Parameters of the ``params`` collection: a server's variables
        carry derived copies beside it (GPT2Model.serving_variables)."""
        return sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(params.get("params", params)))


def _example_batch_fn(seq_len: int) -> Callable[[int], Dict[str, np.ndarray]]:
    def fn(batch_size: int) -> Dict[str, np.ndarray]:
        ones = np.ones((batch_size, seq_len), np.int32)
        ids = np.arange(batch_size * seq_len, dtype=np.int32).reshape(
            batch_size, seq_len) % 7 + 4
        mask = np.zeros_like(ones)
        mask[:, seq_len // 2:] = 1
        return {"input_ids": ids, "input_mask": mask, "pad_mask": ones}
    return fn


def _served_not_trained(params, batch, rng):
    raise NotImplementedError(
        "a chunked-prefill family (deepseek_v32, dots3_note, keye_vl2) is "
        "served, not trained: it has no loss, no partition rules and "
        "forward-only kernels (ROADMAP R3)")


def create_model_from_config(*, model_family: str = "diffuseq",
                             model_size: str = "base",
                             vocab_size: int = 8192, seq_len: int = 128,
                             hidden_size: int = 0, num_layers: int = 0,
                             num_heads: int = 0,
                             diffusion_steps: int = 2000,
                             noise_schedule: str = "sqrt",
                             dtype: str = "bfloat16", remat: bool = False,
                             attention_impl: str = "auto",
                             moe_experts: int = 0, moe_top_k: int = 2,
                             moe_every: int = 2,
                             moe_capacity_factor: float = 1.25,
                             scan_layers: bool = False,
                             pp_chunks: int = 4, pp_schedule: str = "1f1b",
                             pp_virtual: int = 2, scan_unroll: int = 0,
                             arch: Optional[Dict[str, Any]] = None,
                             **_unused: Any) -> Workload:
    """Build a :class:`Workload` from (a superset of) ``TrainSettings`` fields
    — callable as ``create_model_from_config(**settings.dict())`` exactly like
    the reference entry point (``run/train.py:71``). Preset dims can be
    overridden individually via nonzero hidden/layers/heads."""
    if model_family not in PRESETS:
        raise ValueError(f"unknown model family: {model_family!r}; "
                         f"available: {sorted(PRESETS)}")
    if moe_experts > 0 and moe_every < 1:
        raise ValueError(f"moe_every must be >= 1, got {moe_every}")
    preset = PRESETS[model_family].get(model_size)
    if preset is None:
        raise ValueError(f"no preset {model_size!r} for family {model_family!r}; "
                         f"available: {sorted(PRESETS[model_family])}")
    hidden = hidden_size or preset[0]
    layers = num_layers or preset[1]
    if scan_layers and moe_experts > 0 and layers % moe_every:
        raise ValueError(
            f"scan_layers MoE scans uniform groups of moe_every blocks: "
            f"num_layers {layers} must divide by moe_every {moe_every}")
    heads = num_heads or preset[2]
    jdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    if model_family in CHUNKED:
        # explicit size flags win over `arch` (a dict of the source's
        # keys); the presets' zeros leave it alone
        config_cls, model_cls = CHUNKED[model_family]
        cfg = config_cls.from_arch(
            arch or {}, vocab_size=vocab_size, hidden_size=hidden_size,
            n_layers=num_layers, num_attention_heads=num_heads)
        model = model_cls(cfg=cfg, seq_len=seq_len, dtype=jdtype)
        return Workload(
            model=model, family=model_family, seq_len=seq_len,
            hidden_size=cfg.hidden_size, num_layers=cfg.n_layers,
            compute_losses=_served_not_trained,
            example_batch=_example_batch_fn(seq_len))

    # Declared sharding: the family's partition-rule table rides the
    # Workload (parallel/partition.py; function-level import keeps the
    # models layer import-light for tools that only build modules).
    from ..parallel.partition import DIFFUSEQ_RULES, GPT2_RULES
    rules = DIFFUSEQ_RULES if model_family == "diffuseq" else GPT2_RULES

    if model_family == "diffuseq":
        model = DiffuSeqModel(
            vocab_size=vocab_size, seq_len=seq_len, hidden_size=hidden,
            num_layers=layers, num_heads=heads, emb_dim=DIFFUSEQ_EMB_DIM,
            dtype=jdtype, remat=remat, attention_impl=attention_impl,
            moe_experts=moe_experts, moe_top_k=moe_top_k,
            moe_every=moe_every, moe_capacity_factor=moe_capacity_factor,
            scan_layers=scan_layers,
            pp_chunks=pp_chunks, pp_schedule=pp_schedule,
            pp_virtual=pp_virtual, scan_unroll=scan_unroll)
        schedule = make_schedule(noise_schedule, diffusion_steps)

        def compute_losses(params, batch, rng):
            return diffuseq_losses(model, schedule, params, batch, rng)

        return Workload(model=model, family="diffuseq", seq_len=seq_len,
                        hidden_size=hidden, num_layers=layers,
                        compute_losses=compute_losses,
                        example_batch=_example_batch_fn(seq_len),
                        schedule=schedule, partition_rules=rules)

    else:  # "gpt2" — PRESETS membership was validated above
        model = GPT2Model(
            vocab_size=vocab_size, seq_len=seq_len, hidden_size=hidden,
            num_layers=layers, num_heads=heads, dtype=jdtype, remat=remat,
            attention_impl=attention_impl, moe_experts=moe_experts,
            moe_top_k=moe_top_k, moe_every=moe_every,
            moe_capacity_factor=moe_capacity_factor,
            scan_layers=scan_layers, pp_chunks=pp_chunks,
            pp_schedule=pp_schedule, pp_virtual=pp_virtual,
            scan_unroll=scan_unroll)

        def compute_losses(params, batch, rng):
            return gpt2_losses(model, params, batch, rng)

        return Workload(model=model, family="gpt2", seq_len=seq_len,
                        hidden_size=hidden, num_layers=layers,
                        compute_losses=compute_losses,
                        example_batch=_example_batch_fn(seq_len),
                        partition_rules=rules)


def seed_all(seed: int, deterministic: bool = False) -> jax.Array:
    """Global seeding with per-process offset (reference
    ``utils/initialization.py:1-15``: non-deterministic mode offsets the seed
    by rank so hosts draw different data/noise; deterministic mode keeps all
    hosts identical). Returns the root JAX PRNG key — JAX's counter-based
    PRNG replaces torch's stateful seeding."""
    from ..parallel import dist

    offset = 0 if deterministic else dist.get_rank()
    _random.seed(seed + offset)
    np.random.seed((seed + offset) % (2 ** 32))
    return jax.random.PRNGKey(seed + offset)
