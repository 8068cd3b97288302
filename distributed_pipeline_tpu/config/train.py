"""Concrete training settings.

Parity with the reference schema (``/root/reference/config/train.py:6-80``):
``GeneralSettings`` (optimizer/loop hyperparameters, identical defaults),
``DataSettings``, and a composed ``TrainSettings`` whose argparse adds a
mutually-exclusive ``--config_json`` that overrides the whole CLI
(reference train.py:57-77).

Where the reference leaves ``YourSettings`` as an empty stub (train.py:44-46),
this framework fills it with the concrete TPU workload settings:
``ModelSettings`` (DiffuSeq diffusion / GPT-2 causal-LM families) and
``MeshSettings`` (device-mesh axis sizes for data/fsdp/tensor/sequence
parallelism — the TPU-native replacement for DDP process groups).
"""

from __future__ import annotations

import argparse
from typing import Literal, Optional

from .base import ArgparseCompatibleBaseModel as S
from .base import item as _


class GeneralSettings(S):
    """Optimizer and loop hyperparameters (reference config/train.py:6-32)."""

    lr: float = _(1e-4, "learning rate")
    batch_size: int = _(2048, "per-host batch size; global = batch_size * num_hosts "
                              "(reference semantics, trainer.py:89)")
    microbatch: int = _(64, "microbatch size per optimizer step; -1 = batch_size")
    learning_steps: int = _(320000, "total optimizer steps")
    log_interval: int = _(50, "steps between metric dumps")
    save_interval: int = _(10000, "steps between checkpoints")
    eval_interval: int = _(1000, "steps between eval passes")
    ema_rate: str = _("0.5,0.9,0.99", "comma-separated EMA decay rates")
    seed: int = _(102, "global RNG seed")
    resume_checkpoint: str = _("", "explicit checkpoint path to resume from")
    checkpoint_path: str = _("", "run/checkpoint directory (auto-generated if empty)")
    gradient_clipping: float = _(-1.0, "global-norm gradient clip; <=0 disables")
    weight_decay: float = _(0.0, "AdamW decoupled weight decay")
    warmup_steps: int = _(0, "linear LR warmup steps before the anneal "
                             "(0 = reference behavior: no warmup)")
    keep_checkpoints: int = _(0, "retain only the newest N checkpoint steps "
                                 "(model+EMA+opt pruned together); 0 = keep "
                                 "all (reference behavior)")
    debug_nans: bool = _(False, "enable jax_debug_nans: fail loudly at the op "
                                "that first produces a NaN (debug runs only; "
                                "disables async dispatch)")
    eval_decode: bool = _(False, "decode a validation batch at every eval "
                                 "interval and log decode_acc (DiffuSeq "
                                 "reverse diffusion / GPT-2 greedy)")
    eval_decode_sample_steps: int = _(32, "reverse-diffusion steps for "
                                         "eval decoding (diffuseq only)")
    profile_dir: str = _("", "capture a jax.profiler trace of a few steps "
                             "into this directory (TensorBoard format)")
    profile_steps: str = _("", "jax.profiler capture window as 'A:B' loop "
                               "steps counted from loop entry (with "
                               "--profile_dir; empty = the default 3:8 "
                               "window past compilation) — the XLA-level "
                               "view next to the obs/ span timeline")
    trace: bool = _(False, "span tracing (obs/): book step/save/restore/"
                           "compile/eval spans into trace_rank{k}.jsonl "
                           "in the run dir, exportable to a Perfetto "
                           "timeline with python -m "
                           "distributed_pipeline_tpu.obs.export; the "
                           "DPT_TRACE env arms it too (reaches every "
                           "worker of a launcher ring, incl. "
                           "--config_json runs); off = zero-cost no-op")
    cost_ledger: bool = _(False, "per-compiled-program cost ledger (obs/"
                                 "ledger.py): extract XLA's FLOPs/bytes "
                                 "accounting + an HLO collective-bytes "
                                 "tally off the AOT step executables and "
                                 "log the roofline MFU-gap attribution "
                                 "(mfu_gap_host/comms/memory_bound/"
                                 "residual, collective_bytes_per_step, "
                                 "padding_waste_frac) each log window, "
                                 "snapshotted to <run_dir>/"
                                 "perf_ledger.json (read by run/"
                                 "perf_report.py, run/status.py, and "
                                 "obs/export.py counter tracks)")
    sanitize: bool = _(False, "runtime sanitizer mode: count every XLA "
                              "compile into a recompile_count gauge "
                              "(jax_log_compiles) and disallow implicit "
                              "host<->device transfers inside the train/"
                              "eval step dispatch — the dynamic half of "
                              "the graftlint static pass (python -m "
                              "distributed_pipeline_tpu.analysis); cheap "
                              "enough for CI runs")
    compilation_cache_dir: Literal["auto", "off"] = _(
        "auto", "persistent XLA compilation cache: 'auto' = the directory "
                "JAX_COMPILATION_CACHE_DIR names if it is set, else one "
                "fixed git-ignored directory in the checkout "
                "(.compile_cache — every run, restart and server start "
                "shares it); 'off' disables. To place it elsewhere set "
                "the variable")
    prefetch_depth: int = _(
        2, "device-side input prefetch depth: keep N batches already "
           "device_put onto the mesh (with the compiled step's sharding) "
           "while the current step runs, so the TPU never waits on the "
           "host transfer; 2 = classic double buffering, 0 disables "
           "(exact-resume data order is identical either way)")
    dispatch_lag: int = _(
        1, "async metrics dispatch: fetch/log step N-k's device scalars "
           "while step N dispatches instead of blocking on the step just "
           "enqueued; logged values are exact, just k steps late (flushed "
           "at eval/checkpoint/exit boundaries); 0 = eager")
    chaos_plan: str = _(
        "", "fault-injection schedule (chaos harness): inline JSON, "
            "@/path/to/plan.json, or a bare path — faults like "
            '{"kind": "kill", "step": N, "rank": R} / crash_in_save / '
            "stall_data / stall_step (wedge the step loop alive — the "
            "hang the launcher's --hang_timeout_s watchdog detects) / "
            "slow_rank (straggler: seconds delay per step through "
            "until_step — must NOT trip the watchdog) / "
            "corrupt_checkpoint fire at exact optimizer "
            "steps to prove the restart+resume stack survives them; the "
            "DPT_CHAOS_PLAN env var overrides (it reaches --config_json "
            "ring workers like DPT_PREFETCH_DEPTH does); empty disables")


class DataSettings(S):
    """Dataset selection (reference config/train.py:35-41)."""

    dataset: str = _("synthetic-seq2seq", "dataset name")
    data_dir: str = _("", "dataset directory (empty = synthetic data)")
    data_loader_workers: int = _(2, "host-side loader worker threads")


class ModelSettings(S):
    """Workload settings — fills the reference's ``YourSettings`` stub
    (config/train.py:44-46) with the concrete DiffuSeq/GPT-2 families."""

    model_family: Literal["diffuseq", "gpt2"] = _("diffuseq", "model family")
    model_size: Literal["base", "large", "xl", "medium"] = _("base", "preset size")
    vocab_size: int = _(8192, "vocabulary size")
    seq_len: int = _(128, "sequence length (source+target for seq2seq)")
    hidden_size: int = _(0, "override hidden size; 0 = use preset")
    num_layers: int = _(0, "override layer count; 0 = use preset")
    num_heads: int = _(0, "override head count; 0 = use preset")
    diffusion_steps: int = _(2000, "diffusion timesteps (diffuseq only)")
    noise_schedule: Literal["sqrt", "cosine", "linear"] = _(
        "sqrt", "diffusion noise schedule (diffuseq only)"
    )
    dtype: Literal["bfloat16", "float32"] = _("bfloat16", "activation/compute dtype")
    remat: bool = _(False, "rematerialize (jax.checkpoint) each block")
    attention_impl: Literal["auto", "xla", "pallas", "ring"] = _(
        "auto", "attention kernel: XLA dot-product, pallas flash, or ring (SP)"
    )
    moe_experts: int = _(0, "mixture-of-experts: expert count (0 = dense MLPs)")
    moe_top_k: int = _(2, "MoE router top-k")
    moe_every: int = _(2, "MoE replaces the MLP in every k-th block")
    moe_capacity_factor: float = _(
        1.25, "MoE expert capacity = ceil(L/E * factor * top_k) slots; "
        "tokens over capacity fall through on the residual path")
    scan_layers: bool = _(False, "stacked layer weights (lax.scan over "
                                 "blocks; enables pipeline parallelism and "
                                 "fast compiles for deep models)")
    pp_chunks: int = _(4, "GPipe microchunks per per-shard batch "
                          "(pipeline parallelism; bubble = (S-1)/(chunks+S-1))")
    scan_unroll: int = _(
        0, "scan_layers unroll factor: 0 auto-unrolls stacks of <= 16 "
           "layers fully (restores unrolled-graph fusion the scan backward "
           "loses; ~6x compile time) and keeps longer stacks as true "
           "scans; N forces a factor (1 or full recommended — partial "
           "factors measured pathological on TPU)")
    pp_schedule: Literal["1f1b", "gpipe", "interleaved"] = _(
        "1f1b", "pipeline training schedule: 1f1b streams each chunk's "
                "backward as soon as its forward clears the last stage "
                "(peak stash <= 2S-1 chunks, so pp_chunks can grow to "
                "shrink the bubble); interleaved additionally splits each "
                "device into pp_virtual non-contiguous stage slices, "
                "cutting the bubble ~Vx at the cost of V*min(M,3S) "
                "stashed chunks and a per-step weight permute; gpipe "
                "differentiates through the forward-only schedule "
                "(simpler, but activation residuals scale with pp_chunks)")
    pp_virtual: int = _(
        2, "virtual stage slices per device under "
           "--pp_schedule interleaved (bubble ~ (S-1)/(V*M+S-1); "
           "num_layers must divide by pipe * pp_virtual, pp_chunks by "
           "pipe)")


class MeshSettings(S):
    """Device-mesh axes — the TPU-native replacement for the reference's DDP
    process group (utils/trainer.py:115-128). Axis size -1 means "all
    remaining devices"; 1 disables the axis."""

    dp: int = _(-1, "data-parallel axis size (-1 = all remaining devices)")
    fsdp: int = _(1, "FSDP/zero param-sharding axis size")
    tensor: int = _(1, "tensor-parallel axis size")
    sequence: int = _(1, "sequence/context-parallel axis size (ring attention)")
    expert: int = _(1, "expert-parallel axis size (MoE expert sharding)")
    pipe: int = _(1, "pipeline-parallel axis size (GPipe stage streaming; "
                     "requires --scan_layers true)")
    shard_optimizer: bool = _(
        False, "ZeRO-1 cross-replica weight-update sharding: Adam moments "
               "and EMA copies sharded across the data mesh axis with "
               "gather-on-use inside the compiled train step — per-replica "
               "optimizer/EMA memory drops ~dp x at unchanged step math "
               "(params/grads keep their layout; checkpoints restore "
               "across the flag in either direction)")
    fused_update: str = _(
        "auto", "fused optimizer+EMA Pallas kernel (ops/fused_update.py): "
                "one pass per param leaf reads param/grad/mu/nu and writes "
                "param/mu/nu plus every EMA copy, replacing the staged "
                "optax chain that re-reads the tree once per state copy; "
                "losses bit-identical, opt_state structure unchanged "
                "(checkpoints and --shard_optimizer compose either way). "
                "auto (default) = fused on TPU, staged optax elsewhere "
                "(off-TPU the kernel only has interpreter mode, which is "
                "pure overhead); true/false force an arm")
    partition_rules: str = _(
        "", "override the model's parameter partition-rule table "
            "(parallel/partition.py): inline JSON, @/path.json, or a bare "
            "path — an ordered list of [path-regex, spec] pairs, spec a "
            "list of mesh-axis names / null / nested list (several axes "
            "on one dim), ending with an explicit catch-all ['.*', []]; "
            "a TUNER ARTIFACT (run/tune.py) is accepted verbatim — its "
            "rules always apply, and its mesh/ZeRO recommendations apply "
            "when the mesh flags are still at their defaults; empty = "
            "the model family's built-in table")
    auto_tune: bool = _(
        False, "run the sharding auto-tuner's SCREEN inline before "
               "training (tune/): rank 0 measures candidate rule tables "
               "x mesh splits for this exact model/shape/device set in "
               "child processes under --auto_tune_budget_s, writes the "
               "winner to <run_dir>/tune_artifact.json, and the run "
               "consumes it like --partition_rules (mesh/ZeRO "
               "recommendations apply only when those flags are still "
               "at their defaults); a restart attempt reuses the "
               "existing artifact instead of re-tuning; ignored when "
               "--partition_rules is set explicitly")
    auto_tune_budget_s: float = _(
        60.0, "wall-clock budget for the inline --auto_tune screen "
              "(candidates it cannot afford are skipped; the baseline "
              "table is measured first so a tiny budget degrades to "
              "the hand-tuned layout)")

    # --------------------------------------------------- MPMD (ISSUE 16)
    mpmd: bool = _(
        False, "MPMD pipeline training (mpmd/): each stage runs as its "
               "OWN supervised process ring with its own restart budget "
               "and snapshots (stages are independently preemptible), a "
               "jax-free host driver broadcasts the --pp_schedule "
               "microbatch schedule, and activations/grads move over the "
               "StageLink transport instead of a collective; requires "
               "--scan_layers true; the in-program mesh axes (dp/pipe/"
               "...) apply WITHIN each stage, so keep them 1/-1 defaults "
               "unless each stage really has a sub-mesh")
    mpmd_stages: int = _(2, "MPMD stage count (process rings); "
                            "num_layers need not divide it — stages take "
                            "floor-balanced layer slices")
    mpmd_link_capacity: int = _(8, "StageLink in-flight frame cap per "
                                   "direction (backpressure: a sender "
                                   "blocks past this and books the wait "
                                   "as link_wait)")
    mpmd_hang_timeout_s: float = _(0.0, "per-stage beacon watchdog: a "
                                        "stage whose beacons freeze this "
                                        "long is SIGKILLed and restarted "
                                        "by ITS OWN ring (0 = off)")
    mpmd_max_restarts: int = _(3, "per-stage restart budget (sliding "
                                  "window, launcher semantics)")


class TrainSettings(GeneralSettings, DataSettings, ModelSettings, MeshSettings):
    """Composed settings, flat like the reference's reverse-MRO composition
    (config/train.py:49-55): every field addressable as a top-level CLI flag."""

    @classmethod
    def to_argparse(cls, parser=None, add_json: bool = False, **kw):  # type: ignore[override]
        parser = super().to_argparse(parser, **kw)
        if add_json:
            parser.add_argument(
                "--config_json",
                default=None,
                help="JSON config file; mutually exclusive with individual flags "
                "(overrides the entire CLI, reference config/train.py:57-68)",
            )
        return parser

    @classmethod
    def from_argparse(cls, namespace: argparse.Namespace, _consume: bool = True):  # type: ignore[override]
        parsed_argv = vars(namespace).pop("_parsed_argv", "absent")
        config_json = vars(namespace).pop("config_json", None)
        if config_json:
            # True mutual exclusivity (reference's mutually-exclusive group,
            # config/train.py:63-67): a flag explicitly set to its default
            # value still conflicts. Only an argv explicitly recorded on the
            # namespace (by from_argv / parse_and_autorun) is inspected —
            # never the hosting process's sys.argv, whose flags may belong
            # to a wrapper script, not this parse. Programmatic namespaces
            # without a recorded argv fall back to value-vs-default drift.
            import sys
            if parsed_argv == "absent" or parsed_argv is None:
                argv = []
            else:
                argv = parsed_argv
            fields = set(cls.model_fields)
            explicit = sorted({
                tok.split("=")[0].lstrip("-") for tok in argv
                if tok.startswith("--")
                and tok.split("=")[0].lstrip("-") in fields})
            defaults = cls()
            drifted = [
                k for k, v in vars(namespace).items()
                if hasattr(defaults, k) and getattr(defaults, k) != v
            ]
            overridden = sorted(set(explicit) | set(drifted))
            if overridden:
                raise SystemExit(
                    f"--config_json is mutually exclusive with individual flags "
                    f"(got: {', '.join('--' + k for k in overridden)})"
                )
            return cls.parse_file(config_json)
        return super().from_argparse(namespace, _consume=_consume)


class YourSettings(S):
    """Kept for reference-API familiarity (config/train.py:44-46); the real
    workload settings live in :class:`ModelSettings`/:class:`MeshSettings`."""


if __name__ == "__main__":
    # Reference README.md:18-21 one-liner equivalent: dump default config JSON.
    print(TrainSettings().to_json())
