"""Auto-tuner settings (``run/tune.py``).

Same declarative surface as training/serving: every field is a
``--flag``, round-trips through JSON, documents itself in ``--help``.
The knobs mirror the tuner's layers — the model/shape under tune, the
search space (mesh axes, rule-table mutations, ZeRO toggle), the
measurement geometry (screen window, ABBA finals), and the wall-clock
budget + journal/artifact locations.
"""

from __future__ import annotations

from typing import Literal

from .base import ArgparseCompatibleBaseModel as S
from .base import item as _


class TuneSettings(S):
    """Profile-guided layout search for a model/shape on a device set."""

    family: str = _("diffuseq", "model families to tune, comma-separated "
                                "(e.g. 'diffuseq,gpt2'): each family runs "
                                "its own search into the shared journal "
                                "and emits its own artifact")
    model_size: str = _("base", "preset size")
    seq_len: int = _(128, "sequence length")
    vocab_size: int = _(8192, "vocabulary size")
    hidden_size: int = _(0, "override hidden size; 0 = preset")
    num_layers: int = _(0, "override layer count; 0 = preset")
    num_heads: int = _(0, "override head count; 0 = preset")
    dtype: Literal["bfloat16", "float32"] = _("float32",
                                              "activation/compute dtype")
    batch_size: int = _(8, "per-host batch size measured")
    microbatch: int = _(0, "microbatch per optimizer step; 0 = batch")

    n_devices: int = _(0, "device count to tune for: 0 = all visible "
                          "devices; off-TPU the measurement children are "
                          "FORCED to this many host CPU devices "
                          "(xla_force_host_platform_device_count), so a "
                          "one-core box still tunes a dp=2 mesh")
    axes: str = _("data,fsdp,tensor", "mesh axes the search factorizes "
                                      "the device count over (sequence/"
                                      "expert/pipe change step semantics "
                                      "and stay out of the default space)")
    include_zero1: bool = _(True, "search the --shard_optimizer (ZeRO-1) "
                                  "toggle per candidate (only where the "
                                  "data axis is > 1)")
    max_candidates: int = _(0, "cap the enumerated candidate list "
                               "(baseline-first, so the hand-tuned "
                               "reference always survives the cap); "
                               "0 = no cap")

    peak_bytes_ceiling: float = _(
        0.0, "memory-headroom objective (ISSUE 14 satellite; the r15 "
             "NOTE's unwired ranking input): candidates whose measured "
             "peak_live_bytes exceed this ceiling are RANKED OUT — "
             "journaled as over_ceiling with accounting still closed "
             "(measured + pruned + rejected + skipped + over_ceiling == "
             "enumerated) and never a winner. 0 disables. The xl "
             "presets' path onto bigger meshes: the fastest layout that "
             "does not fit is not a layout")
    budget_s: float = _(240.0, "wall-clock budget for the whole tune: "
                               "candidates the budget cannot afford are "
                               "journaled as skipped and the ranking "
                               "proceeds on what WAS measured")
    screen_steps: int = _(4, "timed steps per screen (rung-0) trial; "
                             "halving rungs double it")
    warmup_steps: int = _(2, "child warmup steps before the timed window "
                             "(the first pays the compile)")
    final_rounds: int = _(6, "ABBA rounds for the top-2 final (forced "
                             "even: position balance)")
    final_window_steps: int = _(4, "steps per ABBA window in the final")
    screen_only: bool = _(False, "stop after the screen rung (no halving "
                                 "or finals): the cheap mode --auto_tune "
                                 "runs")
    child_timeout_s: float = _(150.0, "hard cap per measurement child; a "
                                      "wedged candidate folds to a pruned "
                                      "row at this deadline")

    out_dir: str = _("model_checkpoints/tune", "journal + artifact "
                                               "directory")
    resume: bool = _(True, "replay completed trials from an existing "
                           "tune_trials.jsonl instead of re-measuring "
                           "them (an interrupted tune continues); false "
                           "wipes the journal first")
    trace: bool = _(False, "span tracing (obs/): book one span per trial "
                           "into trace_tune.jsonl in out_dir, exportable "
                           "to the Perfetto timeline (DPT_TRACE arms it "
                           "too); journaled trials also export without "
                           "tracing, from tune_trials.jsonl itself")
    seed: int = _(0, "measurement seed (the children's data/init seed)")
