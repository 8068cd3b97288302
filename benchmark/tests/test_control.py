"""The control of "How correct is decided", kept at a size a test run can
hold: the plain reference put in the program's place and computed in the
precision below the configuration's must come out NOT correct under the
cells' own limits, where the same reference in the configuration's own
precision (bfloat16) comes out correct.

On the chip the control was read at the cells' own sizes (PERF.md section 2
gives the readings each limit was set from); this keeps the mechanism from
rotting."""

import json
import os

import jax
import numpy as np
import pytest

from harness import reference_gpt2 as ref
from harness.train_driver import check_train, hyper

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(n_embd=256, n_layer=4, n_head=4, vocab_size=2048, n_positions=256,
           layer_norm_epsilon=1e-5, initializer_range=0.02)


def traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda s: ref.make_weights(CFG, s))(ref.seed_arg(7))


@pytest.fixture(scope="module")
def train_readings(weights):
    rng = np.random.default_rng(7)
    L = CFG["n_positions"]
    # skewed unigrams, as the benchmark's corpus has them
    p = 1.0 / np.arange(1, 301)
    p /= p.sum()
    batches = []
    for _ in range(3):
        ids = rng.choice(300, (8, L), p=p).astype(np.int32) + 4
        mask = np.zeros((8, L), np.int32)
        mask[:, L // 2:] = 1
        batches.append({"input_ids": ids, "input_mask": mask,
                        "pad_mask": np.ones((8, L), np.int32)})
    hp = hyper(traffic("train-packed-1k"))
    return {prec: ref.train_steps(weights, CFG, batches, hp,
                                  rows_per_block=4, precision=prec)
            for prec in ("float32", "bfloat16", "fp8")}


def test_train_control_is_not_correct(train_readings):
    limits = traffic("train-packed-1k")["limits"]
    want = train_readings["float32"]
    own = check_train(train_readings["bfloat16"], want, limits)
    low = check_train(train_readings["fp8"], want, limits)
    assert all(r["ok"] for r in own.values()), own
    assert not all(r["ok"] for r in low.values()), low


def test_serve_control_is_not_correct():
    """Deeper and wider than the train test: the gap grows with depth and
    width, and the limit was set at 36 layers of 1280."""
    cfg = dict(CFG, n_embd=768, n_layer=16, n_head=12, vocab_size=8192)
    weights = jax.jit(lambda s: ref.make_weights(cfg, s))(ref.seed_arg(7))
    limit = traffic("serve-closed-chat")["limits"]["served_logit_gap"]
    rng = np.random.default_rng(11)
    fwd = ref.make_logits_fn(cfg)
    own, low = 0.0, {"fp8": float("inf"), "int8": float("inf")}
    for _ in range(3):
        ids = rng.integers(4, cfg["vocab_size"], (256,)).astype(np.int32)
        own = max(own, ref.served_gaps(weights, cfg, ids, 64,
                                       precision_pick="bfloat16",
                                       fwd=fwd).max())
        for prec in low:
            low[prec] = min(low[prec], ref.served_gaps(
                weights, cfg, ids, 64, precision_pick=prec, fwd=fwd).max())
    assert own <= limit, (own, limit)
    assert all(v > limit for v in low.values()), (low, limit)
