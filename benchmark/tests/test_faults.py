"""The rest of a run with the timed path broken underneath: ``correct`` must
come out false. The harness's look for a chip is skipped (``--rehearse``: tiny
widths on the CPU, the cell's own limits); everything else is a run as the
driver makes it. One fault a case; a sound run passes beside them.

Also the control of "How correct is decided", kept at a size a test run can
hold: the reference computed in the precision below the configuration's must
fail the cell's limits where the reference in the configuration's own passes.
"""

import contextlib
import io
import json

import pytest

import run as bench_run

TRAIN_CELL = "gpt2-base.train.seq1024"
SERVE_CELL = "gpt2-large.serve.closed16"


def one_run(cell, *extra):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                             "--seconds", "1", "--rehearse", *extra])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp
    from distributed_pipeline_tpu.utils.trainer import TrainLoop
    orig = TrainLoop.run_step

    def run_step(self, batch):
        saved = jax.tree_util.tree_map(jnp.copy, self.state)
        metrics = orig(self, batch)
        self.state = saved
        return metrics
    monkeypatch.setattr(TrainLoop, "run_step", run_step)


def half_batch_left_out(monkeypatch):
    """Also what a data-parallel step computes on each replica when the
    exchange between chips is left out: the mean over its own rows."""
    import dataclasses
    import jax.numpy as jnp
    from distributed_pipeline_tpu.utils.trainer import TrainLoop
    orig = TrainLoop.run_step

    def run_step(self, batch):
        # rows of the second half replaced by the first half's: the mean is
        # taken over half of the batch
        def fold(a):
            flat = a.reshape((-1,) + a.shape[2:])
            half = flat[:flat.shape[0] // 2]
            return jnp.concatenate([half, half]).reshape(a.shape)
        with self.mesh:
            arrays = {k: fold(v) for k, v in batch.arrays.items()}
        return orig(self, dataclasses.replace(batch, arrays=arrays))
    monkeypatch.setattr(TrainLoop, "run_step", run_step)


def token_altered(monkeypatch):
    from distributed_pipeline_tpu.serving.engine import DecodeEngine
    orig = DecodeEngine.decode

    def decode(self):
        return orig(self) + 1      # what is fetched, not what is fed back
    monkeypatch.setattr(DecodeEngine, "decode", decode)


def drive(cell, traffic_over, devices, seconds=1.0):
    """The cell's driver at the rehearsal's size with its traffic laid
    over: the paths a later PR reaches with data files alone (a mesh over
    several chips, open-loop arrivals), walked here without a cell."""
    import jax
    from harness import driver_for
    from harness.rehearse import overlay, shrink
    found = bench_run.resolve_cell(cell)
    cfg, traffic = shrink(found["config"], found["traffic"])
    traffic = overlay(traffic, traffic_over)
    return driver_for(traffic).run(
        found["cell"], cfg, traffic, seed=2147483659, seconds=seconds,
        trace=False, devices=jax.devices()[:devices],
        t_process=bench_run.T_PROCESS, annotate=bench_run.annotate,
        profiler=bench_run.Profiler())


MESH4 = {"mesh": {"data": 2, "fsdp": 2}, "global_batch": 16, "microbatch": 8,
         "corpus": {"n_train_lines": 128}}


def test_a_sound_train_run_is_correct():
    got = one_run(TRAIN_CELL)
    assert got["correct"] is True, got["checks"]
    assert got["metrics"] == {} and got["rehearsal"] is True
    assert got["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out],
                         ids=lambda f: f.__name__)
def test_a_broken_train_step_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    got = one_run(TRAIN_CELL)
    assert got["correct"] is False, got["checks"]
    failed = [k for k, row in got["checks"].items() if not row["ok"]]
    assert set(failed) & {"loss_gap", "grad_norm_gap", "delta_norm_gap"}


@pytest.mark.parametrize("fault", [None, half_batch_left_out],
                         ids=["sound", "half_batch_left_out"])
def test_the_sharded_step_over_four_devices(monkeypatch, fault):
    """data=2 x fsdp=2 through the same driver: a sound run passes, and
    half of the batch left out (what each replica computes when the exchange
    between chips is left out) does not."""
    if fault is not None:
        fault(monkeypatch)
    checks = drive(TRAIN_CELL, MESH4, devices=4)["checks"]
    assert all(row["ok"] for row in checks.values()) is (fault is None), checks


def test_a_sound_serve_run_is_correct():
    got = one_run(SERVE_CELL)
    assert got["correct"] is True, got["checks"]
    assert got["failed"] == 0 and got["attempted"] > 0


def test_an_altered_token_is_not_correct(monkeypatch):
    token_altered(monkeypatch)
    got = one_run(SERVE_CELL)
    assert got["correct"] is False, got["checks"]
    assert not got["checks"]["served_logit_gap"]["ok"]


@pytest.mark.parametrize("arrivals", [
    {"process": "poisson", "rate_rps": 12.0},
    {"process": "bursty", "burst_size": 6, "burst_every_s": 0.5}],
    ids=lambda a: a["process"])
def test_open_loop_arrivals_through_the_same_driver(arrivals):
    """An open-loop mix is data: ``loop``, ``arrivals``, ``warm_seconds``."""
    got = drive(SERVE_CELL, {"loop": "open", "arrivals": arrivals,
                             "warm_seconds": 0.5}, devices=1, seconds=2.0)
    assert all(row["ok"] for row in got["checks"].values()), got["checks"]
    assert got["attempted"] >= 6 and got["failed"] == 0
    e2e = got["end_to_end"]
    assert e2e["ttft_p95_ms"] > 0 and e2e["tpot_p95_ms"] > 0
