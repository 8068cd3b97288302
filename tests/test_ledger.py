"""Cost ledger + roofline attribution (ISSUE 14).

Pins the evidence layer the perf front reads from: cost_analysis/
memory_analysis extraction off CPU-compiled programs, the HLO collective
tally against a hand-counted forced-host dp=2 program, the exact
mfu-plus-gaps-equals-one identity, the ledger-vs-goodput seconds
identity (the ledger reuses the trainer's OWN stall sums — same object,
exact equality), padding-waste arithmetic on both the train and serve
sides, perf_report CLI end-to-end, graftlint GL010, and the
status/export ledger surfaces.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_pipeline_tpu.obs import ledger as ledger_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- HLO tally

def test_hlo_collective_tally_hand_counted_text():
    """Literal HLO text with every op class: single shapes, the async
    -start form (whose result TUPLE leads with the aliased input
    operand — only the result element counts, so sync and async forms
    of the same collective tally identical bytes), its -done twin (not
    counted — it moves no new bytes), and a non-collective line."""
    hlo = "\n".join([
        "%x = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)",
        "ROOT %ar = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=1",
        # async all-gather: tuple = (input operand, gathered result)
        "%ag = (f32[2,2]{1,0}, f32[4,2]{1,0}) all-gather-start("
        "f32[2,2]{1,0} %p), dimensions={0}",
        "%agd = f32[4,2]{1,0} all-gather-done(%ag)",
        "%rs = bf16[16]{0} reduce-scatter(bf16[32]{0} %y), dimensions={0}",
        # async permute with trailing context elements: still only the
        # result element (position n_operands) counts
        "%cp = (u8[5]{0}, u8[5]{0}, u32[], u32[]) "
        "collective-permute-start(u8[5]{0} %z)",
    ])
    t = ledger_lib.hlo_collective_tally(hlo)
    assert t["counts"] == {"all-reduce": 1, "all-gather": 1,
                           "reduce-scatter": 1, "collective-permute": 1}
    assert t["bytes"]["all-reduce"] == 8 * 4
    assert t["bytes"]["all-gather"] == 4 * 2 * 4  # result only, not the
    # aliased input — the sync form of this op would tally the same
    assert t["bytes"]["reduce-scatter"] == 16 * 2         # bf16
    assert t["bytes"]["collective-permute"] == 5          # u8 result
    assert t["collective_bytes"] == sum(t["bytes"].values())


def test_collective_tally_matches_hand_count_on_real_dp2_program():
    """A compiled program with exactly ONE all-reduce of known shape
    (a [4, 8] f32 sharded over 2 of the forced host devices, summed
    over the sharded axis to a replicated [8]): the tally must report
    exactly 1 x 32 bytes — hand-counted, not pattern-matched."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_pipeline_tpu.parallel.partition import (
        resolve_shardings)
    from distributed_pipeline_tpu.parallel.sharding import replicated

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rep = replicated(mesh)
    dshard = resolve_shardings(
        mesh, P("data"), jax.ShapeDtypeStruct((4, 8), jnp.float32))

    def f(x):
        return jax.lax.with_sharding_constraint((x * 2.0).sum(axis=0), rep)

    x = jax.device_put(jnp.ones((4, 8), jnp.float32), dshard)
    compiled = jax.jit(f).lower(x).compile()
    cost = ledger_lib.extract_cost(compiled)
    assert cost["collectives"]["counts"] == {"all-reduce": 1}
    assert cost["collective_bytes_per_step"] == 8 * 4


def test_extract_cost_fields_on_cpu_compiled_program():
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: (x @ x.T).sum()).lower(
        jnp.ones((16, 16), jnp.float32)).compile()
    cost = ledger_lib.extract_cost(compiled)
    assert cost["flops_per_execution"] > 0
    assert cost["bytes_accessed"] > 0
    assert cost["memory"]["argument_bytes"] == 16 * 16 * 4
    assert cost["collective_bytes_per_step"] == 0  # single-device program


def test_extract_cost_never_raises_on_hostile_object():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            raise RuntimeError("no")

        def as_text(self):
            raise RuntimeError("no")

    assert ledger_lib.extract_cost(Broken()) == {}


# ------------------------------------------------------ roofline identity

def _ident(row):
    return abs(ledger_lib.gap_sum_identity(row) - 1.0)


def test_roofline_identity_holds_and_components_cap_in_order():
    row = ledger_lib.roofline_attribution(
        tokens_per_s=1e4, flops_per_token=3e5, peak_flops=1e11,
        n_devices=1, steps_per_s=30.0, collective_bytes_per_step=4e5,
        bytes_accessed=8e7, host_stall_s_per_step=0.002,
        device_kind="cpu", padding_waste_frac=0.2)
    assert _ident(row) < 1e-9
    assert 0 < row["mfu"] < 1
    assert all(row[k] >= 0 for k in ledger_lib.GAP_TERMS)
    # host stalls bigger than the whole step: host caps AT the gap and
    # every later (less-trusted) component is squeezed to zero
    capped = ledger_lib.roofline_attribution(
        tokens_per_s=1e4, flops_per_token=3e5, peak_flops=1e11,
        n_devices=1, steps_per_s=30.0, collective_bytes_per_step=1e12,
        bytes_accessed=1e12, host_stall_s_per_step=10.0)
    assert _ident(capped) < 1e-9
    assert capped["mfu_gap_host"] == pytest.approx(1.0 - capped["mfu"])
    assert capped["mfu_gap_comms"] == capped["mfu_gap_memory_bound"] == \
        capped["mfu_gap_residual"] == 0.0


def test_roofline_without_a_step_clock_reports_unattributed():
    """No steps/s -> no modeled component can be estimated: the whole
    gap lands in the residual (reported unattributed, never invented)."""
    row = ledger_lib.roofline_attribution(
        tokens_per_s=0.0, flops_per_token=3e5, peak_flops=1e11,
        n_devices=1, collective_bytes_per_step=4e5, bytes_accessed=8e7,
        padding_waste_frac=2.5)  # clamped too
    assert _ident(row) < 1e-9
    assert row["mfu"] == 0.0 and row["mfu_gap_residual"] == 1.0
    assert row["padding_waste_frac"] == 1.0


def test_padding_meter_arithmetic():
    m = ledger_lib.PaddingMeter()
    assert m.frac == 0.0  # no samples: no waste claimed
    m.add(6, 8)
    m.add(2, 8)
    assert m.frac == pytest.approx(1.0 - 8 / 16)


def test_device_bandwidths_match_known_kinds():
    assert ledger_lib.device_bandwidths("TPU v5 lite")["hbm_bytes_per_s"] \
        == 8.1e11
    assert ledger_lib.device_bandwidths("cpu")["ici_bytes_per_s"] == 1e10
    # an unknown kind is an error, never another chip's numbers
    with pytest.raises(ValueError, match="(?i)tpu v9x"):
        ledger_lib.device_bandwidths("TPU v9x")
    import types

    from distributed_pipeline_tpu.utils import perf
    fake = types.SimpleNamespace(device_kind="TPU v9x", platform="tpu")
    with pytest.raises(ValueError, match="(?i)tpu v9x"):
        perf.device_peak_flops(fake)
    assert perf.device_peak_flops(types.SimpleNamespace(
        device_kind="TPU v5 lite", platform="tpu")) == 197e12


# ------------------------------------------- trainer ledger + goodput tie

@pytest.fixture(scope="module")
def ledger_run(tmp_path_factory):
    """One tiny --cost_ledger training run (real run_loop, real
    perf_ledger.json on disk) shared by the trainer-side tests."""
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils import logger
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    run_dir = str(tmp_path_factory.mktemp("ledger_run"))
    wl = create_model_from_config(
        model_family="diffuseq", vocab_size=64, seq_len=32, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32", diffusion_steps=50)
    data = load_data_from_args(
        "train", batch_size=8, dataset="synthetic-seq2seq", seq_len=32,
        vocab_size=64, seed=0)
    loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                     ema_rate="0.9", learning_steps=5, log_interval=2,
                     save_interval=10 ** 9, mesh=make_mesh(dp=-1),
                     checkpoint_dir=run_dir, seed=0, cost_ledger=True,
                     dispatch_lag=1)
    with logger.scoped_configure(dir=run_dir, format_strs=[]):
        loop.run_loop()
    return loop, run_dir


def test_trainloop_ledger_row_is_populated(ledger_run):
    loop, _ = ledger_run
    rows = loop.ledger_rows()
    tr = rows["train_step"]
    assert tr["flops_per_execution"] > 0
    assert tr["bytes_accessed"] > 0
    # the 8-fake-device dp mesh really emits gradient collectives
    assert tr["collective_bytes_per_step"] > 0
    assert tr["collectives"]["counts"].get("all-reduce", 0) > 0
    # synthetic-seq2seq pads to seq_len: real waste, strictly inside (0,1)
    assert 0 < tr["padding_waste_frac"] < 1
    assert tr["tokens_per_s"] > 0 and tr["steps_per_s"] > 0
    assert _ident(tr) < 1e-9


def test_ledger_and_goodput_report_the_same_seconds(ledger_run):
    """The ledger's data-stall total is the SAME expression the goodput
    summary folds (one owner: StallBreakdown.sums) — exact equality,
    not approx: the two ledgers can never disagree."""
    loop, _ = ledger_run
    tr = loop.ledger_rows()["train_step"]
    assert tr["data_stall_s_total"] == \
        loop.goodput_summary()["data_stall_s"]


def test_padding_waste_matches_the_masks_the_data_carried(ledger_run):
    """The meter's fraction is exactly 1 - sum(pad_mask)/size over every
    batch _prepare saw."""
    loop, _ = ledger_run
    from distributed_pipeline_tpu.data import load_data_from_args

    data = load_data_from_args(
        "train", batch_size=8, dataset="synthetic-seq2seq", seq_len=32,
        vocab_size=64, seed=0)
    active = total = 0
    for _ in range(loop.step):
        b = next(data)
        active += int(b["pad_mask"].sum())
        total += int(b["pad_mask"].size)
    assert loop.padding.frac == pytest.approx(1.0 - active / total)


def test_perf_ledger_snapshot_written_and_readable(ledger_run):
    _, run_dir = ledger_run
    payload = ledger_lib.read_ledger(run_dir)
    assert payload is not None
    assert payload["step"] == 5
    tr = payload["programs"]["train_step"]
    assert _ident(tr) < 1e-6
    assert "collective_bytes_per_step" in tr


def test_perf_report_cli_end_to_end(ledger_run, tmp_path):
    _, run_dir = ledger_run
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "distributed_pipeline_tpu.run.perf_report",
         run_dir], capture_output=True, text=True, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr
    summary = json.loads(p.stdout.strip().splitlines()[-1])
    assert summary["identity_residuals"]["train_step"] < 1e-6
    assert "[train_step]" in p.stderr and "residual" in p.stderr
    # a dir without a ledger exits 2 (a typo'd path must not read as
    # "no gaps")
    p2 = subprocess.run(
        [sys.executable, "-m", "distributed_pipeline_tpu.run.perf_report",
         str(tmp_path)], capture_output=True, text=True, env=env, cwd=REPO)
    assert p2.returncode == 2


def test_status_and_prometheus_surface_the_ledger(ledger_run):
    from distributed_pipeline_tpu.obs import export as export_lib
    from distributed_pipeline_tpu.run.status import render, run_status

    _, run_dir = ledger_run
    snap = run_status(run_dir)
    assert snap["mfu"] is not None
    assert set(snap["mfu_gaps"]) == set(ledger_lib.GAP_TERMS)
    assert "mfu:" in render(snap)
    lines = export_lib.prometheus_lines(run_dir)
    assert any(l.startswith('dpt_mfu{') for l in lines)
    assert any('component="residual"' in l for l in lines)


def test_export_emits_roofline_counter_track(ledger_run):
    from distributed_pipeline_tpu.obs import export as export_lib

    _, run_dir = ledger_run
    trace = export_lib.chrome_trace(run_dir)
    counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
    roof = [c for c in counters if c["name"] == "roofline train_step"]
    assert roof, "perf_ledger.json must export as a counter track"
    args = roof[0]["args"]
    assert set(ledger_lib.GAP_TERMS) <= set(args)
    assert all(isinstance(v, float) for v in args.values())


# --------------------------------------------------------- serving ledger

def test_serving_ledger_rows_and_padding_hand_count():
    import jax
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.serving import DecodeServer

    wl = create_model_from_config(
        model_family="gpt2", model_size="base", seq_len=64,
        dtype="float32", hidden_size=32, num_layers=2, num_heads=2,
        vocab_size=64)
    params = wl.init_params(jax.random.PRNGKey(0))
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=32)
    rng = np.random.default_rng(0)
    for _ in range(3):
        server.submit(rng.integers(4, 64, (5,)).astype(np.int32),
                      max_new_tokens=6)
    server.drain()
    rows = server.cost_ledger(wall_s=1.0, n_devices=1)
    dec, pre = rows["serve_decode"], rows["serve_prefill"]
    assert _ident(dec) < 1e-9
    assert dec["tokens_per_s"] == server.tokens_fetched  # wall_s=1.0
    assert dec["flops_per_execution"] > 0
    # hand count: 3 prompts of 5 tokens over 2 slots -> 2 prefill
    # dispatches at the compiled [2, 8] shape = 32 token slots, 15 real
    assert server.prefill_steps == 2
    assert pre["padding_waste_frac"] == pytest.approx(1 - 15 / 32)
    # decode occupancy waste: dispatches with one empty slot accrue it
    assert 0 <= dec["padding_waste_frac"] < 1
    # what the engine holds of its tree (a float32 model: as given)
    from distributed_pipeline_tpu.run.perf_report import render
    assert rows["weights"] == server.engine.weights
    assert rows["weights"]["leaves_cast"] == 0
    assert "0 leaves cast" in render({"programs": rows})


# ----------------------------------------------------------------- GL010

def test_gl010_flags_inline_flops_and_spares_the_owners(tmp_path):
    from distributed_pipeline_tpu.analysis import run_paths

    pos = tmp_path / "pos.py"
    pos.write_text(
        "def f(n, l, h, s, tps):\n"
        "    fpt = 6.0 * n + 12.0 * l * h * s\n"
        "    mfu = tps * fpt / (1e12 * 8)\n"
        "    return {'model_flops': n * 6}, mfu\n")
    neg = tmp_path / "neg.py"
    neg.write_text(
        "from distributed_pipeline_tpu.utils.perf import (\n"
        "    mfu, transformer_train_flops_per_token)\n\n"
        "def f(n, l, h, s, tps):\n"
        "    fpt = transformer_train_flops_per_token(n, l, h, s)\n"
        "    return {'mfu': round(mfu(tps, fpt), 4), 'fpt': fpt}\n")
    findings, n = run_paths([str(pos), str(neg)])
    gl010 = [f for f in findings if f.rule == "GL010-unattributed-flops"]
    assert n == 2
    assert len(gl010) == 3
    assert all(f.path.endswith("pos.py") for f in gl010)
