"""Benchmark: training throughput on the available hardware, per BASELINE.md
config shape — as a STREAMING, BUDGET-AWARE harness.

Output contract (the driver parses stdout, humans watch stderr):

* stdout carries ONE machine-readable JSON line, printed at the end of every
  run — including budget-truncated ones:
    {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": ...,
     "configs": [...per-leg results, with {"name": ..., "skipped": "budget"}
     markers for legs the wall-clock budget dropped...]}
* every completed leg is ALSO (a) appended immediately to a JSONL artifact
  (``BENCH_ARTIFACT``, default ``bench_legs.jsonl``) and (b) echoed to stderr
  as it finishes — so a timeout can no longer destroy the whole run's signal
  (the r5 failure mode: rc=124 after 12 legs of work, zero numbers captured).

Budget: ``BENCH_BUDGET_S`` (seconds, default 480 — sized to sit inside the
driver's timeout with headroom). The headline leg always runs; before each
later leg the elapsed wall clock is checked and remaining legs are skipped
with explicit markers once the budget is spent. Legs run headline-first so a
truncated run always contains the north star.

Three layers make ``parsed: null`` impossible (the BENCH_r05 regression —
rc=124 with ZERO rows because the run wedged inside a leg):

1. per-leg HARD CAP: every leg runs under a SIGALRM deadline
   (``BENCH_LEG_BUDGET_S``, default 240, further clamped to the remaining
   budget; the headline leg gets max(80% of the whole budget, 120s) — it is
   exempt from the budget SKIP but not from a wedge cap). A leg that
   overruns becomes an ``error`` row, not a hung process.
2. SIGTERM net: the driver's soft kill is caught, remaining legs are
   marked skipped, and the final JSON still prints.
3. watchdog thread: if the main thread is wedged in native code (where a
   Python signal handler cannot run — a stuck compile or a wedged remote
   chip), a daemon watchdog prints the final JSON from the completed rows
   at budget+60s and exits 3.

Steady-state A/B (ISSUE 5): the headline (prefetch OFF) is immediately
followed by a PAIRED A/B leg at the same config — an OFF loop and an ON
loop (device prefetch + async metrics dispatch,
``BENCH_PREFETCH_DEPTH``/``BENCH_DISPATCH_LAG``, defaults 2/1) both kept
alive while short timed windows interleave between them, order
alternating each round. Sequential legs measure the box as much as the
code (a shared host's steady-state rate drifts enough to flip the delta
sign run to run); interleaving hits both arms with the same drift, and
the ``prefetch-ab-delta`` row reports the position-balanced totals ratio
(ABBA ordering cancels the measured second-window position cost). Every
train row carries ``steps_per_s`` plus the four stall-breakdown gauges
(``data_wait_s``/``h2d_wait_s``/``dispatch_s``/``device_step_s``, mean
seconds per step over the timed window) and the HBM/params footprint
columns (``params_bytes``/``opt_state_bytes``/
``opt_state_bytes_per_replica``/``peak_live_bytes``, ISSUE 9).

ZeRO-1 A/B (ISSUE 9): ``diffuseq-base-seq128-zero1`` runs the same
paired-interleaved protocol between ``--shard_optimizer`` ON and OFF in a
child process with a >= 2-way data axis (run/zero1_ab.py); the
``zero1-ab-delta`` row reports steps/s parity plus the ~dp x per-replica
optimizer-bytes drop.

Auto-tuner leg (ISSUE 13): ``diffuseq-base-seq128-tune`` runs a
screen-only budgeted layout search (rule tables x mesh splits, tune/) on
the forced-host dp=2 CPU mesh and passes only if the tuner reproduces or
beats the hand-tuned table's steps/s within the +-3% band with every
enumerated candidate accounted (completed + pruned + rejected + skipped
== enumerated). Child spawn/env/timeout folding for BOTH child legs is
owned by tune/measure.py.

``BENCH_ONLY`` selects legs by EXACT name, or by glob when it contains a
wildcard (``diffuseq-base-seq128*`` = the old substring behavior).

Compile cost is first-class: the persistent XLA compilation cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's one fixed
``.compile_cache`` — utils/perf.py owns the rule, train and serve share
the directory) makes repeat runs near-compile-free, and every
train leg reports its compile-vs-steady-state split (``compile_s``,
``first_step_s`` vs the steady timed window).

The headline config is BASELINE.md's north star (DiffuSeq-base, seq_len=128,
bf16) WITH the reference's default microbatch-64 gradient accumulation (ref
config/train.py:11-12 — also the measured v5e optimum); the ``configs`` list
covers the other single-chip-benchable BASELINE shapes plus the
exceeds-feature legs (MoE, scan_layers, long-context flash, KV-cache decode).
The reference publishes no absolute numbers (BASELINE.md), so ``vs_baseline``
reports achieved MFU / the 40% MFU target from /root/repo/BASELINE.json.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import sys
import threading
import time


def select_legs(legs, only):
    """``BENCH_ONLY`` leg filter: EXACT name match, or an fnmatch glob
    when the pattern contains a wildcard (``*``/``?``/``[``). The old
    substring filter made ``BENCH_ONLY=diffuseq-base-seq128`` run seven
    legs — chaos and the A/B twins included — when the point of the knob
    is iterating on ONE leg; ``diffuseq-base-seq128*`` now spells the
    old family-wide behavior explicitly."""
    if not only:
        return list(legs)
    import fnmatch

    if any(c in only for c in "*?["):
        return [(n, f) for n, f in legs if fnmatch.fnmatchcase(n, only)]
    return [(n, f) for n, f in legs if n == only]


class LegTimeout(Exception):
    """A leg overran its SIGALRM hard cap."""


class BenchInterrupted(Exception):
    """The driver sent SIGTERM (its soft kill before SIGKILL)."""


def _run_capped(thunk, cap_s: float):
    """Run one leg under a SIGALRM deadline. Raises LegTimeout on overrun
    so the leg becomes an error row instead of a hung process. (A native
    call that never returns to the interpreter can still outlive this —
    the watchdog thread is the terminal backstop for that case.)"""

    def _on_alarm(signum, frame):
        raise LegTimeout(f"leg exceeded its {cap_s:.0f}s hard cap")

    unset = object()
    row = unset
    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, max(cap_s, 1.0))
    try:
        try:
            try:
                row = thunk()
            finally:
                # cleared the instant the call ends — success OR error —
                # so a late alarm can neither land in the caller's
                # cleanup nor replace a real exception mid-unwind
                signal.setitimer(signal.ITIMER_REAL, 0.0)
        except LegTimeout:
            if row is not unset:
                # The alarm fired in the gap between the leg completing
                # and the itimer being cleared: the row is fully computed
                # — keep it instead of discarding a finished leg.
                return row
            raise
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)
    return row


def main() -> None:
    t_bench0 = time.perf_counter()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "480"))
    leg_budget_s = float(os.environ.get("BENCH_LEG_BUDGET_S", "240"))
    artifact_path = os.environ.get("BENCH_ARTIFACT", "bench_legs.jsonl")

    import jax

    from distributed_pipeline_tpu.utils import logger
    # stdout is the ONE machine-readable JSON line: silence the logger's
    # sinks (the default logger would print "Logging to ..." on first use).
    logger.configure(format_strs=[])

    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.obs import ledger as ledger_lib
    from distributed_pipeline_tpu.utils.perf import (
        active_param_count,
        enable_persistent_compilation_cache,
        mfu,
        transformer_train_flops_per_token,
    )
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    # Persistent compilation cache, stable across bench invocations AND
    # rounds: leg k of run n+1 reuses leg k of run n's XLA compile.
    cache_dir = enable_persistent_compilation_cache()
    if cache_dir:
        print(f"# compilation cache: {cache_dir}", file=sys.stderr,
              flush=True)

    on_tpu = jax.default_backend() == "tpu"
    dtype = "bfloat16" if on_tpu else "float32"
    steps = 30 if on_tpu else 3

    def _train_ledger_columns(loop, *, tps: float, fpt: float,
                              steps_per_s: float, stall: dict) -> dict:
        """The cost-ledger columns for one train row (ISSUE 14): the
        executable's extracted collective/HBM traffic folded with THIS
        leg's measured tokens/s and (MoE-active) flops/token into the
        roofline MFU-gap attribution — so the row's ``mfu`` and its
        ``mfu_gap_*`` terms share one numerator and the sum identity
        (mfu + gaps == 1) holds exactly. The attribution arithmetic has
        one owner (obs/ledger.py; graftlint GL010)."""
        from distributed_pipeline_tpu.utils.perf import device_peak_flops

        tr = loop.ledger_rows().get("train_step") or {}
        att = ledger_lib.roofline_attribution(
            tokens_per_s=tps, flops_per_token=fpt,
            peak_flops=device_peak_flops(),
            n_devices=jax.device_count(), steps_per_s=steps_per_s,
            collective_bytes_per_step=tr.get("collective_bytes_per_step",
                                             0.0),
            bytes_accessed=tr.get("bytes_accessed", 0.0),
            host_stall_s_per_step=(stall["data_wait_s"]
                                   + stall["h2d_wait_s"]
                                   + stall["dispatch_s"]),
            device_kind=getattr(jax.devices()[0], "device_kind", "cpu"),
            padding_waste_frac=tr.get("padding_waste_frac", 0.0))
        cols = ledger_lib.attribution_columns(att)
        for k in ("flops_per_execution", "bytes_accessed"):
            if k in tr:
                cols[k] = tr[k]
        return cols

    def measure(name: str, *, family: str, size: str, seq_len: int,
                batch, microbatch: int = 0, remat: bool = False,
                vocab: int = 8192, attention_impl: str = "auto",
                moe_experts: int = 0, moe_top_k: int = 2,
                moe_capacity_factor: float = 1.25,
                scan_layers: bool = False,
                prefetch_depth: int = 0, dispatch_lag: int = 0,
                steady_steps: int = 0, fused_update: bool = False):
        """tokens/sec for one config; the first step is timed separately
        (compile + dispatch) from the steady-state window. ``batch`` is PER
        HOST (reference trainer.py:89 semantics: global = batch x hosts); a
        tuple tries sizes left-to-right and falls back on HBM OOM (the
        driver runs this unattended — a too-ambitious batch must degrade,
        not abort the whole bench)."""
        if isinstance(batch, tuple):
            for i, b in enumerate(batch):
                try:
                    return measure(name, family=family, size=size,
                                   seq_len=seq_len, batch=b,
                                   microbatch=microbatch, remat=remat,
                                   vocab=vocab, attention_impl=attention_impl,
                                   moe_experts=moe_experts,
                                   moe_top_k=moe_top_k,
                                   moe_capacity_factor=moe_capacity_factor,
                                   scan_layers=scan_layers,
                                   prefetch_depth=prefetch_depth,
                                   dispatch_lag=dispatch_lag,
                                   steady_steps=steady_steps,
                                   fused_update=fused_update)
                except (LegTimeout, BenchInterrupted):
                    # Not an OOM: the per-leg SIGALRM cap / driver SIGTERM
                    # must reach the leg runner, not restart at a smaller
                    # batch with the itimer already consumed.
                    raise
                except Exception as e:
                    if i == len(batch) - 1:
                        raise
                    # stderr: stdout is the ONE machine-readable JSON line
                    print(f"# {name}: batch {b} failed ({type(e).__name__}); "
                          f"retrying with {batch[i + 1]}", file=sys.stderr,
                          flush=True)
        # Off-TPU (CPU smoke): shrink the model so every config still
        # EXERCISES its code path (remat, grad-accum, families) in seconds;
        # real preset sizes only matter on the hardware being measured.
        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        wl = create_model_from_config(
            model_family=family, model_size=size, seq_len=seq_len,
            dtype=dtype, remat=remat, attention_impl=attention_impl,
            moe_experts=moe_experts, moe_top_k=moe_top_k,
            moe_capacity_factor=moe_capacity_factor,
            scan_layers=scan_layers, **dims)
        dataset = "synthetic-lm" if family == "gpt2" else "synthetic-seq2seq"
        data = load_data_from_args("train", batch_size=batch, dataset=dataset,
                                   seq_len=seq_len,
                                   vocab_size=dims["vocab_size"], seed=0,
                                   num_loader_proc=2)
        # sanitize=True: the runtime half of graftlint — every leg row
        # carries the OBSERVED XLA compile count, so a recompile
        # regression (e.g. an unpinned sharding re-triggering step-2
        # compiles, the r6 bug class) shows up in BENCH artifacts as
        # recompile_count growth instead of a silent throughput dip.
        # cost_ledger=True: every train row carries the per-program
        # roofline attribution (obs/ledger.py) — the MFU gap explained,
        # not just stated (ISSUE 14).
        loop = TrainLoop(model=wl, data=data, batch_size=batch,
                         microbatch=microbatch or batch, lr=1e-4,
                         ema_rate="0.9999", learning_steps=0,
                         log_interval=10 ** 9, save_interval=10 ** 9,
                         mesh=make_mesh(dp=-1), checkpoint_dir="", seed=0,
                         sanitize=True, prefetch_depth=prefetch_depth,
                         dispatch_lag=dispatch_lag, cost_ledger=True,
                         fused_update=fused_update)
        # First step paid separately: with the AOT step (utils/trainer.py)
        # its wall time is compile + dispatch + one step, and
        # loop.compile_time_s isolates the lower()/compile() share — the
        # number the persistent cache collapses on warm runs.
        # try/finally: a leg that dies mid-measure (the HBM-OOM retry path
        # and the per-leg error rows both swallow exceptions) must still
        # detach its monitor — otherwise every failed attempt leaves one
        # more handler on the 'jax' logger and jax_log_compiles stuck on.
        # (A TrainLoop that dies during CONSTRUCTION detaches its own
        # monitor — see TrainLoop.__init__ — so the retry loop above is
        # covered too.)
        n_steady = steady_steps or steps
        try:
            t0 = time.perf_counter()
            m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            first_step_s = time.perf_counter() - t0
            # Warmup: fill the loader prefetch queues + let dispatch
            # pipeline to depth — a cold 1-step warmup undermeasures steady
            # state by ~10% (62.3% -> 68.8% MFU on the v5e headline).
            for _ in range(7 if on_tpu else 2):
                m = loop.run_step(loop.next_batch())
            # device_get: the timed window opens only once the warmup's
            # last step has really finished and its value is on the host
            float(jax.device_get(m["loss"]))
            loop.stalls.lap()  # reset the window: gauges cover ONLY the
            # steady timed steps below, not compile/warmup
            t0 = time.perf_counter()
            for _ in range(n_steady):
                m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            dt = time.perf_counter() - t0
            # flush BEFORE lap: the drain emits the last dispatch_lag
            # steps' device_step_s samples into the stall window (same
            # order as measure_prefetch_ab)
            loop.flush_metrics()
            stall = loop.stalls.lap()
        finally:
            recompiles = loop.stop_sanitizer()
        tps = n_steady * batch * seq_len * jax.process_count() / dt
        # MFU against ACTIVE params: perf.active_param_count owns the
        # top-k MoE adjustment (graftlint GL010: FLOPs-side accounting
        # has one owner — this used to be ~20 inline lines here).
        n_active = active_param_count(loop.state.params, loop.n_params,
                                      moe_experts=moe_experts,
                                      moe_top_k=moe_top_k)
        fpt = transformer_train_flops_per_token(
            n_active, wl.num_layers, wl.hidden_size, seq_len)
        row = {
            "name": name,
            "tokens_per_sec_per_chip": round(tps / jax.device_count(), 1),
            "steps_per_s": round(n_steady / dt, 4),
            "mfu": round(mfu(tps, fpt), 4),
            "n_params": loop.n_params,
            "batch": batch, "microbatch": microbatch or batch,
            "seq_len": seq_len, "remat": remat,
            "prefetch_depth": prefetch_depth, "dispatch_lag": dispatch_lag,
            "compile_s": round(loop.compile_time_s or 0.0, 3),
            "first_step_s": round(first_step_s, 3),
            "time_to_first_step_s": round(loop.time_to_first_step_s or 0.0,
                                          3),
            # total XLA compiles for the WHOLE leg (init + train step +
            # steady window): steady-state growth here is a regression
            # even when tokens/sec still looks plausible
            "recompile_count": recompiles,
        }
        # HBM/params footprint (ISSUE 9): logical + per-replica state
        # bytes — opt_state_bytes_per_replica is the ZeRO-1 acceptance
        # column — and the backend's peak live allocation (0 on CPU).
        fp = loop.footprint()
        row.update({k: fp[k] for k in (
            "params_bytes", "opt_state_bytes",
            "opt_state_bytes_per_replica", "peak_live_bytes")})
        # Stall breakdown over the timed window (mean s/step): data_wait_s
        # (blocked on the host iterator), h2d_wait_s (blocked on transfer/
        # placement), dispatch_s (enqueue), device_step_s (trailing
        # dispatch->ready span, observed via the lagged fetch; 0.0 in
        # eager-dispatch legs, which never block on a step to measure it).
        row.update({k: round(v, 6) for k, v in stall.items()})
        # Cost ledger (ISSUE 14): mfu (unrounded — the gap-sum identity
        # must hold to 1e-6) + mfu_gap_host/comms/memory_bound/residual
        # + collective_bytes_per_step + padding_waste_frac, off the leg's
        # own compiled executable and timed window.
        row.update(_train_ledger_columns(loop, tps=tps, fpt=fpt,
                                         steps_per_s=n_steady / dt,
                                         stall=stall))
        if fused_update:
            # Fused-update HBM accounting (ISSUE 18): kernel arm = the
            # exact per-step traffic of the one-pass kernel
            # (ops/fused_update.py update_hbm_bytes — the TPU lowering's
            # bytes by construction; interpreter emulation can't be
            # cost-analyzed faithfully); XLA twin = cost analysis of the
            # staged optax chain this path replaces, compiled standalone
            # on the leg's own state shapes.
            import optax as _optax

            from distributed_pipeline_tpu.ops.fused_update import (
                update_hbm_bytes,
            )
            st = loop.state
            tmap = jax.tree_util.tree_map
            rates = loop.ema_rates
            rate_val = {r: float(r) for r in rates}  # hoisted: trace-free

            def staged(params, grads, opt_state, ema):
                updates, ns = loop.opt.update(grads, opt_state, params)
                p2 = _optax.apply_updates(params, updates)
                e2 = {r: tmap(lambda e, p, _r=rate_val[r]:
                              e * _r + p * (1.0 - _r), ema[r], p2)
                      for r in rates}
                return p2, ns, e2

            abstract = tmap(lambda x: jax.ShapeDtypeStruct(x.shape,
                                                           x.dtype),
                            (st.params, st.params, st.opt_state, st.ema))
            twin = jax.jit(staged).lower(*abstract).compile()
            xla_bytes = ledger_lib.extract_cost(twin).get(
                "bytes_accessed", 0.0)
            kernel_bytes = update_hbm_bytes(
                st.params, n_ema_rates=len(rates),
                dtype_bytes=2 if dtype == "bfloat16" else 4)
            row.update({
                "fused_update": True,
                "update_hbm_bytes_per_step": kernel_bytes,
                "xla_update_bytes_per_step": round(xla_bytes, 1),
                "update_bytes_ratio": round(
                    kernel_bytes / max(xla_bytes, 1e-9), 4),
            })
        return row

    def measure_decode(name: str, *, gen_tokens: int, batch: int,
                       seq_len: int, vocab: int = 8192):
        """KV-cache generation throughput (tokens/sec DECODED, not
        trained): gpt2-base greedy-continues a batch of prompts by
        ``gen_tokens`` single-position cached steps (models/sampling.py
        gpt2_decode prefill + per-token path). Decode is latency-bound —
        each step is one [B, 1, D] forward against the cache — so the
        right scale is tokens/s, not MFU."""
        import jax.numpy as jnp
        import numpy as np

        from distributed_pipeline_tpu.models.sampling import gpt2_decode

        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype=dtype, **dims)
        params = wl.init_params(jax.random.PRNGKey(0))
        prompt_len = seq_len - gen_tokens
        ids = jnp.asarray(
            np.random.default_rng(0).integers(4, dims["vocab_size"],
                                              (batch, seq_len), np.int32))
        run = jax.jit(lambda p, i: gpt2_decode(wl, p, i, prompt_len))
        t0 = time.perf_counter()
        out = run(params, ids)  # compile
        float(jax.device_get(out.sum().astype(jnp.float32)))  # full drain
        compile_s = time.perf_counter() - t0
        reps = 3 if on_tpu else 1
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run(params, ids)
        float(jax.device_get(out.sum().astype(jnp.float32)))
        dt = time.perf_counter() - t0
        # plain jit, no mesh: the decode runs on ONE device, so tps IS the
        # per-chip number — dividing by device_count would understate it
        # on multi-chip hosts
        tps = reps * batch * gen_tokens / dt
        return {
            "name": name,
            "decode_tokens_per_sec_per_chip": round(tps, 1),
            # canonical serving-schema column (same value; the serve legs
            # write only this spelling — keep both until consumers migrate)
            "decode_tokens_per_s_per_chip": round(tps, 1),
            "batch": batch, "gen_tokens": gen_tokens, "seq_len": seq_len,
            "prompt_len": prompt_len,
            "compile_s": round(compile_s, 3),
        }

    def measure_serve(name: str, *, slots: int, num_requests: int,
                      gen_tokens: int, prompt_len: int, page_size: int,
                      seq_len: int, prefill_batch: int = 0,
                      decode_span: int = 4, dispatch_lag: int = 2,
                      vocab: int = 8192):
        """Continuous-batching decode service throughput (serving/): N
        requests stream through a DecodeServer whose compiled decode batch
        stays full — prefill/decode as separate AOT executables over the
        paged KV cache. Reported per the serving schema:
        ``decode_tokens_per_s_per_chip`` over the timed (post-warmup)
        window plus ``time_to_first_token_s`` mean and p95 (TTFT includes
        queue wait — the number a user feels). ``recompile_count`` is the
        STEADY-window compile delta: the phase split's contract is that it
        stays 0 (both executables compile exactly once, in warmup)."""
        import numpy as np

        from distributed_pipeline_tpu.serving import DecodeServer

        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype=dtype, **dims)
        params = wl.init_params(jax.random.PRNGKey(0))
        # decode_span amortizes host dispatch over several tokens (the
        # token chain stays on device inside one executable); dispatch_lag
        # keeps a couple of dispatches in flight so scheduler bookkeeping
        # overlaps device execution instead of serializing per window
        server = DecodeServer(
            wl, params, decode_slots=slots, page_size=page_size,
            max_prompt_len=prompt_len, max_len=prompt_len + gen_tokens,
            prefill_batch=prefill_batch, decode_span=decode_span,
            dispatch_lag=dispatch_lag, seed=0, sanitize=True)
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            4, dims["vocab_size"], (num_requests, prompt_len)).astype(
                np.int32)
        try:
            # Warmup request: pays the prefill+decode AOT compiles and
            # fills the dispatch pipeline; excluded from the timed window.
            t0 = time.perf_counter()
            server.submit(prompts[0], max_new_tokens=gen_tokens)
            server.drain()
            first_request_s = time.perf_counter() - t0
            compile_s = server.compile_time_s
            recompiles_warm = server.recompile_count
            server.reset_stats()
            t0 = time.perf_counter()
            for p in prompts[1:]:
                server.submit(p, max_new_tokens=gen_tokens)
            server.drain()
            dt = time.perf_counter() - t0
            steady_recompiles = server.recompile_count - recompiles_warm
        finally:
            server.stop_sanitizer()
        ttft = server.ttft.summary()
        # replicated decode state: the service rate IS the per-chip rate
        # (see measure_decode's no-division rationale)
        tps = server.tokens_fetched / dt
        # Cost ledger (ISSUE 14): the decode executable's roofline
        # attribution over the timed window (stats were reset after
        # warmup, so tokens_fetched and wall line up), plus the prefill
        # prompt-padding waste as its own column.
        led = server.cost_ledger(wall_s=dt, n_devices=1)
        ledger_cols = ledger_lib.attribution_columns(
            led.get("serve_decode") or {})
        pre = led.get("serve_prefill") or {}
        if "padding_waste_frac" in pre:
            ledger_cols["prefill_padding_waste_frac"] = \
                pre["padding_waste_frac"]
        return {
            "name": name,
            "decode_tokens_per_s_per_chip": round(tps, 1),
            "time_to_first_token_s": round(ttft["mean"], 4),
            "ttft_p95_s": round(ttft["p95"], 4),
            "batch": slots, "gen_tokens": gen_tokens,
            "prompt_len": prompt_len, "seq_len": seq_len,
            "page_size": page_size, "decode_span": decode_span,
            "dispatch_lag": dispatch_lag, "requests": num_requests - 1,
            "decode_steps": server.decode_steps,
            "prefill_steps": server.prefill_steps,
            "compile_s": round(compile_s, 3),
            "first_request_s": round(first_request_s, 3),
            "recompile_count": steady_recompiles,
            **ledger_cols,
        }

    def measure_serve_decode_kernel(name: str, *, slots: int,
                                    num_requests: int, gen_tokens: int,
                                    prompt_len: int, page_size: int,
                                    seq_len: int, vocab: int = 8192):
        """Flash-decode acceptance leg (ISSUE 18): the measure_serve
        protocol with ``decode_impl='pallas'`` (ops/flash_decode.py — the
        paged pool streamed straight through the kernel, interpreter mode
        on CPU), cross-checked token-for-token against a ``'xla'`` twin
        run on the SAME prompts, plus the HBM bytes/token comparison:
        ``decode_hbm_bytes_per_token`` is the kernel schedule's exact DMA
        traffic (decode_hbm_bytes — the TPU lowering's bytes by grid-spec
        construction; interpreter emulation can't be cost-analyzed
        faithfully) and ``xla_decode_bytes_per_token`` is XLA cost
        analysis of the gather twin (xla_paged_decode) compiled standalone
        at the identical pool geometry. Acceptance: token identity, zero
        steady recompiles, kernel bytes strictly below the twin's."""
        import numpy as np

        from distributed_pipeline_tpu.ops.flash_decode import (
            decode_hbm_bytes,
            xla_paged_decode,
        )
        from distributed_pipeline_tpu.serving import DecodeServer

        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype=dtype, **dims)
        params = wl.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            4, dims["vocab_size"], (num_requests, prompt_len)).astype(
                np.int32)

        def serve(impl):
            server = DecodeServer(
                wl, params, decode_slots=slots, page_size=page_size,
                max_prompt_len=prompt_len, max_len=prompt_len + gen_tokens,
                seed=0, sanitize=True, decode_impl=impl)
            try:
                reqs = [server.submit(prompts[0],
                                      max_new_tokens=gen_tokens)]
                server.drain()
                warm = server.recompile_count
                server.reset_stats()
                t0 = time.perf_counter()
                for p in prompts[1:]:
                    reqs.append(server.submit(p,
                                              max_new_tokens=gen_tokens))
                server.drain()
                dt = time.perf_counter() - t0
                steady = server.recompile_count - warm
                tps = server.tokens_fetched / dt
            finally:
                server.stop_sanitizer()
            return [r.tokens for r in reqs], tps, steady

        toks_pl, tps_pl, rec_pl = serve("pallas")
        toks_xla, tps_xla, rec_xla = serve("xla")
        if toks_pl != toks_xla:
            bad = sum(1 for a, b in zip(toks_pl, toks_xla) if a != b)
            return {"name": name,
                    "error": f"flash-decode token mismatch vs xla path on "
                             f"{bad}/{len(toks_pl)} requests"}

        # --- HBM bytes/token, both arms at the server's pool geometry.
        # Kernel arm: the schedule's exact bytes summed over the steady
        # occupancy trajectory (every slot live, positions advancing one
        # page-aligned token per step — the saturated-service shape).
        h = wl.model.num_heads
        dh = wl.hidden_size // h
        dtype_bytes = 2 if dtype == "bfloat16" else 4
        n_pages = -(-(prompt_len + gen_tokens) // page_size)
        bt = np.arange(1 + slots * n_pages)[1:].reshape(slots, n_pages)
        kernel_bytes = sum(
            decode_hbm_bytes(bt, np.full(slots, prompt_len + t, np.int64),
                             page_size, h, dh, dtype_bytes)
            for t in range(gen_tokens))
        kernel_per_tok = kernel_bytes * wl.num_layers / (
            slots * gen_tokens)
        # XLA twin: cost analysis of the gather path it replaces, compiled
        # standalone on the same shapes (position-independent: the gather
        # always materializes every reserved page).
        pool_pages = 1 + slots * n_pages
        jdt = jax.numpy.dtype("bfloat16") if dtype == "bfloat16" \
            else jax.numpy.dtype("float32")
        abstract = (
            jax.ShapeDtypeStruct((slots, h, dh), jdt),
            jax.ShapeDtypeStruct((pool_pages, page_size, h * dh), jdt),
            jax.ShapeDtypeStruct((pool_pages, page_size, h * dh), jdt),
            jax.ShapeDtypeStruct((slots, n_pages), jax.numpy.int32),
            jax.ShapeDtypeStruct((slots,), jax.numpy.int32),
        )
        twin = jax.jit(xla_paged_decode).lower(*abstract).compile()
        xla_bytes = ledger_lib.extract_cost(twin).get("bytes_accessed", 0.0)
        xla_per_tok = xla_bytes * wl.num_layers / slots
        return {
            "name": name,
            "decode_impl": "pallas",
            "tokens_identical_to_xla": True,
            "decode_tokens_per_s_per_chip": round(tps_pl, 1),
            "xla_decode_tokens_per_s_per_chip": round(tps_xla, 1),
            "batch": slots, "gen_tokens": gen_tokens,
            "prompt_len": prompt_len, "page_size": page_size,
            "requests": num_requests,
            "recompile_count": rec_pl,
            "xla_recompile_count": rec_xla,
            "decode_hbm_bytes_per_token": round(kernel_per_tok, 1),
            "xla_decode_bytes_per_token": round(xla_per_tok, 1),
            "hbm_bytes_ratio": round(
                kernel_per_tok / max(xla_per_tok, 1e-9), 4),
        }

    def measure_serve_spec_decode(name: str, *, slots: int,
                                  num_requests: int, gen_tokens: int,
                                  prompt_len: int, page_size: int,
                                  seq_len: int, spec_tokens: int = 3,
                                  vocab: int = 8192):
        """Speculative-decoding acceptance leg (ISSUE 20): the
        measure_serve protocol with ``spec_tokens=K`` against a
        non-speculative twin on the SAME prompts at ``decode_span=1`` —
        one VERIFY dispatch per up-to-K+1 tokens vs one dispatch per
        token, with the verify forward running the whole chain at ~one
        decode step's op count (backbone span branch). The draft is the
        CPU-friendly ``ngram`` prompt-lookup (zero model flops), so the
        measured win is verified-chain amortization scaled by the accept
        rate; greedy token identity against the twin is checked in-leg
        on EVERY pass (the spec contract: rejection discards device-side
        overshoot, the emitted stream never differs). The greedy streams
        of the leg's model settle into repetition, which is exactly the
        regime prompt-lookup drafting serves (retrieval/code/template
        text); fresh text degrades toward accept_rate 0 and ratio ~1.
        The prompt set is SELECTED for that regime: 4x num_requests
        random candidates pregenerate on the non-spec twin (doubling as
        its compile warmup) and the num_requests whose streams score
        highest on simulated prompt-lookup accept are the workload —
        deterministic (fixed seeds), and the resulting accept_rate is
        reported in the row, so the selection is visible, not baked in.
        Both arms then alternate three timed passes and score their
        MEDIAN tokens/s — single-pass wall clocks on a shared box carry
        ~10% load noise, which alternation + median cancels instead of
        letting it redden (or greenwash) the ratio gate. Acceptance:
        tokens identical, accepted_tokens_per_s_ratio > 1, zero steady
        recompiles on both arms."""
        import statistics

        import numpy as np

        from distributed_pipeline_tpu.serving import DecodeServer
        from distributed_pipeline_tpu.serving.spec import ngram_propose

        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=256, num_layers=4, num_heads=8, vocab_size=512)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype=dtype, **dims)
        params = wl.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        cand = rng.integers(
            4, dims["vocab_size"],
            (4 * num_requests, prompt_len)).astype(np.int32)

        def make(k):
            return DecodeServer(
                wl, params, decode_slots=slots, page_size=page_size,
                max_prompt_len=prompt_len, max_len=prompt_len + gen_tokens,
                decode_span=1, seed=0, sanitize=True,
                spec_tokens=k, spec_draft="ngram")

        def sim_accept(p, toks):
            """Replay the acceptance walk a spec server would run on this
            stream with the ngram draft (host-only, no model)."""
            acc = tot = 0
            t = 1
            while t < len(toks):
                h = np.concatenate([p, np.asarray(toks[:t], np.int32)])
                d = ngram_propose(h, spec_tokens)
                m = 0
                for j in range(spec_tokens):
                    if t + j < len(toks) and d[j] == toks[t + j]:
                        m += 1
                    else:
                        break
                acc += m
                tot += spec_tokens
                t += 1 + m
            return acc / max(tot, 1)

        def one_pass(server):
            server.reset_stats()
            reqs = []
            t0 = time.perf_counter()
            for p in prompts:
                reqs.append(server.submit(p, max_new_tokens=gen_tokens))
            server.drain()
            dt = time.perf_counter() - t0
            return ([r.tokens for r in reqs],
                    server.tokens_fetched / dt, server.accept_rate,
                    server.decode_steps)

        servers = {"spec": make(spec_tokens), "base": make(0)}
        try:
            # pregeneration on the base twin IS its warmup: greedy
            # streams for every candidate, scored for the workload pick
            pre = [servers["base"].submit(p, max_new_tokens=gen_tokens)
                   for p in cand]
            servers["base"].drain()
            scored = sorted(
                ((sim_accept(p, list(r.tokens)), i)
                 for i, (p, r) in enumerate(zip(cand, pre))), reverse=True)
            prompts = [cand[i] for _, i in scored[:num_requests]]
            toks = {}
            tps = {"spec": [], "base": []}
            accept = disp = 0
            for arm in ("spec", "base"):   # warmup: compile + cache touch
                toks[arm], _, _, _ = one_pass(servers[arm])
                servers[arm].reset_stats()
            warm = {a: servers[a].recompile_count for a in servers}
            for _ in range(3):
                for arm in ("spec", "base"):
                    t, r, a, d = one_pass(servers[arm])
                    if t != toks[arm]:
                        return {"name": name,
                                "error": f"{arm} arm not deterministic "
                                         f"across timed passes"}
                    tps[arm].append(r)
                    if arm == "spec":
                        accept, disp = a, d
            rec_spec = servers["spec"].recompile_count - warm["spec"]
            rec_base = servers["base"].recompile_count - warm["base"]
            disp_base = servers["base"].decode_steps
        finally:
            for srv in servers.values():
                srv.stop_sanitizer()
        toks_spec, toks_base = toks["spec"], toks["base"]
        tps_spec = statistics.median(tps["spec"])
        tps_base = statistics.median(tps["base"])
        disp_spec = disp
        if toks_spec != toks_base:
            bad = sum(1 for a, b in zip(toks_spec, toks_base) if a != b)
            return {"name": name,
                    "error": f"speculative token mismatch vs non-spec twin "
                             f"on {bad}/{len(toks_spec)} requests"}
        return {
            "name": name,
            "spec_tokens": spec_tokens, "spec_draft": "ngram",
            "tokens_identical_to_nonspec": True,
            "accept_rate": round(accept, 4),
            # every fetched token is target-verified: accepted/s IS the
            # service rate under speculation
            "accepted_tokens_per_s": round(tps_spec, 1),
            "decode_tokens_per_s_per_chip": round(tps_spec, 1),
            "nonspec_tokens_per_s": round(tps_base, 1),
            "accepted_tokens_per_s_ratio": round(
                tps_spec / max(tps_base, 1e-9), 4),
            "decode_dispatches": disp_spec,
            "nonspec_decode_dispatches": disp_base,
            "batch": slots, "gen_tokens": gen_tokens,
            "prompt_len": prompt_len, "page_size": page_size,
            "requests": num_requests,
            "recompile_count": rec_spec,
            "nonspec_recompile_count": rec_base,
        }

    def measure_serve_decode_int8(name: str, *, slots: int,
                                  num_requests: int, gen_tokens: int,
                                  prompt_len: int, page_size: int,
                                  seq_len: int, vocab: int = 8192):
        """int8 paged-KV acceptance leg (ISSUE 20): the measure_serve
        protocol with ``kv_quant='int8'`` (per-page symmetric scales —
        serving/paged_kv.py) against an fp twin at identical geometry.
        Three claims land as columns: the page-pool bytes ratio from the
        engines' own buffer census (``kv_pool_bytes`` — acceptance
        <= 0.55x: int8 payload + one f32 scale per page vs f32 pages),
        the kernel-schedule HBM bytes/token ratio at the same occupancy
        trajectory (decode_hbm_bytes with quantized=True — dequant
        happens in-kernel off the step table's bitcast scales, so page
        traffic shrinks to 1 byte/elem while q/o stay fp), and SLOT
        DOUBLING: 2x slots under int8 fit inside the fp arm's pool
        budget, proven by the census and exercised by serving the
        request stream on the doubled server. Tokens are NOT asserted
        identical — int8 KV is lossy by contract (divergence bounds in
        tests/test_spec_decode.py); throughput for both arms lands so
        the trajectory watches the quantization overhead too."""
        import numpy as np

        from distributed_pipeline_tpu.ops.flash_decode import (
            decode_hbm_bytes,
        )
        from distributed_pipeline_tpu.serving import DecodeServer

        dims = dict(vocab_size=vocab) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype=dtype, **dims)
        params = wl.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            4, dims["vocab_size"], (num_requests, prompt_len)).astype(
                np.int32)

        def serve(kv_quant, n_slots):
            server = DecodeServer(
                wl, params, decode_slots=n_slots, page_size=page_size,
                max_prompt_len=prompt_len, max_len=prompt_len + gen_tokens,
                seed=0, sanitize=True, kv_quant=kv_quant)
            try:
                pool_bytes = server.engine.kv_pool_bytes()
                reqs = [server.submit(prompts[0],
                                      max_new_tokens=gen_tokens)]
                server.drain()
                warm = server.recompile_count
                server.reset_stats()
                t0 = time.perf_counter()
                for p in prompts[1:]:
                    reqs.append(server.submit(p,
                                              max_new_tokens=gen_tokens))
                server.drain()
                dt = time.perf_counter() - t0
                steady = server.recompile_count - warm
                tps = server.tokens_fetched / dt
                done = all(len(r.tokens) == gen_tokens for r in reqs)
            finally:
                server.stop_sanitizer()
            return pool_bytes, tps, steady, done

        pool_fp, tps_fp, rec_fp, done_fp = serve("fp", slots)
        pool_q8, tps_q8, rec_q8, done_q8 = serve("int8", slots)
        # slot doubling at fixed pool budget: the doubled int8 server's
        # own census must fit the fp budget, and it must actually serve
        pool_q8_2x, tps_q8_2x, rec_2x, done_2x = serve("int8", 2 * slots)
        if not (done_fp and done_q8 and done_2x):
            return {"name": name,
                    "error": "a request finished short of gen_tokens"}
        # kernel-schedule HBM traffic at identical steady occupancy
        h = wl.model.num_heads
        dh = wl.hidden_size // h
        dtype_bytes = 2 if dtype == "bfloat16" else 4
        n_pages = -(-(prompt_len + gen_tokens) // page_size)
        bt = np.arange(1 + slots * n_pages)[1:].reshape(slots, n_pages)
        pos = np.full(slots, prompt_len + gen_tokens // 2, np.int64)
        hbm_fp = decode_hbm_bytes(bt, pos, page_size, h, dh, dtype_bytes)
        hbm_q8 = decode_hbm_bytes(bt, pos, page_size, h, dh, dtype_bytes,
                                  quantized=True)
        return {
            "name": name,
            "kv_quant": "int8",
            "decode_tokens_per_s_per_chip": round(tps_q8, 1),
            "fp_tokens_per_s": round(tps_fp, 1),
            "kv_pool_bytes": pool_q8, "fp_kv_pool_bytes": pool_fp,
            "kv_pool_bytes_ratio": round(pool_q8 / max(pool_fp, 1), 4),
            "decode_hbm_bytes_per_step": hbm_q8,
            "fp_decode_hbm_bytes_per_step": hbm_fp,
            "hbm_bytes_ratio": round(hbm_q8 / max(hbm_fp, 1), 4),
            "slots_at_fixed_pool": 2 * slots,
            "doubled_pool_fits_fp_budget": pool_q8_2x <= pool_fp,
            "doubled_kv_pool_bytes": pool_q8_2x,
            "doubled_tokens_per_s": round(tps_q8_2x, 1),
            "batch": slots, "gen_tokens": gen_tokens,
            "prompt_len": prompt_len, "page_size": page_size,
            "requests": num_requests,
            "recompile_count": rec_q8,
            "fp_recompile_count": rec_fp,
            "doubled_recompile_count": rec_2x,
        }

    def _run_supervised_ring(run_dir_name: str, plan: dict, ring_args,
                             *, timeout_s: float = 230.0, extra_env=None):
        """Shared scaffolding for the chaos/elastic robustness legs: a
        supervised run.train ring in its OWN SESSION (timeout killpg's
        the whole tree — killing only the launcher would orphan its
        worker, leaving it to burn the box and hold the run dir for
        later rounds) against a fresh run dir, with the fault plan in
        the env and the bench's persistent compile cache shared across
        attempts AND rounds (resumed attempts pay a cache lookup, not an
        XLA compile — the recompile_count==0 acceptances ride on it).
        Returns (run_dir, rc, wall_s, output_tail); rc None on timeout."""
        import shutil
        import subprocess

        run_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", run_dir_name))
        shutil.rmtree(run_dir, ignore_errors=True)
        env = dict(os.environ)
        env.update({"DPT_CHAOS_PLAN": json.dumps(plan),
                    "JAX_PLATFORMS": "cpu"})
        env.update(extra_env or {})
        # the ring workers size their own fake-device count
        env.pop("XLA_FLAGS", None)
        cmd = [sys.executable, "-m", "distributed_pipeline_tpu.run.train",
               "--distributed", "--nprocs", "1", *ring_args,
               "--checkpoint_path", run_dir]
        t0 = time.perf_counter()
        ring = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            ring_out, ring_err = ring.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(ring.pid, signal.SIGKILL)
            except OSError:
                pass  # the group died between expiry and the kill
            ring.wait()
            return run_dir, None, time.perf_counter() - t0, ""
        return (run_dir, ring.returncode, time.perf_counter() - t0,
                (ring_err or ring_out or "")[-300:])

    def _resumed_steady_recompiles(run_dir: str, per_attempt) -> int:
        """Max steady-state recompile count over RESUMED attempts, from
        the clean-exit sidecars (preferred) or the post-mortem beacon
        snapshots in attempts.jsonl."""
        from distributed_pipeline_tpu.chaos import read_goodput_records

        sidecars = read_goodput_records(run_dir)
        worst = 0
        for rec in per_attempt:
            a = int(rec.get("attempt", 0))
            if a == 0:
                continue
            src = sidecars.get(a) or rec
            c = src.get("steady_recompile_count")
            if c is not None:
                worst = max(worst, int(c))
        return worst

    def _tiny_ring_train_args(steps: int, save_interval: int, batch: int,
                              hidden: int, layers: int,
                              max_restarts: int, backoff_s: float):
        """The CPU smoke training shape the robustness legs share: they
        measure the recovery stack, not the chip."""
        return ["--max_restarts", str(max_restarts),
                "--restart_backoff_s", str(backoff_s),
                "--batch_size", str(batch), "--microbatch", str(batch // 2),
                "--seq_len", "64", "--vocab_size", "64",
                "--hidden_size", str(hidden), "--num_layers", str(layers),
                "--num_heads", "2", "--diffusion_steps", "50",
                "--dtype", "float32", "--ema_rate", "0.9",
                "--learning_steps", str(steps),
                "--save_interval", str(save_interval),
                "--eval_interval", "1000000", "--log_interval", "1000000",
                "--sanitize", "true"]

    def measure_chaos(name: str, *, steps: int, save_interval: int,
                      kill_step: int, crash_save_step: int,
                      batch: int = 8, hidden: int = 64, layers: int = 2,
                      max_restarts: int = 4, backoff_s: float = 0.2):
        """Robustness leg (ISSUE 8): a SUPERVISED spawned training ring
        with two injected kills — one mid-step (SIGKILL at ``kill_step``),
        one mid-checkpoint-save (SIGKILL between array write and finalize
        at ``crash_save_step``) — must complete to the target step through
        the launcher's restart/backoff machinery and checkpoint
        auto-resume, and the run's GOODPUT (useful-step time / wall time,
        chaos.goodput.aggregate_run over attempts.jsonl + the per-attempt
        records) is the leg's headline number. Uses the CPU smoke shape
        regardless of backend: the leg measures the recovery stack, not
        the chip (and this image's jax cannot run cross-process CPU
        collectives, so the ring is one supervised worker — the restart
        path is identical). ``recompile_count`` reports the max
        STEADY-state compile count over resumed attempts: with the
        persistent compile cache warm, a resumed attempt must not
        recompile after its first step."""
        from distributed_pipeline_tpu.chaos import aggregate_run

        plan = {"faults": [
            {"kind": "kill", "step": kill_step, "rank": 0,
             "sig": "SIGKILL"},
            {"kind": "crash_in_save", "step": crash_save_step, "rank": 0},
        ]}
        # Own timeout UNDER the leg's SIGALRM cap (see
        # _run_supervised_ring for the session/killpg rationale).
        run_dir, rc, wall, tail = _run_supervised_ring(
            "chaos_run", plan,
            _tiny_ring_train_args(steps, save_interval, batch, hidden,
                                  layers, max_restarts, backoff_s))
        if rc is None:
            return {"name": name,
                    "error": "chaos ring exceeded its 230s timeout"}
        agg = aggregate_run(run_dir)
        completed = os.path.isdir(
            os.path.join(run_dir, f"model_{steps:06d}"))
        resumed_recompiles = _resumed_steady_recompiles(
            run_dir, agg["per_attempt"])
        if not completed:
            return {"name": name,
                    "error": f"chaos run did not reach step {steps} "
                             f"(rc={rc}): {tail}"}
        return {
            "name": name,
            "completed": True,
            "goodput": round(agg["goodput"], 4),
            "useful_step_s": round(agg["useful_step_s"], 2),
            "startup_s": round(agg["startup_s"], 2),
            "setup_s": round(agg["setup_s"], 2),
            "restore_s": round(agg["restore_s"], 2),
            "compile_s": round(agg["compile_s"], 2),
            "save_s": round(agg["save_s"], 2),
            "data_stall_s": round(agg["data_stall_s"], 2),
            "recompute_s": round(agg["recompute_s"], 2),
            "hang_s": round(agg["hang_s"], 2),
            "lost_s": round(agg["lost_s"], 2),
            "downtime_s": round(agg["downtime_s"], 2),
            "wall_s": round(agg["wall_s"], 2),
            "accounted_frac": round(agg["accounted_frac"], 4),
            "attempts": agg["attempts"],
            "injected_faults": len(plan["faults"]),
            "recompile_count": resumed_recompiles,
            "steps": steps, "batch": batch,
            "leg_wall_s": round(wall, 1),
        }

    def measure_elastic(name: str, *, steps: int, save_interval: int,
                        stall_step_at: int, hang_timeout_s: float = 2.0,
                        batch: int = 16, hidden: int = 64, layers: int = 2,
                        max_restarts: int = 3, backoff_s: float = 0.2,
                        devices_schedule: str = "2,1"):
        """Elastic-topology + hang-watchdog leg (ISSUE 10): a SUPERVISED
        ring that must survive the two failures r10's chaos leg cannot
        model — a worker that WEDGES without dying (``stall_step``: the
        watchdog must detect the frozen beacons and SIGKILL the ring
        within ``hang_timeout_s`` + poll grace) and a SHRUNK restart
        (the ``DPT_FORCE_DEVICES_PER_PROC`` schedule drops the ring from
        2 fake devices to 1 between attempts: dp=2 -> dp=1, so the
        resume reshards params/opt/EMA onto the smaller mesh). The run
        must still complete to the target step; headline numbers are
        GOODPUT (>= 0.6 acceptance — one bounded hang + one reshape
        restart must not eat the run) with ``accounted_frac == 1.0``
        including the new ``hang`` category, the measured watchdog kill
        latency, and zero steady-state recompiles on resumed attempts
        (each topology compiles once; the cache makes repeats free)."""
        from distributed_pipeline_tpu.chaos import (aggregate_run,
                                                    read_attempts)

        plan = {"faults": [
            {"kind": "stall_step", "step": stall_step_at, "rank": 0,
             "seconds": 600},
        ]}
        run_dir, rc, wall, tail = _run_supervised_ring(
            "elastic_run", plan,
            _tiny_ring_train_args(steps, save_interval, batch, hidden,
                                  layers, max_restarts, backoff_s)
            + ["--hang_timeout_s", str(hang_timeout_s)],
            extra_env={"DPT_FORCE_DEVICES_PER_PROC": devices_schedule})
        if rc is None:
            return {"name": name,
                    "error": "elastic ring exceeded its 230s timeout"}
        agg = aggregate_run(run_dir)
        recs = read_attempts(run_dir)
        completed = os.path.isdir(
            os.path.join(run_dir, f"model_{steps:06d}"))
        hung = [r for r in recs if r.get("hung")]
        resumed_recompiles = _resumed_steady_recompiles(
            run_dir, agg["per_attempt"])
        if not completed:
            return {"name": name,
                    "error": f"elastic run did not reach step {steps} "
                             f"(rc={rc}): {tail}"}
        if not hung:
            return {"name": name,
                    "error": "stall_step injected but no attempt was "
                             "hang-killed — the watchdog never fired"}
        topologies = [(r.get("nprocs"), r.get("devices_per_proc"))
                      for r in recs]
        return {
            "name": name,
            "completed": True,
            "goodput": round(agg["goodput"], 4),
            "useful_step_s": round(agg["useful_step_s"], 2),
            "restore_s": round(agg["restore_s"], 2),
            "compile_s": round(agg["compile_s"], 2),
            "recompute_s": round(agg["recompute_s"], 2),
            "hang_s": round(agg["hang_s"], 2),
            "lost_s": round(agg["lost_s"], 2),
            "downtime_s": round(agg["downtime_s"], 2),
            "wall_s": round(agg["wall_s"], 2),
            "accounted_frac": round(agg["accounted_frac"], 4),
            "attempts": agg["attempts"],
            "hung_attempts": len(hung),
            # watchdog kill latency: frozen-window length the watchdog
            # allowed before killing — the "within hang_timeout_s +
            # grace" acceptance number
            "watchdog_kill_s": round(max(
                float(r.get("hang_s") or 0.0) for r in hung), 2),
            "hang_timeout_s": hang_timeout_s,
            "topologies": [f"{n}x{d}" for n, d in topologies],
            "recompile_count": resumed_recompiles,
            "steps": steps, "batch": batch,
            "leg_wall_s": round(wall, 1),
        }

    def measure_serve_fleet(name: str, *, replicas: int = 3,
                            requests: int = 16, rate_rps: float = 2.0,
                            gen_tokens: int = 10, prompt_len: int = 8,
                            page_size: int = 4, seq_len: int = 32,
                            decode_slots: int = 2,
                            kill_after: int = 2, swap_after: int = 5,
                            # documented CPU-box bounds (measured p50
                            # ~1.9s / p95 ~4.0s warm; p95 headroom covers
                            # a COLD-cache respawn: jax import + both
                            # phase compiles land inside the replayed
                            # requests' TTFT)
                            slo_p50_s: float = 10.0,
                            slo_p95_s: float = 60.0,
                            hang_timeout_s: float = 60.0,
                            timeout_s: float = 225.0):
        """Serving-fleet resilience leg (ISSUE 11): N replica workers
        (each a supervised launcher ring — the workers are always CPU dev
        rings, like every robustness leg: this measures the resilience
        stack, not the chip) behind the request router under sustained
        Poisson load, with ONE injected ``kill_replica`` mid-request and
        ONE checkpoint hot-swap to a newer step mid-stream. Acceptance is
        SLOs UNDER LOAD, not peak throughput: p50/p95 TTFT within the
        documented bounds (p95 includes the replayed requests — the
        respawn + warm-cache recompile window is the bounded degradation
        the ISSUE acceptance names), ZERO dropped admitted requests, the
        swap completing with >= N-1 replicas serving throughout, and the
        serving goodput ledger accounting every replica-second
        (accounted_frac == 1.0)."""
        import shutil
        import subprocess

        # --- a tiny real run dir with TWO finalized checkpoints: the
        # fleet serves the older one and hot-swaps to the newer
        run_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "fleet_run"))
        shutil.rmtree(run_dir, ignore_errors=True)
        dims = dict(hidden_size=32, num_layers=2, num_heads=2,
                    vocab_size=64)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype="float32", **dims)
        data = load_data_from_args(
            "train", batch_size=8, dataset="synthetic-lm",
            seq_len=seq_len, vocab_size=dims["vocab_size"], seed=0)
        loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                         ema_rate="0.99", learning_steps=0,
                         log_interval=10 ** 9, save_interval=10 ** 9,
                         checkpoint_dir=run_dir)
        for _ in range(2):
            loop.run_step(next(loop.data))
        loop.save()                       # model_000002: serving version
        for _ in range(2):
            loop.run_step(next(loop.data))
        loop.save()                       # model_000004: swap target
        loop.wait_for_saves()
        with open(os.path.join(run_dir, "training_args.json"), "w") as f:
            json.dump(dict(model_family="gpt2", model_size="base",
                           seq_len=seq_len, dtype="float32",
                           dataset="synthetic-lm", seed=0, **dims), f)

        plan = {"faults": [{"kind": "kill_replica", "step": kill_after,
                            "rank": 1, "sig": "SIGKILL"}]}
        env = dict(os.environ)
        env.update({"DPT_CHAOS_PLAN": json.dumps(plan),
                    "JAX_PLATFORMS": "cpu"})
        env.pop("XLA_FLAGS", None)  # replica workers size their own
        # (the launcher ships the bench's persistent compile cache via
        # JAX_COMPILATION_CACHE_DIR, so respawned replicas recompile warm)
        fleet_dir = os.path.join(run_dir, "fleet")
        cmd = [sys.executable, "-m", "distributed_pipeline_tpu.run.serve",
               "--checkpoint_path", run_dir, "--step", "2",
               "--replicas", str(replicas), "--fleet_dir", fleet_dir,
               "--decode_slots", str(decode_slots),
               "--page_size", str(page_size),
               "--max_prompt_len", str(prompt_len),
               "--max_new_tokens", str(gen_tokens),
               "--traffic", "poisson", "--rate_rps", str(rate_rps),
               "--synthetic_requests", str(requests),
               "--synthetic_prompt_len", str(prompt_len),
               "--swap_after_requests", str(swap_after),
               "--swap_step", "4",
               "--hang_timeout_s", str(hang_timeout_s),
               "--fleet_deadline_s", str(max(30.0, timeout_s - 25.0)),
               # per-replica roofline snapshots -> fleet decode_roofline
               # aggregate, so this row carries mfu_gap_memory_bound like
               # the single-replica serve rows (ISSUE 18 satellite)
               "--cost_ledger", "true"]
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            out, err = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            return {"name": name,
                    "error": f"fleet run exceeded its {timeout_s:.0f}s "
                             f"timeout"}
        wall = time.perf_counter() - t0
        if proc.returncode != 0 or not out.strip():
            return {"name": name,
                    "error": f"fleet run failed (rc={proc.returncode}): "
                             f"{(err or out or '')[-300:]}"}
        res = json.loads(out.strip().splitlines()[-1])
        gp = res.get("serving_goodput") or {}
        failures = []
        if res.get("dropped"):
            failures.append(f"{res['dropped']} admitted requests dropped")
        if not res.get("replayed"):
            failures.append("kill_replica forced no replay")
        if not (res.get("swap") or {}).get("ok"):
            failures.append(f"hot-swap failed: {res.get('swap')}")
        if abs(gp.get("accounted_frac", 0.0) - 1.0) > 0.05:
            failures.append(
                f"ledger unaccounted (frac={gp.get('accounted_frac')})")
        p50, p95 = res.get("ttft_p50_s"), res.get("ttft_p95_s")
        if p50 is None or p50 > slo_p50_s or p95 > slo_p95_s:
            failures.append(f"TTFT SLO breach: p50={p50} (<= {slo_p50_s}) "
                            f"p95={p95} (<= {slo_p95_s})")
        if failures:
            return {"name": name, "error": "; ".join(failures)[:500],
                    "ttft_p50_s": p50, "ttft_p95_s": p95,
                    "leg_wall_s": round(wall, 1)}
        return {
            "name": name,
            "replicas": replicas,
            "requests": res["requests"],
            "completed": res["completed"],
            "dropped": res["dropped"],
            "replayed": res["replayed"],
            "swap_ok": True,
            "swap_step": res["swap"]["step"],
            "injected_faults": len(plan["faults"]) + 1,  # + the swap
            "ttft_p50_s": p50,
            "ttft_p95_s": p95,
            "slo_p50_s": slo_p50_s,
            "slo_p95_s": slo_p95_s,
            "decode_tokens_per_s": res["decode_tokens_per_s"],
            "serving_s": gp.get("serving_s"),
            "drain_s": gp.get("drain_s"),
            "replay_s": gp.get("replay_s"),
            "swap_s": gp.get("swap_s"),
            "downtime_s": gp.get("downtime_s"),
            "lost_s": gp.get("lost_s"),
            "accounted_frac": gp.get("accounted_frac"),
            "fleet_attempts": gp.get("attempts"),
            "traffic": res.get("traffic"),
            "wall_s": res.get("wall_s"),
            "leg_wall_s": round(wall, 1),
            # fleet-averaged decode roofline attribution (gap terms keyed
            # mfu / mfu_gap_* like every attributed row)
            **(res.get("decode_roofline") or {}),
        }

    def measure_serve_autoscale(name: str, *, requests: int = 20,
                                rate_rps: float = 0.8,
                                diurnal_period_s: float = 20.0,
                                max_replicas: int = 2,
                                gen_tokens: int = 8, prompt_len: int = 12,
                                shared_prefix_len: int = 8,
                                page_size: int = 4, seq_len: int = 32,
                                decode_slots: int = 2,
                                # the autoscaler's internal SLO target —
                                # deliberately TIGHT so the warmup-window
                                # queue waits breach it and drive the
                                # scale-up; the leg's own acceptance
                                # bounds are the documented CPU SLOs below
                                slo_ttft_s: float = 1.0,
                                slo_p50_s: float = 10.0,
                                slo_p95_s: float = 60.0,
                                timeout_s: float = 200.0):
        """Autoscaling-fleet leg (ISSUE 17): three fleet runs over the
        SAME seeded diurnal + shared-prefix workload. (1) a static
        max-size fleet with least-loaded routing — the replica-seconds
        baseline AND the prefix-hit-rate control; (2) the same static
        fleet with prefix-affinity routing ON — the fleet-wide-cache
        A/B arm; (3) --replicas 1 under the SLO-driven autoscaler
        (ceiling max_replicas): the startup/peak pressure must journal
        >= 1 scale-up, the diurnal trough >= 1 drain-based scale-down.
        Acceptance: zero drops everywhere, p50/p95 TTFT inside the
        documented CPU bounds, the autoscaled run's summed replica
        wall (its replica-seconds bill) strictly below the static
        baseline's, affinity's fleet-wide prefix hit rate strictly
        above least-loaded's, and the serving ledger closing at
        accounted_frac 1.0 WITH the paid_idle category booked. Run
        order is cold-cache-fair: the affinity arm pays the one cold
        compile; the two runs being compared (static vs autoscale)
        both start warm."""
        import shutil
        import subprocess

        run_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "autoscale_run"))
        shutil.rmtree(run_dir, ignore_errors=True)
        dims = dict(hidden_size=32, num_layers=2, num_heads=2,
                    vocab_size=64)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype="float32", **dims)
        data = load_data_from_args(
            "train", batch_size=8, dataset="synthetic-lm",
            seq_len=seq_len, vocab_size=dims["vocab_size"], seed=0)
        loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                         ema_rate="0.99", learning_steps=0,
                         log_interval=10 ** 9, save_interval=10 ** 9,
                         checkpoint_dir=run_dir)
        for _ in range(2):
            loop.run_step(next(loop.data))
        loop.save()
        loop.wait_for_saves()
        with open(os.path.join(run_dir, "training_args.json"), "w") as f:
            json.dump(dict(model_family="gpt2", model_size="base",
                           seq_len=seq_len, dtype="float32",
                           dataset="synthetic-lm", seed=0, **dims), f)

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("DPT_CHAOS_PLAN", None)

        def fleet_run(tag, extra):
            fleet_dir = os.path.join(run_dir, f"fleet_{tag}")
            cmd = [sys.executable, "-m",
                   "distributed_pipeline_tpu.run.serve",
                   "--checkpoint_path", run_dir, "--step", "2",
                   "--fleet_dir", fleet_dir,
                   "--decode_slots", str(decode_slots),
                   "--page_size", str(page_size),
                   "--max_prompt_len", str(prompt_len),
                   "--max_new_tokens", str(gen_tokens),
                   "--synthetic_prompt_len", str(prompt_len),
                   "--synthetic_requests", str(requests),
                   "--shared_prefix_len", str(shared_prefix_len),
                   "--prefix_cache", "true",
                   "--traffic", "diurnal", "--rate_rps", str(rate_rps),
                   "--diurnal_period_s", str(diurnal_period_s),
                   "--diurnal_floor", "0.05",
                   # a wide teardown margin: a deadline-hit run must
                   # still drain + stop + print its row inside timeout_s
                   "--fleet_deadline_s",
                   str(max(60.0, timeout_s - 60.0))] + extra
            proc = subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
                start_new_session=True,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            try:
                out, err = proc.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
                proc.wait()
                return None, f"{tag} run exceeded {timeout_s:.0f}s"
            if proc.returncode != 0 or not out.strip():
                return None, (f"{tag} run failed "
                              f"(rc={proc.returncode}): "
                              f"{(err or out or '')[-300:]}")
            return json.loads(out.strip().splitlines()[-1]), None

        t0 = time.perf_counter()
        static_n = str(max_replicas)
        affinity, err = fleet_run("affinity", [
            "--replicas", static_n, "--route_affinity", "true"])
        if err is None:
            static, err = fleet_run("static", ["--replicas", static_n])
        if err is None:
            auto, err = fleet_run("autoscale", [
                "--replicas", "1", "--route_affinity", "true",
                "--autoscale", "true",
                "--autoscale_min", "1",
                "--autoscale_max", static_n,
                "--autoscale_slo_ttft_s", str(slo_ttft_s),
                "--autoscale_up_backlog", "2.0",
                "--autoscale_down_frac", "0.5",
                "--autoscale_cooldown_s", "2.0",
                "--autoscale_window_s", "6.0"])
        wall = time.perf_counter() - t0
        if err is not None:
            return {"name": name, "error": err,
                    "leg_wall_s": round(wall, 1)}

        asc = auto.get("autoscale") or {}
        auto_gp = auto.get("serving_goodput") or {}
        static_gp = static.get("serving_goodput") or {}
        failures = []
        for tag, res in (("affinity", affinity), ("static", static),
                         ("autoscale", auto)):
            if res.get("dropped"):
                failures.append(f"{tag}: {res['dropped']} requests "
                                f"dropped")
            gp = res.get("serving_goodput") or {}
            if abs(gp.get("accounted_frac", 0.0) - 1.0) > 0.05:
                failures.append(f"{tag}: ledger unaccounted "
                                f"(frac={gp.get('accounted_frac')})")
        if not asc.get("scale_ups"):
            failures.append("no scale-up journaled")
        if not asc.get("scale_downs"):
            failures.append("no drain-based scale-down journaled")
        p50, p95 = auto.get("ttft_p50_s"), auto.get("ttft_p95_s")
        if p50 is None or p50 > slo_p50_s or p95 > slo_p95_s:
            failures.append(f"TTFT SLO breach: p50={p50} "
                            f"(<= {slo_p50_s}) p95={p95} "
                            f"(<= {slo_p95_s})")
        # replica-seconds: summed replica wall — the bill an operator
        # pays. The autoscaled fleet must cost less than always-max.
        auto_rs = auto_gp.get("wall_s") or 0.0
        static_rs = static_gp.get("wall_s") or 0.0
        if not auto_rs or not static_rs or auto_rs >= static_rs:
            failures.append(f"autoscale replica-seconds {auto_rs} did "
                            f"not beat static-max {static_rs}")
        hit_aff = affinity.get("prefix_hit_rate") or 0.0
        hit_ll = static.get("prefix_hit_rate") or 0.0
        if hit_aff <= hit_ll:
            failures.append(f"affinity hit rate {hit_aff} did not beat "
                            f"least-loaded {hit_ll}")
        if failures:
            return {"name": name, "error": "; ".join(failures)[:500],
                    "autoscale": asc, "ttft_p50_s": p50,
                    "ttft_p95_s": p95, "leg_wall_s": round(wall, 1)}
        return {
            "name": name,
            "requests": auto["requests"],
            "completed": auto["completed"],
            "dropped": auto["dropped"],
            "scale_ups": asc["scale_ups"],
            "scale_downs": asc["scale_downs"],
            "max_replicas": max_replicas,
            "ttft_p50_s": p50,
            "ttft_p95_s": p95,
            "slo_p50_s": slo_p50_s,
            "slo_p95_s": slo_p95_s,
            "autoscale_slo_ttft_s": slo_ttft_s,
            "replica_seconds": round(auto_rs, 2),
            "static_replica_seconds": round(static_rs, 2),
            "replica_seconds_saved_frac": round(
                1.0 - auto_rs / static_rs, 4),
            "paid_idle_s": auto_gp.get("paid_idle_s"),
            "serving_s": auto_gp.get("serving_s"),
            "accounted_frac": auto_gp.get("accounted_frac"),
            "prefix_hit_rate_affinity": hit_aff,
            "prefix_hit_rate_least_loaded": hit_ll,
            "affinity_hits": affinity.get("affinity_hits"),
            "affinity_placements": affinity.get("affinity_placements"),
            "traffic": auto.get("traffic"),
            "wall_s": auto.get("wall_s"),
            "leg_wall_s": round(wall, 1),
        }

    def measure_mpmd_pipe(name: str, *, steps: int = 3, n_stages: int = 2,
                          n_microbatches: int = 4, batch: int = 8,
                          seq_len: int = 128, hidden: int = 64,
                          layers: int = 4, heads: int = 4,
                          hang_timeout_s: float = 120.0):
        """MPMD pipeline-training leg (ISSUE 16): the host-driven 1F1B
        driver runs a 2-stage diffuseq pipeline where EACH STAGE is its
        own supervised launcher ring (always CPU rings — like every
        robustness leg this measures the substrate, not the chip) and
        activations/grads move over the StageLink host relay. Acceptance:
        every step's loss finite with zero rewinds, the per-stage attempt
        ledgers folding to accounted_frac == 1.0 with the ``link_wait``
        category present, and zero steady-state recompiles on every
        stage."""
        import shutil

        from distributed_pipeline_tpu.mpmd import PipelineDriver
        from distributed_pipeline_tpu.run.status import pipeline_status

        run_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "mpmd_pipe"))
        shutil.rmtree(run_dir, ignore_errors=True)
        config = {
            "n_stages": n_stages,
            "n_microbatches": n_microbatches,
            "schedule": "1f1b",
            "model": dict(model_family="diffuseq", vocab_size=128,
                          seq_len=seq_len, hidden_size=hidden,
                          num_layers=layers, num_heads=heads,
                          diffusion_steps=50, dtype="float32",
                          scan_layers=True),
            "data": dict(dataset="synthetic-seq2seq", seq_len=seq_len,
                         vocab_size=128, seed=0),
            "batch_size": batch,
            "seed": 0,
            "lr": 1e-3,
            "link_capacity": 8,
        }
        driver = PipelineDriver(run_dir, config, max_restarts=1,
                                hang_timeout_s=hang_timeout_s,
                                worker_platform="cpu")
        t0 = time.perf_counter()
        try:
            res = driver.run(steps)
        finally:
            driver.stop()
        wall = time.perf_counter() - t0
        gp = res.get("goodput") or {}
        snap = pipeline_status(run_dir)
        steady = [r.get("steady_recompiles") for r in snap.get("stages", [])]
        failures = []
        losses = res.get("losses") or []
        if len(losses) != steps or any(l != l for l in losses):
            failures.append(f"bad loss stream: {losses}")
        if res.get("rewinds"):
            failures.append(f"{res['rewinds']} rewinds on a fault-free run")
        if abs(gp.get("accounted_frac", 0.0) - 1.0) > 0.05:
            failures.append(
                f"ledger unaccounted (frac={gp.get('accounted_frac')})")
        if "link_wait_s" not in gp:
            failures.append("no link_wait category in the pipeline fold")
        if any(s not in (0, None) for s in steady):
            failures.append(f"steady-state recompiles: {steady}")
        if failures:
            return {"name": name, "error": "; ".join(failures)[:500],
                    "leg_wall_s": round(wall, 1)}
        return {
            "name": name,
            "n_stages": n_stages,
            "schedule": "1f1b",
            "n_microbatches": n_microbatches,
            "steps": steps,
            "final_loss": round(float(losses[-1]), 4),
            "rewinds": res.get("rewinds"),
            "attempts_per_stage": res.get("attempts_per_stage"),
            "goodput": round(gp.get("goodput", 0.0), 4),
            "link_wait_s": round(gp.get("link_wait_s", 0.0), 3),
            "accounted_frac": gp.get("accounted_frac"),
            "steady_recompile_count": sum(int(s or 0) for s in steady),
            "steps_per_s": round(steps / wall, 4) if wall > 0 else None,
            "leg_wall_s": round(wall, 1),
        }

    def measure_serve_disagg(name: str, *, requests: int = 8,
                             gen_tokens: int = 6, prompt_len: int = 6,
                             page_size: int = 4, seq_len: int = 32,
                             decode_slots: int = 2, rate_rps: float = 6.0,
                             burst_size: int = 4,
                             hang_timeout_s: float = 60.0,
                             timeout_s: float = 200.0):
        """Disaggregated prefill/decode serving leg (ISSUE 16): one
        prefill replica streams paged-KV frames over the StageLink host
        relay to a DecodeServer on a separate worker process, admitted
        through the same router as the colocated legs. A BURSTY arrival
        pattern front-loads prefill work so the leg's TTFT reads against
        the colocated gpt2-serve-decode-b8 row under comparable queueing
        pressure. Acceptance: every admitted request completes, zero
        drops, and BOTH tiers' goodput ledgers account every
        replica-second (accounted_frac == 1.0). No steady-recompile
        claim: DecodeServer.submit_prefilled ingests page batches whose
        fill count varies per prompt, so decode-side compile counts are
        shape-dependent by design."""
        import shutil
        import subprocess

        run_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "disagg_run"))
        shutil.rmtree(run_dir, ignore_errors=True)
        dims = dict(hidden_size=32, num_layers=2, num_heads=2,
                    vocab_size=64)
        wl = create_model_from_config(
            model_family="gpt2", model_size="base", seq_len=seq_len,
            dtype="float32", **dims)
        data = load_data_from_args(
            "train", batch_size=8, dataset="synthetic-lm",
            seq_len=seq_len, vocab_size=dims["vocab_size"], seed=0)
        loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                         ema_rate="0.99", learning_steps=0,
                         log_interval=10 ** 9, save_interval=10 ** 9,
                         checkpoint_dir=run_dir)
        for _ in range(2):
            loop.run_step(next(loop.data))
        loop.save()
        loop.wait_for_saves()
        with open(os.path.join(run_dir, "training_args.json"), "w") as f:
            json.dump(dict(model_family="gpt2", model_size="base",
                           seq_len=seq_len, dtype="float32",
                           dataset="synthetic-lm", seed=0, **dims), f)

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # workers size their own
        fleet_dir = os.path.join(run_dir, "fleet")
        cmd = [sys.executable, "-m", "distributed_pipeline_tpu.run.serve",
               "--checkpoint_path", run_dir, "--step", "2",
               "--replicas", "1", "--disagg", "1",
               "--fleet_dir", fleet_dir,
               "--decode_slots", str(decode_slots),
               "--page_size", str(page_size),
               "--max_prompt_len", str(max(8, prompt_len + 2)),
               "--max_new_tokens", str(gen_tokens),
               "--traffic", "bursty", "--rate_rps", str(rate_rps),
               "--burst_size", str(burst_size),
               "--synthetic_requests", str(requests),
               "--synthetic_prompt_len", str(prompt_len),
               "--hang_timeout_s", str(hang_timeout_s),
               "--fleet_deadline_s", str(max(30.0, timeout_s - 25.0))]
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            out, err = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            return {"name": name,
                    "error": f"disagg run exceeded its {timeout_s:.0f}s "
                             f"timeout"}
        wall = time.perf_counter() - t0
        if proc.returncode != 0 or not out.strip():
            return {"name": name,
                    "error": f"disagg run failed (rc={proc.returncode}): "
                             f"{(err or out or '')[-300:]}"}
        res = json.loads(out.strip().splitlines()[-1])
        sgp = res.get("serving_goodput") or {}
        dgp = res.get("decode_goodput") or {}
        failures = []
        if res.get("dropped"):
            failures.append(f"{res['dropped']} admitted requests dropped")
        if res.get("completed") != requests:
            failures.append(f"{res.get('completed')}/{requests} completed")
        if not res.get("disagg"):
            failures.append("router did not run in disagg mode")
        if abs(sgp.get("accounted_frac", 0.0) - 1.0) > 0.05:
            failures.append(
                f"prefill ledger unaccounted "
                f"(frac={sgp.get('accounted_frac')})")
        if abs(dgp.get("accounted_frac", 0.0) - 1.0) > 0.05:
            failures.append(
                f"decode ledger unaccounted "
                f"(frac={dgp.get('accounted_frac')})")
        p50, p95 = res.get("ttft_p50_s"), res.get("ttft_p95_s")
        if p50 is None:
            failures.append("no TTFT percentiles")
        if failures:
            return {"name": name, "error": "; ".join(failures)[:500],
                    "leg_wall_s": round(wall, 1)}
        return {
            "name": name,
            "disagg": True,
            "requests": res["requests"],
            "completed": res["completed"],
            "dropped": res["dropped"],
            "ttft_p50_s": p50,
            "ttft_p95_s": p95,
            "decode_tokens_per_s": res.get("decode_tokens_per_s"),
            "prefill_accounted_frac": sgp.get("accounted_frac"),
            "decode_accounted_frac": dgp.get("accounted_frac"),
            "traffic": res.get("traffic"),
            "wall_s": res.get("wall_s"),
            "leg_wall_s": round(wall, 1),
        }

    def measure_prefetch_ab(name: str, *, family: str, size: str,
                            seq_len: int, batch: int, microbatch: int = 0,
                            window_steps: int = 4, rounds: int = 6,
                            prefetch_depth: int = 2, dispatch_lag: int = 1):
        """Paired interleaved prefetch A/B at the headline settings.

        Sequential OFF-then-ON legs measure the box as much as the code: on
        a shared/throttled host the steady-state rate drifts tens of
        percent over tens of seconds, so one pair of windows flips the
        delta's sign run to run (observed on this box: same config ranged
        24->38 steps/s across back-to-back reps). Here BOTH loops stay
        alive and short timed windows interleave between them, order
        alternating each round (ABBA), so slow drift hits the two arms
        equally. The delta comes from the POSITION-BALANCED TOTALS: on
        this box the second of two back-to-back windows runs ~25% slower
        regardless of arm (scheduler/cache position effect, measured), so
        per-round ratios are bimodal — but with ``rounds`` even, ABBA
        gives each arm first position exactly half the time and the
        position cost cancels in the summed times. Returns the
        prefetch-ON leg row (same schema as ``measure``) with the paired
        baseline attached as ``ab_*`` fields — the ``prefetch-ab-delta``
        row is derived from these, not from cross-leg numbers taken at
        different times."""
        if rounds % 2:
            rounds += 1  # even rounds: the ABBA position balance above
        dims = dict(vocab_size=8192) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        dataset = "synthetic-lm" if family == "gpt2" else "synthetic-seq2seq"

        def build(depth: int, lag: int) -> TrainLoop:
            wl = create_model_from_config(
                model_family=family, model_size=size, seq_len=seq_len,
                dtype=dtype, **dims)
            data = load_data_from_args(
                "train", batch_size=batch, dataset=dataset, seq_len=seq_len,
                vocab_size=dims["vocab_size"], seed=0, num_loader_proc=2)
            # Both arms sanitize: the transfer-guard context is entered per
            # step, so only a symmetric pair is a fair timing comparison.
            return TrainLoop(model=wl, data=data, batch_size=batch,
                             microbatch=microbatch or batch, lr=1e-4,
                             ema_rate="0.9999", learning_steps=0,
                             log_interval=10 ** 9, save_interval=10 ** 9,
                             mesh=make_mesh(dp=-1), checkpoint_dir="",
                             seed=0, sanitize=True, prefetch_depth=depth,
                             dispatch_lag=lag)

        warm = 7 if on_tpu else 2

        def warmup(loop: TrainLoop) -> float:
            t0 = time.perf_counter()
            m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            first_step_s = time.perf_counter() - t0
            for _ in range(warm):
                m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            loop.flush_metrics()
            loop.stalls.lap()  # gauges cover only the timed windows
            return first_step_s

        def window(loop: TrainLoop) -> float:
            t0 = time.perf_counter()
            for _ in range(window_steps):
                m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            return time.perf_counter() - t0

        # Two live TrainLoops double the device residency of measure()'s
        # single loop, and the scalar batch arg has no tuple ladder — so
        # an HBM OOM falls back by halving (keeping the PAIRED protocol)
        # instead of erroring out the one leg whose delta row the bench
        # exists to produce. The row's "batch" reports the size that ran.
        requested_batch = batch
        while True:
            try:
                # OFF arm is built and warmed FIRST, so the ON arm's
                # RecompileMonitor (installed at its construction) never
                # sees the OFF arm's compiles — the reported
                # recompile_count is the ON loop's own compiles plus any
                # steady-window retrace from either arm, which is exactly
                # the regression the gauge exists to catch. (Both
                # monitors hook the process-global 'jax' logger; they are
                # uninstalled in reverse install order below so their
                # saved jax_log_compiles flags nest correctly.)
                loop_off = build(0, 0)
                try:
                    warmup(loop_off)
                    loop_on = build(prefetch_depth, dispatch_lag)
                    try:
                        first_step_s = warmup(loop_on)
                        off_dts: list = []
                        on_dts: list = []
                        for r in range(rounds):
                            pair = ((loop_off, off_dts), (loop_on, on_dts))
                            for loop, dts in (pair[::-1] if r % 2 else pair):
                                dts.append(window(loop))
                        loop_on.flush_metrics()  # drain the lagged ring
                        stall = loop_on.stalls.lap()
                    finally:
                        recompiles = loop_on.stop_sanitizer()
                finally:
                    loop_off.stop_sanitizer()
            except (LegTimeout, BenchInterrupted):
                raise
            except Exception as e:
                msg = str(e)
                if (batch <= 1 or ("RESOURCE_EXHAUSTED" not in msg
                                   and "out of memory" not in msg.lower())):
                    raise
                print(f"# {name}: batch {batch} OOM with two live loops; "
                      f"retrying A/B at {batch // 2}", file=sys.stderr,
                      flush=True)
                batch //= 2
                microbatch = min(microbatch, batch) if microbatch else 0
                continue
            break
        n_steps = rounds * window_steps
        off_sps = n_steps / sum(off_dts)
        on_sps = n_steps / sum(on_dts)
        # identical step counts, so the totals ratio IS the rate ratio
        delta_pct = 100.0 * (sum(off_dts) / sum(on_dts) - 1.0)
        tps = (n_steps * batch * seq_len * jax.process_count()
               / sum(on_dts))
        fpt = transformer_train_flops_per_token(
            loop_on.n_params, loop_on.workload.num_layers,
            loop_on.workload.hidden_size, seq_len)
        row = {
            "name": name,
            "tokens_per_sec_per_chip": round(tps / jax.device_count(), 1),
            "steps_per_s": round(on_sps, 4),
            "mfu": round(mfu(tps, fpt), 4),
            "n_params": loop_on.n_params,
            "batch": batch, "microbatch": microbatch or batch,
            "seq_len": seq_len, "remat": False,
            "prefetch_depth": prefetch_depth, "dispatch_lag": dispatch_lag,
            "compile_s": round(loop_on.compile_time_s or 0.0, 3),
            "first_step_s": round(first_step_s, 3),
            "time_to_first_step_s": round(loop_on.time_to_first_step_s
                                          or 0.0, 3),
            "recompile_count": recompiles,
            "ab_method": "paired-interleaved",
            "ab_rounds": rounds, "ab_window_steps": window_steps,
            "ab_off_steps_per_s": round(off_sps, 4),
            "ab_delta_pct": round(delta_pct, 2),
        }
        if batch != requested_batch:
            row["ab_batch_fallback"] = True
        row.update({k: round(v, 6) for k, v in stall.items()})
        fp = loop_on.footprint()
        row.update({k: fp[k] for k in (
            "params_bytes", "opt_state_bytes",
            "opt_state_bytes_per_replica", "peak_live_bytes")})
        return row

    def measure_trace_ab(name: str, *, family: str, size: str,
                         seq_len: int, batch: int, microbatch: int = 0,
                         window_steps: int = 4, rounds: int = 6):
        """Trace-overhead guard (ISSUE 12): paired interleaved A/B at the
        headline settings between span tracing ON (obs/: one step span +
        flushed JSONL append per step, booked into a real run dir) and
        OFF (the NULL-tracer zero-cost path). Same ABBA protocol as
        measure_prefetch_ab — both loops stay alive, short timed windows
        interleave with alternating order, the delta comes from the
        position-balanced totals — because the contract is a NOISE-BAND
        claim (tracing-on within +-3% of off on this box), and sequential
        legs cannot distinguish a 1% instrumentation cost from host
        drift. The ``trace-ab-delta`` row derives from this leg's paired
        fields; the ON arm's trace shard is also sanity-checked non-empty
        (a silently disarmed tracer would 'prove' a zero overhead no one
        is paying)."""
        import shutil

        if rounds % 2:
            rounds += 1  # even rounds: ABBA position balance
        dims = dict(vocab_size=8192) if on_tpu else dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=256)
        dataset = ("synthetic-lm" if family == "gpt2"
                   else "synthetic-seq2seq")
        trace_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "trace_ab"))
        shutil.rmtree(trace_dir, ignore_errors=True)

        def build(tag: str, trace: bool) -> TrainLoop:
            # both arms get a (fresh) run dir so construction is
            # symmetric; only the tracer differs. trace is passed as an
            # explicit bool: False FORCES the control arm off even when
            # DPT_TRACE is exported (the env fallback would otherwise
            # trace both arms and "prove" a zero overhead nobody pays)
            run_dir = os.path.join(trace_dir, tag)
            os.makedirs(run_dir, exist_ok=True)
            wl = create_model_from_config(
                model_family=family, model_size=size, seq_len=seq_len,
                dtype=dtype, **dims)
            data = load_data_from_args(
                "train", batch_size=batch, dataset=dataset,
                seq_len=seq_len, vocab_size=dims["vocab_size"], seed=0,
                num_loader_proc=2)
            return TrainLoop(model=wl, data=data, batch_size=batch,
                             microbatch=microbatch or batch, lr=1e-4,
                             ema_rate="0.9999", learning_steps=0,
                             log_interval=10 ** 9, save_interval=10 ** 9,
                             mesh=make_mesh(dp=-1), checkpoint_dir=run_dir,
                             seed=0, sanitize=True, trace=trace)

        warm = 7 if on_tpu else 2

        def warmup(loop: TrainLoop) -> None:
            m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            for _ in range(warm):
                m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))

        def window(loop: TrainLoop) -> float:
            t0 = time.perf_counter()
            for _ in range(window_steps):
                m = loop.run_step(loop.next_batch())
            float(jax.device_get(m["loss"]))
            return time.perf_counter() - t0

        from distributed_pipeline_tpu.obs.trace import trace_path

        # Two live TrainLoops double the device residency (the same
        # situation measure_prefetch_ab handles): an HBM OOM halves the
        # batch and retries the PAIRED protocol instead of erroring out
        # the overhead-guard leg. The row's "batch" reports what ran.
        requested_batch = batch
        while True:
            try:
                loop_off = build("off", trace=False)
                try:
                    assert not loop_off.tracer.enabled  # a traced OFF
                    # arm would invalidate the whole comparison
                    warmup(loop_off)
                    loop_on = build("on", trace=True)
                    try:
                        warmup(loop_on)
                        off_dts: list = []
                        on_dts: list = []
                        for r in range(rounds):
                            pair = ((loop_off, off_dts), (loop_on, on_dts))
                            for loop, dts in (pair[::-1] if r % 2
                                              else pair):
                                dts.append(window(loop))
                        traced_events = 0
                        shard = trace_path(os.path.join(trace_dir, "on"),
                                           0)
                        if os.path.exists(shard):
                            with open(shard) as f:
                                traced_events = sum(
                                    1 for line in f if line.strip())
                        loop_on.tracer.close()
                    finally:
                        loop_on.stop_sanitizer()
                finally:
                    loop_off.stop_sanitizer()
            except (LegTimeout, BenchInterrupted):
                raise
            except Exception as e:
                msg = str(e)
                if (batch <= 1 or ("RESOURCE_EXHAUSTED" not in msg
                                   and "out of memory"
                                   not in msg.lower())):
                    raise
                print(f"# {name}: batch {batch} OOM with two live loops; "
                      f"retrying A/B at {batch // 2}", file=sys.stderr,
                      flush=True)
                batch //= 2
                microbatch = min(microbatch, batch) if microbatch else 0
                shutil.rmtree(trace_dir, ignore_errors=True)
                continue
            break
        n_steps = rounds * window_steps
        off_sps = n_steps / sum(off_dts)
        on_sps = n_steps / sum(on_dts)
        delta_pct = 100.0 * (sum(off_dts) / sum(on_dts) - 1.0)
        if not traced_events:
            return {"name": name,
                    "error": "trace arm wrote no events — the A/B "
                             "measured nothing (tracer disarmed?)"}
        fallback = {"ab_batch_fallback": True} \
            if batch != requested_batch else {}
        tps = (n_steps * batch * seq_len * jax.process_count()
               / sum(on_dts))
        fpt = transformer_train_flops_per_token(
            loop_on.n_params, loop_on.workload.num_layers,
            loop_on.workload.hidden_size, seq_len)
        return {
            "name": name,
            "tokens_per_sec_per_chip": round(tps / jax.device_count(), 1),
            "steps_per_s": round(on_sps, 4),
            "mfu": round(mfu(tps, fpt), 4),
            "n_params": loop_on.n_params,
            "batch": batch, "microbatch": microbatch or batch,
            "seq_len": seq_len,
            "trace_events": traced_events,
            "compile_s": round(loop_on.compile_time_s or 0.0, 3),
            "ab_method": "paired-interleaved",
            "ab_rounds": rounds, "ab_window_steps": window_steps,
            "ab_off_steps_per_s": round(off_sps, 4),
            "ab_delta_pct": round(delta_pct, 2),
            **fallback,
        }

    def measure_zero1_ab(name: str, *, batch: int, microbatch: int,
                         seq_len: int, window_steps: int, rounds: int,
                         size: str = "base", cpu_hidden: int = 256,
                         cpu_layers: int = 2, timeout_s: float = 200.0):
        """ZeRO-1 A/B leg (ISSUE 9): paired interleaved shard_optimizer
        ON/OFF at the headline shape on a >= 2-way data axis, run in a
        CHILD PROCESS (run/zero1_ab.py) so the CPU smoke box — one real
        device — still gets a dp=2 mesh via forced host devices; on TPU
        the child sees the real chips. The row's acceptance numbers:
        ``opt_bytes_replica_ratio`` ~ dp (per-replica optimizer+EMA bytes
        drop by the data-parallel factor) while ``ab_delta_pct`` stays
        inside the box noise band (steps/s parity — ZeRO-1 trades a
        per-step update all-gather for dp x less weight-update memory)
        and ``steady_recompile_count`` == 0 (pinned out_shardings: the
        sharded layout compiles exactly once).

        ``size`` selects the preset — the xl leg (ISSUE 10 satellite)
        runs the SAME protocol at the xl shape the ZeRO-1 headroom
        exists for; a child that dies (HBM OOM at xl with two live
        loops) comes back as an error row, never an abort.

        Spawn/env-pinning/timeout-folding is the tuner's shared
        child-measurement scaffold (tune/measure.py — one owner, ISSUE
        13 satellite); only the ZeRO flag set and CPU dims live here."""
        from distributed_pipeline_tpu.tune import measure as tune_measure

        args = ["--family", "diffuseq", "--size", size,
                "--batch", str(batch), "--microbatch", str(microbatch),
                "--seq_len", str(seq_len), "--dtype", dtype,
                "--window_steps", str(window_steps),
                "--rounds", str(rounds)]
        if not on_tpu:
            # Wider than the usual CPU smoke dims (hidden 256 vs 64): the
            # per-step weight-update all-gather is a fixed ~per-leaf op
            # cost on CPU, so the step must carry enough matmul for the
            # parity contract to be measurable (at hidden 64 the op
            # overhead alone reads as -15%; at 256 the delta sits inside
            # the +-3% noise band — measured on this box). The xl leg
            # scales these up so its CPU smoke row still exercises a
            # bigger-model shape than the base leg.
            args += ["--hidden", str(cpu_hidden),
                     "--layers", str(cpu_layers), "--heads", "4",
                     "--vocab", "256"]
        row = tune_measure.run_child(
            "distributed_pipeline_tpu.run.zero1_ab", args,
            env=tune_measure.child_env(None if on_tpu else 2),
            timeout_s=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            tag="zero1 A/B child")
        row["name"] = name
        return row

    def measure_tune(name: str, *, budget_s: float = 150.0,
                     timeout_s: float = 215.0, screen_steps: int = 5,
                     noise_band_pct: float = 3.0):
        """Auto-tuner acceptance leg (ISSUE 13): a SCREEN-ONLY budgeted
        layout search for the headline family on the forced-host dp=2
        CPU mesh — always the CPU tuner stack, like every robustness
        leg: it measures the control loop, not the chip. Acceptance:
        the tuner must REPRODUCE OR BEAT the hand-tuned family table's
        steps/s (the baseline candidate, measured first) within the
        box's +-3% noise band, account for every enumerated candidate
        (rejected + measured + pruned + skipped == enumerated), and the
        winner's steady recompile count must be 0."""
        import shutil

        from distributed_pipeline_tpu.tune import measure as tune_measure

        out_dir = os.path.abspath(
            os.path.join("model_checkpoints", "bench", "tune_run"))
        shutil.rmtree(out_dir, ignore_errors=True)
        args = ["--family", "diffuseq", "--n_devices", "2",
                "--screen_only", "true", "--budget_s", str(budget_s),
                "--batch_size", "8", "--microbatch", "8",
                "--seq_len", "128", "--vocab_size", "256",
                "--hidden_size", "64", "--num_layers", "2",
                "--num_heads", "4", "--dtype", "float32",
                "--screen_steps", str(screen_steps),
                "--child_timeout_s", "90",
                "--out_dir", out_dir]
        row = tune_measure.run_child(
            "distributed_pipeline_tpu.run.tune", args,
            # the tune PARENT runs on 2 forced CPU host devices too (its
            # candidate validation is arithmetic; children re-force)
            env=tune_measure.child_env(2), timeout_s=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            tag="tune leg")
        if "error" in row:
            return {"name": name, "error": row["error"]}
        fam = (row.get("families") or {}).get("diffuseq") or {}
        counts = fam.get("counts") or {}
        winner = fam.get("winner") or {}
        base_sps = fam.get("baseline_steps_per_s")
        win_sps = winner.get("steps_per_s")
        failures = []
        if fam.get("accounted") != counts.get("enumerated"):
            failures.append(
                f"trial accounting broken: {fam.get('accounted')} "
                f"accounted != {counts.get('enumerated')} enumerated")
        if not base_sps:
            failures.append("hand-tuned baseline candidate not measured")
        if not win_sps:
            failures.append("no winner measured")
        ratio = (win_sps / base_sps) if base_sps and win_sps else 0.0
        if base_sps and win_sps and \
                ratio < 1.0 - noise_band_pct / 100.0:
            failures.append(
                f"tuner lost to the hand-tuned table: winner "
                f"{win_sps} vs baseline {base_sps} steps/s "
                f"({100 * (ratio - 1):+.1f}%, band +-{noise_band_pct}%)")
        if winner and winner.get("steady_recompile_count") not in (0, None):
            failures.append(
                f"winner recompiled in steady state "
                f"({winner.get('steady_recompile_count')})")
        if failures:
            return {"name": name, "error": "; ".join(failures)[:500]}
        return {
            "name": name,
            "winner": winner.get("cid"),
            "winner_mesh": winner.get("mesh"),
            "winner_zero1": winner.get("shard_optimizer"),
            "winner_steps_per_s": win_sps,
            "baseline_steps_per_s": base_sps,
            "winner_vs_baseline": round(ratio, 4),
            "noise_band_pct": noise_band_pct,
            "enumerated": counts.get("enumerated"),
            "measured": counts.get("measured"),
            "rejected": counts.get("rejected"),
            "pruned": counts.get("pruned"),
            "skipped": counts.get("skipped"),
            "steady_recompile_count": winner.get("steady_recompile_count"),
            "tune_elapsed_s": row.get("elapsed_s"),
            "n_devices": row.get("n_devices"),
        }

    # Per-chip batch sizes are the measured MFU sweet spots on v5e (base:
    # 64/128/256/512 sweep in r2; large/gpt2 sized to fit one chip's HBM
    # with the single-EMA bench loop); tiny on CPU so smoke runs finish.
    bsz = (lambda b: b if on_tpu else 4)
    # Legs are LAZY (name, thunk) pairs so the budget guard can drop a leg
    # without paying its compile, ordered headline-first so a truncated run
    # always contains the north star.
    legs = [
        # Headline: BASELINE config 2/3 shape with the reference's DEFAULT
        # microbatch of 64 (ref config/train.py:11-12) — which the sweep
        # (16/32/64/128 at batch 256) also measures as the v5e throughput
        # optimum (76% MFU vs 68% unaccumulated: the scan's smaller
        # working set schedules better).
        ("diffuseq-base-seq128", functools.partial(
            measure, "diffuseq-base-seq128", family="diffuseq", size="base",
            seq_len=128, batch=bsz(256), microbatch=bsz(256) // 4 or 1,
            steady_steps=30 if on_tpu else 12)),
        # Steady-state A/B (ISSUE 5): the EXACT headline settings with
        # device-side double-buffered prefetch + async lagged-metrics
        # dispatch ON, measured as PAIRED INTERLEAVED windows against a
        # live prefetch-OFF twin (see measure_prefetch_ab: sequential legs
        # confound the delta with host drift). On TPU the batch transfer
        # overlaps the running step (the real win); on CPU (synchronous
        # backend) the contract is "no slower". The prefetch-ab-delta row
        # below reports the paired delta.
        ("diffuseq-base-seq128-prefetch", functools.partial(
            measure_prefetch_ab, "diffuseq-base-seq128-prefetch",
            family="diffuseq", size="base", seq_len=128, batch=bsz(256),
            microbatch=bsz(256) // 4 or 1,
            window_steps=10 if on_tpu else 4,
            rounds=6 if on_tpu else 32,
            prefetch_depth=int(os.environ.get("BENCH_PREFETCH_DEPTH", "2")),
            dispatch_lag=int(os.environ.get("BENCH_DISPATCH_LAG", "1")))),
        # ZeRO-1 A/B (ISSUE 9): the headline shape with cross-replica
        # optimizer/EMA sharding ON vs OFF, paired-interleaved in a child
        # process on a >= 2-way data axis (forced 2 host devices on the
        # CPU box; the real chips on TPU). Acceptance: per-replica
        # optimizer bytes / dp at steps/s parity, steady recompiles 0.
        ("diffuseq-base-seq128-zero1", functools.partial(
            measure_zero1_ab, "diffuseq-base-seq128-zero1",
            # CPU smoke: batch 8 unaccumulated (the child's dp=2 mesh
            # needs the microbatch divisible by 2, and the wider CPU
            # model wants the larger per-step compute — see
            # measure_zero1_ab's dims note)
            batch=256 if on_tpu else 8,
            microbatch=64 if on_tpu else 8, seq_len=128,
            window_steps=10 if on_tpu else 6,
            rounds=6 if on_tpu else 8)),
        # Fused optimizer+EMA update leg (ISSUE 18): the headline shape
        # with --fused_update (ops/fused_update.py one-pass kernel;
        # interpreter mode on CPU), landing the kernel's exact bytes/step
        # next to the staged optax chain's cost-analysis bytes
        # (acceptance: strictly below, losses bit-identical — the parity
        # suite owns the loss check, this row owns the traffic claim).
        ("diffuseq-base-seq128-fusedupd", functools.partial(
            measure, "diffuseq-base-seq128-fusedupd", family="diffuseq",
            size="base", seq_len=128, batch=bsz(256),
            microbatch=bsz(256) // 4 or 1,
            steady_steps=30 if on_tpu else 6, fused_update=True)),
        # Trace-overhead guard (ISSUE 12): span tracing ON vs OFF at the
        # headline settings, paired-interleaved like the other A/B twins.
        # The contract is a noise-band claim — tracing must cost within
        # +-3% on the headline leg, or it cannot be left armed on real
        # runs. The trace-ab-delta row below derives from this leg.
        ("diffuseq-base-seq128-trace", functools.partial(
            measure_trace_ab, "diffuseq-base-seq128-trace",
            family="diffuseq", size="base", seq_len=128, batch=bsz(256),
            microbatch=bsz(256) // 4 or 1,
            window_steps=10 if on_tpu else 4,
            rounds=6 if on_tpu else 32)),
        # Serving decode legs (ISSUE 7): continuous-batching decode
        # tokens/s/chip at 1 / 8 / 64 slots plus time-to-first-token,
        # through the prefill/decode AOT split + paged KV cache
        # (serving/). Early in the order so a truncated run still lands
        # the serving acceptance rows; the one-shot batch-1 twin right
        # after anchors the serve-vs-oneshot ratio on the same box.
        ("gpt2-serve-decode-b1", functools.partial(
            measure_serve, "gpt2-serve-decode-b1", slots=1,
            num_requests=5 if on_tpu else 4,
            gen_tokens=128 if on_tpu else 24,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 64)),
        ("gpt2-serve-decode-b8", functools.partial(
            measure_serve, "gpt2-serve-decode-b8", slots=8,
            num_requests=25 if on_tpu else 25,
            gen_tokens=128 if on_tpu else 24,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 64)),
        # the b64 leg ramps 64 slots full through prefill_batch-16
        # admissions, then holds occupancy across the request stream —
        # the acceptance leg for the >= 3x serve-vs-oneshot ratio
        ("gpt2-serve-decode-b64", functools.partial(
            measure_serve, "gpt2-serve-decode-b64", slots=64,
            num_requests=193 if on_tpu else 193,
            gen_tokens=128 if on_tpu else 24,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 64, prefill_batch=16)),
        # Flash-decode kernel leg (ISSUE 18): decode_impl=pallas through
        # the same continuous-batching protocol, token-identity checked
        # against an xla twin run, with the kernel's schedule-exact HBM
        # bytes/token landed next to the gather path's cost-analysis
        # bytes (acceptance: strictly below).
        ("gpt2-serve-decode-kernel", functools.partial(
            measure_serve_decode_kernel, "gpt2-serve-decode-kernel",
            slots=8, num_requests=25 if on_tpu else 6,
            gen_tokens=128 if on_tpu else 12,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 64)),
        # Speculative-decoding leg (ISSUE 20): spec_tokens=K with the
        # zero-flop ngram draft vs a decode_span=1 twin on the same
        # prompts — accepted-tokens/s ratio from dispatch amortization,
        # greedy token identity checked in-leg.
        ("gpt2-serve-spec-decode", functools.partial(
            measure_serve_spec_decode, "gpt2-serve-spec-decode",
            slots=4, num_requests=25 if on_tpu else 6,
            gen_tokens=128 if on_tpu else 160,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 256,
            spec_tokens=3 if on_tpu else 2)),
        # int8 paged-KV leg (ISSUE 20): kv_quant=int8 vs fp twin at the
        # same geometry — pool-bytes ratio <= 0.55x from the engines'
        # buffer census, kernel-schedule HBM bytes ratio, and 2x slots
        # served inside the fp pool budget.
        ("gpt2-serve-decode-int8", functools.partial(
            measure_serve_decode_int8, "gpt2-serve-decode-int8",
            slots=4, num_requests=25 if on_tpu else 6,
            gen_tokens=128 if on_tpu else 12,
            prompt_len=128 if on_tpu else 8,
            page_size=16 if on_tpu else 4,
            seq_len=1024 if on_tpu else 64)),
        ("gpt2-base-decode-oneshot-b1", functools.partial(
            measure_decode, "gpt2-base-decode-oneshot-b1",
            gen_tokens=128 if on_tpu else 24,
            batch=1, seq_len=1024 if on_tpu else 64)),
        # Chaos/goodput leg (ISSUE 8): headline-named because it proves
        # the headline WORKFLOW (elastic launcher + auto-resume + warm
        # compile cache) survives two injected kills — one mid-step, one
        # mid-checkpoint-save — with goodput >= 0.7 and zero steady-state
        # recompiles on resumed attempts. Always the CPU smoke shape: the
        # leg measures the recovery stack, not the chip. Step counts are
        # sized so useful step time dominates the ~3 attempts' fixed
        # startup+compile overhead on this box.
        # kill_step is deliberately OFF the save cadence: the 100 steps
        # since the last checkpoint are lost and re-run after resume —
        # the recompute_s share of the breakdown.
        ("diffuseq-base-seq128-chaos", functools.partial(
            measure_chaos, "diffuseq-base-seq128-chaos",
            steps=4000, save_interval=250, batch=16,
            kill_step=1600, crash_save_step=2750)),
        # Elastic + hang-watchdog leg (ISSUE 10): the failures the chaos
        # leg cannot model — a worker that WEDGES without exiting (the
        # stall_step fault; the --hang_timeout_s watchdog must detect
        # the frozen beacons and kill the ring) and a SHRUNK restart
        # (DPT_FORCE_DEVICES_PER_PROC drops the ring dp=2 -> dp=1, so
        # the resume reshards state onto the smaller mesh). Acceptance:
        # completes with goodput >= 0.6, accounted_frac == 1.0 including
        # the new hang category, watchdog kill within timeout + grace,
        # steady recompiles 0 on resumed attempts.
        ("diffuseq-base-seq128-elastic", functools.partial(
            measure_elastic, "diffuseq-base-seq128-elastic",
            steps=3000, save_interval=250, stall_step_at=1400,
            hang_timeout_s=2.0, batch=16)),
        # Auto-tuner leg (ISSUE 13): screen-only budgeted layout search
        # on the forced-host dp=2 CPU mesh — the tuner must reproduce or
        # beat the hand-tuned family table within the +-3% noise band,
        # journal every trial (accounting closed), and land a winner
        # with steady recompiles 0. Always the CPU tuner stack: the leg
        # measures the control loop, not the chip.
        ("diffuseq-base-seq128-tune", functools.partial(
            measure_tune, "diffuseq-base-seq128-tune")),
        # Serving-fleet resilience leg (ISSUE 11): 3 replicas under
        # sustained Poisson load, one kill_replica mid-request + one
        # checkpoint hot-swap; acceptance is p50/p95 TTFT SLOs under
        # load, zero dropped admitted requests, and serving
        # accounted_frac 1.0. Placed AFTER the headline glob so an
        # OOM/timeout degrades to an error row and can never block the
        # headline. (Replica workers are always CPU dev rings — this
        # leg measures the resilience stack, not the chip.)
        ("gpt2-serve-fleet-chaos", functools.partial(
            measure_serve_fleet, "gpt2-serve-fleet-chaos",
            replicas=3, requests=16, rate_rps=2.0, gen_tokens=10,
            kill_after=2, swap_after=5)),
        # MPMD pipeline leg (ISSUE 16): host-driven 1F1B across two
        # single-process stage rings with activations/grads over the
        # StageLink host relay. Acceptance: finite losses with zero
        # rewinds, the per-stage fold accounting every stage-second
        # (accounted_frac 1.0, link_wait category present), steady
        # recompiles 0. Always the CPU substrate shape — this measures
        # the MPMD runtime, not the chip.
        ("diffuseq-base-seq128-mpmd-pipe", functools.partial(
            measure_mpmd_pipe, "diffuseq-base-seq128-mpmd-pipe",
            steps=3, n_stages=2, n_microbatches=4, batch=8,
            seq_len=128)),
        # Disaggregated serving leg (ISSUE 16): prefill tier streams
        # paged-KV frames over StageLink to a decode tier on a separate
        # worker, bursty arrivals; TTFT reads against the colocated
        # gpt2-serve-decode-b8 row. Acceptance: all requests complete,
        # zero drops, BOTH tiers' ledgers hold accounted_frac 1.0.
        ("gpt2-serve-disagg", functools.partial(
            measure_serve_disagg, "gpt2-serve-disagg",
            requests=8, gen_tokens=6, rate_rps=6.0, burst_size=4)),
        # Autoscaling fleet leg (ISSUE 17): seeded diurnal traffic over
        # a shared-prefix workload, three fleet runs on one checkpoint —
        # prefix-affinity A/B arm, static-max baseline, and --replicas 1
        # under the SLO autoscaler. Acceptance: >= 1 journaled scale-up
        # AND drain-based scale-down, zero drops, p95 TTFT inside the
        # documented CPU SLO, the autoscaled replica-seconds bill below
        # static-max, affinity's fleet-wide prefix hit rate above
        # least-loaded's, and every ledger closing at accounted_frac
        # 1.0 with paid_idle booked.
        ("gpt2-serve-autoscale", functools.partial(
            measure_serve_autoscale, "gpt2-serve-autoscale",
            requests=20, rate_rps=0.8, diurnal_period_s=20.0,
            max_replicas=2, gen_tokens=8)),
        # no-accumulation variant (pure config-2 semantics)
        ("diffuseq-base-seq128-noaccum", functools.partial(
            measure, "diffuseq-base-seq128-noaccum", family="diffuseq",
            size="base", seq_len=128, batch=bsz(256))),
        # config 3 shape: large model, long sequence, +/- remat. Small
        # microbatches are the big lever at this scale (46% MFU at
        # batch=microbatch=32 -> 69.7% at batch 128/microbatch 4: the tiny
        # per-chunk working set keeps everything near the MXU while the
        # scan amortizes the optimizer/EMA); at these chunk sizes XLA's
        # dense attention beats the flash kernel, which "auto" already
        # picks below 1k context.
        ("diffuseq-large-seq512", functools.partial(
            measure, "diffuseq-large-seq512", family="diffuseq",
            size="large", seq_len=512, batch=(bsz(128), bsz(32), bsz(8)),
            microbatch=bsz(4))),
        ("diffuseq-large-seq512-remat", functools.partial(
            measure, "diffuseq-large-seq512-remat", family="diffuseq",
            size="large", seq_len=512, batch=(bsz(128), bsz(32), bsz(8)),
            microbatch=bsz(8), remat=True)),
        # config 4: the causal-LM path (different xent/attention profile);
        # microbatch 32 is its measured optimum (74.8% vs 66.7% at 128).
        ("gpt2-medium-seq128", functools.partial(
            measure, "gpt2-medium-seq128", family="gpt2", size="medium",
            seq_len=128, batch=(bsz(256), bsz(64), bsz(32)),
            microbatch=bsz(32))),
        # Long context (exceeds the BASELINE shapes): the Pallas flash
        # kernel path — "auto" picks it on TPU from 1k context — at 4k,
        # where the dense [L, L] logits would dominate HBM traffic
        # (measured 1.67x the XLA path at this shape on v5e). The CPU
        # smoke run shrinks the sequence: a 4k dense attention on one CPU
        # core takes minutes and measures nothing.
        # batch/microbatch are the r4 sweep optimum (saturates from b=32;
        # microbatch 2 beats 1 and 4 at both lengths); 1024x1024 kernel
        # blocks + the diagonal-only causal masking lifted this shape
        # 41.5% -> 49.6% MFU (PARITY.md long-context section).
        ("gpt2-base-seq4096-flash", functools.partial(
            measure, "gpt2-base-seq4096-flash", family="gpt2", size="base",
            seq_len=4096 if on_tpu else 256,
            batch=(bsz(64), bsz(16), bsz(4)), microbatch=bsz(2))),
        # Long-context curve extension: 8k context through the same flash
        # path (quadratic attention share doubles vs 4k).
        ("gpt2-base-seq8192-flash", functools.partial(
            measure, "gpt2-base-seq8192-flash", family="gpt2", size="base",
            seq_len=8192 if on_tpu else 256,
            batch=(bsz(32), bsz(8), bsz(2)), microbatch=bsz(2))),
        # MoE: 8 experts top-2 in every 2nd block — measures the one-hot
        # dispatch/combine einsum cost on real hardware (MFU against
        # ACTIVE params: only top_k experts run per token).
        ("diffuseq-base-seq128-moe8", functools.partial(
            measure, "diffuseq-base-seq128-moe8", family="diffuseq",
            size="base", seq_len=128, batch=(bsz(256), bsz(64)),
            microbatch=bsz(256) // 4 or 1, moe_experts=8, moe_top_k=2)),
        # Same MoE at capacity_factor 1.0: zero padding slots (E*C == K*L).
        # artifacts/moe_gap.py decomposes the moe8 MFU gap — at cf 1.25 the
        # expert GEMMs pay ~2x the +25% slot flops (non-power-of-two row
        # tiling), at cf 1.0 they run at dense efficiency; the knob
        # (--moe_capacity_factor) trades overflow drops for throughput.
        ("diffuseq-base-seq128-moe8-cf1", functools.partial(
            measure, "diffuseq-base-seq128-moe8-cf1", family="diffuseq",
            size="base", seq_len=128, batch=(bsz(256), bsz(64)),
            microbatch=bsz(256) // 4 or 1, moe_experts=8, moe_top_k=2,
            moe_capacity_factor=1.0)),
        # scan_layers: the stacked-weights layer scan (one traced block) —
        # quantifies the compile-time-vs-MFU tradeoff PARITY.md documents,
        # in the driver signal.
        ("diffuseq-base-seq128-scan", functools.partial(
            measure, "diffuseq-base-seq128-scan", family="diffuseq",
            size="base", seq_len=128, batch=bsz(256),
            microbatch=bsz(256) // 4 or 1, scan_layers=True)),
        # KV-cache decode throughput (generation, not training) at two
        # batch sizes — the pair anchors the batch-scaling curve (decode
        # is latency-bound per step, so tokens/s should scale near-
        # linearly with batch until the weight-streaming bandwidth wall).
        ("gpt2-base-decode128", functools.partial(
            measure_decode, "gpt2-base-decode128",
            gen_tokens=128 if on_tpu else 8,
            batch=bsz(64), seq_len=1024 if on_tpu else 64)),
        ("gpt2-base-decode128-b8", functools.partial(
            measure_decode, "gpt2-base-decode128-b8",
            gen_tokens=128 if on_tpu else 8,
            batch=8 if on_tpu else 2,
            seq_len=1024 if on_tpu else 64)),
        # First xl-preset leg (ISSUE 10 satellite, CHANGES r11 note):
        # ZeRO-1's per-replica headroom is what makes the xl shape fit a
        # chip at all, so it runs the zero1 A/B protocol at model_size
        # xl. Last in the order and budget-capped like every leg — an
        # OOM or overrun becomes an error row, never a blocked headline.
        # (CPU smoke scales the child dims up vs the base leg so the row
        # still exercises a bigger shape.)
        ("diffuseq-xl-seq128-zero1", functools.partial(
            measure_zero1_ab, "diffuseq-xl-seq128-zero1", size="xl",
            batch=64 if on_tpu else 8,
            microbatch=16 if on_tpu else 8, seq_len=128,
            window_steps=8 if on_tpu else 4,
            rounds=4 if on_tpu else 6,
            cpu_hidden=320, cpu_layers=3, timeout_s=220.0)),
    ]

    only = os.environ.get("BENCH_ONLY", "")
    if only:  # iteration filter: BENCH_ONLY=<exact name | *glob*>
        legs = select_legs(legs, only)

    # Fresh artifact per run (a crash mid-run leaves the completed prefix).
    if artifact_path:
        open(artifact_path, "w").close()

    # Bench HISTORY (ISSUE 14): unlike the per-run artifact, this file is
    # APPEND-ONLY across runs — every leg row lands here stamped with this
    # run's id, so the empty bench trajectory becomes a watched time
    # series (obs/regress.py compares the newest run against a trailing
    # baseline window). BENCH_HISTORY= (empty) disables.
    history_path = os.environ.get("BENCH_HISTORY", "bench_history.jsonl")
    run_id = f"{time.strftime('%Y%m%d-%H%M%S')}.{os.getpid()}"

    configs = []

    def emit(row: dict) -> None:
        """Record one leg NOW: final-JSON list + JSONL artifact + stderr
        + history. A later timeout/crash can only lose legs that never
        finished."""
        configs.append(row)
        if artifact_path:
            with open(artifact_path, "a") as f:
                f.write(json.dumps(row) + "\n")
        if history_path:
            try:  # history is telemetry: a read-only disk must not
                with open(history_path, "a") as f:  # sink the bench
                    f.write(json.dumps({**row, "run_id": run_id,
                                        "t": time.time()}) + "\n")
                    f.flush()
            except OSError as e:
                print(f"# bench history append failed: {e}",
                      file=sys.stderr, flush=True)
        print(f"# leg {json.dumps(row)} [t+"
              f"{time.perf_counter() - t_bench0:.0f}s]", file=sys.stderr,
              flush=True)

    # ------------------------------------------------------- hang hardening
    # The final JSON must print NO MATTER WHAT happens inside a leg (the
    # BENCH_r05 regression: the whole run wedged inside leg 1, rc=124,
    # parsed: null). Three nets, outermost last:
    #   per-leg SIGALRM cap -> SIGTERM catch -> native-hang watchdog.
    printed = threading.Lock()

    def final_payload() -> str:
        # a TRAIN row: serving rows also carry "mfu" now (the decode
        # roofline attribution), so the headline pick keys on the
        # train-schema column it actually reports
        if only:
            head = next((c for c in configs
                         if "tokens_per_sec_per_chip" in c), None)
        else:
            head = (configs[0] if configs
                    and "tokens_per_sec_per_chip" in configs[0] else None)
        if only and head is not None:
            metric = (f"tokens/sec/chip ({head['name']} [BENCH_ONLY={only}], "
                      f"{jax.devices()[0].device_kind})")
        else:
            metric = ("tokens/sec/chip (DiffuSeq-base seq128 train, "
                      f"{jax.devices()[0].device_kind})")
        return json.dumps({
            "metric": metric,
            "value": head["tokens_per_sec_per_chip"] if head else None,
            "unit": "tokens/s/chip",
            "vs_baseline": round(head["mfu"] / 0.40, 4) if head else None,
            "mfu": head["mfu"] if head else None,
            "n_params": head["n_params"] if head else None,
            "n_devices": jax.device_count(),
            "budget_s": budget_s,
            "elapsed_s": round(time.perf_counter() - t_bench0, 1),
            "compilation_cache": cache_dir,
            "configs": configs,
        })

    def print_final_once() -> None:
        if printed.acquire(blocking=False):
            print(final_payload(), flush=True)

    # The headline leg is EXEMPT from the budget skip (a bench run that
    # reports nothing is strictly worse than one that overruns a little),
    # so its hard cap gets a 120s floor — a 1s test budget must not kill
    # the one leg whose numbers are the contract. It is still capped: the
    # r5 wedge (a leg that never returns) cannot eat the driver's timeout.
    headline_cap_s = max(budget_s * 0.8, 120.0)

    # Anchored HERE — after jax import / distributed init / cache setup —
    # not at t_bench0: the per-leg SIGALRM caps are leg-start-relative, so
    # a slow startup (minutes on a TPU pod) must not let the watchdog
    # shoot a headline leg that is still inside its own hard cap.
    t_legs0 = time.perf_counter()

    def _watchdog() -> None:
        # Terminal backstop: a native call that never returns to the
        # interpreter (stuck XLA compile, wedged chip) defeats both
        # signal handlers — after the longest legitimate wall clock plus
        # 60s grace, print the completed rows and exit hard. The thread is
        # a daemon: a normal finish just abandons it.
        deadline = t_legs0 + max(budget_s, headline_cap_s) + 60.0
        while time.perf_counter() < deadline:
            time.sleep(1.0)
        try:
            print("# bench watchdog: wall clock exceeded budget inside a "
                  "leg; emitting final JSON with completed rows",
                  file=sys.stderr, flush=True)
            print_final_once()
        finally:
            # exit even if the prints raise (closed pipe): a wedged
            # process that lingers past the backstop defeats its purpose
            os._exit(3)

    threading.Thread(target=_watchdog, daemon=True).start()

    def _on_term(signum, frame):
        raise BenchInterrupted()

    prev_term = signal.signal(signal.SIGTERM, _on_term)

    try:
        try:
            for i, (name, thunk) in enumerate(legs):
                elapsed = time.perf_counter() - t_bench0
                if i > 0 and elapsed > budget_s:
                    emit({"name": name, "skipped": "budget"})
                    continue
                cap = (headline_cap_s if i == 0
                       else min(leg_budget_s, budget_s - elapsed))
                try:
                    emit(_run_capped(thunk, cap))
                except BenchInterrupted:
                    raise
                except Exception as e:
                    # One leg must not sink the others (or the final JSON
                    # line).
                    emit({"name": name,
                          "error": f"{type(e).__name__}: {e}"[:500]})
        except BenchInterrupted:
            done = {c.get("name") for c in configs}
            for name, _ in legs:
                if name not in done:
                    emit({"name": name, "skipped": "sigterm"})
            print("# bench: SIGTERM received; emitting final JSON with "
                  "completed rows", file=sys.stderr, flush=True)

        # Serving acceptance row (ISSUE 7): continuous-batched 64-slot
        # decode vs the one-shot batch-1 path, BOTH measured this run on
        # this box — the ratio the serving layer exists to move (>= 3x is
        # the acceptance bar; batch 64 amortizes the per-step weight
        # streaming that batch-1 decode pays per token).
        s64 = next((c for c in configs
                    if c.get("name") == "gpt2-serve-decode-b64"
                    and "decode_tokens_per_s_per_chip" in c), None)
        o1 = next((c for c in configs
                   if c.get("name") == "gpt2-base-decode-oneshot-b1"
                   and "decode_tokens_per_s_per_chip" in c), None)
        if s64 and o1:
            emit({"name": "serve-vs-oneshot-decode",
                  "serve_b64_tokens_per_s_per_chip":
                      s64["decode_tokens_per_s_per_chip"],
                  "oneshot_b1_tokens_per_s_per_chip":
                      o1["decode_tokens_per_s_per_chip"],
                  "ratio": round(s64["decode_tokens_per_s_per_chip"]
                                 / max(o1["decode_tokens_per_s_per_chip"],
                                       1e-9), 2)})

        # Steady-state A/B delta row: prefetch-off vs prefetch-on at
        # identical settings — the number ISSUE 5 exists to produce. Both
        # sides come from the SAME paired-interleaved leg
        # (measure_prefetch_ab), never from two legs timed minutes apart
        # on a drifting host.
        on = next((c for c in configs
                   if c.get("name") == "diffuseq-base-seq128-prefetch"
                   and "ab_delta_pct" in c), None)
        if on:
            emit({"name": "prefetch-ab-delta",
                  "off_steps_per_s": on["ab_off_steps_per_s"],
                  "on_steps_per_s": on["steps_per_s"],
                  "delta_pct": on["ab_delta_pct"],
                  "method": "paired-interleaved",
                  "rounds": on["ab_rounds"],
                  "window_steps": on["ab_window_steps"],
                  "prefetch_depth": on.get("prefetch_depth"),
                  "dispatch_lag": on.get("dispatch_lag")})

        # Trace-overhead row (ISSUE 12): tracing-off vs tracing-on at
        # identical settings from ONE paired-interleaved leg — the
        # "observability is affordable" acceptance number (|delta| within
        # the box's +-3% noise band).
        tr = next((c for c in configs
                   if c.get("name") == "diffuseq-base-seq128-trace"
                   and "ab_delta_pct" in c), None)
        if tr:
            emit({"name": "trace-ab-delta",
                  "off_steps_per_s": tr["ab_off_steps_per_s"],
                  "on_steps_per_s": tr["steps_per_s"],
                  "delta_pct": tr["ab_delta_pct"],
                  "trace_events": tr["trace_events"],
                  "method": "paired-interleaved",
                  "rounds": tr["ab_rounds"],
                  "window_steps": tr["ab_window_steps"]})

        # ZeRO-1 acceptance row (ISSUE 9): the headline-twin A/B's two
        # numbers in one place — per-replica optimizer-bytes ratio (~dp)
        # and the paired steps/s delta (parity within the noise band).
        z = next((c for c in configs
                  if c.get("name") == "diffuseq-base-seq128-zero1"
                  and "opt_bytes_replica_ratio" in c), None)
        if z:
            emit({"name": "zero1-ab-delta",
                  "off_steps_per_s": z["ab_off_steps_per_s"],
                  "on_steps_per_s": z["steps_per_s"],
                  "delta_pct": z["ab_delta_pct"],
                  "opt_bytes_replica_ratio": z["opt_bytes_replica_ratio"],
                  "dp": z["dp"],
                  "steady_recompile_count": z.get("steady_recompile_count"),
                  "method": "paired-interleaved"})

        # The headline contract holds only for a FULL leg list (legs[0] is
        # the DiffuSeq north star). Under BENCH_ONLY (iteration mode) the
        # first surviving train config — if any — is reported under its own
        # name, never as the north star. In a full run the headline value
        # must come from the headline LEG specifically: if that leg
        # errored, report null (its error row stays in configs) rather
        # than silently promoting the next leg's numbers under the
        # north-star label. (Selection logic lives in final_payload so the
        # watchdog emits the same contract.)
        print_final_once()
    except BenchInterrupted:
        # SIGTERM landed in the post-leg tail (delta-row emit / payload
        # serialization): the rows are complete, so the contract — the
        # final JSON always prints — still holds.
        print_final_once()
    finally:
        # Restored only AFTER the final print: a soft kill in the tail
        # must hit the BenchInterrupted handler above, never the default
        # action (which would end the process with no final JSON).
        signal.signal(signal.SIGTERM, prev_term)


if __name__ == "__main__":
    main()
