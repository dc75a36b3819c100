"""Benchmark: flagship Llama training throughput on the available chip(s).

Prints ONE JSON line PER CONFIG: {"metric", "value", "unit", "vs_baseline"}
where value is model FLOPs utilization (MFU) of the fused train step and
vs_baseline compares to the BASELINE.json north-star of 45% MFU (reference
fsdp2 target). BENCH_CONFIG takes a comma-separated list; on TPU it defaults
to "large,vocab128k" so the realistic-shape 128k-vocab row is a standing
headline next to the swept-shape one (the headline row stays first).

vocab128k sweep envs: BENCH_VOCAB_CHUNK / BENCH_FUSED_DTYPE /
BENCH_FUSED_UNROLL / BENCH_FUSED_BWD / BENCH_REMAT_POLICY (mirrored by
benchmarks/vocab128k_profile.py at the op level). The persistent compilation
cache lives in JAX_COMPILATION_CACHE_DIR when that is set, else in
<checkout>/.jax_cache: the second run of this script compiles from it.

The backend is the one JAX gives, in this process. A run whose platform is not
``tpu`` is a rehearsal: every line says so (``platform``, ``device_kind``,
``device_count``) and carries ``null`` as its ``*_mfu_per_chip`` value. A
config that raises still prints its failure line, and the script then exits
non-zero.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


# JSON-line schema version: bump when the line's structure changes so the
# BENCH_*.json trajectory stays machine-comparable as the detail payload
# grows. v2 = schema_version field + detail.telemetry timeline summary.
# v3 = detail.audit program-audit summary (collectives per mesh axis,
# donation aliasing, host callbacks) on every line; a dp-axis all-gather in
# the audited program fails the config's line outright.
# v4 = detail.profile (telemetry/profiler.py): when a trace capture engaged
# during a config (ACCELERATE_PROFILE_STEPS et al.), its parsed attribution
# report — compute/collective/host/idle fractions and the measured
# compute<->collective overlap — rides the line; absent otherwise.
# v5 = detail.memory (analysis/memory.py): the static HBM audit of the exact
# program each config runs — per-device bytes by class (param/opt-state/
# accum/batch/activation-workspace), dp-replicated opt-state bytes (the
# ROADMAP item 2 ZeRO target), reshard count, and the OOM verdict; the
# telemetry memory section gains predicted_peak_bytes (+ predicted_vs_
# observed where memory_stats() reports a peak).
# v6 = ZeRO lever (BENCH_ZERO=1 shards optimizer state + the weight update
# over dp): detail.zero_sharding on every line, detail.memory gains the full
# replication_findings inventory (per class x axis, savings bytes), and
# detail.audit gains zero_collectives — the update's deliberate dp
# reduce-scatter/all-gather traffic, attributed separately from violations —
# so the 1/dp opt-state drop AND the traffic that buys it are both visible
# round-over-round.
# v7 = autotuner replay (tune/; docs/tuning.md): BENCH_FROM_TUNE=<report.json>
# maps the tune winner's candidate onto this script's env levers (explicit env
# wins) and stamps detail.from_tune with the report path + winner, so a
# replayed row is distinguishable from a hand-swept one.
# v8 = program identity (analysis/fingerprint.py): detail.fingerprint on
# every line — the short content hash of the exact program this config ran
# (canonical collective/donation/dtype-flow/replication contract) plus the
# drift verdict against a committed golden when one exists for this config
# ("no-golden" otherwise) — so bench rounds are joinable to exact program
# identity, not just to flag settings.
# v9 = serving lever (BENCH_SERVING=1): detail.serving on every line — the
# serving decode wave's attribution (benchmarks/serving_decode_profile.py):
# admitted tokens per KV slot of a mixed wave whose outputs are verified
# against solo generate, chunked-vs-monolithic prefill max decode
# stall, per-request TTFT/TPOT, and the op-level paged-gather overhead the
# ROADMAP item 3 Pallas kernel will be measured against. Absent otherwise.
# v10 = Pallas kernel lever (ROADMAP item 3 shipped): BENCH_KERNELS sets the
# registry spec (ACCELERATE_KERNELS — pallas | interpret | reference, or a
# per-op map) for the config's programs, and detail.kernels on every line
# records (a) the per-op resolved backend and (b) the audited pallas_call
# inventory of the program that actually ran, so a kernel-vs-reference sweep
# is attributed op-by-op (benchmarks/kernel_profile.py is the op-level
# harness behind it).
# v11 = SLO sentinel + request traces (telemetry/slo.py / requests.py):
# detail.slo on every line — the configured targets and the
# accelerate_slo_breaches_total deltas per target accrued DURING the measured
# window (zero counts mean the window ran inside budget, absent targets mean
# nothing was armed); BENCH_SERVING=1 lines additionally gain
# detail.serving.requests — TTFT/TPOT p50/p90/max and the slowest-request
# table from the serving engine's per-request lifecycle tracer.
# v12 = disaggregated serving lever (serving_net/): BENCH_SERVING_DISAGG=1
# drives the full 3-tier rig (router + prefill + decode workers over real
# loopback HTTP/SSE — benchmarks/serving_disagg_profile.py) and embeds
# detail.serving.routing — the tier routing split and affinity hit rate,
# handoff chains/blocks/bytes shipped prefill → decode, per-tier TTFT/TPOT,
# and the bit-identical-output parity verdict vs one unified engine. Absent
# otherwise; composes with BENCH_SERVING (both land under detail.serving).
# v13 = serving chaos lever (serving_net/ fault tolerance): BENCH_SERVING_CHAOS=1
# drives the same prompt mix through a 2-decode-worker router rig twice —
# clean, then with a mid-stream worker_kill armed via the req: fault grammar
# (benchmarks/serving_chaos_profile.py) — and embeds detail.serving.chaos:
# recovered/lost request counts, the added-TTFT and added-completion-latency
# the recovered request paid under fault, the router's retry/eviction
# rollups, and the bit-identical-output verdict clean vs faulted. Absent
# otherwise; composes with the other serving levers under detail.serving.
# v14 = durable telemetry journal (telemetry/journal.py): when
# ACCELERATE_JOURNAL_DIR is armed the run finalizes a run_summary record
# (step-time quantiles, MFU, goodput fraction, TTFT/TPOT, breach/retry
# counts, fingerprint hash — `accelerate-tpu report` compares runs from it)
# and stamps detail.journal with the journal directory + per-kind record
# counts, so a bench row is joinable to its full causal timeline
# (`accelerate-tpu timeline`). Absent when journaling is off.
# v15 = decode-speed levers on the paged serving engine, one cell each:
# BENCH_SPEC=1 embeds detail.serving.spec (benchmarks/spec_decode_profile.py
# — speculative-decode waves vs baseline at bit-identical outputs, with
# acceptance rate and accepted-tokens/s), BENCH_KV_QUANT=1 embeds
# detail.serving.kv_quant (benchmarks/kv_quant_profile.py — int8 pool
# capacity_x, dequant-gather tax, output-divergence fraction), and
# BENCH_INT8_SERVING=1 embeds detail.serving.int8_serving
# (benchmarks/int8_serving_profile.py — weight-quantized serving wave vs
# default precision). All compose with the other serving levers under
# detail.serving; absent when unarmed.
# v16 = the device on every line (platform, device_kind, device_count,
# rehearsal); value and vs_baseline are null on any platform but tpu and on a
# failure line (failed: true), and a failed config makes the exit code 1.
BENCH_SCHEMA_VERSION = 16


class BenchAuditFailure(RuntimeError):
    """The audited program violates a zero-tolerance invariant; the config's
    JSON line becomes a schema'd failure carrying the audit evidence."""

    def __init__(self, message: str, audit: dict):
        super().__init__(message)
        self.audit = audit


def _resolved_kernel_backends(accelerator) -> dict:
    """{op: backend} the registry resolves for this run's spec; never raises
    (the lever must not take a row down on a registry import problem)."""
    try:
        from accelerate_tpu.ops.registry import resolved_backends

        return resolved_backends(accelerator.kernels)
    except Exception as exc:
        return {"error": f"{type(exc).__name__}: {exc}"[:200]}


def peak_flops_per_chip() -> float:
    """bf16 peak of the local chip, from the shared table in
    telemetry/timeline.py (which the MFU gauge also uses). A device that is
    not in the table has no peak, and no utilisation can be stated for it."""
    import jax

    from accelerate_tpu.telemetry.timeline import device_peak_flops

    peak = device_peak_flops(jax.devices()[0])
    if peak is None:
        raise ValueError(
            f"no bf16 peak is known for device kind "
            f"{jax.devices()[0].device_kind!r}: utilisation cannot be stated"
        )
    return peak


def device_identity() -> dict:
    """The device this run really used, as JAX reports it: on every line.
    Anywhere but on a TPU the run is a rehearsal."""
    import jax

    first = jax.devices()[0]
    return {"rehearsal": first.platform != "tpu", "platform": first.platform,
            "device_kind": first.device_kind, "device_count": jax.device_count()}


def apply_tune_winner(report_path: str):
    """BENCH_FROM_TUNE=<tune_report.json>: replay the autotuner's winner by
    mapping its candidate onto this script's env levers (docs/tuning.md).
    Explicitly-set env vars win — the replay fills gaps, it never overrides an
    operator's own sweep knobs. Returns the winner dict for the JSON line."""
    from accelerate_tpu.tune.report import load_winner

    winner = load_winner(report_path)
    # Every lever the winner defines maps to an env knob — including the
    # DISABLED/default settings: BENCH_ZERO=0 and BENCH_PREFETCH=0 are
    # expressible, so a winner that measured them off really replays them off.
    # Engaging BENCH_WINDOW even at window 1 keeps every replayed row on the
    # fixed 8+64 discipline, comparable regardless of the window.
    mapping = {
        "BENCH_WINDOW": str(int(winner.get("train_window", 1))),
        "BENCH_PREFETCH": str(int(winner.get("prefetch", 0))),
        "BENCH_ZERO": "1" if winner.get("zero_sharding") else "0",
    }
    if winner.get("remat_policy"):
        mapping["BENCH_REMAT_POLICY"] = str(winner["remat_policy"])
    if int(winner.get("vocab_chunk", 0)) > 0:
        mapping["BENCH_VOCAB_CHUNK"] = str(int(winner["vocab_chunk"]))
    preset = str(winner.get("xla_preset", "") or "")
    if preset and preset != "off":
        # PartialState installs it into LIBTPU_INIT_ARGS before backend init.
        mapping["ACCELERATE_XLA_PRESET"] = preset
    # Levers the winner leaves at the MODEL/library default have no value to
    # export — but an inherited env var would silently contradict the winner,
    # so name the conflict instead of letting the row claim a clean replay.
    winner_defaults = []
    if not winner.get("remat_policy"):
        winner_defaults.append("BENCH_REMAT_POLICY")
    if int(winner.get("vocab_chunk", 0)) <= 0:
        winner_defaults.append("BENCH_VOCAB_CHUNK")
    if not preset or preset == "off":
        winner_defaults.append("ACCELERATE_XLA_PRESET")
    applied = {}
    for key, value in mapping.items():
        if key in os.environ and os.environ[key] != value:
            print(
                f"# BENCH_FROM_TUNE: {key} already set "
                f"({os.environ[key]!r}); keeping it over the winner's "
                f"{value!r} — this row does NOT replay the winner exactly.",
                file=sys.stderr,
            )
        elif key not in os.environ:
            os.environ[key] = value
            applied[key] = value
    for key in winner_defaults:
        if key in os.environ:
            print(
                f"# BENCH_FROM_TUNE: {key} inherited as "
                f"({os.environ[key]!r}) but the winner measured the default; "
                "keeping the env — this row does NOT replay the winner "
                "exactly.",
                file=sys.stderr,
            )
    print(
        f"# BENCH_FROM_TUNE: replaying {report_path} winner "
        f"{winner} -> {applied}",
        file=sys.stderr,
    )
    return winner


def main() -> int:
    if os.environ.get("BENCH_FROM_TUNE"):
        apply_tune_winner(os.environ["BENCH_FROM_TUNE"])
    import jax

    from accelerate_tpu.utils.environment import maybe_enable_compilation_cache

    maybe_enable_compilation_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
    )
    on_tpu = jax.default_backend() == "tpu"
    modes = [
        m.strip()
        for m in os.environ.get("BENCH_CONFIG", "large,vocab128k" if on_tpu else "tiny").split(",")
        if m.strip()
    ]
    for mode in modes:
        if mode not in ("large", "ref-shape", "long", "340m", "tiny", "moe", "moe-ceiling", "vocab128k"):
            raise ValueError(
                "BENCH_CONFIG must be a comma-separated subset of "
                f"large|ref-shape|long|340m|tiny|moe|moe-ceiling|vocab128k, got {mode!r}"
            )
    failed = False
    for mode in modes:
        try:
            run_one(mode)
        except Exception as exc:  # one config failing must not mute the others
            _print_failure(mode, exc)
            failed = True
        finally:
            import gc

            gc.collect()  # drop the previous config's params before the next compile
    return int(failed)


def run_one(mode: str):
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, LlamaConfig

    if mode == "large":
        # ~740M params — tuned on-chip (PERF.md): wider-and-shallower beats
        # deep at fixed params (fewer, larger matmuls per elementwise byte),
        # adafactor's factored second moments free ~5G HBM over Adam, and
        # that headroom buys the dots-saveable remat policy. Round-4 shape
        # sweep: h2304/i9216/L7 at batch 12 measures 65.0% MFU vs the
        # round-3 h1408/L20/b8 recipe's 57.0% (flash attention both; b14
        # regresses to 63.1%, b16 OOMs at compile).
        metric_name = "llama700m_train_mfu_per_chip"
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2304,
            intermediate_size=9216,
            num_hidden_layers=7,
            num_attention_heads=18,  # head_dim 128: fills the MXU/VPU lanes
            num_key_value_heads=18,
            max_position_embeddings=1024,
            remat=True,
            remat_policy="dots_with_no_batch_dims_saveable",
        )
        batch, seq, steps, warmup = 12, 1024, 20, 3
    elif mode == "ref-shape":
        # The FIXED round-3 anchor shape (VERDICT r4 weak #2): h1408/L20/b8 is
        # a Llama-proportioned ~725M tower, held constant round-over-round so
        # framework regressions can't hide behind benchmark-shape choice. The
        # 'large' config above is the swept-best shape and may move; this one
        # must not. r3 measured 57.0% MFU here.
        metric_name = "llama725m_refshape_train_mfu_per_chip"
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=1408,
            intermediate_size=5632,
            num_hidden_layers=20,
            num_attention_heads=11,  # head_dim 128
            num_key_value_heads=11,
            max_position_embeddings=1024,
            remat=True,
            remat_policy="dots_with_no_batch_dims_saveable",
        )
        batch, seq, steps, warmup = 8, 1024, 20, 3
    elif mode == "long":
        # Long-context datapoint (VERDICT r2 #3): same ~740M wide-shallow
        # model at S=4096 through the Mosaic flash kernel with tuned tiles
        # (crossover 512 on v5e — ops/attention.py; dense at this shape
        # cannot even compile, its fp32 score matrix exceeds HBM). Same
        # tokens/step as 'large'; r4 shape sweep lifted 58.0% -> 64.6%
        # (official 20-step run; the 12-step probe measured 63.9%).
        metric_name = "llama700m_long4k_train_mfu_per_chip"
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2304,
            intermediate_size=9216,
            num_hidden_layers=7,
            num_attention_heads=18,
            num_key_value_heads=18,
            max_position_embeddings=4096,
            remat=True,
            remat_policy="dots_with_no_batch_dims_saveable",
        )
        batch, seq, steps, warmup = 3, 4096, 20, 3
    elif mode == "moe":
        # MoE datapoint (VERDICT r3 ask #2): 8-expert, top-2, Mixtral-style
        # sparsity at bench scale (946M total / ~330M active per token). Auto
        # dispatch resolves to einsum at this shape — r5 (k-collapsed routing
        # front-end) measures 42.6% active-MFU at cf1.0 / 38.3% at cf1.25,
        # b16, vs indexed 33.1 / sorted 27.7; the routing-free ceiling for
        # this tower is 59.4% (BENCH_CONFIG=moe-ceiling; full attribution in
        # PERF.md). ACCELERATE_MOE_DISPATCH overrides; BENCH_MOE_BATCH/
        # BENCH_MOE_CF/BENCH_MOE_SEQ/BENCH_MOE_REMAT sweep the envelope.
        # MFU counts ACTIVE FLOPs only (router + k experts), the standard
        # MoE accounting.
        from accelerate_tpu.models import MoELlamaConfig

        metric_name = "moe8e_train_mfu_per_chip"
        # BENCH_MOE_SHAPE=wide swaps in a Mixtral-proportioned tower (h2048,
        # head_dim 128) at roughly the same total params — the r5 ceiling
        # analysis showed the DEFAULT h1024 shape's routing-free ceiling is
        # itself 59.4%, so the 45% target is shape-bound there (PERF.md).
        wide = os.environ.get("BENCH_MOE_SHAPE") == "wide"
        # Depth override: the wide tower defaults to L3 (~0.95B params).
        moe_layers = int(os.environ.get("BENCH_MOE_LAYERS", "3" if wide else "12"))
        cfg = MoELlamaConfig(
            vocab_size=32000,
            hidden_size=2048 if wide else 1024,
            intermediate_size=5632 if wide else 2816,
            num_hidden_layers=moe_layers,
            num_attention_heads=16 if wide else 8,
            num_key_value_heads=16 if wide else 8,
            max_position_embeddings=1024,
            num_experts=8,
            moe_top_k=2,
            capacity_factor=1.25,
            remat=os.environ.get("BENCH_MOE_REMAT", "1") == "1",
            remat_policy="dots_with_no_batch_dims_saveable",
        )
        # BENCH_MOE_CF sweeps the capacity factor (1.0 = no padding headroom,
        # more drops; 4.0 = E/k drop-free); BENCH_MOE_SEQ the sequence length.
        cfg.capacity_factor = float(os.environ.get("BENCH_MOE_CF", cfg.capacity_factor))
        seq = int(os.environ.get("BENCH_MOE_SEQ", "1024"))
        cfg.max_position_embeddings = seq
        batch, steps, warmup = int(os.environ.get("BENCH_MOE_BATCH", "16")), 20, 3
    elif mode == "moe-ceiling":
        # Routing-free ceiling for the MoE config (VERDICT r4 ask #3): a DENSE
        # model with intermediate_size = k·i — the same active FLOPs per token
        # as BENCH_CONFIG=moe's router+top-2 experts, but zero routing,
        # dispatch, padding, or combine work. Its MFU is the number the MoE
        # path would measure if routing were free; the moe configs' gap to it
        # is the true routing tax (their gap to 65% is mostly the narrower
        # h1024 shape, not MoE-ness).
        metric_name = "moe_ceiling_dense_active_mfu_per_chip"
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=1024,
            intermediate_size=5632,  # k=2 experts' worth of i=2816
            num_hidden_layers=12,
            num_attention_heads=8,
            num_key_value_heads=8,
            max_position_embeddings=1024,
            remat=True,
            remat_policy="dots_with_no_batch_dims_saveable",
        )
        batch, seq, steps, warmup = int(os.environ.get("BENCH_MOE_BATCH", "16")), 1024, 20, 3
    elif mode == "vocab128k":
        # The fused vocab-chunked CE at its TARGET scale (VERDICT r4 weak #5):
        # a Llama-3.2-1B-proportioned model whose V=128k head materializes
        # B·S·V fp32 logits (2.1 GB at b4/S1024, plus backward copies) on the
        # dense path. BENCH_FUSED=0 runs the dense head for the comparison
        # row; BENCH_VOCAB_BATCH sweeps the envelope.
        fused = os.environ.get("BENCH_FUSED", "1") == "1"
        metric_name = "llama_v128k_train_mfu_per_chip"
        # Llama-3.2-1B proportions (h2048/i8192/32 heads/kv8/V=128256, tied
        # embeddings) at BENCH_VOCAB_LAYERS depth, 8 by default (~0.7B): V
        # stays full 128k because the LOGITS allocation (B·S·V fp32 = 4.2 GB
        # at b8) is what the fused loss exists to eliminate, and that is
        # depth-independent.
        # Sweep surface (PERF.md records the winning knobs, which are the
        # library defaults): BENCH_VOCAB_CHUNK tiles the vocab scan,
        # BENCH_FUSED_DTYPE=bf16 halves the chunk-exp bytes, BENCH_FUSED_BWD
        # ad|custom A/Bs the single-pass VJP, BENCH_FUSED_UNROLL unrolls the
        # chunk scan, BENCH_REMAT_POLICY swaps e.g. names_saveable in.
        cfg = LlamaConfig(
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            num_hidden_layers=int(os.environ.get("BENCH_VOCAB_LAYERS", "8")),
            num_attention_heads=32,
            num_key_value_heads=8,
            max_position_embeddings=1024,
            tie_word_embeddings=True,
            remat=True,
            remat_policy=os.environ.get(
                "BENCH_REMAT_POLICY", "dots_with_no_batch_dims_saveable"
            ),
            fused_loss=fused,
            fused_loss_chunk=int(os.environ.get("BENCH_VOCAB_CHUNK", "8192")),
            fused_loss_dtype=os.environ.get("BENCH_FUSED_DTYPE", "fp32"),
            fused_loss_unroll=int(os.environ.get("BENCH_FUSED_UNROLL", "1")),
            fused_loss_backward=os.environ.get("BENCH_FUSED_BWD", "custom"),
        )
        batch, seq, steps, warmup = int(os.environ.get("BENCH_VOCAB_BATCH", "8")), 1024, 20, 3
    elif mode == "340m":
        metric_name = "llama340m_train_mfu_per_chip"
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=1024,
            intermediate_size=4096,
            num_hidden_layers=16,
            num_attention_heads=8,
            num_key_value_heads=8,
            max_position_embeddings=1024,
            remat=True,
        )
        batch, seq, steps, warmup = 8, 1024, 20, 3
    else:
        metric_name = "llama_tiny_train_mfu_per_chip"
        cfg = LlamaConfig.tiny()
        batch, seq, steps, warmup = 8, 128, 5, 2

    from accelerate_tpu.resilience.goodput import get_ledger

    ledger = get_ledger()
    ledger.reset()  # fresh goodput window per config

    # Dispatch-amortization levers (docs/performance.md "Dispatch
    # amortization"): BENCH_WINDOW=K runs the K-step fused train window
    # (build_train_window) instead of the per-step fused program;
    # BENCH_PREFETCH=N stages batches N ahead on a background thread
    # (DeviceBatchPrefetcher). When either lever is engaged, the run executes
    # a FIXED 8 warmup + 64 measured steps so rounds at different window
    # sizes execute the same step sequence — identical final loss, and
    # detail.dispatches compares directly round-over-round.
    bench_window = int(os.environ.get("BENCH_WINDOW", "1") or 1)
    bench_prefetch = int(os.environ.get("BENCH_PREFETCH", "0") or 0)
    if bench_window < 1:
        raise ValueError(f"BENCH_WINDOW must be >= 1, got {bench_window}")
    amortized = "BENCH_WINDOW" in os.environ or bench_prefetch > 0
    if amortized:
        if 64 % bench_window or (bench_window <= 8 and 8 % bench_window):
            # A window that does not divide the fixed 8+64 budget would run a
            # DIFFERENT step sequence than other window sizes — final_loss and
            # detail.dispatches stop being comparable round-over-round.
            raise ValueError(
                f"BENCH_WINDOW={bench_window} must divide the fixed 64 measured "
                "steps (and 8 warmup steps when <= 8): use 1, 2, 4, 8, 16, 32 or 64."
            )
        warmup_disp = max(8 // bench_window, 1)
        meas_disp = max(64 // bench_window, 1)
        if bench_window > 8:
            print(
                f"# BENCH_WINDOW={bench_window}: warmup is one dispatch = "
                f"{bench_window} steps (not 8); final_loss compares only "
                "against rounds at the same window size.",
                file=sys.stderr,
            )
    else:
        warmup_disp, meas_disp = warmup, steps

    # ZeRO lever (ROADMAP item 2): BENCH_ZERO=1 shards optimizer state and
    # the weight update over dp (sweep it off/on round-over-round; the 1/dp
    # opt-state drop lands in detail.memory.replication_findings and the
    # added update traffic in detail.audit.zero_collectives).
    bench_zero = bool(int(os.environ.get("BENCH_ZERO", "0") or 0))

    # Pallas kernel lever (schema v10, ROADMAP item 3): BENCH_KERNELS sets
    # the registry spec for everything this config builds (the fused-update
    # kernel in the train step; paged_gather/paged_decode in a BENCH_SERVING
    # wave). Exported via ACCELERATE_KERNELS so subprocesses and the serving
    # profile harness resolve identically.
    bench_kernels = os.environ.get("BENCH_KERNELS", "").strip()
    if bench_kernels:
        os.environ["ACCELERATE_KERNELS"] = bench_kernels

    accelerator = Accelerator(mixed_precision="bf16")
    accelerator.zero_sharding = bench_zero or accelerator.zero_sharding
    accelerator.telemetry.timeline.reset()  # fresh step-timeline window too
    if mode == "moe":
        from accelerate_tpu.models import MoELlama

        model = MoELlama(cfg)
    else:
        model = Llama(cfg)
    model.init_params(jax.random.key(0))
    # adafactor in the large config: factored second moments cost ~0 extra HBM
    # (vs Adam's 8 bytes/param), which is what lets the dots-saveable remat
    # policy fit — the standard TPU-pretraining optimizer choice (T5/PaLM).
    tx = (
        optax.adafactor(3e-4)
        if mode in ("large", "ref-shape", "long", "moe", "moe-ceiling", "vocab128k")
        else optax.adamw(3e-4)
    )
    pmodel, popt = accelerator.prepare(model, tx)
    if bench_window > 1:
        step = accelerator.build_train_window(pmodel, popt, window=bench_window)
    else:
        step = accelerator.build_train_step(pmodel, popt)

    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    data = {"input_ids": ids, "labels": ids}

    if bench_prefetch > 0:
        from accelerate_tpu.data_loader import DeviceBatchPrefetcher

        def _stream(n=(warmup_disp + meas_disp) * bench_window):
            for _ in range(n):
                yield data

        _batches = iter(DeviceBatchPrefetcher(
            _stream(), mesh=accelerator.mesh,
            prefetch=bench_prefetch, window=bench_window,
        ))
        next_batch = lambda: next(_batches)  # noqa: E731
    elif bench_window > 1:
        window_data = {k: np.stack([v] * bench_window) for k, v in data.items()}
        next_batch = lambda: window_data  # noqa: E731
    else:
        next_batch = lambda: data  # noqa: E731

    # Program audit (analysis/audit.py): lower the exact program this config
    # will run and inspect it BEFORE spending chip time — collectives per
    # mesh axis, donation aliasing, host callbacks. The summary rides the
    # JSON line as detail.audit so program regressions (a stray dp-axis
    # all-gather, lost donation) are visible in the perf trajectory; a
    # dp-axis all-gather fails the config's line outright, like the
    # BENCH_WINDOW validation above.
    if bench_window > 1:
        audit_batch = {k: np.stack([v] * bench_window) for k, v in data.items()}
    else:
        audit_batch = data
    audit_report = accelerator.audit(step, audit_batch)
    audit_summary = audit_report.summary_dict()
    # Static HBM audit of the same lowering (schema v5 detail.memory): class
    # byte attribution, dp-replicated opt-state, and the OOM verdict travel
    # with every line; the audit also armed the timeline's predicted-peak
    # cross-check, so detail.telemetry.memory carries predicted_peak_bytes.
    memory_summary = (
        audit_report.memory.summary_dict() if audit_report.memory is not None else None
    )
    if audit_summary["dp_allgathers"]:
        raise BenchAuditFailure(
            f"program audit: {audit_summary['dp_allgathers']} all-gather(s) on "
            "the dp mesh axis inside the step body — dp-replicated data is "
            "re-materialized every step (see detail.audit)",
            audit_summary,
        )
    # Program identity (schema v8 detail.fingerprint): the canonical contract
    # of the exact program this config runs, extracted from the audit above
    # (its stashed StableHLO — no second lowering). The drift verdict engages
    # when a committed golden exists for this bench config (none are shipped
    # by default — the gated matrix lives in `accelerate-tpu fingerprint`);
    # the hash excludes the config label, so it joins bench rounds to the
    # goldens and tune rankings that lowered the identical program.
    from accelerate_tpu.analysis.fingerprint import (
        classify_drift, default_goldens_dir, drift_verdict, fingerprint_hash,
        load_golden,
    )

    fp_doc = accelerator.fingerprint(
        step, audit_batch, config=f"bench_{mode}", report=audit_report
    ).to_dict()
    golden = load_golden(default_goldens_dir(), fp_doc["config"])
    fingerprint_summary = {
        "hash": fingerprint_hash(fp_doc),
        "drift": (
            drift_verdict(classify_drift(golden, fp_doc))
            if golden is not None else "no-golden"
        ),
    }

    def _sync(x):
        # Fetch to the host: ends the timed region and yields the loss. Under
        # windowed dispatch x is the per-step K-vector — last element is the
        # newest step's loss.
        return float(np.asarray(jax.device_get(x)).reshape(-1)[-1])

    t_compile = time.perf_counter()
    loss = step(next_batch())
    _sync(loss)
    # First step ≈ trace + XLA compile (+ one step): the number the persistent
    # compilation cache collapses on re-runs. The goodput ledger's ``compile``
    # bucket books the trace, lowering and compile itself (telemetry/spans.py).
    compile_s = time.perf_counter() - t_compile
    for _ in range(warmup_disp - 1):
        loss = step(next_batch())
    _sync(loss)
    # SLO accounting (schema v11): breach counters are cumulative; snapshot
    # around the measured window so detail.slo reports the breaches THIS
    # window accrued, not the whole process's.
    from accelerate_tpu.telemetry.slo import breach_counts, slo_targets_from_env

    slo_before = breach_counts()
    t0 = time.perf_counter()
    for _ in range(meas_disp):
        loss = step(next_batch())
    final_loss = _sync(loss)  # sync end of timed region
    dt = time.perf_counter() - t0
    slo_targets = slo_targets_from_env()
    slo_breaches = {
        target: count - slo_before.get(target, 0)
        for target, count in breach_counts().items()
        if count - slo_before.get(target, 0)
    }
    # Schema contract: an ARMED target reports its delta even at zero (the
    # window ran inside budget) — only never-armed targets are absent.
    for target, key in (("step_time", "step_time_s"), ("ttft", "ttft_s"),
                        ("tpot", "tpot_s")):
        if slo_targets.get(key) is not None:
            slo_breaches.setdefault(target, 0)
    slo_summary = {"targets": slo_targets, "breaches": slo_breaches}
    steps = meas_disp * bench_window  # measured steps this config actually ran
    ledger.record_step(dt, steps=steps)

    # Which attention kernel 'auto' resolved to at this shape (driver-visible
    # evidence that the long config really engages flash; VERDICT r2 #3).
    from accelerate_tpu.ops.attention import resolve_auto_impl

    resolved_impl = resolve_auto_impl(seq, cfg.num_attention_heads, cfg.head_dim, batch=batch)

    # Health self-report (health/numerics.py): a bench row produced by a run
    # whose loss went non-finite is noise, not a measurement — flag it in the
    # JSON instead of leaving the reader to infer it from final_loss.
    from accelerate_tpu.health import finite_scalar

    finite_loss = finite_scalar(final_loss)

    steps_per_sec = steps / dt
    tokens_per_sec = steps_per_sec * batch * seq
    n_params = model.num_params()
    attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    if mode == "moe":
        # Active-params accounting: router + top-k experts per token (the
        # model's flops_per_token uses max_position_embeddings == seq here).
        flops_per_token = model.flops_per_token()
    else:
        # 6N per token fwd+bwd plus attention score/mix FLOPs.
        flops_per_token = 6 * n_params + attn_flops
    # Utilisation is a device metric: stated on a TPU only. Anywhere else the
    # run is a rehearsal and the value is null.
    identity = device_identity()
    mfu = (
        None if identity["rehearsal"]
        else tokens_per_sec * flops_per_token / (peak_flops_per_chip() * jax.device_count())
    )

    # Telemetry (telemetry/): the fused step fed the per-step timeline; its
    # summary rides each config's JSON line so step-time quantiles, transfer
    # counts, and memory travel with the MFU headline.
    telemetry_summary = accelerator.telemetry.timeline.summary()

    # Serving lever (schema v9): BENCH_SERVING=1 runs the serving decode
    # attribution wave (its own fixed shapes — benchmarks/
    # serving_decode_profile.py; BENCH_PROFILE_SMALL shrinks it) and embeds
    # the summary, so the wave's capacity and the chunked-stall ratios travel in
    # the same trajectory as the training MFU headline.
    serving_summary = None
    if os.environ.get("BENCH_SERVING", "0") == "1":
        bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "benchmarks")
        sys.path.insert(0, bench_dir)
        try:
            import serving_decode_profile

            serving_summary = serving_decode_profile.summarize()
        except Exception as exc:  # the lever must never take the row down
            serving_summary = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        finally:
            # Remove by value: the imported module prepends the repo root to
            # sys.path itself, so pop(0) would evict the wrong entry.
            try:
                sys.path.remove(bench_dir)
            except ValueError:
                pass

    # Disaggregated serving lever (schema v12): BENCH_SERVING_DISAGG=1 runs
    # the 3-tier router/prefill/decode rig over real loopback HTTP
    # (benchmarks/serving_disagg_profile.py) and embeds the routing payload
    # under detail.serving.routing — composing with BENCH_SERVING when both
    # levers are armed.
    if os.environ.get("BENCH_SERVING_DISAGG", "0") == "1":
        bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "benchmarks")
        sys.path.insert(0, bench_dir)
        try:
            import serving_disagg_profile

            routing_summary = serving_disagg_profile.summarize()
        except Exception as exc:  # the lever must never take the row down
            routing_summary = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        finally:
            try:
                sys.path.remove(bench_dir)
            except ValueError:
                pass
        serving_summary = dict(serving_summary or {})
        serving_summary["routing"] = routing_summary

    # Serving chaos lever (schema v13): BENCH_SERVING_CHAOS=1 runs the
    # clean-vs-faulted comparative rig (benchmarks/serving_chaos_profile.py
    # — mid-stream worker_kill, retry on the survivor) and embeds the
    # recovery payload under detail.serving.chaos.
    if os.environ.get("BENCH_SERVING_CHAOS", "0") == "1":
        bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "benchmarks")
        sys.path.insert(0, bench_dir)
        try:
            import serving_chaos_profile

            chaos_summary = serving_chaos_profile.summarize()
        except Exception as exc:  # the lever must never take the row down
            chaos_summary = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        finally:
            try:
                sys.path.remove(bench_dir)
            except ValueError:
                pass
        serving_summary = dict(serving_summary or {})
        serving_summary["chaos"] = chaos_summary

    # Decode-speed levers (schema v15): each embeds its own cell under
    # detail.serving so the three compounding levers — speculation, int8 KV
    # blocks, int8 weights — report independently and compose with
    # BENCH_SERVING's base wave in one trajectory.
    for lever_env, lever_key, lever_module in (
        ("BENCH_SPEC", "spec", "spec_decode_profile"),
        ("BENCH_KV_QUANT", "kv_quant", "kv_quant_profile"),
        ("BENCH_INT8_SERVING", "int8_serving", "int8_serving_profile"),
    ):
        if os.environ.get(lever_env, "0") != "1":
            continue
        bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "benchmarks")
        sys.path.insert(0, bench_dir)
        try:
            lever_summary = __import__(lever_module).summarize()
        except Exception as exc:  # the lever must never take the row down
            lever_summary = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        finally:
            try:
                sys.path.remove(bench_dir)
            except ValueError:
                pass
        serving_summary = dict(serving_summary or {})
        serving_summary[lever_key] = lever_summary

    # Durable journal (schema v14): when ACCELERATE_JOURNAL_DIR armed a
    # journal, finalize this run's run_summary record (fingerprint hash
    # joined in so `accelerate-tpu report` can flag identity changes) and
    # point the row at the journal for `accelerate-tpu timeline`.
    journal_summary = None
    try:
        from accelerate_tpu.telemetry.journal import get_journal

        _journal = get_journal()
        if _journal is not None:
            _journal.finalize_run(
                extra={"fingerprint": fingerprint_summary["hash"],
                       "config": f"bench_{mode}"}
            )
            journal_summary = {
                "dir": _journal.directory,
                "path": _journal.path,
                "records": dict(_journal.counts),
            }
    except Exception:  # the journal must never take the row down
        journal_summary = None

    print(
        json.dumps(
            {
                "metric": metric_name,
                "value": None if mfu is None else round(float(mfu), 4),
                "unit": "fraction_of_peak_bf16",
                "vs_baseline": None if mfu is None else round(float(mfu) / 0.45, 4),
                "schema_version": BENCH_SCHEMA_VERSION,
                **identity,
                "detail": {
                    "steps_per_sec": round(steps_per_sec, 3),
                    "tokens_per_sec": round(tokens_per_sec, 1),
                    "params": n_params,
                    "final_loss": round(final_loss, 4),
                    "backend": jax.default_backend(),
                    "device": str(jax.devices()[0].device_kind),
                    "seq": seq,
                    "batch": batch,
                    # Explicit model shape (VERDICT r4 weak #2): the metric's
                    # identity is (name, shape) — shape drift must be visible
                    # in the JSON, not hidden behind a stable metric name.
                    "shape": (
                        f"h{cfg.hidden_size}/i{cfg.intermediate_size}"
                        f"/L{cfg.num_hidden_layers}/a{cfg.num_attention_heads}"
                    ),
                    "attention_impl": resolved_impl,
                    "compile_s": round(compile_s, 2),
                    # Dispatch amortization: program dispatches this config's
                    # timeline saw (compile+warmup+measured; K-step windows
                    # count once) and the wall-clock the train loop spent
                    # blocked on input transfers — the two numbers the
                    # BENCH_WINDOW / BENCH_PREFETCH levers exist to shrink.
                    "dispatches": telemetry_summary["dispatches"],
                    "input_wait_s": telemetry_summary["transfers"]["input_wait_s"],
                    # Whether the ZeRO plan actually engaged for this config
                    # (requested AND dp > 1 AND something partitionable).
                    "zero_sharding": bool(
                        getattr(popt, "zero_active", False)
                    ),
                    # Kernel layer (schema v10): per-op resolved backend +
                    # the audited program's named pallas_call inventory.
                    "kernels": {
                        "spec": accelerator.kernels,
                        "backends": _resolved_kernel_backends(accelerator),
                        "inventory": audit_summary.get("kernels", {}),
                    },
                    **(
                        {"train_window": bench_window, "prefetch": bench_prefetch}
                        if amortized
                        else {}
                    ),
                    # Wall-clock classification for this config's window
                    # (resilience/goodput.py): productive step time vs
                    # compile / checkpoint / restart / rollback / hang
                    # badput. Warmup steps are unattributed and land in
                    # other_s by design.
                    "goodput": ledger.summary(),
                    "health": {"finite_final_loss": finite_loss},
                    "slo": slo_summary,
                    "telemetry": telemetry_summary,
                    "audit": audit_summary,
                    "memory": memory_summary,
                    "fingerprint": fingerprint_summary,
                    **({"journal": journal_summary} if journal_summary else {}),
                    **({"serving": serving_summary} if serving_summary else {}),
                    # Profiling (telemetry/profiler.py): present only when a
                    # trace capture engaged during this config — the capture
                    # list with each parsed attribution report (compute /
                    # collective / host / idle fractions + overlap).
                    **(
                        {"profile": telemetry_summary["profile"]}
                        if "profile" in telemetry_summary
                        else {}
                    ),
                    "compile_cache": jax.config.jax_compilation_cache_dir,
                    **(
                        {"from_tune": os.environ["BENCH_FROM_TUNE"]}
                        if os.environ.get("BENCH_FROM_TUNE")
                        else {}
                    ),
                    **(
                        {
                            "fused_loss": {
                                "enabled": cfg.fused_loss,
                                "chunk": cfg.fused_loss_chunk,
                                "dtype": cfg.fused_loss_dtype,
                                "unroll": cfg.fused_loss_unroll,
                                "backward": cfg.fused_loss_backward,
                                "remat_policy": cfg.remat_policy,
                            }
                        }
                        if mode == "vocab128k"
                        else {}
                    ),
                    **(
                        # auto resolves to einsum at this shape (S<=2048,
                        # cf<=2, no ep axis) — see ops/moe.py moe_ffn.
                        {"moe_dispatch": os.environ.get("ACCELERATE_MOE_DISPATCH", "auto:einsum")}
                        if mode == "moe"
                        else {}
                    ),
                },
            }
        )
    )


_FAIL_METRIC = {
    "large": "llama700m_train_mfu_per_chip",
    "ref-shape": "llama725m_refshape_train_mfu_per_chip",
    "long": "llama700m_long4k_train_mfu_per_chip",
    "340m": "llama340m_train_mfu_per_chip",
    "tiny": "llama_tiny_train_mfu_per_chip",
    "moe": "moe8e_train_mfu_per_chip",
    "moe-ceiling": "moe_ceiling_dense_active_mfu_per_chip",
    "vocab128k": "llama_v128k_train_mfu_per_chip",
}

def _print_failure(mode: str, exc: Exception):
    # Match the success-path metric name so a failure record lands in the same
    # series instead of looking like a gap. A failed config measured nothing:
    # its value is null, never a number under a device metric's name.
    detail = {"error": f"{type(exc).__name__}: {exc}"[:500]}
    if isinstance(exc, BenchAuditFailure):
        detail["audit"] = exc.audit  # the schema'd evidence for the failure
    try:
        identity = device_identity()
    except Exception as id_exc:  # no backend at all: say so on the line
        identity = {"rehearsal": True, "platform": None, "device_kind": None,
                    "device_count": 0, "device_error": f"{type(id_exc).__name__}: {id_exc}"[:200]}
    print(
        json.dumps(
            {
                "metric": _FAIL_METRIC.get(mode, "llama_train_mfu_per_chip"),
                "value": None,
                "unit": "fraction_of_peak_bf16",
                "vs_baseline": None,
                "schema_version": BENCH_SCHEMA_VERSION,
                "failed": True,
                **identity,
                "detail": detail,
            }
        )
    )


if __name__ == "__main__":
    try:
        rc = main()
    except Exception as exc:  # emit a parseable JSON line, then fail
        _print_failure(os.environ.get("BENCH_CONFIG", "large").split(",")[0].strip(), exc)
        rc = 1
    sys.exit(rc)
