"""`accelerate-tpu launch` — set the env contract and start worker processes.

Reference parity: ``src/accelerate/commands/launch.py:141-1198``. The reference
merges config-yaml defaults with CLI flags (:993-1174) then dispatches to
torchrun / deepspeed / xmp.spawn launchers. The JAX-native topology is simpler:

- **one process per host** owns all local chips (vs one process per GPU), so a
  single-host TPU run needs no spawning at all — we exec the script with the env
  contract set;
- **multi-host** runs exec one process too, pointing every host at the JAX
  coordinator (``ACCELERATE_COORDINATOR_ADDRESS``) — the pod runtime or gcloud
  fans the same command out to each host (reference's xla_dist ssh fan-out,
  launch.py:914-970);
- **CPU simulation** (`--cpu --num_processes N` or `--cpu_virtual_devices M`)
  spawns N local processes rendezvousing on localhost and/or exposes M virtual
  XLA host devices — the no-hardware test path (reference's gloo-on-CPU trick).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

from ..utils.constants import (
    ENV_COMPILE_CACHE_DIR,
    ENV_COORDINATOR,
    ENV_CPU,
    ENV_DEBUG_MODE,
    ENV_ELASTIC,
    ENV_FAULT_PLAN,
    ENV_FLEET_METRICS,
    ENV_FLIGHT_RING,
    ENV_GUARD_NUMERICS,
    ENV_HANDLE_PREEMPTION,
    ENV_HANG_TIMEOUT,
    ENV_JOURNAL_DIR,
    ENV_MESH_SHAPE,
    ENV_METRICS_PORT,
    ENV_MIN_DATA_PARALLEL,
    ENV_MIXED_PRECISION,
    ENV_KERNELS,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
    ENV_PROFILE_SLOW_ZSCORE,
    ENV_PROFILE_STEPS,
    ENV_DRAIN_GRACE_S,
    ENV_RESTART_ATTEMPT,
    ENV_ROUTER_ENDPOINT,
    ENV_SERVING_LEASE_TTL,
    ENV_SERVING_RETRY_BUDGET,
    ENV_SERVING_ROLE,
    ENV_SLO_STEP_TIME,
    ENV_SLO_TPOT,
    ENV_SLO_TTFT,
    ENV_SPECULATIVE_K,
    ENV_DRAFT_MODEL,
    ENV_KV_QUANT,
    ENV_SPIKE_ZSCORE,
    ENV_STRAGGLER_THRESHOLD,
    ENV_TELEMETRY,
    ENV_TRACE_RING,
    ENV_TRAIN_WINDOW,
    ENV_TUNE_BUDGET,
    ENV_XLA_PRESET,
    ENV_ZERO_SHARDING,
)
from .config_args import ClusterConfig, load_config_from_file


def launch_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = "Launch a script on TPU (or simulated CPU devices) with accelerate-tpu"
    if subparsers is not None:
        parser = subparsers.add_parser("launch", description=description, allow_abbrev=False)
    else:
        parser = argparse.ArgumentParser(
            "accelerate-tpu launch", description=description, allow_abbrev=False
        )
    parser.add_argument("--config_file", default=None, help="Config yaml to read defaults from")
    # Hardware/topology group (reference launch.py:160-258)
    parser.add_argument("--cpu", action="store_true", default=None, help="Force CPU platform")
    parser.add_argument("--num_processes", type=int, default=None, help="Total processes (hosts)")
    parser.add_argument("--num_machines", type=int, default=None, help="Number of hosts")
    parser.add_argument("--machine_rank", type=int, default=None, help="Rank of this host")
    parser.add_argument("--main_process_ip", default=None, help="JAX coordinator host IP")
    parser.add_argument("--main_process_port", type=int, default=None, help="JAX coordinator port")
    parser.add_argument(
        "--cpu_virtual_devices",
        type=int,
        default=None,
        help="Expose N virtual XLA host devices per process (CPU simulation)",
    )
    # Precision / debug
    parser.add_argument("--mixed_precision", choices=["no", "bf16", "fp16", "fp8"], default=None)
    parser.add_argument("--debug", action="store_true", default=None, help="Enable collective shape checks")
    parser.add_argument(
        "--max_restarts", type=int, default=None,
        help="Relaunch the whole process gang up to N times after a failure "
             "(full-gang restart is the TPU elastic model: collectives cannot "
             "survive a lost participant, so recovery = restart + resume from "
             "the latest checkpoint via save_state/load_state).",
    )
    # Mesh axes (reference buries these in plugin args; first-class here)
    for axis, helptext in (
        ("dp", "data-parallel size (0 = absorb remaining devices)"),
        ("fsdp", "fully-sharded (ZeRO-3-like) size"),
        ("tp", "tensor-parallel size"),
        ("pp", "pipeline-parallel size"),
        ("sp", "sequence-parallel size"),
        ("ep", "expert-parallel size"),
        ("dcn", "multi-slice count (0 = auto-detect slices)"),
    ):
        parser.add_argument(f"--{axis}_size", type=int, default=None, help=helptext)
    parser.add_argument(
        "--compile_cache_dir", default=None,
        help="Persistent XLA compilation cache directory (exported as "
             "ACCELERATE_COMPILE_CACHE_DIR; restarted jobs skip recompiles). "
             "Ignored where JAX_COMPILATION_CACHE_DIR is set.",
    )
    parser.add_argument(
        "--handle_preemption", action="store_true", default=None,
        help="Install the SIGTERM/SIGINT preemption watcher at startup "
             "(ACCELERATE_HANDLE_PREEMPTION): scripts calling "
             "Accelerator.checkpoint_on_preemption() each step then take an "
             "emergency checkpoint and exit cleanly when the platform preempts.",
    )
    parser.add_argument(
        "--fault_plan", default=None,
        help="Deterministic fault-injection plan for resilience/health drills, "
             "e.g. 'step:37=kill;step:40=loss_spike:50x;step:80=hang:600' "
             "(exported as ACCELERATE_FAULT_PLAN; see docs/resilience.md and "
             "docs/health.md for the grammar).",
    )
    parser.add_argument(
        "--elastic", action=argparse.BooleanOptionalAction, default=None,
        help="Elastic world-size training (ACCELERATE_ELASTIC): "
             "run_resilient re-forms the mesh at whatever dp degree the "
             "surviving devices support after a shrink/grow (preemption took "
             "a slice / maintenance returned one), reshards params+optimizer "
             "state onto it, and rescales gradient accumulation to preserve "
             "the global batch (docs/resilience.md 'Elastic world size'). "
             "--no-elastic pins fixed-size restarts explicitly.",
    )
    parser.add_argument(
        "--min_data_parallel", type=int, default=None,
        help="Floor for the elastic dp degree (ACCELERATE_MIN_DATA_PARALLEL): "
             "a shrink that would drop data parallelism below this refuses to "
             "re-form — the job queues for capacity instead of limping on too "
             "few replicas.",
    )
    parser.add_argument(
        "--guard_numerics", action="store_true", default=None,
        help="Always-on training-health guard (ACCELERATE_GUARD_NUMERICS): "
             "on-device finite checks of loss/grad-norm plus the loss-spike "
             "detector, driven by Accelerator.guard_step() each step "
             "(docs/health.md). The sentinel defaults on for loops that call "
             "guard_step; this flag pins it on explicitly.",
    )
    parser.add_argument(
        "--spike_zscore", type=float, default=None,
        help="Robust z-score threshold for the loss-spike detector "
             "(ACCELERATE_SPIKE_ZSCORE; library default 6.0; 0 disables).",
    )
    parser.add_argument(
        "--telemetry", action=argparse.BooleanOptionalAction, default=None,
        help="Pin the telemetry stack on (or, --no-telemetry, off) explicitly "
             "(ACCELERATE_TELEMETRY; on by default — the always-on per-step "
             "timeline, span ring, metrics registry, and straggler monitor "
             "behind Accelerator.telemetry, docs/observability.md).",
    )
    parser.add_argument(
        "--metrics_port", type=int, default=None,
        help="Serve the Prometheus metrics endpoint on this port on every "
             "worker (ACCELERATE_METRICS_PORT): /metrics exposes the shared "
             "registry — step time, tokens/s, MFU, goodput/badput classes, "
             "health trips, restarts, straggler skew. Co-located workers "
             "(CPU-sim gangs) serve on port + local_process_index.",
    )
    parser.add_argument(
        "--fleet_metrics", action=argparse.BooleanOptionalAction, default=None,
        help="Fleet metric aggregation (ACCELERATE_FLEET_METRICS): every "
             "worker registers its bound metrics endpoint in the "
             "coordination-service KV registry and the lead host scrapes "
             "them all into per-host-labeled series + fleet rollups at "
             "/fleet on its own endpoint — `accelerate-tpu top` is the "
             "console. Requires --metrics_port. --no-fleet_metrics pins it "
             "off explicitly.",
    )
    parser.add_argument(
        "--slo_step_time", type=float, default=None,
        help="SLO sentinel: target per-step wall time in seconds "
             "(ACCELERATE_SLO_STEP_TIME). Every breach books "
             "accelerate_slo_breaches_total{target=\"step_time\"}, a "
             "flight-recorder event, and a rate-limited warning. 0 scrubs an "
             "inherited value (dimension off).",
    )
    parser.add_argument(
        "--slo_ttft", type=float, default=None,
        help="SLO sentinel: serving time-to-first-token target in seconds "
             "(ACCELERATE_SLO_TTFT). Reaches ContinuousBatcher as its "
             "SLOTargets default (admission escalates at-risk prefills) and "
             "arms per-request breach booking in the request tracer. 0 "
             "scrubs an inherited value.",
    )
    parser.add_argument(
        "--slo_tpot", type=float, default=None,
        help="SLO sentinel: serving time-per-output-token target in seconds "
             "(ACCELERATE_SLO_TPOT; the decode-pacing twin of --slo_ttft). "
             "0 scrubs an inherited value.",
    )
    parser.add_argument(
        "--serving_role", default=None,
        help="Disaggregated-serving tier for the launched workers "
             "(ACCELERATE_SERVING_ROLE; docs/serving.md 'Disaggregated "
             "serving'): unified (default — each host prefills AND decodes), "
             "prefill (chunked prefill only, finished KV chains ship to a "
             "decode host), decode (decodes imported chains + short local "
             "prompts), router (no engine; admits requests and routes by "
             "prefix-cache affinity). Tri-state: unset inherits; an explicit "
             "'unified' scrubs a stale inherited role.",
    )
    parser.add_argument(
        "--router_endpoint", default=None,
        help="host:port of the serving router tier workers should announce "
             "to / clients should target (ACCELERATE_ROUTER_ENDPOINT). "
             "Tri-state: unset inherits, '' scrubs an inherited value.",
    )
    parser.add_argument(
        "--serving_retry_budget", type=float, default=None,
        help="Serving fault tolerance: how many times the router re-dispatches "
             "a failed request on a surviving worker under the SAME rid "
             "before surfacing the error (ACCELERATE_SERVING_RETRY_BUDGET; "
             "library default 2; docs/serving.md 'Failure semantics'). "
             "Tri-state per the SLO precedent: unset inherits, an explicit 0 "
             "scrubs an inherited value back to the default.",
    )
    parser.add_argument(
        "--serving_lease_ttl", type=float, default=None,
        help="Serving fault tolerance: seconds a worker's heartbeat-refreshed "
             "discovery lease stays valid without a refresh — an expired "
             "lease is an eviction (ACCELERATE_SERVING_LEASE_TTL; library "
             "default 15). Tri-state: unset inherits, an explicit 0 scrubs "
             "an inherited value back to the default.",
    )
    parser.add_argument(
        "--drain_grace_s", type=float, default=None,
        help="Serving fault tolerance: seconds a SIGTERM'd serving worker "
             "waits for in-flight requests to finish before exiting — "
             "admission stops immediately, the lease is revoked after "
             "(ACCELERATE_DRAIN_GRACE_S; library default 30). Tri-state: "
             "unset inherits, an explicit 0 scrubs an inherited value back "
             "to the default.",
    )
    parser.add_argument(
        "--speculative_k", type=int, default=None,
        help="Speculative decoding draft depth for the paged serving engine "
             "(ACCELERATE_SPECULATIVE_K; docs/serving.md 'Speculative "
             "decoding'): a draft model proposes k tokens per slot and the "
             "target verifies the whole window in one paged forward — greedy "
             "outputs stay bit-identical to non-speculative decode. "
             "Tri-state: unset inherits, an explicit 0 scrubs an inherited "
             "value (speculation off).",
    )
    parser.add_argument(
        "--draft_model", default=None,
        help="Draft model preset for speculative decoding "
             "(ACCELERATE_DRAFT_MODEL): a LlamaConfig classmethod name, e.g. "
             "'tiny' (the default when --speculative_k is set). The engine "
             "builds the draft at the target's vocab. Tri-state: unset "
             "inherits, '' scrubs an inherited value.",
    )
    parser.add_argument(
        "--kv_quant", default=None,
        help="KV-cache pool storage quantization for the paged serving "
             "engine (ACCELERATE_KV_QUANT; docs/serving.md 'Quantized KV "
             "cache'): 'int8' stores pool blocks int8 with per-token scales "
             "(~2x tokens per HBM byte; dequantized in the paged kernels' "
             "DMA step). Tri-state: unset inherits, an explicit 'off'/'none' "
             "scrubs an inherited value (full-precision pool).",
    )
    parser.add_argument(
        "--journal_dir", default=None,
        help="Durable telemetry journal directory (ACCELERATE_JOURNAL_DIR; "
             "docs/observability.md 'Telemetry journal'): each worker "
             "appends its step/span/request/flight/goodput streams to "
             "journal_<rank>.jsonl here, flushed per record so the tail "
             "survives SIGKILL; `accelerate-tpu timeline`/`report` read it "
             "back. Tri-state: unset inherits, '' scrubs an inherited value "
             "(journaling off).",
    )
    parser.add_argument(
        "--trace_ring", type=int, default=None,
        help="RequestTracer ring capacity — completed request records "
             "retained in memory (ACCELERATE_TRACE_RING; library default "
             "1024). Tri-state: unset inherits, an explicit 0 scrubs an "
             "inherited value back to the default.",
    )
    parser.add_argument(
        "--flight_ring", type=int, default=None,
        help="Flight-recorder ring size — forensic events retained for the "
             "crash dump (ACCELERATE_FLIGHT_RING; library default 2048). "
             "Tri-state: unset inherits, an explicit 0 scrubs an inherited "
             "value back to the default.",
    )
    parser.add_argument(
        "--straggler_threshold", type=float, default=None,
        help="Cross-host slowness ratio that raises a straggler alert "
             "(ACCELERATE_STRAGGLER_THRESHOLD; library default 1.5): a host "
             "whose mean step time exceeds threshold x the cross-host median "
             "is named in a rate-limited warning and the skew gauges.",
    )
    parser.add_argument(
        "--train_window", type=int, default=None,
        help="Dispatch-amortization window K (ACCELERATE_TRAIN_WINDOW): "
             "Accelerator.build_train_window fuses K full train steps into "
             "ONE compiled program per dispatch — the per-step dispatch RTT "
             "is paid once per K steps (docs/performance.md 'Dispatch "
             "amortization'). 1 = one dispatch per step.",
    )
    parser.add_argument(
        "--xla_preset", default=None,
        help="Curated XLA latency-hiding flag preset installed into "
             "LIBTPU_INIT_ARGS before backend creation "
             "(ACCELERATE_XLA_PRESET): off | latency (latency-hiding "
             "scheduler + async all-gather/reduce-scatter/collective-permute "
             "fusion) | collective_matmul (latency + windowed-einsum). "
             "Echoed into telemetry snapshots.",
    )
    parser.add_argument(
        "--zero_sharding", action=argparse.BooleanOptionalAction, default=None,
        help="Cross-replica (ZeRO-style) sharding of optimizer state and the "
             "weight update along the dp axis (ACCELERATE_ZERO_SHARDING): "
             "opt-state HBM drops to ~1/dp and the fused update lowers as "
             "reduce-scatter(grads) -> sharded clip+update -> all-gather(new "
             "params), overlapped by the --xla_preset latency schedules. "
             "Gate the win with `accelerate-tpu memcheck "
             "--replicated-opt-gib` (docs/performance.md).",
    )
    parser.add_argument(
        "--kernels", default=None,
        help="Pallas kernel-layer backend spec (ACCELERATE_KERNELS; "
             "docs/kernels.md): 'pallas' (compiled Mosaic on TPU, "
             "interpreter elsewhere), 'interpret' (force the interpreter — "
             "CPU parity testing), 'reference'/'off' (the always-available "
             "reference lowerings; an explicit off scrubs an inherited "
             "value), or a per-op map like "
             "'paged_decode=pallas,int8_matmul=off'. Resolved per op at "
             "build time by ops/registry.py.",
    )
    parser.add_argument(
        "--profile_steps", default=None,
        help="Capture an XLA trace over these training steps "
             "(ACCELERATE_PROFILE_STEPS): comma-separated 1-based inclusive "
             "ranges, e.g. '10-12' or '10-12,50'. Captures align to step "
             "(and K-step window) boundaries; overhead books as `profile` "
             "badput and the parsed attribution lands in telemetry summaries "
             "(docs/observability.md 'Profiling'). 'off' scrubs an inherited "
             "value.",
    )
    parser.add_argument(
        "--tune_budget", type=int, default=None,
        help="Short-bench trial budget for `accelerate-tpu tune` runs in the "
             "launched job's environment (ACCELERATE_TUNE_BUDGET): tri-state "
             "— unset inherits, > 0 caps the trials, an explicit 0 scrubs a "
             "stale inherited value (library default applies). See "
             "docs/tuning.md.",
    )
    parser.add_argument(
        "--profile_slow_zscore", type=float, default=None,
        help="Slow-step trace trigger (ACCELERATE_PROFILE_SLOW_ZSCORE): when "
             "a step's wall time lands this many robust sigmas (EMA+MAD "
             "z-score, health/spike.py's idiom host-side) above the recent "
             "baseline, the next steps are captured automatically. 0 "
             "disables; captures share the max-captures-per-run budget.",
    )
    parser.add_argument(
        "--hang_timeout", type=float, default=None,
        help="Hang-watchdog deadline in seconds (ACCELERATE_HANG_TIMEOUT): "
             "when no training step completes within the deadline, every "
             "thread's stack is dumped and the process exits with code 113 "
             "so --max_restarts (or the scheduler) can restart the gang "
             "instead of burning reserved chips on a deadlock.",
    )
    parser.add_argument("-m", "--module", action="store_true", help="Run script as a python module")
    parser.add_argument("training_script", help="Path to the script to launch")
    parser.add_argument(
        "training_script_args", nargs=argparse.REMAINDER, help="Arguments for the script"
    )
    if subparsers is not None:
        parser.set_defaults(func=launch_command)
    return parser


def _merge_config(args) -> ClusterConfig:
    """Merge yaml defaults with CLI flags — flags win (reference :993-1174)."""
    cfg = load_config_from_file(args.config_file) or ClusterConfig()
    for flag, attr in [
        ("cpu", "use_cpu"),
        ("num_processes", "num_processes"),
        ("num_machines", "num_machines"),
        ("machine_rank", "machine_rank"),
        ("main_process_ip", "main_process_ip"),
        ("main_process_port", "main_process_port"),
        ("cpu_virtual_devices", "cpu_virtual_devices"),
        ("mixed_precision", "mixed_precision"),
        ("debug", "debug"),
        ("dp_size", "dp_size"),
        ("fsdp_size", "fsdp_size"),
        ("tp_size", "tp_size"),
        ("pp_size", "pp_size"),
        ("sp_size", "sp_size"),
        ("ep_size", "ep_size"),
        ("dcn_size", "dcn_size"),
        ("max_restarts", "max_restarts"),
        ("compile_cache_dir", "compile_cache_dir"),
        ("handle_preemption", "handle_preemption"),
        ("fault_plan", "fault_plan"),
        ("elastic", "elastic"),
        ("min_data_parallel", "min_data_parallel"),
        ("guard_numerics", "guard_numerics"),
        ("spike_zscore", "spike_zscore"),
        ("hang_timeout", "hang_timeout"),
        ("telemetry", "telemetry"),
        ("metrics_port", "metrics_port"),
        ("straggler_threshold", "straggler_threshold"),
        ("fleet_metrics", "fleet_metrics"),
        ("slo_step_time", "slo_step_time"),
        ("slo_ttft", "slo_ttft"),
        ("slo_tpot", "slo_tpot"),
        ("serving_role", "serving_role"),
        ("router_endpoint", "router_endpoint"),
        ("serving_retry_budget", "serving_retry_budget"),
        ("serving_lease_ttl", "serving_lease_ttl"),
        ("drain_grace_s", "drain_grace_s"),
        ("speculative_k", "speculative_k"),
        ("draft_model", "draft_model"),
        ("kv_quant", "kv_quant"),
        ("journal_dir", "journal_dir"),
        ("trace_ring", "trace_ring"),
        ("flight_ring", "flight_ring"),
        ("train_window", "train_window"),
        ("xla_preset", "xla_preset"),
        ("zero_sharding", "zero_sharding"),
        ("kernels", "kernels"),
        ("profile_steps", "profile_steps"),
        ("profile_slow_zscore", "profile_slow_zscore"),
        ("tune_budget", "tune_budget"),
    ]:
        val = getattr(args, flag, None)
        if val is not None:
            setattr(cfg, attr, val)
    return cfg


def prepare_launch_env(cfg: ClusterConfig, process_id: int | None = None, attempt: int = 0) -> dict:
    """Build the ACCELERATE_* env contract (reference ``utils/launch.py:100-352``).

    ``attempt`` is the gang incarnation (0 = first launch); scripts key
    resume-vs-fresh decisions off it the way torchrun scripts use
    TORCHELASTIC_RESTART_COUNT."""
    env = dict(os.environ)
    env[ENV_RESTART_ATTEMPT] = str(attempt)
    # Make sure workers can import accelerate_tpu even without a pip install.
    import accelerate_tpu

    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(accelerate_tpu.__file__)))
    if pkg_parent not in env.get("PYTHONPATH", "").split(os.pathsep):
        env["PYTHONPATH"] = pkg_parent + os.pathsep + env.get("PYTHONPATH", "")
    env[ENV_MIXED_PRECISION] = cfg.mixed_precision
    env[ENV_MESH_SHAPE] = cfg.mesh_shape_env()
    # Per-feature sections from the guided wizard; Accelerator() reads these
    # when the corresponding constructor argument is not given.
    if cfg.gradient_accumulation_steps and cfg.gradient_accumulation_steps > 1:
        env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] = str(cfg.gradient_accumulation_steps)
    if cfg.fsdp_min_shard_size:
        env["ACCELERATE_FSDP_MIN_SHARD_SIZE"] = str(cfg.fsdp_min_shard_size)
    if cfg.fsdp_cpu_offload:
        env["ACCELERATE_FSDP_CPU_OFFLOAD"] = "1"
    if cfg.pp_schedule:
        env["ACCELERATE_PP_SCHEDULE"] = cfg.pp_schedule
    if cfg.pp_microbatches:
        env["ACCELERATE_PP_MICROBATCHES"] = str(cfg.pp_microbatches)
    if cfg.project_dir:
        env["ACCELERATE_PROJECT_DIR"] = cfg.project_dir
    if cfg.checkpoint_total_limit:
        env["ACCELERATE_CHECKPOINT_TOTAL_LIMIT"] = str(cfg.checkpoint_total_limit)
    if cfg.checkpoint_auto_naming:
        env["ACCELERATE_CHECKPOINT_AUTO_NAMING"] = "1"
    if cfg.log_with:
        env["ACCELERATE_LOG_WITH"] = cfg.log_with
    if cfg.compile_cache_dir:
        env[ENV_COMPILE_CACHE_DIR] = os.path.expanduser(cfg.compile_cache_dir)
    if cfg.handle_preemption:
        env[ENV_HANDLE_PREEMPTION] = "1"
    if cfg.fault_plan:
        env[ENV_FAULT_PLAN] = cfg.fault_plan
    # Elastic is tri-state like the health knobs: None = not configured
    # (nothing exported, run_resilient's default applies), and an explicit
    # --no-elastic must reach the workers as a disable.
    if cfg.elastic is not None:
        env[ENV_ELASTIC] = "1" if cfg.elastic else "0"
    if cfg.min_data_parallel:
        env[ENV_MIN_DATA_PARALLEL] = str(int(cfg.min_data_parallel))
    # Tri-state health knobs: None = not configured (export nothing, library
    # defaults apply); an explicit False / 0 must reach the workers as a
    # disable, not vanish behind a truthiness check.
    if cfg.guard_numerics is not None:
        env[ENV_GUARD_NUMERICS] = "1" if cfg.guard_numerics else "0"
    if cfg.spike_zscore is not None:
        env[ENV_SPIKE_ZSCORE] = str(cfg.spike_zscore)
    if cfg.hang_timeout:
        env[ENV_HANG_TIMEOUT] = str(cfg.hang_timeout)
    # Telemetry is tri-state like the health knobs: None exports nothing
    # (library default: ON), an explicit disable must reach the workers.
    if cfg.telemetry is not None:
        env[ENV_TELEMETRY] = "1" if cfg.telemetry else "0"
    if cfg.metrics_port:
        env[ENV_METRICS_PORT] = str(int(cfg.metrics_port))
    if cfg.straggler_threshold:
        env[ENV_STRAGGLER_THRESHOLD] = str(cfg.straggler_threshold)
    # Fleet aggregation is tri-state like telemetry: None exports nothing,
    # an explicit --no-fleet_metrics reaches the workers as a disable.
    if cfg.fleet_metrics is not None:
        env[ENV_FLEET_METRICS] = "1" if cfg.fleet_metrics else "0"
    # SLO targets are tri-state per the profile_slow_zscore precedent: an
    # explicit 0 must SCRUB a stale inherited value, not forward it.
    for value, env_name in ((cfg.slo_step_time, ENV_SLO_STEP_TIME),
                            (cfg.slo_ttft, ENV_SLO_TTFT),
                            (cfg.slo_tpot, ENV_SLO_TPOT)):
        if value:
            env[env_name] = str(value)
        elif value is not None:
            env.pop(env_name, None)
    # Disaggregated-serving tier (serving_net/roles.py): tri-state per the
    # xla_preset precedent — an explicit 'unified' (the library default)
    # scrubs a stale inherited role instead of forwarding it.
    if cfg.serving_role and cfg.serving_role.strip().lower() != "unified":
        env[ENV_SERVING_ROLE] = cfg.serving_role.strip().lower()
    elif cfg.serving_role is not None:
        env.pop(ENV_SERVING_ROLE, None)
    if cfg.router_endpoint and cfg.router_endpoint.strip():
        env[ENV_ROUTER_ENDPOINT] = cfg.router_endpoint.strip()
    elif cfg.router_endpoint is not None:
        env.pop(ENV_ROUTER_ENDPOINT, None)
    # Serving fault-tolerance knobs (serving_net/lease.py): tri-state per the
    # SLO precedent — an explicit 0 scrubs a stale inherited value back to
    # the library default instead of forwarding it.
    for value, env_name in (
        (cfg.serving_retry_budget, ENV_SERVING_RETRY_BUDGET),
        (cfg.serving_lease_ttl, ENV_SERVING_LEASE_TTL),
        (cfg.drain_grace_s, ENV_DRAIN_GRACE_S),
    ):
        if value:
            env[env_name] = str(value)
        elif value is not None:
            env.pop(env_name, None)
    # Speculative decoding + KV quantization (serving.py decode-speed
    # levers): tri-state per the SLO precedent — an explicit 0 / 'off'
    # scrubs a stale inherited value instead of forwarding it.
    if cfg.speculative_k and cfg.speculative_k > 0:
        env[ENV_SPECULATIVE_K] = str(int(cfg.speculative_k))
    elif cfg.speculative_k is not None:
        env.pop(ENV_SPECULATIVE_K, None)
    if cfg.draft_model and cfg.draft_model.strip():
        env[ENV_DRAFT_MODEL] = cfg.draft_model.strip()
    elif cfg.draft_model is not None:
        env.pop(ENV_DRAFT_MODEL, None)
    if cfg.kv_quant and cfg.kv_quant.strip().lower() not in ("off", "none"):
        env[ENV_KV_QUANT] = cfg.kv_quant.strip().lower()
    elif cfg.kv_quant is not None:
        env.pop(ENV_KV_QUANT, None)
    # Telemetry journal (telemetry/journal.py): tri-state per the
    # router_endpoint precedent — a path arms per-rank journaling, an
    # explicit '' scrubs a stale inherited directory (journaling off).
    if cfg.journal_dir and cfg.journal_dir.strip():
        env[ENV_JOURNAL_DIR] = os.path.expanduser(cfg.journal_dir.strip())
    elif cfg.journal_dir is not None:
        env.pop(ENV_JOURNAL_DIR, None)
    # Forensic ring capacities: tri-state per the tune_budget precedent —
    # an explicit 0 scrubs a stale inherited value back to the defaults.
    for value, env_name in ((cfg.trace_ring, ENV_TRACE_RING),
                            (cfg.flight_ring, ENV_FLIGHT_RING)):
        if value:
            env[env_name] = str(int(value))
        elif value is not None:
            env.pop(env_name, None)
    # Dispatch amortization: the window K reaches Accelerator.train_window;
    # the XLA preset is installed by PartialState BEFORE backend creation in
    # the worker (libtpu reads LIBTPU_INIT_ARGS once at init).
    if cfg.train_window and cfg.train_window > 1:
        env[ENV_TRAIN_WINDOW] = str(int(cfg.train_window))
    elif cfg.train_window is not None:
        # An explicit --train_window 1 beats a stale inherited env value —
        # env = dict(os.environ) above would otherwise forward it silently.
        env.pop(ENV_TRAIN_WINDOW, None)
    if cfg.xla_preset and cfg.xla_preset not in ("off", "none"):
        env[ENV_XLA_PRESET] = cfg.xla_preset
    elif cfg.xla_preset:
        # Same for an explicit --xla_preset off/none.
        env.pop(ENV_XLA_PRESET, None)
    # ZeRO sharding is tri-state like telemetry/elastic: None exports nothing
    # (an inherited env flows; library default off), and an explicit
    # --no-zero_sharding reaches the workers as a disable.
    if cfg.zero_sharding is not None:
        env[ENV_ZERO_SHARDING] = "1" if cfg.zero_sharding else "0"
    # Pallas kernel layer: tri-state per the xla_preset precedent — None =
    # unspecified (an inherited ACCELERATE_KERNELS flows through), an
    # explicit spec reaches the workers, and an explicit 'off'/'reference'
    # scrubs a stale inherited value (workers then run the reference
    # lowerings, the library default).
    if cfg.kernels and cfg.kernels.strip().lower() not in ("off", "none", "reference"):
        env[ENV_KERNELS] = cfg.kernels.strip()
    elif cfg.kernels is not None:
        env.pop(ENV_KERNELS, None)
    # Profiling (telemetry/profiler.py): tri-state per the telemetry
    # precedent — None exports nothing (an inherited env flows through), an
    # explicit value reaches the workers, and an explicit disable
    # ('off'/''/0) scrubs a stale inherited value.
    if cfg.profile_steps and cfg.profile_steps.strip().lower() not in ("off", "none", "0"):
        env[ENV_PROFILE_STEPS] = cfg.profile_steps.strip()
    elif cfg.profile_steps is not None:
        env.pop(ENV_PROFILE_STEPS, None)
    if cfg.profile_slow_zscore:
        env[ENV_PROFILE_SLOW_ZSCORE] = str(cfg.profile_slow_zscore)
    elif cfg.profile_slow_zscore is not None:
        env.pop(ENV_PROFILE_SLOW_ZSCORE, None)
    # Autotuner trial budget: tri-state like train_window — an explicit 0
    # ("library default") must scrub a stale inherited value, not forward it.
    if cfg.tune_budget and cfg.tune_budget > 0:
        env[ENV_TUNE_BUDGET] = str(int(cfg.tune_budget))
    elif cfg.tune_budget is not None:
        env.pop(ENV_TUNE_BUDGET, None)
    # A child inherits the JAX_PLATFORMS the user set; --cpu overrides it.
    if cfg.use_cpu:
        env[ENV_CPU] = "1"
        env["JAX_PLATFORMS"] = "cpu"
    if cfg.debug:
        env[ENV_DEBUG_MODE] = "1"
    if cfg.cpu_virtual_devices and cfg.cpu_virtual_devices > 1:
        flags = env.get("XLA_FLAGS", "")
        token = f"--xla_force_host_platform_device_count={cfg.cpu_virtual_devices}"
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags + " " + token).strip()
    nproc = max(cfg.num_processes, cfg.num_machines, 1)
    if nproc > 1:
        ip = cfg.main_process_ip or "127.0.0.1"
        port = cfg.main_process_port or 8476
        env[ENV_COORDINATOR] = f"{ip}:{port}"
        env[ENV_NUM_PROCESSES] = str(nproc)
        if process_id is not None:
            env[ENV_PROCESS_ID] = str(process_id)
            env["ACCELERATE_LOCAL_PROCESS_ID"] = str(process_id if cfg.num_machines <= 1 else 0)
    return env


def _script_cmd(args) -> list:
    cmd = [sys.executable]
    if args.module:
        cmd.append("-m")
    cmd.append(args.training_script)
    cmd.extend(args.training_script_args)
    return cmd


def simple_launcher(args, cfg: ClusterConfig) -> int:
    """Single process on this host (reference ``launch.py:778-788``)."""
    rank = cfg.machine_rank if cfg.num_machines > 1 else None
    for attempt in range(cfg.max_restarts + 1):
        env = prepare_launch_env(cfg, process_id=rank, attempt=attempt)
        proc = subprocess.run(_script_cmd(args), env=env)
        if proc.returncode == 0:
            return 0
        if attempt < cfg.max_restarts:
            print(
                f"Process failed (rc={proc.returncode}){_rc_hint(proc.returncode)}; "
                f"restart {attempt + 1}/{cfg.max_restarts} (resume from the latest "
                "checkpoint is the script's responsibility via load_state)."
            )
    return proc.returncode


def multi_process_launcher(args, cfg: ClusterConfig) -> int:
    """Spawn N local processes rendezvousing on localhost — the CPU-sim multi-host
    path (reference's multi-CPU gloo path, ``launchers.py:269-302``). On failure
    with ``max_restarts`` > 0, the WHOLE gang is relaunched: collectives cannot
    survive a lost participant, so TPU-elastic = full-gang restart + checkpoint
    resume (the torchrun-restart analog the reference delegates to)."""
    rc = 1
    for attempt in range(cfg.max_restarts + 1):
        rc = _run_gang_once(args, cfg, attempt)
        if rc == 0:
            return 0
        if attempt < cfg.max_restarts:
            print(
                f"Gang failed (rc={rc}){_rc_hint(rc)}; restarting all ranks "
                f"{attempt + 1}/{cfg.max_restarts}."
            )
    return rc


def _rc_hint(rc: int) -> str:
    """Name the exit codes with framework-defined meaning."""
    from ..health.hang import HANG_EXIT_CODE

    if rc == HANG_EXIT_CODE:
        return " [hang watchdog: no step within --hang_timeout; stacks on stderr]"
    return ""


def _run_gang_once(args, cfg: ClusterConfig, attempt: int = 0) -> int:
    import time

    nproc = cfg.num_processes
    procs = []
    for rank in range(nproc):
        env = prepare_launch_env(cfg, process_id=rank, attempt=attempt)
        procs.append(subprocess.Popen(_script_cmd(args), env=env))
    # Poll rather than wait sequentially: if one rank dies before the JAX
    # rendezvous completes, the others would block in initialize() forever —
    # kill the survivors and report the failure instead.
    rc = 0
    while True:
        codes = [p.poll() for p in procs]
        failed = [c for c in codes if c not in (None, 0)]
        if failed:
            rc = failed[0]
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                p.wait()
            break
        if all(c == 0 for c in codes):
            break
        time.sleep(0.2)
    return rc


def launch_command(args) -> None:
    cfg = _merge_config(args)
    if cfg.max_restarts < 0:
        raise ValueError(f"--max_restarts must be >= 0, got {cfg.max_restarts}")
    if cfg.fault_plan:
        # Fail a malformed plan at launch, not after every worker has paid the
        # XLA compile and hit its first checkpoint_on_preemption call. Covers
        # the health kinds (nan/loss_spike/hang) and their arguments too.
        from ..resilience.faults import FaultPlan

        FaultPlan.parse(cfg.fault_plan)
    if cfg.min_data_parallel and cfg.min_data_parallel < 1:
        raise ValueError(
            f"--min_data_parallel must be >= 1, got {cfg.min_data_parallel}"
        )
    if cfg.spike_zscore and cfg.spike_zscore < 0:
        raise ValueError(f"--spike_zscore must be >= 0, got {cfg.spike_zscore}")
    if cfg.hang_timeout and cfg.hang_timeout < 0:
        raise ValueError(f"--hang_timeout must be >= 0, got {cfg.hang_timeout}")
    if cfg.metrics_port and not (0 < cfg.metrics_port < 65536):
        raise ValueError(f"--metrics_port must be in [1, 65535], got {cfg.metrics_port}")
    if cfg.straggler_threshold and cfg.straggler_threshold < 1.0:
        raise ValueError(
            f"--straggler_threshold must be >= 1.0 (a ratio to the cross-host "
            f"median step time), got {cfg.straggler_threshold}"
        )
    for name, value in (("--slo_step_time", cfg.slo_step_time),
                        ("--slo_ttft", cfg.slo_ttft),
                        ("--slo_tpot", cfg.slo_tpot)):
        if value is not None and value < 0:
            raise ValueError(f"{name} must be >= 0 seconds (0 = off), got {value}")
    if cfg.serving_role:
        from ..serving_net.roles import SERVING_ROLES

        if cfg.serving_role.strip().lower() not in SERVING_ROLES:
            raise ValueError(
                f"--serving_role must be one of {'/'.join(SERVING_ROLES)}, "
                f"got {cfg.serving_role!r}"
            )
    for name, value in (
        ("--serving_retry_budget", cfg.serving_retry_budget),
        ("--serving_lease_ttl", cfg.serving_lease_ttl),
        ("--drain_grace_s", cfg.drain_grace_s),
    ):
        if value is not None and value < 0:
            raise ValueError(
                f"{name} must be >= 0 (0 = library default), got {value}"
            )
    for name, value in (("--trace_ring", cfg.trace_ring),
                        ("--flight_ring", cfg.flight_ring)):
        if value is not None and value < 0:
            raise ValueError(
                f"{name} must be >= 0 entries (0 = library default), got {value}"
            )
    if cfg.speculative_k is not None and cfg.speculative_k < 0:
        raise ValueError(
            f"--speculative_k must be >= 0 draft tokens (0 = off), got "
            f"{cfg.speculative_k}"
        )
    if cfg.kv_quant and cfg.kv_quant.strip().lower() not in ("int8", "off",
                                                             "none"):
        raise ValueError(
            f"--kv_quant must be int8 or off/none, got {cfg.kv_quant!r}"
        )
    from ..telemetry import metrics_port_from_env

    # An inherited ACCELERATE_METRICS_PORT of "0" means "no endpoint"
    # (the shared env-contract parser) — it must not satisfy the fleet
    # requirement just by being a non-empty string.
    if cfg.fleet_metrics and not cfg.metrics_port and metrics_port_from_env() <= 0:
        raise ValueError(
            "--fleet_metrics aggregates the workers' Prometheus endpoints, "
            "which --metrics_port starts: pass --metrics_port too (the lead "
            "host serves /fleet on its own endpoint)."
        )
    if cfg.train_window is not None and cfg.train_window < 1:
        raise ValueError(f"--train_window must be >= 1, got {cfg.train_window}")
    if cfg.tune_budget is not None and cfg.tune_budget < 0:
        raise ValueError(
            f"--tune_budget must be >= 0 (0 = library default), got "
            f"{cfg.tune_budget}"
        )
    if cfg.profile_steps:
        # Fail a malformed range grammar at launch, not mid-run when the
        # profiler first arms (the fault-plan validation precedent).
        from ..telemetry.profiler import parse_profile_steps

        parse_profile_steps(cfg.profile_steps)
    if cfg.profile_slow_zscore and cfg.profile_slow_zscore < 0:
        raise ValueError(
            f"--profile_slow_zscore must be >= 0, got {cfg.profile_slow_zscore}"
        )
    profiling_armed = (
        (cfg.profile_steps and cfg.profile_steps.strip().lower()
         not in ("off", "none", "0"))
        or (cfg.profile_slow_zscore and cfg.profile_slow_zscore > 0)
    )
    if profiling_armed and cfg.telemetry is False:
        raise ValueError(
            "--profile_steps/--profile_slow_zscore ride the telemetry step "
            "hooks, which --no-telemetry disables: the requested captures "
            "could never engage. Drop --no-telemetry (or the profiling flags)."
        )
    if cfg.xla_preset:
        # Fail an unknown preset at launch, not after every worker compiled —
        # normalize_preset_name's error enumerates the valid names (the same
        # message install_xla_preset raises inside a worker).
        from ..utils.xla_flags import normalize_preset_name

        normalize_preset_name(cfg.xla_preset)
    if cfg.kernels:
        # Same discipline for the kernel spec: parse_kernel_spec's error
        # enumerates the valid backend tokens (the message the registry
        # would raise at first build inside a worker).
        from ..ops.registry import parse_kernel_spec

        if cfg.kernels.strip().lower() not in ("off", "none", "reference"):
            parse_kernel_spec(cfg.kernels)
    if cfg.max_restarts > 0 and cfg.num_machines > 1:
        raise ValueError(
            "--max_restarts only applies to single-machine jobs: on a pod, a "
            "per-host restart cannot re-rendezvous with live ranks from the "
            "old incarnation. Restart the WHOLE pod launch (e.g. via "
            "`accelerate-tpu tpu-config` or your scheduler) and resume with "
            "load_state."
        )
    if cfg.num_machines <= 1 and cfg.num_processes > 1:
        if not cfg.main_process_ip:
            cfg.main_process_ip = "127.0.0.1"
        rc = multi_process_launcher(args, cfg)
    else:
        rc = simple_launcher(args, cfg)
    if rc:
        raise SystemExit(rc)


def main() -> None:  # pragma: no cover - thin shim
    parser = launch_command_parser()
    launch_command(parser.parse_args())


if __name__ == "__main__":  # pragma: no cover
    main()
