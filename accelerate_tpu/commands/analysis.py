"""`accelerate-tpu lint` / `audit` / `memcheck` — the static-analysis CLI.

``lint`` runs the invariant linter (analysis/lint.py) over source paths and
exits non-zero on any finding that is neither inline-suppressed nor
baselined. ``audit`` builds the tiny training config on the local backend,
lowers the fused train step (or a K-step window), and prints the program
audit report (analysis/audit.py) as JSON — exit status reflects the
zero-tolerance invariants (dp-axis all-gathers, host callbacks, donation
misses). ``memcheck`` lowers the same artifact through the static memory
auditor (analysis/memory.py) and prints the per-device HBM attribution —
param / opt-state / accum / batch / activation-workspace bytes, the
sharded-vs-replicated split per mesh axis, implicit resharding copies, and
the OOM verdict — exiting 1 on a predicted OOM (``--budget-gib`` overrides
the generation-table budget) or an over-threshold dp-replicated opt-state
footprint (``--replicated-opt-gib``). All three are pre-chip gates: they
inspect programs and source, never run a training step.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


# --------------------------------------------------------------------- lint
def lint_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Statically lint source for violations of the framework's "
        "zero-sync / shim / donation disciplines"
    )
    if subparsers is not None:
        parser = subparsers.add_parser("lint", description=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu lint", description=description)
    parser.add_argument(
        "paths", nargs="*", default=None,
        help="Files or directories to lint (default: the installed accelerate_tpu package)",
    )
    parser.add_argument(
        "--baseline", default=None,
        help="Baseline JSON of grandfathered findings (default: "
             ".accelerate-lint-baseline.json next to the scanned package or in CWD)",
    )
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="Ignore any baseline file — report every finding",
    )
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="Write the current findings to the baseline file and exit 0",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="Print the rule table and exit"
    )
    parser.add_argument(
        "--json", action="store_true", help="Machine-readable findings on stdout"
    )
    if subparsers is not None:
        parser.set_defaults(func=lint_command)
    return parser


def _default_paths() -> list:
    import accelerate_tpu

    return [os.path.dirname(os.path.abspath(accelerate_tpu.__file__))]


def _default_baseline(paths: list) -> str:
    from ..analysis.lint import DEFAULT_BASELINE_NAME

    candidates = [os.path.join(os.getcwd(), DEFAULT_BASELINE_NAME)]
    for p in paths:
        p = os.path.abspath(p)
        root = p if os.path.isdir(p) else os.path.dirname(p)
        candidates.append(os.path.join(os.path.dirname(root), DEFAULT_BASELINE_NAME))
        candidates.append(os.path.join(root, DEFAULT_BASELINE_NAME))
    for c in candidates:
        if os.path.exists(c):
            return c
    return candidates[0]


def lint_command(args) -> None:
    from ..analysis.lint import (
        RULES, lint_paths, load_baseline, write_baseline,
    )

    if args.list_rules:
        for rule in RULES:
            scope = ", ".join(rule.include) if rule.include else "whole package"
            print(f"{rule.name}\n  what:  {rule.summary}\n  fix:   {rule.remedy}"
                  f"\n  scope: {scope}\n")
        return

    paths = args.paths or _default_paths()
    baseline_path = args.baseline or _default_baseline(paths)
    baseline = set() if (args.no_baseline or args.write_baseline) else load_baseline(
        baseline_path
    )
    findings = lint_paths(paths, baseline=baseline)
    live = [f for f in findings if not f.suppressed and not f.baselined]
    suppressed = sum(1 for f in findings if f.suppressed)
    baselined = sum(1 for f in findings if f.baselined)

    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(f"wrote {len({f.key() for f in findings if not f.suppressed})} "
              f"grandfathered findings to {baseline_path}")
        return

    if args.json:
        print(json.dumps({
            "findings": [
                {"path": f.path, "rule": f.rule, "line": f.line,
                 "message": f.message}
                for f in live
            ],
            "suppressed": suppressed,
            "baselined": baselined,
        }, indent=1))
    else:
        for f in live:
            print(f.format())
        print(
            f"accelerate-lint: {len(live)} finding(s) "
            f"({suppressed} suppressed, {baselined} baselined)"
        )
    if live:
        raise SystemExit(1)


# -------------------------------------------------------------------- audit
def audit_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Build the tiny train config, lower the fused step, and audit the "
        "program: collectives per mesh axis, donation aliasing, host "
        "callbacks, dtype upcasts"
    )
    if subparsers is not None:
        parser = subparsers.add_parser("audit", description=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu audit", description=description)
    parser.add_argument(
        "--window", type=int, default=1,
        help="Audit a K-step fused train window instead of the per-step program",
    )
    parser.add_argument(
        "--batch", type=int, default=8, help="Batch rows for the lowered program"
    )
    parser.add_argument(
        "--seq", type=int, default=16, help="Sequence length for the lowered program"
    )
    parser.add_argument(
        "--threshold-mb", type=float, default=64.0,
        help="Large-intermediate report threshold (per-device MiB)",
    )
    parser.add_argument(
        "--summary", action="store_true",
        help="Print the compact summary (bench.py detail.audit form) instead "
             "of the full report",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="Machine-readable output: a schema'd verdict document "
             "({verdict, failures, report}) instead of the bare report, so "
             "the autotuner and CI consume the result without scraping "
             "stdout. Exit codes are unchanged.",
    )
    if subparsers is not None:
        parser.set_defaults(func=audit_command)
    return parser


# Schema of the ``--json`` verdict document shared by ``audit`` and
# ``memcheck``: bump when its structure changes so machine consumers (the
# autotuner, CI) can gate on compatibility.
VERDICT_SCHEMA_VERSION = 1


def _verdict_doc(command: str, failures: list, report: dict) -> dict:
    return {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "command": command,
        "verdict": "fail" if failures else "pass",
        "failures": list(failures),
        "report": report,
    }


def _build_tiny_artifact(window: int, batch_rows: int, seq: int,
                         optimizer: str = "sgd"):
    """The shared audit/memcheck fixture: the tiny training config built on
    the local backend, as a (accelerator, built_artifact, batch) triple —
    window-stacked when ``window > 1``."""
    import numpy as np
    import jax
    import optax

    from ..accelerator import Accelerator
    from ..models import Llama, LlamaConfig

    accelerator = Accelerator()
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    tx = {
        "sgd": lambda: optax.sgd(0.1),
        "adamw": lambda: optax.adamw(3e-4),
        "adafactor": lambda: optax.adafactor(3e-4),
    }[optimizer]()
    pmodel, popt = accelerator.prepare(model, tx)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch_rows, seq)
    ).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    if window > 1:
        built = accelerator.build_train_window(pmodel, popt, window=window)
        batch = {k: np.stack([v] * window) for k, v in batch.items()}
    else:
        built = accelerator.build_train_step(pmodel, popt)
    return accelerator, built, batch


def audit_command(args) -> None:
    if args.window < 1:
        raise SystemExit("--window must be >= 1")
    accelerator, built, batch = _build_tiny_artifact(args.window, args.batch, args.seq)
    report = accelerator.audit(
        built, batch,
        intermediate_threshold_bytes=int(args.threshold_mb * 1024 * 1024),
    )
    payload = report.summary_dict() if args.summary else report.to_dict()
    if getattr(args, "json", False):
        failures = [] if report.clean else [
            "program audit: zero-tolerance invariant violated "
            "(dp all-gathers / host callbacks / donation misses — see report)"
        ]
        payload = _verdict_doc("audit", failures, payload)
    print(json.dumps(payload, indent=1))
    if not report.clean:
        raise SystemExit(1)


# ----------------------------------------------------------------- memcheck
def memcheck_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Static HBM audit of the tiny train config: per-device bytes by "
        "class (param/opt-state/accum/batch/activation-workspace), "
        "sharded-vs-replicated split per mesh axis, implicit resharding "
        "copies, and an OOM-before-launch verdict. --serving audits the "
        "paged serving decode window instead (per-device KV-pool bytes "
        "against the HBM budget)."
    )
    if subparsers is not None:
        parser = subparsers.add_parser("memcheck", description=description)
    else:
        parser = argparse.ArgumentParser(
            "accelerate-tpu memcheck", description=description
        )
    parser.add_argument(
        "--window", type=int, default=1,
        help="Audit a K-step fused train window instead of the per-step program",
    )
    parser.add_argument(
        "--batch", type=int, default=8, help="Batch rows for the lowered program"
    )
    parser.add_argument(
        "--seq", type=int, default=16, help="Sequence length for the lowered program"
    )
    parser.add_argument(
        "--optimizer", choices=("adamw", "sgd", "adafactor"), default="adamw",
        help="Optimizer whose state is audited (default adamw: the "
             "2-moments-per-param worst case the replication findings target)",
    )
    parser.add_argument(
        "--budget-gib", type=float, default=None,
        help="Per-device HBM budget override (GiB); default is the chip "
             "generation's HBM x the 90%% headroom contract. Exit 1 when the "
             "predicted peak exceeds it.",
    )
    parser.add_argument(
        "--replicated-opt-gib", type=float, default=None,
        help="Exit 1 when opt-state bytes replicated on the dp axis exceed "
             "this many GiB per chip (the ZeRO-sharding acceptance gate — "
             "pair with ACCELERATE_ZERO_SHARDING=1 to prove the fix; "
             "default: report only)",
    )
    parser.add_argument(
        "--cpu-virtual-devices", type=int, default=0,
        help="Pin an N-device virtual CPU mesh before building (launcher "
             "flag's analog): dp-axis findings — the --replicated-opt-gib "
             "gate above — are vacuous on a 1-device backend, so single-"
             "host rigs need this to make the gate enforceable.",
    )
    parser.add_argument(
        "--serving", action="store_true",
        help="Audit the paged ContinuousBatcher decode window instead of the "
             "train step: predicted per-device KV-pool bytes (plus params "
             "and the gather-view workspace) gate against the HBM budget "
             "BEFORE a serving launch — the OOM-before-launch discipline for "
             "the decode path (docs/serving.md).",
    )
    parser.add_argument(
        "--serving-slots", type=int, default=4,
        help="Serving mode: engine batch slots (decode rows)",
    )
    parser.add_argument(
        "--serving-blocks", type=int, default=64,
        help="Serving mode: KV-pool blocks (per-device pool capacity = "
             "blocks x block size)",
    )
    parser.add_argument(
        "--serving-block-size", type=int, default=16,
        help="Serving mode: tokens per pool block (16 = the bf16 sublane "
             "multiple the future Pallas kernel wants)",
    )
    parser.add_argument(
        "--serving-kv-quant", choices=("none", "int8"), default="none",
        help="Serving mode: price the pool at this storage dtype. int8 "
             "stores blocks quantized with per-token f32 scales (k_scale/"
             "v_scale ride the kv_pool class), roughly doubling tokens per "
             "HBM byte — the audit prices blocks AND scales, so the budget "
             "gate covers the real layout, not the naive blocks/2 estimate.",
    )
    parser.add_argument(
        "--serving-spec-k", type=int, default=0,
        help="Serving mode: audit with speculative decoding at this draft "
             "depth. Prices the draft model's weights and its mirror KV "
             "pool (the draft_params/draft_pool classes of the verify "
             "program) — residency a spec-decode launch pays on top of the "
             "target's, and the OOM-before-launch gate must see it.",
    )
    parser.add_argument(
        "--serving-role", choices=("unified", "prefill", "decode"),
        default="unified",
        help="Serving mode: size the pool for this disaggregated tier "
             "(docs/serving.md 'Disaggregated serving'). prefill audits the "
             "chunked-prefill program instead of the decode window (a "
             "prefill host never compiles decode, so its peak excludes the "
             "decode lookahead buffers); decode audits the decode window "
             "AND gates on import headroom — the pool must hold a full "
             "complement of imported chains (slots x max_blocks_per_slot "
             "+ trash block) or chain imports from the prefill tier will "
             "be refused at runtime.",
    )
    parser.add_argument(
        "--summary", action="store_true",
        help="Print the compact summary (bench.py detail.memory form) instead "
             "of the full report",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="Machine-readable output: a schema'd verdict document "
             "({verdict, failures, report}) instead of the bare report — the "
             "failures stdout-vs-stderr split stays for humans, but machine "
             "consumers get everything in one parseable doc. Exit codes are "
             "unchanged.",
    )
    if subparsers is not None:
        parser.set_defaults(func=memcheck_command)
    return parser


def _build_serving_artifact(slots: int, blocks: int, block_size: int,
                            role: str = "unified", kv_quant: str | None = None,
                            speculative_k: int = 0):
    """The serving analog of ``_build_tiny_artifact``: a tiny paged
    ContinuousBatcher whose compiled decode window is the audited program.
    Returns ``(engine, built, args)`` — the pool rides the program's
    ``_audit_meta.memory_classes`` join as the ``kv_pool`` class. A
    ``prefill`` role audits the chunked-prefill program instead: that is
    the ONLY program a disaggregated prefill host compiles, so its peak
    deliberately excludes the decode window's lookahead buffers. With
    ``speculative_k`` the audited decode program is the verify window —
    the one that holds target pool + draft pool + both param sets live —
    so the gate prices the draft model's full residency."""
    import jax

    from ..models import Llama, LlamaConfig
    from ..serving import ContinuousBatcher

    model = Llama(LlamaConfig.tiny())
    model.init_params(jax.random.key(0))
    engine = ContinuousBatcher(
        model, batch_slots=slots, max_new_tokens=32,
        max_cache_len=blocks * block_size, bucket_sizes=(16, 32, 64),
        sync_every=4, block_size=block_size, num_blocks=blocks,
        kv_quant=kv_quant, speculative_k=speculative_k,
    )
    if role == "prefill":
        P = engine.prefill_chunk
        return engine, engine._chunk_fn(P), engine._chunk_args(P)
    if speculative_k:
        return engine, engine._spec_verify(), engine._verify_args()
    return engine, engine._decode(), engine._decode_args()


def memcheck_command(args) -> None:
    if args.window < 1:
        raise SystemExit("--window must be >= 1")
    if getattr(args, "cpu_virtual_devices", 0):
        if args.cpu_virtual_devices < 1:
            raise SystemExit("--cpu-virtual-devices must be >= 1")
        from ..utils.environment import pin_cpu_platform

        # Must precede the first backend touch (_build_tiny_artifact's
        # Accelerator() below); pin_cpu_platform documents the contract.
        pin_cpu_platform(args.cpu_virtual_devices)
    budget = int(args.budget_gib * (1 << 30)) if args.budget_gib is not None else None
    if getattr(args, "serving", False):
        from ..analysis.memory import memory_report_from_built

        role = getattr(args, "serving_role", "unified")
        kv_quant = getattr(args, "serving_kv_quant", "none")
        spec_k = getattr(args, "serving_spec_k", 0)
        engine, built, built_args = _build_serving_artifact(
            args.serving_slots, args.serving_blocks, args.serving_block_size,
            role=role, kv_quant=None if kv_quant == "none" else kv_quant,
            speculative_k=spec_k,
        )
        report = memory_report_from_built(built, *built_args, budget_bytes=budget)
        failures = []
        pool_bytes = (
            report.classes["kv_pool"].per_device_bytes
            if "kv_pool" in report.classes else 0
        )
        program = "chunked-prefill" if role == "prefill" else (
            "verify-window" if spec_k else "decode-window")
        if not report.fits:
            failures.append(
                f"predicted serving OOM: {program} peak "
                f"{report.predicted_peak_bytes} B/device (KV pool {pool_bytes} B) "
                f"exceeds budget {report.budget_bytes} B — shrink "
                "--serving-blocks/--serving-slots or raise the budget"
            )
        payload = report.summary_dict() if args.summary else report.to_dict()
        payload["kv_pool_bytes_per_device"] = pool_bytes
        payload["pool"] = engine.pool_stats()
        payload["serving_role"] = role
        if spec_k:
            # Draft residency the spec launch pays on top of the target's —
            # priced from the verify program's memory classes, not estimated.
            payload["draft_pool_bytes_per_device"] = (
                report.classes["draft_pool"].per_device_bytes
                if "draft_pool" in report.classes else 0
            )
            payload["draft_params_bytes_per_device"] = (
                report.classes["draft_params"].per_device_bytes
                if "draft_params" in report.classes else 0
            )
        if role == "decode":
            # Import headroom: a decode tier refuses a chain import
            # (serving_net/handoff.py) when the free list cannot cover the
            # exporter's reservation — worst case max_blocks_per_slot blocks
            # per slot, plus the pinned trash block. Gate it at audit time,
            # not at the first mid-traffic refusal.
            required = args.serving_slots * engine.max_blocks_per_slot + 1
            payload["import_headroom"] = {
                "pool_blocks": engine.num_blocks,
                "required_blocks": required,
                "max_blocks_per_slot": engine.max_blocks_per_slot,
            }
            if engine.num_blocks < required:
                failures.append(
                    f"decode tier lacks import headroom: pool has "
                    f"{engine.num_blocks} blocks but a full complement of "
                    f"imported chains needs {required} "
                    f"({args.serving_slots} slots x "
                    f"{engine.max_blocks_per_slot} blocks + trash) — raise "
                    "--serving-blocks or shrink --serving-slots"
                )
        if getattr(args, "json", False):
            payload = _verdict_doc("memcheck", failures, payload)
        print(json.dumps(payload, indent=1))
        if not getattr(args, "json", False):
            for f in failures:
                print(f"memcheck: {f}", file=sys.stderr)
        if failures:
            raise SystemExit(1)
        return
    accelerator, built, batch = _build_tiny_artifact(
        args.window, args.batch, args.seq, optimizer=args.optimizer
    )
    report = accelerator.memory_report(built, batch, budget_bytes=budget)
    failures = []
    if not report.fits:
        failures.append(
            f"predicted OOM: peak {report.predicted_peak_bytes} B/device "
            f"exceeds budget {report.budget_bytes} B"
        )
    if args.replicated_opt_gib is not None:
        rep = report.replicated_bytes("opt_state", "dp")
        limit = int(args.replicated_opt_gib * (1 << 30))
        if rep > limit:
            failures.append(
                f"opt_state replicated on dp: {rep} B/chip exceeds "
                f"--replicated-opt-gib {args.replicated_opt_gib}"
            )
    payload = report.summary_dict() if args.summary else report.to_dict()
    if getattr(args, "json", False):
        payload = _verdict_doc("memcheck", failures, payload)
    print(json.dumps(payload, indent=1))
    if not getattr(args, "json", False):
        for f in failures:
            print(f"memcheck: {f}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


def lint_main() -> None:
    """Console-script entry (`accelerate-tpu-lint`, pyproject [project.scripts])."""
    lint_command(lint_command_parser().parse_args())


def audit_main() -> None:
    """Console-script entry (`accelerate-tpu-audit`, pyproject [project.scripts])."""
    audit_command(audit_command_parser().parse_args())


def memcheck_main() -> None:
    """Console-script entry (`accelerate-tpu-memcheck`, pyproject [project.scripts])."""
    memcheck_command(memcheck_command_parser().parse_args())


if __name__ == "__main__":
    # Three commands share this module; `python -m` can't pick one.
    sys.exit("Run via `accelerate-tpu lint` / `audit` / `memcheck` "
             "(or the accelerate-tpu-lint / -audit / -memcheck scripts).")
