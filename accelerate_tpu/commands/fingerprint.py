"""`accelerate-tpu fingerprint` — the compiled-program drift gate.

Re-lowers the shipped builder matrix (train step / K-step window × ZeRO
sharding × fsdp/tp plans × the ContinuousBatcher decode window) on a pinned
virtual CPU mesh, extracts each program's canonical
:class:`~..analysis.fingerprint.ProgramFingerprint`, and diffs it against the
committed goldens under ``tests/goldens/``:

- ``--check`` (default): exit 1 when any config's drift classifies as a
  **violation** (new dp all-gather, host callback, narrowed/missed donation,
  grown replicated bytes, new low-precision accumulation, vanished ZeRO
  traffic) or a golden is missing. Benign-shape and improvement drifts
  report but pass — an improvement is a prompt to re-bank the golden.
- ``--update``: regenerate the goldens from HEAD — the deliberate-change
  path. Commit the diff; the golden diff IS the review surface for a
  program-contract change.
- ``--json``: one machine-readable verdict document (the audit/memcheck
  ``{verdict, failures, ...}`` shape) for CI and the autotuner.

Determinism contract: the command pins an N-virtual-device CPU mesh
(default 8 — the same rig tier-1 runs on) and scrubs the persistent compile
cache before the first backend touch, so donation is LIVE (the
``safe_donate_argnums`` CPU+cache policy would otherwise waive donor marks
and disarm the dropped-donation detector) and extraction is byte-identical
across processes and rigs. ``--keep-compile-cache`` opts out for in-process
callers that must not disturb a session cache.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# The shipped builder matrix. Tiny shapes keep the whole matrix' lower+compile
# under a minute on a CPU rig; the CONTRACT (collectives, donation, dtype
# flow, replication split) is shape-independent, so tiny pins it as well as
# large would.
_TRAIN_CONFIGS = {
    # name: (window, optimizer, zero_sharding, parallelism kwargs)
    "step": (1, "sgd", False, None),
    "step_zero": (1, "adamw", True, None),
    "window4": (4, "sgd", False, None),
    "window4_zero": (4, "adamw", True, None),
    "step_fsdp8": (1, "sgd", False, {"fsdp_size": 8}),
    "step_tp2_fsdp4": (1, "sgd", False, {"tp_size": 2, "fsdp_size": 4}),
    # Kernel-backed ZeRO step (ops/pallas/fused_update.py engaged via
    # ACCELERATE_KERNELS=interpret — the deterministic CPU-rig resolution of
    # the pallas token): its golden pins the fused-update pallas_call
    # inventory + the unchanged donation contract, so a silently vanished
    # kernel classifies as a violation.
    "step_zero_kernel": (1, "adamw", True, None),
}

# Configs extracted with the Pallas kernel layer pinned to interpret mode
# (byte-stable on the CPU fingerprint rig; the compiled-Mosaic program is a
# TPU-rig artifact the CPU goldens deliberately do not cover).
# `decode_paged_int8` pins the dequant-in-DMA gather inventory: a silently
# vanished dequant kernel classifies as a violation, not silence.
# `spec_verify` pins the speculative verify program — draft scan + one
# multi-token target forward + block-table truncation commit — whose
# donation contract (pool, draft pool, state) is the rejection-surgery seam.
_KERNEL_CONFIGS = ("step_zero_kernel", "decode_paged_kernel",
                   "decode_paged_int8", "spec_verify")

_SERVING_CONFIGS = ("decode_paged", "decode_paged_kernel", "prefill_paged",
                    "decode_paged_int8", "spec_verify")

CONFIG_NAMES = tuple(_TRAIN_CONFIGS) + _SERVING_CONFIGS


def _reset_singletons():
    from ..state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def _tiny_config():
    from ..models import LlamaConfig

    return LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2,
    )


def _train_fingerprint(name: str):
    import numpy as np
    import jax
    import optax

    from ..accelerator import Accelerator
    from ..models import Llama

    window, optimizer, zero, parallelism = _TRAIN_CONFIGS[name]
    _reset_singletons()
    kwargs = {}
    if parallelism:
        from ..parallel.mesh import ParallelismConfig

        kwargs["parallelism_config"] = ParallelismConfig(**parallelism)
    accelerator = Accelerator(**kwargs)
    if zero:
        accelerator.zero_sharding = True
    cfg = _tiny_config()
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    tx = {
        "sgd": lambda: optax.sgd(0.1),
        "adamw": lambda: optax.adamw(3e-4),
    }[optimizer]()
    pmodel, popt = accelerator.prepare(model, tx)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 16)
    ).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    if window > 1:
        built = accelerator.build_train_window(pmodel, popt, window=window)
        batch = {k: np.stack([v] * window) for k, v in batch.items()}
    else:
        built = accelerator.build_train_step(pmodel, popt)
    try:
        return accelerator.fingerprint(built, batch, config=name)
    finally:
        _reset_singletons()


def _decode_fingerprint(name: str = "decode_paged"):
    import jax

    from ..models import Llama, LlamaConfig
    from ..serving import ContinuousBatcher

    _reset_singletons()
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=1,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    # The paged decode window: its committed golden pins the block-table
    # gather inventory and the pool+state donation contract, so the
    # ROADMAP item 3 kernel swap (or any regression in the gather
    # lowering) classifies as deliberate drift, not silence. The
    # `_kernel` variant runs the Pallas chain-walk assembly
    # (op `paged_gather`) and pins its pallas_call inventory instead.
    kwargs = dict(block_size=4)
    if name == "decode_paged_int8":
        # int8 KV pool: the golden pins the dequant-in-DMA gather kernel
        # (`paged_gather_dequant_kernel`) plus the per-block scale plumbing.
        kwargs["kv_quant"] = "int8"
    if name == "spec_verify":
        # Draft == target keeps the golden self-contained (no preset drift);
        # the program contract is draft-independent.
        kwargs.update(speculative_k=2, draft_model=model)
    engine = ContinuousBatcher(
        model, batch_slots=2, max_new_tokens=4, max_cache_len=64,
        bucket_sizes=(8,), sync_every=2, **kwargs,
    )
    try:
        if name == "prefill_paged":
            # The prefill-ONLY tier's program (serving_net disaggregation):
            # a prefill host never compiles the decode window, so its
            # contract — chunked prefill writing the paged pool through the
            # block table, first-token sampling — needs its own golden.
            return engine.fingerprint_prefill(config=name)
        if name == "spec_verify":
            return engine.fingerprint_verify(config=name)
        return engine.fingerprint_decode(config=name)
    finally:
        _reset_singletons()


def extract_config(name: str):
    """Build one matrix config and extract its fingerprint. The kernel layer
    is pinned SYMMETRICALLY for every config (restored after): kernel-backed
    configs build under ACCELERATE_KERNELS=interpret (the deterministic
    CPU-rig resolution, so their goldens carry a stable pallas_call
    inventory), and every other config builds with the env SCRUBBED — an
    inherited fleet-wide kernel spec must not leak kernel-backed programs
    into the reference goldens (an `--update` run under such an env would
    otherwise corrupt 8/10 goldens and fail every clean-env `--check`)."""
    from ..utils.constants import ENV_KERNELS

    prev = os.environ.get(ENV_KERNELS)
    if name in _KERNEL_CONFIGS:
        os.environ[ENV_KERNELS] = "interpret"
    else:
        os.environ.pop(ENV_KERNELS, None)
    try:
        if name in _SERVING_CONFIGS:
            return _decode_fingerprint(name)
        if name not in _TRAIN_CONFIGS:
            raise SystemExit(
                f"unknown fingerprint config {name!r}; choose from "
                f"{', '.join(CONFIG_NAMES)}"
            )
        return _train_fingerprint(name)
    finally:
        if prev is None:
            os.environ.pop(ENV_KERNELS, None)
        else:
            os.environ[ENV_KERNELS] = prev


def run_fingerprints(configs, goldens_dir: str, update: bool = False):
    """Extract + compare (or rewrite) each config's golden.

    Returns ``(results, failures)``: ``results`` is ``{config: {hash,
    verdict, drift:[...]}}`` (verdict ``updated`` in update mode, else
    ``match`` / ``benign-shape`` / ``improvement`` / ``violation`` /
    ``missing-golden``); ``failures`` is the exit-1 list for check mode."""
    from ..analysis.fingerprint import (
        classify_drift,
        drift_verdict,
        fingerprint_hash,
        load_golden,
        write_golden,
    )

    results: dict = {}
    failures: list = []
    for name in configs:
        doc = extract_config(name).to_dict()
        digest = fingerprint_hash(doc)
        if update:
            path = write_golden(goldens_dir, doc)
            results[name] = {"hash": digest, "verdict": "updated", "golden": path,
                             "drift": []}
            continue
        golden = load_golden(goldens_dir, name)
        if golden is None:
            results[name] = {"hash": digest, "verdict": "missing-golden",
                             "drift": []}
            failures.append(
                f"{name}: no golden at {goldens_dir} — run "
                f"`accelerate-tpu fingerprint --update --configs {name}` and "
                "commit the file"
            )
            continue
        drifts = classify_drift(golden, doc)
        verdict = drift_verdict(drifts)
        results[name] = {
            "hash": digest,
            "verdict": verdict,
            "drift": [d.to_dict() for d in drifts],
        }
        if verdict == "violation":
            details = "; ".join(
                d.detail for d in drifts if d.kind == "violation"
            )
            failures.append(f"{name}: program-contract violation — {details}")
    return results, failures


# ------------------------------------------------------------------ front end
def fingerprint_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Re-lower the shipped builder matrix, extract canonical program "
        "fingerprints (collectives per mesh axis, donation contract, dtype "
        "flow, replication split), and diff against the committed goldens — "
        "exit 1 on classified violations"
    )
    if subparsers is not None:
        parser = subparsers.add_parser("fingerprint", description=description)
    else:
        parser = argparse.ArgumentParser(
            "accelerate-tpu fingerprint", description=description
        )
    parser.add_argument(
        "--check", action="store_true",
        help="Diff HEAD's fingerprints against the goldens (the default)",
    )
    parser.add_argument(
        "--update", action="store_true",
        help="Regenerate the goldens from HEAD — the deliberate-change path; "
             "commit the diff",
    )
    parser.add_argument(
        "--configs", default=None,
        help=f"Comma-separated subset of the matrix (default: all of "
             f"{','.join(CONFIG_NAMES)})",
    )
    parser.add_argument(
        "--goldens-dir", default=None,
        help="Golden directory (default: tests/goldens next to the package)",
    )
    parser.add_argument(
        "--cpu-virtual-devices", type=int, default=8,
        help="Pin an N-device virtual CPU mesh before building (default 8 — "
             "the tier-1 rig; 0 skips pinning and fingerprints the live "
             "backend, which will NOT match the committed goldens)",
    )
    parser.add_argument(
        "--keep-compile-cache", action="store_true",
        help="Do not scrub ACCELERATE_COMPILE_CACHE_DIR: donation stays "
             "platform-waived on CPU (fingerprints are policy-independent "
             "either way, but the dropped-donor detector is disarmed)",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="Machine-readable verdict document ({verdict, failures, "
             "configs}) instead of the human report; exit codes unchanged",
    )
    parser.add_argument(
        "--list-configs", action="store_true",
        help="Print the config matrix and exit",
    )
    if subparsers is not None:
        parser.set_defaults(func=fingerprint_command)
    return parser


def fingerprint_command(args) -> None:
    from ..analysis.fingerprint import default_goldens_dir

    if args.list_configs:
        for name in CONFIG_NAMES:
            if name == "decode_paged":
                print(f"{name}: paged ContinuousBatcher decode window "
                      "(block-table gather + pool scatter)")
                continue
            if name == "decode_paged_kernel":
                print(f"{name}: paged decode window with the Pallas "
                      "chain-walk kernels engaged (ACCELERATE_KERNELS="
                      "interpret; pins the pallas_call inventory)")
                continue
            if name == "prefill_paged":
                print(f"{name}: chunked-prefill program of a prefill-only "
                      "serving tier (paged pool writes through the block "
                      "table + first-token sampling; no decode window)")
                continue
            if name == "decode_paged_int8":
                print(f"{name}: paged decode window over an int8-quantized "
                      "KV pool with the dequant-in-DMA gather kernel "
                      "engaged (ACCELERATE_KERNELS=interpret)")
                continue
            if name == "spec_verify":
                print(f"{name}: speculative verify program — k-draft scan + "
                      "one multi-token target forward + block-table "
                      "truncation commit (ACCELERATE_KERNELS=interpret)")
                continue
            if name == "step_zero_kernel":
                print(f"{name}: window=1 optimizer=adamw zero=on mesh=dp8 "
                      "with the fused-update Pallas kernel engaged "
                      "(ACCELERATE_KERNELS=interpret)")
                continue
            window, optimizer, zero, parallelism = _TRAIN_CONFIGS[name]
            plan = ",".join(f"{k}={v}" for k, v in (parallelism or {}).items()) or "dp8"
            print(f"{name}: window={window} optimizer={optimizer} "
                  f"zero={'on' if zero else 'off'} mesh={plan}")
        return
    if args.update and args.check:
        raise SystemExit("--check and --update are mutually exclusive")

    if args.cpu_virtual_devices:
        from ..utils.environment import pin_cpu_platform

        # Must precede the first backend touch; the goldens are extracted on
        # exactly this mesh.
        pin_cpu_platform(args.cpu_virtual_devices)
    if not args.keep_compile_cache:
        # Donation must be LIVE for the dropped-donor detector: the CPU +
        # persistent-cache policy (safe_donate_argnums) would waive every
        # donor mark. Scrub before the first Accelerator touches the env.
        os.environ.pop("ACCELERATE_COMPILE_CACHE_DIR", None)

    configs = [c.strip() for c in (args.configs or "").split(",") if c.strip()] \
        or list(CONFIG_NAMES)
    unknown = [c for c in configs if c not in CONFIG_NAMES]
    if unknown:
        raise SystemExit(
            f"unknown config(s) {', '.join(unknown)}; choose from "
            f"{', '.join(CONFIG_NAMES)}"
        )
    goldens_dir = args.goldens_dir or default_goldens_dir()
    results, failures = run_fingerprints(configs, goldens_dir, update=args.update)

    if args.json:
        print(json.dumps({
            "schema_version": 1,
            "command": "fingerprint",
            "verdict": "fail" if failures else "pass",
            "failures": failures,
            "goldens_dir": goldens_dir,
            "configs": results,
        }, indent=1))
    else:
        for name, res in results.items():
            print(f"{name}: {res['verdict']} (hash {res['hash']})")
            for entry in res["drift"]:
                print(f"  [{entry['kind']}] {entry['field']}: {entry['detail']}")
        if args.update:
            print(f"wrote {len(results)} golden(s) to {goldens_dir}")
        else:
            for f in failures:
                print(f"fingerprint: {f}", file=sys.stderr)
            print(
                f"fingerprint: {len(configs)} config(s), "
                f"{len(failures)} violation(s)"
            )
    if failures and not args.update:
        raise SystemExit(1)


def fingerprint_main() -> None:
    """Console-script entry (`accelerate-tpu-fingerprint`)."""
    fingerprint_command(fingerprint_command_parser().parse_args())


if __name__ == "__main__":
    fingerprint_main()
