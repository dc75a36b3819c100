"""Evidence report — the ranked outcome of a tune run, plus the winner config.

One schema'd JSON document (``TUNE_SCHEMA_VERSION``) carries everything a
reader — human, CI, or ``bench.py``'s ``BENCH_FROM_TUNE`` replay — needs:

- ``ranked``: every trialed candidate best-first by measured per-step time,
  each with its step time, MFU estimate, traceview attribution fractions,
  predicted peak bytes vs budget, and the program-audit summary;
- ``dropped``: the statically-pruned candidates with their booked reasons
  (``predicted_oom`` / ``audit_violation`` / ``build_failed``) and evidence;
- ``search_trail``: the per-round decision log (bottleneck classification,
  proposed moves, prunes) so the search's reasoning is auditable;
- ``winner`` / ``baseline`` / ``winner_vs_baseline``: the best candidate, the
  base (current-config) candidate's own trial, and the speedup between them;
- ``goodput``: the run's ledger summary — the trials' wall-clock shows up as
  the ``tune`` badput class, never as productive step time.

:func:`winner_cluster_config` turns the winner into a ready-to-use
:class:`~..commands.config_args.ClusterConfig` (``train_window`` /
``xla_preset`` / ``zero_sharding`` are first-class fields; the model-level
levers ride ``extra`` as ``tune_*`` keys so the yaml round-trips losslessly),
and :func:`load_winner` reads a report back for the bench replay path.
"""

from __future__ import annotations

import json
import os

TUNE_SCHEMA_VERSION = 1


def build_report(
    *,
    ranked,
    dropped,
    trail,
    space,
    trial_budget: int,
    trials_run: int,
    backend: str | None = None,
    device: str | None = None,
) -> dict:
    """Assemble the report dict from ``run_search`` outputs (``ranked`` is
    ``[(Candidate, result_dict), ...]`` best-first)."""
    from ..resilience.goodput import get_ledger

    ranked_entries = [
        {"rank": i + 1, **result} for i, (_cand, result) in enumerate(ranked)
    ]
    base_key = space.base.key()
    baseline = next((e for e in ranked_entries if e["key"] == base_key), None)
    winner = ranked_entries[0] if ranked_entries else None
    vs = None
    if winner is not None and baseline is not None and baseline["step_time_s"] > 0:
        vs = {
            "winner_step_time_s": winner["step_time_s"],
            "baseline_step_time_s": baseline["step_time_s"],
            "speedup": round(baseline["step_time_s"] / winner["step_time_s"], 4)
            if winner["step_time_s"] > 0 else None,
        }
    return {
        "schema_version": TUNE_SCHEMA_VERSION,
        "tool": "accelerate-tpu tune",
        "backend": backend,
        "device": device,
        "trial_budget": int(trial_budget),
        "trials_run": int(trials_run),
        "space": space.to_dict(),
        "base": space.base.to_dict(),
        "ranked": ranked_entries,
        "dropped": list(dropped),
        "search_trail": list(trail),
        "winner": winner,
        "baseline": baseline,
        "winner_vs_baseline": vs,
        "goodput": get_ledger().summary(),
    }


def winner_cluster_config(winner_candidate: dict, base_cfg=None):
    """A ClusterConfig carrying the winner's levers: the launcher-native
    fields directly, the model-level levers (vocab chunk, remat policy,
    prefetch) as ``tune_*`` extras — ready for ``launch --config_file``."""
    import copy

    from ..commands.config_args import ClusterConfig

    cfg = copy.deepcopy(base_cfg) if base_cfg is not None else ClusterConfig()
    cfg.train_window = int(winner_candidate.get("train_window", 1))
    cfg.xla_preset = str(winner_candidate.get("xla_preset", "off"))
    cfg.zero_sharding = bool(winner_candidate.get("zero_sharding", False))
    extras = dict(getattr(cfg, "extra", None) or {})
    extras.update({
        "tune_vocab_chunk": int(winner_candidate.get("vocab_chunk", 0)),
        "tune_remat_policy": str(winner_candidate.get("remat_policy", "")),
        "tune_prefetch": int(winner_candidate.get("prefetch", 0)),
        "tuned_by": "accelerate-tpu tune",
    })
    cfg.extra = extras
    return cfg


def write_winner_yaml(path: str, winner_candidate: dict, base_cfg=None) -> str:
    cfg = winner_cluster_config(winner_candidate, base_cfg=base_cfg)
    cfg.to_yaml_file(path)
    return path


def write_report(path: str, report: dict) -> str:
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return path


def load_report(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        report = json.load(f)
    version = report.get("schema_version")
    if version != TUNE_SCHEMA_VERSION:
        raise ValueError(
            f"{path!r} has tune schema_version {version!r}; this build reads "
            f"{TUNE_SCHEMA_VERSION}"
        )
    return report


def load_winner(path: str) -> dict:
    """The winner's flat candidate dict from a report file — the
    ``BENCH_FROM_TUNE`` consumer. Raises on a report without a winner (a run
    where every candidate was pruned has nothing to replay)."""
    report = load_report(path)
    winner = report.get("winner")
    if not winner or "candidate" not in winner:
        raise ValueError(f"{path!r} records no winner to replay")
    return dict(winner["candidate"])


def _fmt_mfu(mfu) -> str:
    """Utilisation exists only where the device's peak is known."""
    return "n/a" if mfu is None else f"{mfu:.4f}"


def format_summary(report: dict, top: int = 5) -> str:
    """The human-facing ranked table `tune` prints (the full evidence lives
    in the JSON)."""
    lines = []
    backend = report.get("backend") or "?"
    lines.append(
        f"tune: {report['trials_run']}/{report['trial_budget']} trials on "
        f"{backend}, {len(report['ranked'])} candidate(s) ranked, "
        f"{len(report['dropped'])} statically pruned"
    )
    for entry in report["ranked"][:top]:
        frac = entry.get("fractions") or {}
        attrib = (
            " compute/coll/host/idle="
            f"{frac.get('compute')}/{frac.get('collective')}"
            f"/{frac.get('host')}/{frac.get('idle')}"
            if frac else ""
        )
        lines.append(
            f"  #{entry['rank']} {entry['key']}: "
            f"{entry['step_time_s'] * 1e3:.2f} ms/step "
            f"(mfu~{_fmt_mfu(entry['mfu_est'])}, peak {entry['predicted_peak_bytes']} B)"
            + attrib
        )
    for drop in report["dropped"]:
        lines.append(f"  pruned {drop['key']}: {drop['reason']}")
    vs = report.get("winner_vs_baseline")
    if vs and vs.get("speedup") is not None:
        lines.append(
            f"winner vs current config: {vs['speedup']:.2f}x "
            f"({vs['baseline_step_time_s'] * 1e3:.2f} -> "
            f"{vs['winner_step_time_s'] * 1e3:.2f} ms/step)"
        )
    return "\n".join(lines)
