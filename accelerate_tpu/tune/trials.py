"""Short-bench trials — measure a surviving candidate for warmup+N steps.

One :class:`TrialRig` owns the model-shape fixture (the tiny train config by
default — the same fixture ``accelerate-tpu audit`` / ``memcheck`` lower) and
builds each candidate's artifact: window program vs per-step program, fused
vocab-chunked loss, remat policy, ZeRO sharding, prefetcher. The built
artifacts are cached per :meth:`~.space.Candidate.lowering_key`, so the static
prune's lowering is the SAME program object the trial then executes, and
candidates differing only in env-level levers (preset) or host-side levers
(prefetch) never recompile.

:func:`run_trial` reuses bench.py's fixed-step discipline — dispatch counts
derived from steps ÷ window, sync only at the measured region's edges — with
the PR-8 capture machinery armed: a per-trial
:class:`~..telemetry.profiler.ProfileManager` manual capture brackets the
measured region, and its parsed traceview attribution (compute / collective /
host / idle fractions) rides the trial result to steer the search.

Accounting: the ENTIRE trial wall-clock (build, compile, warmup, measured
steps) books as the goodput ledger's ``tune`` badput class — trial steps are
never recorded as productive ``step`` time, so a tuned job's MFU/goodput
reflects training only. Capture overhead the ProfileManager already booked as
``profile`` badput is subtracted from the ``tune`` booking so the two classes
never double-count one second.
"""

from __future__ import annotations

import contextlib
import gc
import os
import tempfile
import time
from dataclasses import dataclass

from .space import Candidate

# Default short-bench shape: bench.py's fixed-discipline numbers scaled down —
# enough measured steps to rank, cheap enough to run a dozen trials in minutes.
DEFAULT_WARMUP_STEPS = 2
DEFAULT_MEASURED_STEPS = 8


@dataclass
class BuiltCandidate:
    """One lowered/compiled artifact and everything a trial needs to drive it."""

    candidate: Candidate
    accelerator: object
    model_config: object
    built: object          # build_train_step / build_train_window output
    base_batch: dict       # one per-step host batch
    window: int
    tokens_per_step: int
    flops_per_token: float
    params: int


@dataclass
class TrialResult:
    candidate: Candidate
    measured_steps: int
    warmup_steps: int
    step_time_s: float
    steps_per_sec: float
    tokens_per_sec: float
    mfu_est: float | None  # None on a device with no known peak
    final_loss: float
    wall_s: float
    compile_s: float
    fractions: dict | None = None
    overlap_fraction: float | None = None
    trace_dir: str | None = None
    predicted_peak_bytes: int = 0
    budget_bytes: int = 0
    audit: dict | None = None
    memory: dict | None = None
    fingerprint: str | None = None   # short program-identity hash
    xla_preset_flags: tuple = ()
    preset_applied: bool = True

    def to_dict(self) -> dict:
        return {
            "candidate": self.candidate.to_dict(),
            "key": self.candidate.key(),
            "measured_steps": self.measured_steps,
            "warmup_steps": self.warmup_steps,
            "step_time_s": round(self.step_time_s, 6),
            "steps_per_sec": round(self.steps_per_sec, 3),
            "tokens_per_sec": round(self.tokens_per_sec, 1),
            "mfu_est": None if self.mfu_est is None else round(self.mfu_est, 4),
            "final_loss": round(self.final_loss, 4),
            "wall_s": round(self.wall_s, 3),
            "compile_s": round(self.compile_s, 3),
            "fractions": self.fractions,
            "overlap_fraction": self.overlap_fraction,
            "trace_dir": self.trace_dir,
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "budget_bytes": self.budget_bytes,
            "audit": self.audit,
            "memory": self.memory,
            "fingerprint": self.fingerprint,
            "xla_preset_flags": list(self.xla_preset_flags),
            "preset_applied": self.preset_applied,
        }


class TrialRig:
    """Builds, audits, and short-benches candidates on one fixture shape.

    ``batch_rows`` / ``seq`` / ``optimizer`` mirror the ``memcheck`` CLI
    fixture knobs (adamw default: the 2-moments-per-param worst case that
    makes the ZeRO and memory levers visible). ``model_config`` overrides the
    tiny Llama for callers tuning a real shape. ``budget_bytes`` overrides the
    HBM budget the prune verdict gates on (the ``--budget-gib`` path).
    """

    def __init__(
        self,
        batch_rows: int = 8,
        seq: int = 16,
        optimizer: str = "adamw",
        model_config=None,
        budget_bytes: int | None = None,
        profile_dir: str | None = None,
        start_trace=None,
        stop_trace=None,
    ):
        self.batch_rows = int(batch_rows)
        self.seq = int(seq)
        self.optimizer = optimizer
        self.model_config = model_config
        self.budget_bytes = budget_bytes
        self.profile_dir = profile_dir
        self._start_trace = start_trace
        self._stop_trace = stop_trace
        self._built: dict = {}      # lowering_key -> BuiltCandidate
        self._evidence: dict = {}   # lowering_key -> (evidence, failures)

    # ---------------------------------------------------------------- builder
    def _model_config(self, candidate: Candidate):
        from ..models import LlamaConfig

        base = self.model_config if self.model_config is not None else LlamaConfig.tiny()
        kw = {}
        if candidate.vocab_chunk > 0:
            kw["fused_loss"] = True
            kw["fused_loss_chunk"] = min(candidate.vocab_chunk, base.vocab_size)
        if candidate.remat_policy:
            kw["remat"] = True
            kw["remat_policy"] = candidate.remat_policy
        if not kw:
            return base
        cfg = type(base)(**{**_config_dict(base), **kw})
        return cfg

    def build(self, candidate: Candidate) -> BuiltCandidate:
        """The candidate's artifact, cached per lowering_key (preset and
        prefetch do not change the lowered program in-process)."""
        key = candidate.lowering_key()
        cached = self._built.get(key)
        if cached is not None:
            return cached
        import numpy as np
        import jax
        import optax

        from ..accelerator import Accelerator
        from ..models import Llama

        cfg = self._model_config(candidate)
        accelerator = Accelerator()
        accelerator.zero_sharding = candidate.zero_sharding
        # Kernel lever: 'off' = reference lowerings ('' spec), anything else
        # is the registry spec verbatim (resolved per op at build/trace time).
        accelerator.kernels = "" if candidate.kernels == "off" else candidate.kernels
        model = Llama(cfg)
        model.init_params(jax.random.key(0))
        tx = {
            "sgd": lambda: optax.sgd(0.1),
            "adamw": lambda: optax.adamw(3e-4),
            "adafactor": lambda: optax.adafactor(3e-4),
        }[self.optimizer]()
        pmodel, popt = accelerator.prepare(model, tx)
        if candidate.train_window > 1:
            built = accelerator.build_train_window(
                pmodel, popt, window=candidate.train_window
            )
        else:
            built = accelerator.build_train_step(pmodel, popt)
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (self.batch_rows, self.seq)
        ).astype(np.int32)
        base_batch = {"input_ids": ids, "labels": ids}
        n_params = model.num_params()
        attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * self.seq
        out = BuiltCandidate(
            candidate=candidate,
            accelerator=accelerator,
            model_config=cfg,
            built=built,
            base_batch=base_batch,
            window=candidate.train_window,
            tokens_per_step=self.batch_rows * self.seq,
            flops_per_token=6 * n_params + attn_flops,  # fwd+bwd, bench.py's form
            params=n_params,
        )
        self._built[key] = out
        return out

    # ------------------------------------------------------------ prune hooks
    def audit_candidate(self, candidate: Candidate):
        """The ``audit_fn`` contract of :func:`~.prune.static_prune`: lower
        (without running), audit program + memory, and return ``(evidence,
        failures)`` — cached per lowering_key like the build."""
        import numpy as np

        from .prune import audit_failures

        key = candidate.lowering_key()
        cached = self._evidence.get(key)
        if cached is not None:
            return cached
        built = self.build(candidate)
        if built.window > 1:
            audit_batch = {
                k: np.stack([v] * built.window) for k, v in built.base_batch.items()
            }
        else:
            audit_batch = built.base_batch
        report = built.accelerator.audit(built.built, audit_batch)
        audit_summary = report.summary_dict()
        memory_summary = (
            report.memory.summary_dict() if report.memory is not None else None
        )
        # Program identity: the short fingerprint hash names the exact
        # program this candidate lowers (and, if kept, measures) — rides the
        # evidence into both the pruned-drop bookings and the trial rankings.
        from ..analysis.fingerprint import fingerprint_built, fingerprint_hash

        fp = fingerprint_built(
            built.built, audit_batch,
            config=f"tune_{candidate.key()}", report=report,
        )
        evidence = {
            "audit": audit_summary,
            "memory": memory_summary,
            "fingerprint": fingerprint_hash(fp),
        }
        failures = audit_failures(
            audit_summary, memory_summary, budget_bytes=self.budget_bytes
        )
        self._evidence[key] = (evidence, failures)
        return evidence, failures

    # ----------------------------------------------------------------- trials
    def run_trial(
        self,
        candidate: Candidate,
        evidence: dict | None = None,
        measured_steps: int = DEFAULT_MEASURED_STEPS,
        warmup_steps: int = DEFAULT_WARMUP_STEPS,
        capture: bool = True,
    ) -> TrialResult:
        """Short-bench one candidate; see the module docstring for the
        discipline and accounting. Returns the TrialResult (raises on trial
        failure — commands/tune.py converts that into a skipped candidate)."""
        import numpy as np

        from ..resilience.goodput import get_ledger
        from ..telemetry.profiler import ProfileManager
        from ..telemetry.timeline import device_peak_flops
        from ..utils.xla_flags import (
            active_preset_flags,
            install_xla_preset,
            _backend_already_initialized,
        )

        ledger = get_ledger()
        t_start = time.perf_counter()
        profile_before = ledger.summary()["profile_s"]
        try:
            # The preset is an env-level lever read once at backend init:
            # install records the ask and the resolved flag list for the
            # evidence report, but cannot re-apply to a live backend —
            # preset_applied says which happened (always False mid-tune on a
            # real TPU; inert-but-true before first backend touch).
            preset_applied = not _backend_already_initialized()
            install_xla_preset(candidate.xla_preset)
            preset_flags_resolved = active_preset_flags()

            built = self.build(candidate)
            window = built.window
            warmup_disp = max(int(warmup_steps) // window, 1)
            meas_disp = max(int(measured_steps) // window, 1)
            total_disp = warmup_disp + meas_disp

            if window > 1:
                window_batch = {
                    k: np.stack([v] * window) for k, v in built.base_batch.items()
                }
            else:
                window_batch = built.base_batch
            if candidate.prefetch > 0:
                from ..data_loader import DeviceBatchPrefetcher

                def _stream(n=total_disp * window):
                    for _ in range(n):
                        yield built.base_batch

                batches = iter(DeviceBatchPrefetcher(
                    _stream(), mesh=built.accelerator.mesh,
                    prefetch=candidate.prefetch, window=window,
                ))
                next_batch = lambda: next(batches)  # noqa: E731
            else:
                next_batch = lambda: window_batch  # noqa: E731

            step = built.built

            def _sync(x):
                # Deliberate, counted host sync (utils/transfer.py discipline);
                # under windowed dispatch x is the per-step K-vector — the last
                # element is the newest step's loss.
                from ..utils.transfer import host_fetch

                return float(host_fetch(x).reshape(-1)[-1])

            t_compile = time.perf_counter()
            loss = step(next_batch())
            _sync(loss)
            compile_s = time.perf_counter() - t_compile
            for _ in range(warmup_disp - 1):
                loss = step(next_batch())
            _sync(loss)

            manager = None
            cm = contextlib.nullcontext(None)
            if capture:
                manager = ProfileManager(
                    output_dir=self.profile_dir
                    or os.path.join(tempfile.gettempdir(), "accelerate_tune_traces"),
                    max_captures=1,
                    start_trace=self._start_trace,
                    stop_trace=self._stop_trace,
                )
                trace_dir = os.path.join(
                    manager.output_dir, f"trial_{candidate.key()}"
                )
                cm = manager.manual_capture(trace_dir=trace_dir)
            with cm:
                t0 = time.perf_counter()
                for _ in range(meas_disp):
                    loss = step(next_batch())
                final_loss = _sync(loss)
                dt = time.perf_counter() - t0
            # Capture stop + traceview parse ran at `with` exit — outside the
            # timed region, booked by the manager as `profile` badput.

            steps_ran = meas_disp * window
            steps_per_sec = steps_ran / dt
            tokens_per_sec = steps_per_sec * built.tokens_per_step
            # Peak FLOPs and chip count come from the LIVE mesh the trial ran
            # on, not a raw device-list baseline (elastic reshards change it).
            mesh_devices = built.accelerator.mesh.devices
            peak = device_peak_flops(mesh_devices.flat[0])
            mfu = (
                None if peak is None
                else tokens_per_sec * built.flops_per_token / (peak * mesh_devices.size)
            )

            fractions = overlap = trace_path = None
            if manager is not None and manager.captures:
                record = manager.captures[-1]
                trace_path = record.get("trace_dir")
                report = record.get("report")
                if report is not None:
                    fractions = report.get("fractions")
                    overlap = report.get("overlap_fraction")

            ev = evidence or {}
            memory_summary = ev.get("memory") or {}
            return TrialResult(
                candidate=candidate,
                measured_steps=steps_ran,
                warmup_steps=warmup_disp * window,
                step_time_s=dt / steps_ran,
                steps_per_sec=steps_per_sec,
                tokens_per_sec=tokens_per_sec,
                mfu_est=mfu,
                final_loss=final_loss,
                wall_s=time.perf_counter() - t_start,
                compile_s=compile_s,
                fractions=fractions,
                overlap_fraction=overlap,
                trace_dir=trace_path,
                predicted_peak_bytes=int(
                    memory_summary.get("predicted_peak_bytes", 0) or 0
                ),
                budget_bytes=int(
                    self.budget_bytes
                    if self.budget_bytes is not None
                    else memory_summary.get("budget_bytes", 0) or 0
                ),
                audit=ev.get("audit"),
                memory=ev.get("memory") or None,
                fingerprint=ev.get("fingerprint"),
                xla_preset_flags=preset_flags_resolved,
                preset_applied=preset_applied,
            )
        finally:
            # The WHOLE trial is `tune` badput, minus whatever the capture
            # machinery already booked as `profile` during it (stop/parse) —
            # the two classes must partition the wall-clock, not double it.
            wall = time.perf_counter() - t_start
            profile_delta = ledger.summary()["profile_s"] - profile_before
            ledger.add("tune", max(wall - profile_delta, 0.0))
            gc.collect()  # drop this candidate's arrays before the next build


def _config_dict(cfg) -> dict:
    """A model config's constructor kwargs (dataclass or attrs-style)."""
    import dataclasses

    if dataclasses.is_dataclass(cfg):
        return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return dict(vars(cfg))
