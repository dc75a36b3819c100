"""Always-on per-step timeline — what is this run doing right now?

``StepTimeline`` records one sample per training step with the same
no-forced-host-sync discipline as the health guard: the only per-step work is
a ``perf_counter`` read, a deque append, and a couple of registry updates.
Device scalars (the step loss) are *retained*, not fetched — they drain
through :func:`...utils.transfer.host_fetch` only once materialized
(``summary()`` checks ``is_ready`` first), so a telemetry-enabled loop adds
ZERO blocking device→host transfers per step versus telemetry-off — the
acceptance bar tests/test_telemetry.py pins with the transfer counters.

A sample's wall time is the gap between consecutive step boundaries (the
first boundary only sets the baseline — it covers trace+compile, which the
goodput ledger already classifies). ``summary()`` folds in everything the
"which host / which step / which resource" questions need: step-time
quantiles, tokens/s, an achieved-MFU estimate from the model flop count
(``set_model_flops`` — ``Accelerator.build_train_step`` wires it from
``module.flops_per_token()``), compile events from the goodput ledger,
deliberate device→host transfer counts (and how many blocked) from
``utils/transfer.py``, and live/peak device memory via
``jax.local_devices()[*].memory_stats()``.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass

import jax

from ..utils.transfer import array_is_ready, host_fetch

# bf16 peak FLOPs per chip, keyed by a substring of ``device_kind`` (first
# match wins): the denominator of the MFU estimate; bench.py's
# peak_flops_per_chip delegates here. Source: Google Cloud TPU documentation
# (v5e: 197 TFLOP/s). A device that is not listed has NO peak.
_PEAK_FLOPS_BF16 = {
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def device_peak_flops(device=None) -> float | None:
    """bf16 peak of ``device`` (default: the first local one), or None for a
    device kind the table does not know, the CPU included: no utilisation is
    stated against a guessed peak."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for key, val in _PEAK_FLOPS_BF16.items():
        if key in kind:
            return val
    return None


def device_memory_stats() -> dict:
    """Summed ``memory_stats()`` over local devices; {} when the backend has
    none (CPU). A pure host call — never syncs the device stream."""
    in_use = peak = limit = 0
    found = False
    for device in jax.local_devices():
        stats_fn = getattr(device, "memory_stats", None)
        if stats_fn is None:
            continue
        try:
            stats = stats_fn() or {}
        except Exception:
            continue
        if not stats:
            continue
        found = True
        in_use += int(stats.get("bytes_in_use", 0))
        peak += int(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)))
        limit += int(stats.get("bytes_limit", 0))
    if not found:
        return {}
    return {"bytes_in_use": in_use, "peak_bytes_in_use": peak, "bytes_limit": limit}


def batch_token_count(batch) -> int | None:
    """Tokens in a language-model batch (``input_ids`` element count); None
    for batches without one — the timeline then reports step time only."""
    if isinstance(batch, dict):
        ids = batch.get("input_ids")
        if ids is not None and hasattr(ids, "shape"):
            count = 1
            for dim in ids.shape:
                count *= int(dim)
            return count
    return None


@dataclass
class StepSample:
    step: int | None
    wall_s: float
    tokens: int | None


def _quantile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


class StepTimeline:
    """See module docstring. ``clock`` is injectable for deterministic tests."""

    def __init__(self, capacity: int = 1024, registry=None, clock=time.perf_counter):
        from ..utils import transfer
        from .metrics import get_registry

        self._clock = clock
        self._registry = registry if registry is not None else get_registry()
        self._ring: collections.deque[StepSample] = collections.deque(maxlen=capacity)
        self._count = 0
        self._boundaries = 0
        self._dispatches = 0
        self._last_end = None
        self._last_step = None
        self._flops_per_token = None
        self._predicted_peak = None
        self._last_mfu = None
        # Retained (NOT fetched) device loss scalars; drained when materialized.
        self._pending_loss: collections.deque = collections.deque(maxlen=4)
        self._last_loss = None
        self._window_s = 0.0
        self._window_steps = 0
        self._transfer0 = transfer.transfer_stats()
        self._steps_total = self._registry.counter(
            "accelerate_steps_total", "Training steps observed by the timeline"
        )
        self._step_hist = self._registry.histogram(
            "accelerate_step_seconds", "Wall-clock per training step"
        )
        self._tokens_gauge = self._registry.gauge(
            "accelerate_tokens_per_second", "Instantaneous training throughput"
        )
        self._mfu_gauge = self._registry.gauge(
            "accelerate_mfu_estimate", "Achieved model-FLOPs utilization estimate"
        )

    # ------------------------------------------------------------- configure
    def set_model_flops(self, flops_per_token: float):
        """Forward+backward FLOPs per token — enables the MFU estimate."""
        self._flops_per_token = float(flops_per_token) if flops_per_token else None

    def set_predicted_peak(self, nbytes: int | None):
        """Static per-device peak-HBM prediction (analysis/memory.py, fed by
        ``Accelerator.audit``/``memory_report``) — ``summary()`` then carries
        it next to the observed ``memory_stats()`` peak so a prediction that
        drifts from reality is visible in every bench line and Prometheus
        scrape, not just at memcheck time."""
        self._predicted_peak = int(nbytes) if nbytes else None

    @property
    def count(self) -> int:
        """Completed step samples (the first boundary is baseline only)."""
        return self._count

    @property
    def boundaries(self) -> int:
        """Every ``step_end`` call, INCLUDING the baseline — what the hook
        dedupe compares, so a fused baseline still marks the step covered."""
        return self._boundaries

    @property
    def dispatches(self) -> int:
        """Program dispatches observed (each ``step_end`` boundary is one —
        a K-step window boundary counts once while contributing K steps)."""
        return self._dispatches

    @property
    def last_wall_s(self) -> float | None:
        return self._ring[-1].wall_s if self._ring else None

    @property
    def last_mfu(self) -> float | None:
        """Most recent per-boundary achieved-MFU estimate (None until tokens
        and a model flop count are both known) — the SLO sentinel's MFU feed."""
        return self._last_mfu

    @property
    def last_loss(self) -> float | None:
        """Most recently DRAINED loss (None until a retained device scalar
        materialized and a ``summary()`` drained it) — a plain attribute
        read, so hot-path consumers (the journal's step records) can carry a
        loss without ever forcing a device fetch."""
        return self._last_loss

    # ------------------------------------------------------------- recording
    def step_end(self, step: int | None = None, tokens: int | None = None,
                 loss=None, steps: int = 1) -> float | None:
        """Mark a step boundary; returns the per-step wall time (None on the
        baseline call). ``loss`` may be an in-flight device scalar — or, under
        windowed dispatch, a retained K-vector — it is never fetched here.

        ``steps`` is how many *training steps* this boundary covers: a K-step
        fused train window is ONE dispatch but K steps, so the boundary's wall
        time is split into K per-step samples and ``tokens`` (the boundary's
        TOTAL) into K per-step token counts — tokens/s, the MFU estimate, and
        the step-time quantiles stay per-step correct at any window size.
        """
        steps = max(int(steps), 1)
        now = self._clock()
        wall = None
        self._boundaries += 1
        self._dispatches += 1
        if self._last_end is not None:
            wall = (now - self._last_end) / steps
            per_tokens = tokens // steps if tokens else tokens
            first = None if step is None else step - steps + 1
            for i in range(steps):
                self._count += 1
                self._ring.append(StepSample(
                    step=None if first is None else first + i,
                    wall_s=wall, tokens=per_tokens,
                ))
                self._step_hist.observe(wall)
            self._window_s += wall * steps
            self._window_steps += steps
            self._steps_total.inc(steps)
            if per_tokens and wall > 0:
                tps = per_tokens / wall
                self._tokens_gauge.set(tps)
                peak = device_peak_flops() if self._flops_per_token else None
                if peak is not None:
                    self._last_mfu = (
                        tps * self._flops_per_token / (peak * jax.device_count())
                    )
                    self._mfu_gauge.set(self._last_mfu)
        self._last_end = now
        self._last_step = step if step is not None else self._last_step
        if loss is not None:
            self._pending_loss.append(loss)
        return wall

    def _drain_loss(self):
        """Fetch retained losses whose results have materialized (a counted
        copy via host_fetch, never a stall); unready ones stay queued. A
        windowed boundary retains a K-vector — its last element is the most
        recent step's loss."""
        import numpy as np

        while self._pending_loss:
            head = self._pending_loss[0]
            if not array_is_ready(head):
                break
            self._pending_loss.popleft()
            try:
                self._last_loss = float(host_fetch(head).reshape(-1)[-1])
            except Exception:
                self._last_loss = None

    def take_window(self) -> tuple[float, int]:
        """(seconds, steps) accumulated since the last take — the straggler
        monitor's per-report window."""
        out = (self._window_s, self._window_steps)
        self._window_s, self._window_steps = 0.0, 0
        return out

    # --------------------------------------------------------------- reading
    def summary(self) -> dict:
        """The step-timeline schema (docs/observability.md); also embedded in
        bench.py's per-config JSON lines as ``detail.telemetry``."""
        from ..resilience.goodput import get_ledger
        from ..utils import transfer

        samples = list(self._ring)
        walls = sorted(s.wall_s for s in samples)
        token_samples = [s for s in samples if s.tokens]
        tok_time = sum(s.wall_s for s in token_samples)
        tokens_per_s = (
            sum(s.tokens for s in token_samples) / tok_time if tok_time > 0 else None
        )
        mfu = None
        if tokens_per_s is not None and self._flops_per_token:
            peak = device_peak_flops()
            if peak is not None:
                mfu = tokens_per_s * self._flops_per_token / (peak * jax.device_count())
        self._drain_loss()
        now_stats = transfer.transfer_stats()
        # A reset_transfer_stats() since this timeline baselined its deltas
        # zeroed the global counters underneath the snapshot — comparing
        # against the stale baseline would go negative. Re-anchor at the
        # reset: deltas then cover counts since the reset, never below zero.
        if now_stats.get("resets", 0) != self._transfer0.get("resets", 0):
            self._transfer0 = {k: (0 if k != "resets" else now_stats["resets"])
                               for k in now_stats}
        ledger = get_ledger()
        from ..utils.xla_flags import active_preset

        out = {
            "steps": self._count,
            # Program dispatches vs steps: equal in step-per-dispatch training;
            # under K-step fused windows steps ≈ K × dispatches — the
            # amortization bench.py's detail.dispatches makes visible.
            "dispatches": self._dispatches,
            "last_step": self._last_step,
            "step_s": {
                "mean": sum(walls) / len(walls) if walls else 0.0,
                "p50": _quantile(walls, 0.50),
                "p90": _quantile(walls, 0.90),
                "max": walls[-1] if walls else 0.0,
            },
            "tokens_per_s": tokens_per_s,
            "mfu_estimate": mfu,
            "last_loss": self._last_loss,
            "compile": {
                "count": ledger.counts.get("compile", 0),
                "seconds": round(ledger.seconds.get("compile", 0.0), 3),
            },
            "transfers": {
                "fetches": now_stats["fetches"] - self._transfer0["fetches"],
                "blocking": now_stats["blocking"] - self._transfer0["blocking"],
                "h2d_puts": now_stats["h2d_puts"] - self._transfer0.get("h2d_puts", 0),
                "h2d_blocking": now_stats["h2d_blocking"]
                - self._transfer0.get("h2d_blocking", 0),
                "input_wait_s": round(
                    now_stats["input_wait_s"] - self._transfer0.get("input_wait_s", 0.0), 6
                ),
            },
            "xla_preset": active_preset(),
            "memory": self._memory_summary(),
        }
        # Profiling (telemetry/profiler.py): present only when a trace capture
        # engaged this run — un-profiled summaries keep their schema.
        from .profiler import default_manager_summary

        profile = default_manager_summary()
        if profile is not None:
            out["profile"] = profile
        return out

    def _memory_summary(self) -> dict:
        """Live ``memory_stats()`` plus, once a static audit armed it, the
        predicted per-device peak — and the predicted/observed ratio when the
        backend reports a peak (TPU/GPU; CPU devices have no memory_stats, so
        the prediction stands alone there). memory_stats() sums are TOTALS
        over local devices; the prediction is per device, so the ratio
        normalizes by the local device count."""
        out = device_memory_stats()
        if self._predicted_peak is not None:
            out["predicted_peak_bytes"] = self._predicted_peak
            observed = out.get("peak_bytes_in_use", 0)
            n_local = max(len(jax.local_devices()), 1)  # accelerate-lint: disable=raw-device-baseline
            if observed > 0:
                out["predicted_vs_observed"] = round(
                    self._predicted_peak / (observed / n_local), 3
                )
        return out

    def reset(self):
        from ..utils import transfer

        self._ring.clear()
        self._count = 0
        self._boundaries = 0
        self._dispatches = 0
        self._last_end = None
        self._last_step = None
        self._pending_loss.clear()
        self._last_loss = None
        self._predicted_peak = None
        self._last_mfu = None
        self._window_s, self._window_steps = 0.0, 0
        self._transfer0 = transfer.transfer_stats()
