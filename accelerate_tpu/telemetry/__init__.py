"""Unified telemetry — one answer to "what is this run doing right now?"

The subsystems that grew their own observability silos — bench probes, the
goodput ledger, health verdicts, transfer counters — publish into ONE stack:

- :mod:`.spans` — nestable ``span("data_load")`` blocks recorded into a
  lock-free ring buffer AND a ``jax.profiler.TraceAnnotation``, so host-side
  and XLA-trace views share names (the framework pre-instruments
  prepare / train_step / checkpoint / gather);
- :mod:`.timeline` — the always-on per-step timeline: step wall time,
  tokens/s, achieved-MFU estimate, compile events, deliberate device→host
  transfer counts, device memory — with zero forced host syncs (device
  scalars drain only when materialized);
- :mod:`.metrics` — the process-wide counter/gauge/histogram registry every
  layer (goodput, health, resilience, data loader, optimizer, serving)
  publishes into, exported as a Prometheus endpoint
  (``launch --metrics_port``) and as structured records through the tracker
  stack (``Accelerator.log_telemetry``);
- :mod:`.straggler` — periodic cross-host step-time aggregation over the
  one-scalar-collective/KV-agreement machinery, naming the slow host;
- :mod:`.profiler` — triggered XLA trace capture aligned to step/window
  boundaries (explicit ranges, slow-step z-score, straggler trips, POST
  /profile), budgeted and booked as ``profile`` badput;
- :mod:`.traceview` — parses captured traces into the
  compute/collective/idle/host attribution report (with the measured
  compute↔collective overlap fraction);
- :mod:`.flight` — the always-on flight-recorder black box, dumped to JSON
  on hang/trip/restart/crash and rendered by ``accelerate-tpu blackbox``;
- :mod:`.fleet` — the fleet plane: every worker registers its bound metrics
  endpoint in the coordination-service KV registry, and the lead host's
  ``FleetAggregator`` scrapes them all into per-host-labeled series + fleet
  rollups at ``/fleet`` (``accelerate-tpu top`` is the console);
- :mod:`.requests` — per-request serving lifecycle traces (submit →
  admission decision → prefill chunks → first token → decode windows →
  finish/cancel) in a bounded ring, fed by ``ContinuousBatcher``;
- :mod:`.slo` — the continuous SLO sentinel: step-time/MFU/TTFT/TPOT targets
  (explicit or EMA+MAD self-baselined), every breach booked as
  ``accelerate_slo_breaches_total{target}`` + a flight-recorder event.

:class:`Telemetry` binds them behind ``Accelerator.telemetry``; the per-step
hooks loops already call (``guard_step`` / ``checkpoint_on_preemption``) and
the fused ``build_train_step`` feed it automatically. See
docs/observability.md.
"""

from __future__ import annotations

import os

from .fleet import (
    FleetAggregator,
    discover_endpoints,
    install_fleet_provider,
    metrics_endpoint,
    publish_metrics_endpoint,
    reset_fleet,
)
from .flight import (
    FlightRecorder,
    get_flight_recorder,
    record_event,
    reset_flight_recorder,
)
from .journal import (
    TelemetryJournal,
    exchange_clock_sync,
    get_journal,
    journal_event,
    reset_journal,
    set_journal,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsServer,
    get_registry,
    start_default_server,
    stop_default_server,
)
from .requests import RequestTracer
from .slo import SLOSentinel, breach_counts, record_breach, slo_targets_from_env
from .profiler import (
    ProfileManager,
    SlowStepDetector,
    get_profile_manager,
    parse_profile_steps,
    reset_profile_manager,
    set_profile_manager,
)
from .spans import SpanRecord, SpanRing, get_span_ring, record_span, reset_spans, span
from .straggler import SkewReport, StragglerMonitor
from .timeline import StepTimeline, device_memory_stats, device_peak_flops

__all__ = [
    "Counter",
    "FleetAggregator",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "ProfileManager",
    "RequestTracer",
    "SLOSentinel",
    "SkewReport",
    "SlowStepDetector",
    "SpanRecord",
    "SpanRing",
    "StepTimeline",
    "StragglerMonitor",
    "Telemetry",
    "TelemetryJournal",
    "breach_counts",
    "device_memory_stats",
    "device_peak_flops",
    "discover_endpoints",
    "exchange_clock_sync",
    "get_flight_recorder",
    "get_journal",
    "get_profile_manager",
    "get_registry",
    "get_span_ring",
    "get_telemetry",
    "install_default_collectors",
    "install_fleet_provider",
    "journal_event",
    "live_telemetry",
    "metrics_endpoint",
    "metrics_port_from_env",
    "parse_profile_steps",
    "publish_metrics_endpoint",
    "record_breach",
    "record_event",
    "record_span",
    "reset_fleet",
    "reset_flight_recorder",
    "reset_journal",
    "reset_profile_manager",
    "reset_spans",
    "reset_telemetry",
    "set_journal",
    "set_profile_manager",
    "set_telemetry",
    "slo_targets_from_env",
    "span",
    "start_default_server",
    "start_endpoint_from_env",
    "stop_default_server",
]


def install_default_collectors(registry: MetricsRegistry | None = None):
    """Register the pull-model publishers (idempotent per registry): the
    goodput ledger (goodput/badput classes + restarts), the transfer counters,
    and device memory — all refreshed at scrape/snapshot time, zero per-step
    cost."""
    registry = registry if registry is not None else get_registry()
    if getattr(registry, "_at_default_collectors", False):
        return
    registry._at_default_collectors = True

    def _goodput(reg: MetricsRegistry):
        from ..resilience.goodput import BADPUT_CATEGORIES, get_ledger

        summary = get_ledger().summary()
        reg.gauge(
            "accelerate_goodput_fraction",
            "Fraction of wall-clock spent in productive steps",
        ).set(summary["goodput_fraction"])
        reg.gauge(
            "accelerate_goodput_seconds", "Productive step wall-clock"
        ).set(summary["productive_s"])
        badput = reg.gauge(
            "accelerate_badput_seconds",
            "Wall-clock lost per badput class",
            labelnames=("category",),
        )
        for category in BADPUT_CATEGORIES:
            badput.set(summary[f"{category}_s"], category=category)
        reg.gauge(
            "accelerate_restarts", "Gang incarnations observed by the ledger"
        ).set(summary["restarts"])

    def _transfers(reg: MetricsRegistry):
        from ..utils.transfer import transfer_stats

        stats = transfer_stats()
        reg.gauge(
            "accelerate_host_fetches",
            "Deliberate device-to-host fetches (utils/transfer.py)",
        ).set(stats["fetches"])
        reg.gauge(
            "accelerate_host_fetches_blocking",
            "Device-to-host fetches that stalled on an unmaterialized result",
        ).set(stats["blocking"])
        reg.gauge(
            "accelerate_host_puts",
            "Deliberate host-to-device batch uploads (utils/transfer.py)",
        ).set(stats["h2d_puts"])
        reg.gauge(
            "accelerate_host_puts_blocking",
            "Input batches the train loop had to wait on (prefetch misses)",
        ).set(stats["h2d_blocking"])
        reg.gauge(
            "accelerate_input_wait_seconds",
            "Wall-clock the train loop spent waiting on input transfers",
        ).set(stats["input_wait_s"])

    def _memory(reg: MetricsRegistry):
        stats = device_memory_stats()
        if not stats:
            return
        reg.gauge("accelerate_device_bytes_in_use", "Live device memory").set(
            stats["bytes_in_use"]
        )
        reg.gauge("accelerate_device_peak_bytes", "Peak device memory").set(
            stats["peak_bytes_in_use"]
        )
        if stats.get("bytes_limit"):
            reg.gauge("accelerate_device_bytes_limit", "Device memory limit").set(
                stats["bytes_limit"]
            )

    registry.register_collector(_goodput)
    registry.register_collector(_transfers)
    registry.register_collector(_memory)


def metrics_port_from_env() -> int:
    """The ACCELERATE_METRICS_PORT contract, parsed in ONE place (the worker
    install, `launch --fleet_metrics` validation, and `accelerate-tpu top`'s
    default endpoint all call this, so the contract cannot drift): 0 means
    no endpoint is configured (unset/empty/explicit 0), garbage raises the
    same enumerating error everywhere."""
    from ..utils.constants import ENV_METRICS_PORT

    port_raw = os.environ.get(ENV_METRICS_PORT, "").strip()
    if not port_raw:
        return 0
    try:
        return int(port_raw)
    except ValueError:
        raise ValueError(
            f"{ENV_METRICS_PORT}={port_raw!r} must be an integer port"
        ) from None


def start_endpoint_from_env(local_rank: int | None = None) -> "MetricsServer | None":
    """Start the env-contract Prometheus endpoint (ACCELERATE_METRICS_PORT),
    shared by PartialState's init install and ``get_telemetry``'s fallback so
    the contract cannot drift between them: 0/unset = no endpoint, co-located
    workers offset the port by their local rank (``local_rank``; defaults to
    ACCELERATE_LOCAL_PROCESS_ID), and a bind failure degrades to a warning —
    never a training failure. Returns the running server, or None."""
    import logging

    port = metrics_port_from_env()
    if port <= 0:
        # Env contract: 0 = no HTTP endpoint (the registry still feeds
        # trackers). Ephemeral-port binding is the explicit-API path
        # (Telemetry(metrics_port=0)), never the env's.
        return None
    install_default_collectors()
    if local_rank is None:
        local_rank = int(os.environ.get("ACCELERATE_LOCAL_PROCESS_ID", "0") or 0)
    if local_rank:
        port += local_rank
    try:
        return start_default_server(port)
    except (OSError, OverflowError) as exc:
        # OverflowError: the local-rank offset pushed past 65535 — same
        # degradation as an in-use port.
        logging.getLogger(__name__).warning(
            "metrics endpoint could not bind port %s (%s); continuing without "
            "the HTTP exposition (the registry still feeds trackers).",
            port, exc,
        )
        return None


def _transfer_snapshot() -> dict:
    from ..utils.transfer import transfer_stats

    return transfer_stats()


class Telemetry:
    """Binds timeline + straggler monitor + registry (+ optional endpoint),
    plus the profiling/forensics pair: the process-wide
    :class:`~.profiler.ProfileManager` (triggered trace capture — fed one
    call per step/window boundary, so captures align to whole steps) and the
    :class:`~.flight.FlightRecorder` black box (every boundary lands in its
    event ring with the transfer-counter delta it produced).

    ``enabled=False`` turns every hook into a no-op (ACCELERATE_TELEMETRY=0)
    — including the profiler feed: trace triggers ride the telemetry hooks.
    ``metrics_port`` starts the process-wide Prometheus endpoint (0 binds an
    ephemeral port; None leaves HTTP off — the registry still feeds trackers).
    A custom ``registry`` scopes the timeline/straggler series only (tests);
    the framework-wide publishers (health guard, optimizer, data loader,
    serving, spans) always target the global ``get_registry()``.
    """

    def __init__(
        self,
        enabled: bool = True,
        timeline: StepTimeline | None = None,
        straggler: StragglerMonitor | None = None,
        straggler_every: int = 50,
        straggler_threshold: float = 1.5,
        metrics_port: int | None = None,
        registry: MetricsRegistry | None = None,
        profiler: "ProfileManager | None" = None,
        slo: "SLOSentinel | None" = None,
    ):
        self.enabled = bool(enabled)
        self.registry = registry if registry is not None else get_registry()
        install_default_collectors(self.registry)
        self.timeline = timeline or StepTimeline(registry=self.registry)
        self.straggler = straggler or StragglerMonitor(
            every_steps=straggler_every,
            slow_ratio=straggler_threshold,
            registry=self.registry,
        )
        if profiler is not None:
            set_profile_manager(profiler)
            self.profiler = profiler
        elif self.enabled:
            self.profiler = get_profile_manager()
        else:
            # Disabled telemetry never feeds step boundaries, so creating the
            # default manager here would also install a POST /profile trigger
            # whose accepted requests could never engage (and would wedge the
            # pending slot into permanent 409s). Leave it uninstalled — the
            # endpoint then answers 503 "no profiler armed", which is true.
            self.profiler = None
        self.flight = get_flight_recorder()
        # SLO sentinel (telemetry/slo.py): explicit instance wins; otherwise
        # the launcher's env contract (ACCELERATE_SLO_STEP_TIME/TTFT/TPOT)
        # arms one, or no target is configured and the sentinel stays off.
        # Disabled telemetry never feeds step boundaries, so no sentinel.
        if slo is not None:
            self.slo = slo
        elif self.enabled:
            from .slo import sentinel_from_env

            self.slo = sentinel_from_env()
        else:
            self.slo = None
        self.server: MetricsServer | None = None
        if metrics_port is not None:
            self.server = start_default_server(int(metrics_port), registry=self.registry)
        self._seen_timeline_n = 0
        self._last_hook_step = None

    # -------------------------------------------------------------- per-step
    def on_step(self, step: int, tokens: int | None = None, loss=None,
                state=None, window: int = 1) -> None:
        """Per-step hook (``guard_step``/``checkpoint_on_preemption`` call it).
        Records a timeline sample unless the fused path already did since the
        last hook; repeated hooks at one step (a loop calling both) count
        once. Drives the periodic straggler exchange when ``state`` is given —
        that exchange is a collective, so hooks must stay SPMD-aligned.
        Windowed loops hook once per K-step boundary with ``window=K`` so the
        straggler cadence stays per-STEP correct."""
        if not self.enabled:
            return
        step = int(step)
        if self.timeline.boundaries < self._seen_timeline_n:
            # The timeline was reset (bench.py does this per config): the
            # dedupe watermarks are from the old window and would silently
            # swallow the new window's first samples.
            self._seen_timeline_n = 0
            self._last_hook_step = None
        if step != self._last_hook_step:
            if self.timeline.boundaries == self._seen_timeline_n:
                # Fallback feed (the loop's fused program didn't): a windowed
                # boundary still covers `window` training steps.
                wall = self.timeline.step_end(step=step, tokens=tokens,
                                              loss=loss, steps=window)
                self.profiler.step_boundary(step=step, wall_s=wall, steps=window)
                self.flight.note_step(step=step, wall_s=wall, steps=window,
                                      transfers=_transfer_snapshot())
                self._journal_step(step, wall, window, tokens)
                if self.slo is not None and wall is not None:
                    self.slo.observe_step(wall, steps=window, step=step,
                                          mfu=self.timeline.last_mfu)
            else:
                # The fused program already marked this boundary (and fed the
                # profiler/black box); just pin the loop's step numbering so
                # explicit profile ranges refer to real steps.
                self.profiler.sync_step(step)
            self._seen_timeline_n = self.timeline.boundaries
            self._last_hook_step = step
        if state is not None and self.straggler.due(step, window):
            window_s, window_steps = self.timeline.take_window()
            if window_steps:
                report = self.straggler.report(
                    state, window_s / window_steps, step=step
                )
                if report is not None and report.tripped:
                    # Name the skew AND capture the evidence: a straggler trip
                    # arms a trace of the next steps on every host (the
                    # exchange is collective, so all hosts trip together) —
                    # budget/rate limits live in the manager.
                    self.flight.record(
                        "straggler_trip", step=step,
                        slowest_host=report.slowest_host,
                        ratio=round(report.ratio, 3),
                    )
                    self.profiler.request_capture(
                        steps=self.profiler.slow_capture_steps,
                        trigger="straggler",
                    )

    def on_fused_step(self, tokens: int | None = None, loss=None,
                      steps: int = 1) -> None:
        """Fed by ``build_train_step``'s compiled step — one call per
        microbatch dispatch, host-side cost of a clock read. Under windowed
        dispatch (``build_train_window``) one call covers ``steps`` training
        steps: ``tokens`` is the window TOTAL and ``loss`` the retained
        per-step K-vector — the timeline splits both so per-step statistics
        stay correct (see ``StepTimeline.step_end``)."""
        if not self.enabled:
            return
        wall = self.timeline.step_end(tokens=tokens, loss=loss, steps=steps)
        self.profiler.step_boundary(wall_s=wall, steps=steps)
        self.flight.note_step(wall_s=wall, steps=steps,
                              transfers=_transfer_snapshot())
        self._journal_step(None, wall, steps, tokens)
        if self.slo is not None and wall is not None:
            self.slo.observe_step(wall, steps=steps,
                                  mfu=self.timeline.last_mfu)

    def _journal_step(self, step, wall, steps, tokens):
        """Durable step-boundary record (telemetry/journal.py). Every field
        is host bookkeeping the boundary already produced — ``loss`` is the
        timeline's last DRAINED value (never a device fetch), so
        journaling-on adds zero blocking transfers versus journaling-off
        (the comparative pin in tests/test_journal.py). No-op when
        journaling is off (one global read)."""
        if wall is None:
            return  # baseline boundary: covers trace+compile, not a step
        journal_event(
            "step", step=step, wall_s=round(float(wall), 6), steps=int(steps),
            tokens=None if tokens is None else int(tokens),
            mfu=self.timeline.last_mfu, loss=self.timeline.last_loss,
        )

    # --------------------------------------------------------------- reading
    def summary(self) -> dict:
        out = {"timeline": self.timeline.summary()}
        if self.straggler.last_report is not None:
            out["straggler"] = self.straggler.last_report.to_dict()
        if self.slo is not None and self.slo.active:
            out["slo"] = self.slo.summary()
        return out

    def close(self):
        if self.server is not None:
            stop_default_server()
            self.server = None


_DEFAULT: Telemetry | None = None


def get_telemetry() -> Telemetry:
    """The process-wide default, built from the launcher's env contract on
    first use (ACCELERATE_TELEMETRY / ACCELERATE_METRICS_PORT /
    ACCELERATE_STRAGGLER_THRESHOLD)."""
    global _DEFAULT
    if _DEFAULT is None:
        from ..utils.constants import ENV_STRAGGLER_THRESHOLD, ENV_TELEMETRY

        from .metrics import default_server

        enabled = os.environ.get(ENV_TELEMETRY, "").strip().lower() not in (
            "0", "false", "no",
        )
        # Threshold 0/unset = library default 1.5 (the convention the config
        # wizard documents and prepare_launch_env's truthiness gate implies).
        threshold_raw = os.environ.get(ENV_STRAGGLER_THRESHOLD, "").strip()
        threshold = float(threshold_raw) if threshold_raw else 0.0
        telemetry = Telemetry(
            enabled=enabled,
            straggler_threshold=threshold if threshold > 0 else 1.5,
        )
        # Reuse the server PartialState already installed (its port carries
        # the co-located-worker offset — re-requesting the raw env port would
        # warn spuriously); otherwise run the same shared env install.
        telemetry.server = default_server() or start_endpoint_from_env()
        _DEFAULT = telemetry
    return _DEFAULT


def live_telemetry() -> Telemetry | None:
    """The default instance IF one exists — the peek cold paths use
    (journal.finalize_run) so assembling a run summary in a process that
    never built telemetry doesn't construct one as a side effect."""
    return _DEFAULT


def set_telemetry(telemetry: Telemetry | None):
    global _DEFAULT
    _DEFAULT = telemetry


def reset_telemetry():
    """Drop the default instance — tests."""
    set_telemetry(None)
