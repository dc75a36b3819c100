"""Unified-telemetry tests — spans, step timeline, metrics endpoint,
straggler detection (ISSUE 4 acceptance: a guarded, telemetry-enabled
training loop adds ZERO blocking device→host transfers per step versus
telemetry-off, pinned with the utils/transfer.py counters, while the per-step
timeline, Prometheus scrape, and straggler skew report all populate; health
trips, goodput classes, and restarts appear as metrics in one registry).

All deterministic and CPU-fast: the timeline takes an injectable clock, the
straggler drill feeds synthetic per-host step times, and the 2-process drill
rides the real launcher (test_utils/straggler_script.py)."""

import logging
import os
import re
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import jax
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.telemetry import (
    MetricsRegistry,
    MetricsServer,
    SpanRing,
    StepTimeline,
    StragglerMonitor,
    Telemetry,
    get_registry,
    get_span_ring,
    get_telemetry,
    reset_spans,
    reset_telemetry,
    span,
)
from accelerate_tpu.telemetry.timeline import batch_token_count, device_peak_flops
from accelerate_tpu.test_utils import RegressionModel
from accelerate_tpu.utils.transfer import reset_transfer_stats, transfer_stats

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _reset_telemetry_state():
    yield
    from accelerate_tpu.resilience import reset_active_plan
    from accelerate_tpu.telemetry import stop_default_server

    reset_active_plan()
    stop_default_server()
    reset_telemetry()
    reset_spans()


def _build():
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    accelerator = Accelerator()
    model = RegressionModel()
    model.init_params(None)
    pmodel, popt = accelerator.prepare(model, optax.adam(0.1))
    return accelerator, pmodel, popt


def _batch(step):
    rng = np.random.default_rng(100 + step)
    x = rng.normal(size=(8,)).astype(np.float32)
    return {"x": x, "y": (2.0 * x + 3.0).astype(np.float32)}


# ------------------------------------------------------------------- spans
def test_span_nesting_records_depth_and_path():
    ring = SpanRing(capacity=16)
    with span("outer", ring=ring):
        with span("inner", ring=ring):
            pass
    records = ring.snapshot()
    assert [r.name for r in records] == ["inner", "outer"]  # pushed at exit
    inner, outer = records
    assert inner.depth == 1 and inner.path == "outer/inner"
    assert outer.depth == 0 and outer.path == "outer"
    assert outer.duration_s >= inner.duration_s >= 0.0


def test_span_ring_wraparound_keeps_newest():
    ring = SpanRing(capacity=4)
    for i in range(10):
        with span(f"s{i}", ring=ring):
            pass
    assert ring.total == 10
    records = ring.snapshot()
    assert len(records) == 4
    assert [r.name for r in records] == ["s6", "s7", "s8", "s9"]


def test_framework_spans_cover_prepare_and_train_step():
    reset_spans()
    accelerator, pmodel, popt = _build()
    step = accelerator.build_train_step(pmodel, popt)
    step(_batch(1))
    names = {r.name for r in get_span_ring().snapshot()}
    assert {"prepare", "train_step"} <= names


def test_fused_step_program_is_named_train_step():
    """The fused step's name in a profile (``jit_train_step`` on the device
    plane's module line) is the name of the host span around its dispatch."""
    accelerator, pmodel, popt = _build()
    step = accelerator.build_train_step(pmodel, popt)
    assert "module @jit_train_step " in step.lower(_batch(1)).as_text().split("\n", 1)[0]


# ---------------------------------------------------------------- timeline
def test_fused_loop_timeline_zero_blocking_transfers():
    """Acceptance: the always-on timeline never stalls the dispatch thread —
    retained loss scalars drain only when materialized."""
    accelerator, pmodel, popt = _build()
    step = accelerator.build_train_step(pmodel, popt)
    reset_transfer_stats()
    for i in range(1, 9):
        step(_batch(i))
    stats = transfer_stats()
    stats.pop("resets", None)  # reset-generation counter, not a transfer
    assert stats == {
        "fetches": 0, "blocking": 0,  # hot loop async
        "h2d_puts": 0, "h2d_blocking": 0, "input_wait_s": 0.0,  # no prefetcher in play
    }
    timeline = accelerator.telemetry.timeline
    assert timeline.count == 7  # first boundary is the compile baseline
    summary = timeline.summary()
    assert summary["steps"] == 7
    assert summary["step_s"]["p50"] > 0
    assert summary["last_loss"] is not None  # drained once materialized...
    stats = transfer_stats()
    assert stats["blocking"] == 0  # ...as a copy, never a stall
    assert stats["fetches"] <= 4


def test_timeline_baseline_survives_transfer_reset():
    """Regression (PR 6's health+window suite-combo failure): a
    reset_transfer_stats() AFTER a timeline captured its delta baseline used
    to drive summary()['transfers'] negative — the timeline now detects the
    reset generation and re-anchors at zero."""
    from accelerate_tpu.telemetry.timeline import StepTimeline
    from accelerate_tpu.utils import transfer

    transfer._stats["fetches"] += 3
    transfer._stats["blocking"] += 2
    timeline = StepTimeline()  # baseline captures the non-zero globals
    reset_transfer_stats()     # ...then someone zeroes them underneath
    stats = timeline.summary()["transfers"]
    assert stats["blocking"] == 0 and stats["fetches"] == 0
    # Counts after the reset are attributed normally.
    transfer._stats["fetches"] += 1
    assert timeline.summary()["transfers"]["fetches"] == 1


def test_guarded_telemetry_loop_populates_without_blocking():
    """The guarded-loop acceptance drill: guard + telemetry together, zero
    blocking transfers, timeline populated, trip surfaces in the registry.
    The blocking==0 assert is wall-clock-sensitive under machine load, so the
    drill runs through the shared load-tolerant helper — a deterministic
    regression still fails every attempt."""
    from accelerate_tpu.resilience import FaultPlan, set_active_plan
    from accelerate_tpu.test_utils import run_nonblocking_drill

    box = {}

    def drill():
        set_active_plan(FaultPlan.parse("step:8=nan"))
        accelerator, pmodel, popt = _build()
        accelerator.configure_health(spike_warmup=50, snapshot_every=3)
        guard = accelerator.health_guard
        reset_transfer_stats()
        trips = []
        while accelerator.step < 12:
            step = accelerator.step + 1
            if guard.should_skip(step):
                accelerator.step = step
                continue
            out = pmodel(**_batch(step))
            accelerator.backward(out.loss)
            popt.step()
            popt.zero_grad()
            accelerator.step = step
            verdict = accelerator.guard_step(out.loss)
            if verdict.tripped:
                trips.append(verdict)
        box.update(accelerator=accelerator, trips=trips)
        return transfer_stats()

    stats = run_nonblocking_drill(drill)
    assert stats["blocking"] == 0
    accelerator, trips = box["accelerator"], box["trips"]
    assert len(trips) == 1
    timeline = accelerator.telemetry.timeline
    assert timeline.count >= 10  # one sample per hooked step
    snapshot = get_registry().snapshot()
    trip_keys = [k for k in snapshot if k.startswith("accelerate_health_trips_total")]
    assert trip_keys and any(snapshot[k] >= 1 for k in trip_keys)
    rollbacks = snapshot.get("accelerate_health_rollbacks_total", 0)
    assert rollbacks >= 1
    # Goodput classes and restarts ride the same registry via collectors.
    assert "accelerate_goodput_fraction" in snapshot
    assert 'accelerate_badput_seconds{category="rollback"}' in snapshot
    assert "accelerate_restarts" in snapshot


def test_on_step_dedupes_same_step_hooks():
    telemetry = Telemetry(registry=MetricsRegistry())
    telemetry.on_step(4)  # first hook sets the baseline
    telemetry.on_step(5)
    telemetry.on_step(5)  # second hook at one step (guard + preemption)
    telemetry.on_step(6)
    assert telemetry.timeline.count == 2

    # A fused dispatch between hooks marks the step covered — even the
    # baseline call of a fresh fused loop (timeline.boundaries, not count).
    fused = Telemetry(registry=MetricsRegistry())
    fused.on_fused_step()  # compile baseline: count stays 0
    fused.on_step(1)       # hook at the same step must not add a sample
    assert fused.timeline.count == 0
    fused.on_fused_step()
    fused.on_step(2)
    assert fused.timeline.count == 1

    # Fallback feed under windowed hooks: a loop whose own fused program does
    # NOT feed the timeline still gets K per-step samples per K-step boundary,
    # and a retained per-step K-vector of losses drains to its last element.
    windowed = Telemetry(registry=MetricsRegistry())
    windowed.on_step(4, window=4)  # baseline boundary
    windowed.on_step(8, window=4, loss=np.arange(4.0))
    assert windowed.timeline.count == 4
    assert windowed.timeline.summary()["last_loss"] == 3.0


@pytest.mark.parametrize("device_kind, peak", [("TPU v5 lite", 197e12), ("cpu", None)])
def test_mfu_estimate_needs_a_known_peak(monkeypatch, device_kind, peak):
    """A device kind in the peak table gives the formula's value; one that is
    not (the CPU) has no peak, and so no mfu_estimate."""
    import types

    from accelerate_tpu.telemetry import timeline as timeline_module

    stub = types.SimpleNamespace(device_kind=device_kind)
    monkeypatch.setattr(timeline_module.jax, "devices", lambda *a, **k: [stub])
    assert device_peak_flops() == peak
    clock = [0.0]
    timeline = StepTimeline(registry=MetricsRegistry(), clock=lambda: clock[0])
    flops_per_token = 2.5e9
    timeline.set_model_flops(flops_per_token)
    timeline.step_end()  # baseline
    for step in range(1, 6):
        clock[0] += 0.5
        timeline.step_end(step=step, tokens=1000)
    summary = timeline.summary()
    assert summary["tokens_per_s"] == pytest.approx(2000.0)
    assert summary["step_s"]["p50"] == pytest.approx(0.5)
    if peak is None:
        assert summary["mfu_estimate"] is None
        assert timeline.last_mfu is None
    else:
        expected = 2000.0 * flops_per_token / (peak * jax.device_count())
        assert summary["mfu_estimate"] == pytest.approx(expected, rel=1e-9)
        assert timeline.last_mfu == pytest.approx(expected, rel=1e-9)


def test_batch_token_count():
    assert batch_token_count({"input_ids": np.zeros((4, 16), np.int32)}) == 64
    assert batch_token_count({"x": np.zeros((8,), np.float32)}) is None
    assert batch_token_count([1, 2, 3]) is None


# ----------------------------------------------------------------- metrics
def test_registry_counter_gauge_histogram_and_conflicts():
    registry = MetricsRegistry()
    counter = registry.counter("t_total", "help", labelnames=("kind",))
    counter.inc(kind="a")
    counter.inc(2, kind="a")
    assert counter.value(kind="a") == 3
    gauge = registry.gauge("g")
    gauge.set(1.5)
    gauge.inc()
    assert gauge.value() == 2.5
    hist = registry.histogram("h", buckets=(0.1, 1.0))
    hist.observe(0.05)
    hist.observe(0.5)
    hist.observe(5.0)
    total, count = hist.value()
    assert count == 3 and total == pytest.approx(5.55)
    with pytest.raises(ValueError):
        registry.gauge("t_total")  # type conflict
    with pytest.raises(ValueError):
        registry.counter("t_total", labelnames=("other",))  # label conflict
    with pytest.raises(ValueError):
        counter.inc(kind="a", extra="no")  # unknown label
    snapshot = registry.snapshot()
    assert snapshot['t_total{kind="a"}'] == 3.0
    assert snapshot["h_count"] == 3.0


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?([0-9.eE+-]+|inf|nan)$"
)


def test_prometheus_endpoint_scrape_parses():
    registry = MetricsRegistry()
    registry.counter("scrape_total", "requests", labelnames=("kind",)).inc(kind="x")
    registry.gauge("val").set(1.25)
    hist = registry.histogram("lat", "latency", buckets=(0.1, 1.0))
    hist.observe(0.05)
    hist.observe(0.5)
    server = MetricsServer(0, registry=registry, host="127.0.0.1")
    port = server.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ).read().decode()
    finally:
        server.stop()
    assert health == "ok\n"
    lines = [l for l in body.splitlines() if l]
    assert "# TYPE scrape_total counter" in lines
    assert "# TYPE lat histogram" in lines
    for line in lines:
        if line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), line
    # Histogram exposition: cumulative buckets, +Inf == count.
    assert 'lat_bucket{le="0.1"} 1' in lines
    assert 'lat_bucket{le="1.0"} 2' in lines
    assert 'lat_bucket{le="+Inf"} 2' in lines
    assert "lat_count 2" in lines
    assert 'scrape_total{kind="x"} 1.0' in lines


def test_env_contract_builds_default_telemetry(monkeypatch):
    monkeypatch.setenv("ACCELERATE_TELEMETRY", "0")
    monkeypatch.setenv("ACCELERATE_STRAGGLER_THRESHOLD", "2.5")
    # Env contract: port 0 = NO endpoint (only the explicit
    # Telemetry(metrics_port=0) API means "ephemeral").
    monkeypatch.setenv("ACCELERATE_METRICS_PORT", "0")
    reset_telemetry()
    telemetry = get_telemetry()
    assert telemetry.enabled is False
    assert telemetry.straggler.slow_ratio == 2.5
    assert telemetry.server is None
    telemetry.on_step(1)  # disabled: records nothing
    assert telemetry.timeline.count == 0


# --------------------------------------------------------------- straggler
def test_straggler_report_single_host():
    monitor = StragglerMonitor(every_steps=4, slow_ratio=1.5,
                               registry=MetricsRegistry())
    assert not monitor.due(3) and monitor.due(4)
    # Windowed boundaries advance by K: the exchange is due when ANY in-window
    # step crossed the cadence, not only when the boundary itself lands on it
    # (every_steps=4, window=3 → boundaries 3, 6, 9, 12: step 4 is inside the
    # [4..6] window, step 8 inside [7..9], neither boundary divides 4).
    assert monitor.due(6, window=3) and monitor.due(9, window=3)
    assert not monitor.due(3, window=3)

    class _State:
        num_processes, process_index = 1, 0

    report = monitor.report(_State(), 0.02, step=4)
    assert report.per_host_s == [0.02]
    assert report.ratio == 1.0 and not report.tripped
    assert monitor.last_report is report


def test_straggler_two_process_drill_identifies_slow_rank():
    """Satellite: on the real 2-process CPU harness every rank's exchange
    names the same slow rank (the script asserts per-rank; the KV fallback
    carries the gather exactly like the health agreement)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "2", "-m",
            "accelerate_tpu.test_utils.straggler_script",
        ],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    assert proc.stdout.count("STRAGGLER_OK") == 2
    assert proc.stdout.count("slowest=1") == 2


# ------------------------------------------------------------ rate limiting
def test_log_every_n_is_per_callsite(caplog):
    from accelerate_tpu.logging import get_logger

    logger = get_logger("telemetry_test_logger")
    logger.logger.setLevel(logging.INFO)
    with caplog.at_level(logging.INFO, logger="telemetry_test_logger"):
        for i in range(10):
            logger.log_every_n(4, logging.INFO, f"alert {i}")
    emitted = [r.message for r in caplog.records]
    assert len(emitted) == 3  # calls 0, 4, 8
    assert emitted[0] == "alert 0"
    assert emitted[1].startswith("alert 4 [1/4")
    with caplog.at_level(logging.INFO, logger="telemetry_test_logger"):
        logger.log_every_n(4, logging.INFO, "other site")  # fresh callsite
    assert any("other site" in r.message for r in caplog.records)
    with pytest.raises(ValueError):
        logger.log_every_n(0, logging.INFO, "bad n")


# ------------------------------------------------ config / launch / env
def test_launch_flags_export_telemetry_env():
    from accelerate_tpu.commands.launch import (
        _merge_config,
        launch_command_parser,
        prepare_launch_env,
    )

    args = launch_command_parser().parse_args(
        ["--cpu", "--telemetry", "--metrics_port", "9109",
         "--straggler_threshold", "2.0", "x.py"]
    )
    env = prepare_launch_env(_merge_config(args))
    assert env["ACCELERATE_TELEMETRY"] == "1"
    assert env["ACCELERATE_METRICS_PORT"] == "9109"
    assert env["ACCELERATE_STRAGGLER_THRESHOLD"] == "2.0"

    # Tri-state: unconfigured exports nothing (telemetry defaults ON)...
    bare = prepare_launch_env(
        _merge_config(launch_command_parser().parse_args(["--cpu", "x.py"]))
    )
    for key in ("ACCELERATE_TELEMETRY", "ACCELERATE_METRICS_PORT",
                "ACCELERATE_STRAGGLER_THRESHOLD"):
        assert key not in bare
    # ...while an explicit --no-telemetry must reach the workers as a disable.
    off = prepare_launch_env(
        _merge_config(launch_command_parser().parse_args(
            ["--cpu", "--no-telemetry", "x.py"]
        ))
    )
    assert off["ACCELERATE_TELEMETRY"] == "0"


def test_launch_validates_telemetry_flags():
    from accelerate_tpu.commands.launch import launch_command, launch_command_parser

    with pytest.raises(ValueError, match="metrics_port"):
        launch_command(launch_command_parser().parse_args(
            ["--cpu", "--metrics_port", "70000", "x.py"]
        ))
    with pytest.raises(ValueError, match="straggler_threshold"):
        launch_command(launch_command_parser().parse_args(
            ["--cpu", "--straggler_threshold", "0.5", "x.py"]
        ))


def test_bench_failure_line_carries_schema_version(capsys):
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)
    bench._print_failure("tiny", RuntimeError("boom"))
    import json

    line = json.loads(capsys.readouterr().out.strip())
    assert line["schema_version"] == bench.BENCH_SCHEMA_VERSION
    # A failed config measured nothing, and this is not a TPU: no number goes
    # under the device metric's name, and the line names the device it ran on.
    assert line["value"] is None and line["failed"] is True
    assert line["platform"] == "cpu" and line["rehearsal"] is True
    assert line["device_count"] == jax.device_count()


# ------------------------------------------------------------- shard_map shim
def test_shard_map_compat_psum_over_named_axis():
    """utils.jax_compat.shard_map (the repo's one home of jax.shard_map) runs
    a manual-axis collective with the other mesh axes left automatic. Under
    jit, as every call site in the repo is: partial-auto mapping is not
    offered eagerly."""
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils.jax_compat import shard_map

    mesh = PartialState().mesh
    fn = shard_map(
        lambda x: jax.lax.psum(x, "dp"),
        mesh=mesh,
        in_specs=P("dp"),
        out_specs=P(),
        axis_names={"dp"},
        check_vma=False,
    )
    dp = mesh.shape["dp"]
    x = np.arange(float(dp), dtype=np.float32)
    out = np.asarray(jax.jit(fn)(x))
    np.testing.assert_allclose(out, np.full_like(out, x.sum()))
