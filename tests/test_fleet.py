"""Fleet observability plane (telemetry/fleet.py, requests.py, slo.py,
commands/top.py): cross-host metric aggregation over the KV endpoint
registry, per-request serving lifecycle traces, and the continuous SLO
sentinel. Acceptance properties pinned here: the 2-process launcher drill
joins BOTH hosts' step-time series by host label via KV discovery alone
(``accelerate-tpu top --once --json`` parses it end to end), a serving wave
with tracing + SLO targets yields complete lifecycle records and a
breach-triggered capture + flight-recorder evidence, and the traced
steady-state loop still performs zero blocking device-to-host transfers."""

import json
import os
import socket
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.serving import ContinuousBatcher, SLOTargets
from accelerate_tpu.telemetry.fleet import (
    FleetAggregator,
    _inject_host_label,
    fetch_fleet_snapshot,
    install_fleet_provider,
    parse_prometheus_text,
    publish_metrics_endpoint,
)
from accelerate_tpu.telemetry.metrics import (
    MetricsRegistry,
    MetricsServer,
    set_fleet_provider,
    set_profile_trigger,
    stop_default_server,
)

pytestmark = pytest.mark.fleet

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


def _host_registry(step_s: float, mfu: float) -> MetricsRegistry:
    registry = MetricsRegistry()
    hist = registry.histogram("accelerate_step_seconds", "h")
    for _ in range(3):
        hist.observe(step_s)
    registry.gauge("accelerate_mfu_estimate", "g").set(mfu)
    registry.gauge("accelerate_goodput_fraction", "g").set(0.9)
    registry.gauge("accelerate_badput_seconds", "g",
                   labelnames=("category",)).set(1.5, category="compile")
    return registry


# ==================================================================== parsing
def test_parse_prometheus_text_families():
    text = (
        "# HELP accelerate_mfu_estimate h\n"
        "# TYPE accelerate_mfu_estimate gauge\n"
        "accelerate_mfu_estimate 0.41\n"
        "# TYPE accelerate_step_seconds histogram\n"
        'accelerate_step_seconds_bucket{le="0.1"} 3\n'
        "accelerate_step_seconds_sum 0.42\n"
        "accelerate_step_seconds_count 3\n"
        "# TYPE accelerate_badput_seconds gauge\n"
        'accelerate_badput_seconds{category="compile"} 1.5\n'
    )
    families = parse_prometheus_text(text)
    assert families["accelerate_mfu_estimate"]["kind"] == "gauge"
    assert families["accelerate_mfu_estimate"]["series"]["accelerate_mfu_estimate"] == 0.41
    # Histogram suffixes fold into the base family so nothing is lost.
    series = families["accelerate_step_seconds"]["series"]
    assert series["accelerate_step_seconds_sum"] == 0.42
    assert series["accelerate_step_seconds_count"] == 3
    assert series['accelerate_step_seconds_bucket{le="0.1"}'] == 3
    assert families["accelerate_badput_seconds"]["series"][
        'accelerate_badput_seconds{category="compile"}'
    ] == 1.5


def test_inject_host_label():
    assert _inject_host_label("accelerate_mfu_estimate 0.4", "2") == (
        'accelerate_mfu_estimate{host="2"} 0.4'
    )
    assert _inject_host_label(
        'accelerate_badput_seconds{category="compile"} 1.5', "0"
    ) == 'accelerate_badput_seconds{host="0",category="compile"} 1.5'
    assert _inject_host_label("# TYPE x gauge", "0") == "# TYPE x gauge"
    # A series already carrying a host label (the straggler's per-host
    # gauges) must NOT gain a duplicate — the scraped-rank label wins the
    # name, the original renames to exported_host (honor_labels=false).
    assert _inject_host_label(
        'accelerate_host_step_seconds{host="0"} 0.02', "1"
    ) == 'accelerate_host_step_seconds{host="1",exported_host="0"} 0.02'
    assert _inject_host_label(
        'x{kind="a",host="3"} 1', "0"
    ) == 'x{host="0",kind="a",exported_host="3"} 1'


def test_aggregator_renders_unregistered_rank_down():
    """A rank whose metrics bind failed never registers an endpoint — the
    pane renders it as a down row (discovery degrades, never raises)."""
    live = MetricsServer(0, registry=_host_registry(0.1, 0.4), host="127.0.0.1")
    try:
        live.start()
        publish_metrics_endpoint(process_index=0, server=live)

        class _State:
            num_processes = 2

        aggregator = FleetAggregator(state=_State(), cache_s=0.0)
        snap = aggregator.snapshot()
        assert snap["hosts"]["0"]["up"]
        assert not snap["hosts"]["1"]["up"]
        assert "registered" in snap["hosts"]["1"]["error"]
        assert snap["fleet"]["hosts_up"] == 1 and snap["fleet"]["hosts_total"] == 2
        # The console renders the endpoint-less row instead of dying on it.
        from accelerate_tpu.commands.top import render_snapshot

        frame = render_snapshot(snap)
        assert "DOWN" in frame and "registered" in frame
    finally:
        from accelerate_tpu.telemetry.fleet import reset_fleet

        reset_fleet()
        live.stop()


# ================================================================ aggregation
def test_aggregator_joins_hosts_rollups_and_fleet_route():
    """Two live endpoints with distinct series → one snapshot with per-host
    rows, host-labeled joined series, and fleet rollups; GET /fleet and
    /fleet/metrics serve it from the existing HTTP server."""
    servers = [
        MetricsServer(0, registry=_host_registry(0.1, 0.4), host="127.0.0.1"),
        MetricsServer(0, registry=_host_registry(0.3, 0.3), host="127.0.0.1"),
    ]
    try:
        for s in servers:
            s.start()
        aggregator = FleetAggregator(
            endpoints=[f"127.0.0.1:{s.port}" for s in servers], cache_s=0.0
        )
        snap = aggregator.snapshot()
        assert snap["hosts"]["0"]["up"] and snap["hosts"]["1"]["up"]
        assert snap["hosts"]["0"]["step_s_mean"] == pytest.approx(0.1)
        assert snap["hosts"]["1"]["step_s_mean"] == pytest.approx(0.3)
        fleet = snap["fleet"]
        assert fleet["hosts_up"] == 2
        assert fleet["mfu"] == pytest.approx(0.35)
        assert fleet["step_s"]["skew"] == pytest.approx(1.5)
        assert fleet["goodput"]["badput_s"]["compile"] == pytest.approx(3.0)
        for host in ("0", "1"):
            assert f'accelerate_step_seconds_sum{{host="{host}"}}' in snap["series"]
        text = aggregator.prometheus_text()
        assert 'accelerate_mfu_estimate{host="0"} 0.4' in text
        assert 'accelerate_mfu_estimate{host="1"} 0.3' in text
        assert text.count("# TYPE accelerate_mfu_estimate gauge") == 1

        install_fleet_provider(aggregator)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{servers[0].port}/fleet", timeout=5
        ) as response:
            got = json.loads(response.read())
        assert got["fleet"]["hosts_up"] == 2
        with urllib.request.urlopen(
            f"http://127.0.0.1:{servers[0].port}/fleet/metrics", timeout=5
        ) as response:
            assert b'host="1"' in response.read()
    finally:
        set_fleet_provider(None)
        for s in servers:
            s.stop()


def test_aggregator_marks_dead_host_down():
    """One dead worker degrades to an up=false row — it must not blank the
    pane for the rest of the fleet."""
    live = MetricsServer(0, registry=_host_registry(0.1, 0.4), host="127.0.0.1")
    try:
        live.start()
        # Reserve a port with nothing listening for the dead endpoint.
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        aggregator = FleetAggregator(
            endpoints=[f"127.0.0.1:{live.port}", f"127.0.0.1:{dead_port}"],
            timeout_s=0.5, cache_s=0.0,
        )
        snap = aggregator.snapshot()
        assert snap["hosts"]["0"]["up"] and not snap["hosts"]["1"]["up"]
        assert "error" in snap["hosts"]["1"]
        assert snap["fleet"]["hosts_up"] == 1 and snap["fleet"]["hosts_total"] == 2
    finally:
        live.stop()


def test_fetch_falls_back_to_client_side_aggregation():
    """Against a worker with no /fleet provider, the top transport aggregates
    that one endpoint client-side — a bare worker is still inspectable."""
    server = MetricsServer(0, registry=_host_registry(0.2, 0.5), host="127.0.0.1")
    try:
        server.start()
        snap = fetch_fleet_snapshot(f"127.0.0.1:{server.port}")
        assert snap["fleet"]["hosts_up"] == 1
        assert snap["hosts"]["0"]["mfu"] == pytest.approx(0.5)
    finally:
        server.stop()


def test_top_render_and_cli_once_json():
    """render_snapshot is pure; the CLI's --once --json frame parses back to
    the snapshot (the CI-consumable contract)."""
    from accelerate_tpu.commands.top import render_snapshot

    server = MetricsServer(0, registry=_host_registry(0.1, 0.4), host="127.0.0.1")
    try:
        server.start()
        aggregator = FleetAggregator(
            endpoints=[f"127.0.0.1:{server.port}"], cache_s=0.0
        )
        snap = aggregator.snapshot()
        frame = render_snapshot(snap)
        assert "hosts 1/1 up" in frame and "mfu 0.4000" in frame
        assert f"127.0.0.1:{server.port}" in frame
        result = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "top", "--once", "--json", "--endpoint",
             f"127.0.0.1:{server.port}"],
            capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
            env={**os.environ, "PYTHONPATH": REPO_ROOT},
        )
        assert result.returncode == 0, result.stderr[-1500:]
        got = json.loads(result.stdout)
        assert got["hosts"]["0"]["step_s_mean"] == pytest.approx(0.1)
    finally:
        server.stop()


def test_metrics_endpoint_property_publishes_bound_port(monkeypatch):
    """Satellite: PartialState publishes the ACTUALLY bound host:port and
    exposes it as .metrics_endpoint — no more guessing offset ports."""
    from accelerate_tpu.state import PartialState
    from accelerate_tpu.telemetry import fleet

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    monkeypatch.setenv("ACCELERATE_METRICS_PORT", str(port))
    try:
        state = PartialState(cpu=True)
        endpoint = state.metrics_endpoint
        assert endpoint is not None and endpoint.endswith(f":{port}"), endpoint
        assert fleet.metrics_endpoint() == endpoint
        assert fleet.cached_endpoint(state.process_index) == endpoint
        with urllib.request.urlopen(f"http://{endpoint}/metrics", timeout=5) as r:
            assert b"accelerate" in r.read() or r.status == 200
    finally:
        stop_default_server()


def test_fleet_two_process_launcher_drill():
    """Tentpole acceptance: 2 ranks on the real launcher, EPHEMERAL metrics
    ports registered in the coordination-service KV namespace, the lead
    host's aggregator discovers + scrapes both with no address list, and
    `accelerate-tpu top --once --json` returns both hosts' step-time series
    under distinct host labels plus fleet rollups (asserted in the script)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "2", "-m",
            "accelerate_tpu.test_utils.fleet_script",
        ],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    assert proc.stdout.count("FLEET_OK") == 2


# ============================================================ request tracing
def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=8, max_cache_len=512,
              cache_dtype=jnp.float32, bucket_sizes=(8,), sync_every=2,
              block_size=4)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def test_request_tracer_full_lifecycle_with_breach_capture(llama, tmp_path):
    """Serving drill acceptance: a chunked-prefill request walks every
    lifecycle state (submit → admit → prefill chunks → first token → decode
    windows → finish), the sub-microsecond TTFT target breaches —
    incrementing accelerate_slo_breaches_total{target="ttft"}, landing
    slo_breach + admission events in a flight-recorder dump the blackbox
    renders — and the breach arms a capture via the installed profile
    trigger."""
    from accelerate_tpu.telemetry.flight import get_flight_recorder
    from accelerate_tpu.telemetry.slo import breach_counts

    armed = []
    set_profile_trigger(lambda steps, trigger: armed.append((steps, trigger))
                        or {"accepted": True})
    try:
        before = breach_counts().get("ttft", 0)
        # bucket == prefill_chunk pins the escalation path off, so the long
        # prompt stays chunked and the admission decision is plain "admit".
        engine = _paged(llama, prefill_chunk=8, max_tokens_per_request=64,
                        slo=SLOTargets(ttft_s=1e-7, tpot_s=1e-9))
        prompt = np.random.default_rng(7).integers(1, 256, (21,)).astype(np.int32)
        rid = engine.submit(prompt)
        outs = engine.run()
        assert rid in outs and len(outs[rid]) > 0

        record = {r["rid"]: r for r in engine.tracer.records()}[rid]
        assert record["state"] == "finished"
        assert record["decision"] == "admit"
        assert record["queue_wait_s"] is not None
        assert record["chunks"] == [8, 8, 8]  # 2 exact chunks + bucketed final
        assert record["ttft_s"] is not None and record["ttft_s"] > 0
        assert record["decode_windows"] >= 1
        assert record["tokens_out"] == len(outs[rid])
        assert "ttft" in record["breached"]
        assert breach_counts().get("ttft", 0) > before
        assert armed and armed[0][1] == "slo"

        summary = engine.tracer.summary()
        assert summary["ttft_s"]["max"] >= record["ttft_s"]
        assert summary["slowest"][0]["rid"] == rid
        assert summary["breaches"] >= 1

        events = get_flight_recorder().snapshot()
        kinds = {e["kind"] for e in events}
        assert "slo_breach" in kinds and "admission" in kinds
        breach = next(e for e in events if e["kind"] == "slo_breach")
        assert breach["target"] == "ttft" and breach["rid"] == rid

        # The black box renders the SLO/admission story in the timeline view.
        dump_path = str(tmp_path / "dump.json")
        assert get_flight_recorder().dump("test", path=dump_path) == dump_path
        render = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "blackbox", dump_path],
            capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
            env={**os.environ, "PYTHONPATH": REPO_ROOT},
        )
        assert render.returncode == 0, render.stderr[-1500:]
        assert "slo breaches in window:" in render.stdout
        assert "ttft=" in render.stdout and "admit=" in render.stdout
        assert "slo_breach" in render.stdout  # the raw timeline line too
    finally:
        set_profile_trigger(None)


def test_request_tracer_defer_and_cancel(llama):
    """Deferred prefills count per request (one admission event), and a
    reset() mid-wave closes in-flight records as cancelled."""
    from accelerate_tpu.telemetry.requests import RequestTracer

    tracer = RequestTracer(capacity=4)
    tracer.submit(1, 10)
    tracer.admit(1, "admit")
    tracer.defer(1)
    tracer.defer(1)
    assert tracer.records()[0]["defers"] == 2
    # Overwrite-oldest: capacity 4, submit 5 → rid 1 evicted, total keeps counting.
    for rid in range(2, 7):
        tracer.submit(rid, 1)
    assert len(tracer.records()) == 4 and tracer.total == 6
    assert tracer.records()[0]["rid"] == 3

    engine = _paged(llama)
    rid = engine.submit(np.arange(1, 6, dtype=np.int32))
    # Admit without finishing: drive admission surgery only.
    engine._admit_paged(0.0)
    engine.reset()
    record = {r["rid"]: r for r in engine.tracer.records()}[rid]
    assert record["state"] == "cancelled"


def test_traced_steady_state_loop_stays_nonblocking(llama):
    """Acceptance pin: tracing + SLO sentinel + aggregator scrapes add ZERO
    device-to-host transfers to the paged steady-state loop vs telemetry-off.
    Pinned COMPARATIVELY in one process: identical waves run telemetry-off and
    fully traced (tracer + SLO targets + a live scrape either side), and the
    traced wave must perform exactly the untraced wave's deliberate fetch/put
    counts (deterministic — the tracer hooks ride host bookkeeping the loop
    already pays) and no additional blocking fetches. Absolute blocking of
    the lookahead report read is wall-clock-sensitive on the warm-compile-
    cache CPU rig, so the DELTA is judged through run_nonblocking_drill —
    load jitter retries, a deterministic tracing regression still fails."""
    from accelerate_tpu.telemetry.metrics import start_default_server
    from accelerate_tpu.test_utils.drills import run_nonblocking_drill
    from accelerate_tpu.utils.transfer import reset_transfer_stats, transfer_stats

    server = start_default_server(0)
    stash = {}
    wave_kw = dict(batch_slots=1, max_new_tokens=24, max_tokens_per_request=40)
    prompt = np.arange(1, 6, dtype=np.int32)
    try:
        aggregator = FleetAggregator(
            endpoints=[f"127.0.0.1:{server.port}"], cache_s=0.0
        )

        def wave(traced: bool):
            if traced:
                engine = _paged(llama, slo=SLOTargets(ttft_s=1e-7, tpot_s=1e-9),
                                **wave_kw)
                assert engine.tracer is not None
                aggregator.snapshot()  # pre-wave scrape
            else:
                engine = _paged(llama, trace_requests=False, **wave_kw)
                assert engine.tracer is None and engine.slo is None
            rid = engine.submit(prompt)
            reset_transfer_stats()
            out = engine.run()[rid]
            stats = transfer_stats()
            if traced:
                aggregator.snapshot()  # post-wave scrape joins serving gauges
                stash["engine"], stash["rid"], stash["out"] = engine, rid, out
            return stats, out

        wave(traced=False)  # warm the jit cache so both measured arms match

        def drill():
            base, base_out = wave(traced=False)
            traced, traced_out = wave(traced=True)
            np.testing.assert_array_equal(base_out, traced_out)
            return {
                "extra_fetches": abs(traced["fetches"] - base["fetches"]),
                "extra_h2d_puts": abs(traced["h2d_puts"] - base["h2d_puts"]),
                "h2d_blocking": traced["h2d_blocking"],
                "extra_blocking": max(0, traced["blocking"] - base["blocking"]),
            }

        run_nonblocking_drill(
            drill, keys=("extra_fetches", "extra_h2d_puts", "h2d_blocking",
                         "extra_blocking")
        )
        engine, rid = stash["engine"], stash["rid"]
        record = {r["rid"]: r for r in engine.tracer.records()}[rid]
        assert record["state"] == "finished" and "ttft" in record["breached"]
        assert stash["out"].size > 0
    finally:
        stop_default_server()


# ================================================================== sentinel
def test_sentinel_explicit_target_books_breach():
    from accelerate_tpu.telemetry.flight import get_flight_recorder
    from accelerate_tpu.telemetry.slo import SLOSentinel, breach_counts

    before = breach_counts().get("step_time", 0)
    sentinel = SLOSentinel(step_time_s=0.05)
    assert sentinel.active
    assert not sentinel.observe_step(0.01, step=1)
    assert sentinel.observe_step(0.20, step=2)
    assert breach_counts().get("step_time", 0) == before + 1
    events = [e for e in get_flight_recorder().snapshot()
              if e["kind"] == "slo_breach"]
    assert events and events[-1]["step"] == 2
    summary = sentinel.summary()
    assert summary["targets"]["step_time_s"] == 0.05
    assert summary["breaches"].get("step_time", 0) >= 1


def test_sentinel_auto_baseline_uses_ema_mad():
    """With no explicit target the sentinel self-baselines on the run's own
    history (EMA + MAD-proxy robust z, the health/spike.py idiom): a stable
    regime never breaches, an outlier does."""
    from accelerate_tpu.telemetry.slo import SLOSentinel, breach_counts

    from accelerate_tpu.telemetry.flight import get_flight_recorder

    before = breach_counts().get("step_time", 0)
    sentinel = SLOSentinel(auto_zscore=4.0, warmup_steps=5)
    assert sentinel.active
    for i in range(20):
        assert not sentinel.observe_step(0.010 + 0.0001 * (i % 3), step=i)
    assert sentinel.observe_step(0.100, step=20)
    assert breach_counts().get("step_time", 0) == before + 1
    # The booked threshold is the budget actually enforced (EMA + z·σ̂),
    # strictly above the bare EMA and below the tripping value.
    event = [e for e in get_flight_recorder().snapshot()
             if e["kind"] == "slo_breach"][-1]
    ema = sentinel._detector._ema
    assert ema < event["threshold"] < 0.100, (ema, event["threshold"])


def test_sentinel_mfu_floor():
    from accelerate_tpu.telemetry.slo import SLOSentinel, breach_counts

    before = breach_counts().get("mfu", 0)
    sentinel = SLOSentinel(mfu_min=0.3)
    assert not sentinel.observe_step(0.01, mfu=0.5)
    assert sentinel.observe_step(0.01, mfu=0.1)
    assert breach_counts().get("mfu", 0) == before + 1


def test_telemetry_binds_sentinel_from_env(monkeypatch):
    from accelerate_tpu.telemetry import Telemetry, reset_telemetry
    from accelerate_tpu.telemetry.slo import (
        sentinel_from_env,
        serving_slo_from_env,
        slo_targets_from_env,
    )

    assert sentinel_from_env() is None  # nothing configured
    monkeypatch.setenv("ACCELERATE_SLO_STEP_TIME", "0.25")
    monkeypatch.setenv("ACCELERATE_SLO_TTFT", "0.5")
    targets = slo_targets_from_env()
    assert targets == {"step_time_s": 0.25, "ttft_s": 0.5, "tpot_s": None}
    telemetry = Telemetry(enabled=True)
    assert telemetry.slo is not None and telemetry.slo.step_time_s == 0.25
    serving = serving_slo_from_env()
    assert serving is not None and serving.ttft_s == 0.5 and serving.tpot_s is None
    assert "slo" in telemetry.summary()
    reset_telemetry()
    monkeypatch.setenv("ACCELERATE_SLO_STEP_TIME", "0")
    monkeypatch.delenv("ACCELERATE_SLO_TTFT")
    assert sentinel_from_env() is None  # explicit 0 = off
    assert serving_slo_from_env() is None


# ============================================================== launch / env
def test_launch_flags_export_fleet_and_slo_env(monkeypatch):
    from accelerate_tpu.commands.launch import (
        _merge_config,
        launch_command_parser,
        prepare_launch_env,
    )

    args = launch_command_parser().parse_args(
        ["--cpu", "--metrics_port", "9100", "--fleet_metrics",
         "--slo_step_time", "0.25", "--slo_ttft", "0.5", "--slo_tpot", "0.05",
         "x.py"]
    )
    env = prepare_launch_env(_merge_config(args))
    assert env["ACCELERATE_FLEET_METRICS"] == "1"
    assert env["ACCELERATE_SLO_STEP_TIME"] == "0.25"
    assert env["ACCELERATE_SLO_TTFT"] == "0.5"
    assert env["ACCELERATE_SLO_TPOT"] == "0.05"

    # Tri-state: unspecified forwards an inherited env var ...
    monkeypatch.setenv("ACCELERATE_SLO_TTFT", "0.9")
    monkeypatch.setenv("ACCELERATE_FLEET_METRICS", "1")
    bare = prepare_launch_env(
        _merge_config(launch_command_parser().parse_args(["--cpu", "x.py"]))
    )
    assert bare["ACCELERATE_SLO_TTFT"] == "0.9"
    assert bare["ACCELERATE_FLEET_METRICS"] == "1"
    # ... and an explicit off SCRUBS it / reaches workers as a disable.
    off = prepare_launch_env(_merge_config(launch_command_parser().parse_args(
        ["--cpu", "--slo_ttft", "0", "--no-fleet_metrics", "x.py"]
    )))
    assert "ACCELERATE_SLO_TTFT" not in off
    assert off["ACCELERATE_FLEET_METRICS"] == "0"


def test_launch_validates_fleet_and_slo_flags(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('ok')\n")
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    for flags in (["--slo_ttft", "-1"], ["--fleet_metrics"]):
        result = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
             *flags, str(script)],
            capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=120,
        )
        assert result.returncode != 0, flags  # -1 invalid; fleet needs a port


def test_wizard_fleet_slo_questions_tristate():
    from unittest import mock

    from accelerate_tpu.commands.config import get_user_input

    def run(section, fleet, ttft):
        def fake_input(prompt=""):
            if "configure observability" in prompt:
                return section
            if "fleet metric aggregation" in prompt:
                return fleet
            if "time-to-first-token" in prompt:
                return ttft
            if "Prometheus metrics port" in prompt:
                return "9100"
            return ""

        with mock.patch("builtins.input", fake_input):
            return get_user_input()

    declined = run("no", "", "")
    assert declined.fleet_metrics is None and declined.slo_ttft is None
    answered = run("yes", "yes", "0.5")
    assert answered.fleet_metrics is True and answered.slo_ttft == 0.5
    defaults = run("yes", "", "")  # opened the section, accepted defaults
    assert defaults.fleet_metrics is False and defaults.slo_ttft == 0.0
