"""Durable telemetry journal + fleet causal trace assembly + run reports.

Pins the observability tentpole: per-host JSONL journals are flushed per
record (SIGKILL-durable, the JSONTracker precedent) with size-based rotation
and seq-resume; the metrics server tails them over ``GET /journal?since=``;
the coordination-KV clock exchange recovers per-rank wall skew; the
collector merges every rank into ONE Chrome-trace where a request's legs are
causally linked under its rid with skew corrected (3-process launcher
drill); and ``accelerate-tpu report --compare`` classifies run-over-run
deltas, exit 1 on regression. Journaling-on vs off is pinned COMPARATIVELY
at zero added blocking device→host transfers in the serving steady state.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.telemetry.journal import (
    TelemetryJournal,
    exchange_clock_sync,
    get_journal,
    journal_event,
    reset_journal,
    set_journal,
)

pytestmark = pytest.mark.journal

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ================================================================ durability
def test_journal_flushes_per_record_and_resumes_seq(tmp_path):
    """Every record is readable the instant emit() returns (the SIGKILL
    contract — no close needed), and a restarted process resumes seq where
    the dead one stopped, so since= tails stay monotonic across restarts."""
    journal = TelemetryJournal(str(tmp_path), process_index=0)
    journal.emit("step", step=1, wall_s=0.1)
    journal.emit("flight", event="guard_trip", step=1)
    # Read back WITHOUT closing: the line-buffered handle + flush per record
    # means a SIGKILL right now loses nothing.
    with open(journal.path, encoding="utf-8") as fh:
        records = [json.loads(line) for line in fh]
    assert [r["kind"] for r in records] == ["journal_open", "step", "flight"]
    assert [r["seq"] for r in records] == [0, 1, 2]
    assert all(r["host"] == 0 for r in records)
    assert records[1]["step"] == 1 and records[1]["wall_s"] == 0.1
    journal.close()

    reopened = TelemetryJournal(str(tmp_path), process_index=0)
    record = reopened.emit("step", step=2, wall_s=0.1)
    assert record["seq"] == 4  # 3 = reopened journal_open, then this
    reopened.close()


def test_journal_rotation_bounds_retention_and_keeps_tail(tmp_path):
    journal = TelemetryJournal(str(tmp_path), process_index=0, max_bytes=2048)
    for i in range(200):
        journal.emit("span", name=f"s{i}", duration_s=0.001)
    assert os.path.exists(journal.path + ".1"), "rotation never happened"
    assert os.path.getsize(journal.path) < 2048 + 512
    tail = journal.tail(since=0)
    seqs = [r["seq"] for r in tail["records"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert tail["next"] == seqs[-1] + 1
    # since= filters strictly: re-tailing from `next` returns nothing new.
    assert journal.tail(since=tail["next"])["records"] == []
    mid = seqs[len(seqs) // 2]
    assert all(r["seq"] >= mid for r in journal.tail(since=mid)["records"])
    journal.close()


def test_journal_emit_never_raises(tmp_path):
    """The black-box discipline: a broken journal must never take the run
    down — emit on a closed file returns None instead of raising."""
    journal = TelemetryJournal(str(tmp_path), process_index=0)
    journal._file.close()
    assert journal.emit("step", step=1) is None
    journal.close()


def test_journal_env_arming_tristate(tmp_path, monkeypatch):
    """get_journal(): unset/empty env = journaling off (None), a path arms
    the process journal and installs the flight tap."""
    reset_journal()
    monkeypatch.delenv("ACCELERATE_JOURNAL_DIR", raising=False)
    assert get_journal() is None
    assert journal_event("step", step=1) is None  # cheap no-op when off
    reset_journal()
    monkeypatch.setenv("ACCELERATE_JOURNAL_DIR", str(tmp_path))
    journal = get_journal()
    assert journal is not None and journal.directory == str(tmp_path)
    # The flight tap is installed: a flight event lands in the journal...
    from accelerate_tpu.telemetry.flight import get_flight_recorder

    get_flight_recorder().record("serving_drain", role="decode", drained=1)
    # ...but step boundary events are skipped (Telemetry journals the richer
    # step record for the same boundary).
    get_flight_recorder().note_step(step=7, wall_s=0.2)
    kinds = [(r.get("kind"), r.get("event"))
             for r in journal.tail()["records"]]
    assert ("flight", "serving_drain") in kinds
    assert not any(e == "step" for _, e in kinds), kinds


# ================================================================= HTTP tail
def test_metrics_server_journal_route(tmp_path):
    """GET /journal?since= serves the installed journal's tail; 400 on a
    non-integer cursor; 503 once the journal is gone."""
    from accelerate_tpu.telemetry.metrics import MetricsServer

    journal = TelemetryJournal(str(tmp_path), process_index=0)
    set_journal(journal)
    journal.emit("step", step=1, wall_s=0.1)
    server = MetricsServer(0, host="127.0.0.1")
    port = server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/journal?since=0", timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["host"] == 0 and payload["schema_version"] == 1
        assert [rec["kind"] for rec in payload["records"]] == [
            "journal_open", "step"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/journal?since={payload['next']}",
                timeout=10) as r:
            assert json.loads(r.read())["records"] == []
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/journal?since=nope", timeout=10)
        assert err.value.code == 400
        reset_journal()
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/journal", timeout=10)
        assert err.value.code == 503
    finally:
        server.stop()


# ============================================================ clock exchange
def test_clock_sync_single_process_journals_skew(tmp_path):
    """No distributed client: the exchange degrades to {0: 0.0} and still
    journals the clock_sync record the collector looks for — and the
    injectable wall clock feeds the stamps (the skew-drill seam)."""
    journal = TelemetryJournal(str(tmp_path), process_index=0,
                               wall_clock=lambda: 1_000_000.0)
    set_journal(journal)
    skew = exchange_clock_sync(num_processes=1, process_index=0)
    assert skew == {0: 0.0}
    sync = [r for r in journal.tail()["records"] if r["kind"] == "clock_sync"]
    assert len(sync) == 1
    assert sync[0]["skew"] == {"0": 0.0}
    assert sync[0]["offsets"]["0"]["wall"] == 1_000_000.0
    reset_journal()


# ================================================================= collector
def _write_host_journal(tmp_path, host: int, records: list):
    path = tmp_path / f"journal_{host}.jsonl"
    with open(path, "w", encoding="utf-8") as fh:
        for i, record in enumerate(records):
            fh.write(json.dumps(
                {"seq": i, "host": host, "t_s": float(i), **record}) + "\n")


def test_collector_merges_with_skew_correction(tmp_path):
    """Host 1's wall clock runs 50s ahead; the journaled clock_sync recovers
    it and the merge orders host 1's leg BETWEEN host 0's, where it causally
    belongs — raw wall order would banish it to the far future."""
    from accelerate_tpu.telemetry.collect import (
        chrome_trace, clock_skew, merge_records, read_journal_dir,
    )

    base = 1000.0
    _write_host_journal(tmp_path, 0, [
        {"wall": base + 0.0, "kind": "clock_sync",
         "skew": {"0": 0.0, "1": 50.0}},
        {"wall": base + 0.1, "kind": "request_leg", "rid": 5,
         "leg": "submit", "tier": "router"},
        {"wall": base + 0.9, "kind": "request_leg", "rid": 5,
         "leg": "finish", "tier": "router", "tpot_s": 0.01},
    ])
    _write_host_journal(tmp_path, 1, [
        {"wall": base + 50.5, "kind": "request_leg", "rid": 5,
         "leg": "first_token", "tier": "decode", "ttft_s": 0.4},
    ])
    by_host = read_journal_dir(str(tmp_path))
    assert set(by_host) == {0, 1}
    assert clock_skew(by_host) == {0: 0.0, 1: 50.0}
    merged = merge_records(by_host)
    legs = [r for r in merged if r["kind"] == "request_leg"]
    assert [r["leg"] for r in legs] == ["submit", "first_token", "finish"]
    trace = chrome_trace(by_host)
    leg_events = [e for e in trace["traceEvents"]
                  if e.get("ph") == "X" and e.get("cat") == "request"]
    # Corrected: every event inside one second of trace time, not 50 apart.
    assert max(e["ts"] for e in leg_events) < 2e6
    flows = [e for e in trace["traceEvents"] if e.get("ph") in "stf"]
    assert {e["id"] for e in flows} == {5}
    assert {e["pid"] for e in flows} == {0, 1}


def test_chrome_trace_lanes_flows_and_filters(tmp_path):
    from accelerate_tpu.telemetry.collect import chrome_trace, read_journal_dir

    base = 2000.0
    _write_host_journal(tmp_path, 0, [
        {"wall": base + 1.0, "kind": "step", "step": 1, "wall_s": 0.5,
         "steps": 1, "mfu": 0.4},
        {"wall": base + 10.0, "kind": "step", "step": 2, "wall_s": 0.5,
         "steps": 1, "mfu": 0.4},
        {"wall": base + 11.0, "kind": "step", "step": 3, "wall_s": 0.5,
         "steps": 1, "mfu": 0.4},
        {"wall": base + 1.2, "kind": "span", "name": "train_step",
         "duration_s": 0.2},
        {"wall": base + 1.3, "kind": "request_leg", "rid": 9,
         "leg": "submit", "tier": "router"},
        {"wall": base + 1.6, "kind": "request_leg", "rid": 9,
         "leg": "finish", "tier": "decode"},
        {"wall": base + 1.4, "kind": "goodput", "category": "checkpoint",
         "seconds": 0.1},
        {"wall": base + 1.5, "kind": "flight", "event": "slo_breach",
         "rid": 9, "target": "ttft"},
    ])
    by_host = read_journal_dir(str(tmp_path))
    trace = chrome_trace(by_host)
    events = trace["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"step 1", "step 2", "step 3", "train_step", "router:submit",
            "decode:finish", "goodput:checkpoint", "slo_breach"} <= names
    lanes = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"steps", "requests", "spans", "events", "goodput"} <= lanes
    # The breach (flight event carrying the rid) joins the request's flow.
    flows = [e for e in events if e.get("ph") in "stf" and e.get("id") == 9]
    assert len(flows) == 3 and [e["ph"] for e in flows] == ["s", "t", "f"]

    # --rid keeps only that request's events (plus metadata).
    rid_trace = chrome_trace(by_host, rid=9)
    kept = [e for e in rid_trace["traceEvents"] if e.get("ph") == "X"]
    assert kept and all(e["args"].get("rid") == 9 for e in kept)
    assert not any(e["name"].startswith("step") for e in kept)

    # --steps keeps the range plus what falls inside its time window.
    step_trace = chrome_trace(by_host, steps="2-3")
    step_names = {e["name"] for e in step_trace["traceEvents"]
                  if e.get("ph") == "X" and e.get("cat") == "step"}
    assert step_names == {"step 2", "step 3"}
    with pytest.raises(ValueError):
        chrome_trace(by_host, steps="nope")


# ================================================================== reports
def _summary(**over) -> dict:
    base = {"step_p50": 0.10, "step_p90": 0.12, "mfu": 0.40,
            "tokens_per_s": 1000.0, "goodput_fraction": 0.9,
            "ttft_mean": 0.3, "tpot_mean": 0.01,
            "breaches": 0, "retries": 1, "restarts": 0, "evictions": 0,
            "fingerprint": "abc"}
    base.update(over)
    return base


def test_compare_runs_classification():
    from accelerate_tpu.telemetry.collect import compare_runs

    rows = {r["field"]: r for r in compare_runs(
        _summary(),
        _summary(step_p50=0.15, mfu=0.30, breaches=2, retries=0,
                 fingerprint="def"),
    )}
    assert rows["step_p50"]["kind"] == "regression"   # lower-better rose 50%
    assert rows["mfu"]["kind"] == "regression"        # higher-better fell 25%
    assert rows["breaches"]["kind"] == "regression"   # count rose (no slack)
    assert rows["retries"]["kind"] == "improvement"
    assert rows["step_p90"]["kind"] == "benign"       # within tolerance
    assert rows["fingerprint"]["kind"] == "note"
    # Symmetric: a faster run classifies as improvement, not regression.
    improved = {r["field"]: r for r in compare_runs(
        _summary(), _summary(step_p50=0.05))}
    assert improved["step_p50"]["kind"] == "improvement"


def _run_report(*argv) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "report", *argv],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"},
    )


def test_report_cli_exit_codes(tmp_path):
    """The CI-gate contract: exit 1 on an injected step-time regression,
    exit 0 on a clean re-run (and on improvements)."""
    prev, cur = tmp_path / "prev.json", tmp_path / "cur.json"
    prev.write_text(json.dumps(_summary()))
    cur.write_text(json.dumps(_summary(step_p50=0.2)))  # 2x step time
    regressed = _run_report("--journal", str(cur), "--compare", str(prev))
    assert regressed.returncode == 1, regressed.stdout + regressed.stderr
    assert "REGRESSION: step_p50" in regressed.stderr

    clean = _run_report("--journal", str(prev), "--compare", str(prev))
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "no regressions" in clean.stdout

    faster = tmp_path / "faster.json"
    faster.write_text(json.dumps(_summary(step_p50=0.05)))
    improved = _run_report("--journal", str(faster), "--compare", str(prev),
                           "--json")
    assert improved.returncode == 0
    payload = json.loads(improved.stdout)
    kinds = {r["field"]: r["kind"] for r in payload["comparison"]}
    assert kinds["step_p50"] == "improvement"

    # A journal directory source: the latest run_summary record is the unit.
    journal = TelemetryJournal(str(tmp_path / "jd"), process_index=0)
    journal.emit("request_leg", rid=1, leg="first_token", tier="decode",
                 ttft_s=0.5)
    journal.finalize_run(extra={"fingerprint": "xyz"})
    journal.close()
    from_dir = _run_report("--journal", str(tmp_path / "jd"))
    assert from_dir.returncode == 0, from_dir.stdout + from_dir.stderr
    assert "ttft_mean" in from_dir.stdout


# ================================================== ring env + launch contract
def test_ring_capacity_env_resolution(monkeypatch):
    from accelerate_tpu.telemetry.flight import (
        get_flight_recorder, reset_flight_recorder, ring_capacity_from_env,
    )
    from accelerate_tpu.telemetry.requests import RequestTracer

    monkeypatch.delenv("ACCELERATE_TRACE_RING", raising=False)
    assert RequestTracer().capacity == 1024  # library default
    monkeypatch.setenv("ACCELERATE_TRACE_RING", "16")
    assert RequestTracer().capacity == 16
    monkeypatch.setenv("ACCELERATE_TRACE_RING", "0")  # 0 = library default
    assert RequestTracer().capacity == 1024
    monkeypatch.setenv("ACCELERATE_TRACE_RING", "-5")
    with pytest.raises(ValueError):
        ring_capacity_from_env("ACCELERATE_TRACE_RING", 1024)
    monkeypatch.setenv("ACCELERATE_FLIGHT_RING", "64")
    reset_flight_recorder()
    assert get_flight_recorder().capacity == 64


def test_journal_launch_contract_tristate(monkeypatch, tmp_path):
    """--journal_dir / --trace_ring / --flight_ring ride the launcher
    tri-state contract: None = unspecified (inherited env flows), explicit
    values export, ''/0 scrub stale inherited values."""
    from accelerate_tpu.commands.config_args import ClusterConfig
    from accelerate_tpu.commands.launch import (
        _merge_config, launch_command_parser, prepare_launch_env,
    )

    monkeypatch.setenv("ACCELERATE_JOURNAL_DIR", "/stale")
    monkeypatch.setenv("ACCELERATE_TRACE_RING", "99")
    env = prepare_launch_env(ClusterConfig())  # unspecified → inherited flows
    assert env["ACCELERATE_JOURNAL_DIR"] == "/stale"
    assert env["ACCELERATE_TRACE_RING"] == "99"
    env = prepare_launch_env(ClusterConfig(
        journal_dir=str(tmp_path), trace_ring=512, flight_ring=4096))
    assert env["ACCELERATE_JOURNAL_DIR"] == str(tmp_path)
    assert env["ACCELERATE_TRACE_RING"] == "512"
    assert env["ACCELERATE_FLIGHT_RING"] == "4096"
    env = prepare_launch_env(ClusterConfig(journal_dir="", trace_ring=0))
    assert "ACCELERATE_JOURNAL_DIR" not in env  # explicit scrub
    assert "ACCELERATE_TRACE_RING" not in env

    args = launch_command_parser().parse_args(
        ["--cpu", "--journal_dir", str(tmp_path), "--trace_ring", "256",
         "--flight_ring", "1024", "script.py"])
    cfg = _merge_config(args)
    assert cfg.journal_dir == str(tmp_path)
    assert cfg.trace_ring == 256 and cfg.flight_ring == 1024

    # Launch-time validation: negative rings die before any worker spawns.
    from accelerate_tpu.commands.launch import launch_command

    bad = launch_command_parser().parse_args(
        ["--cpu", "--trace_ring", "-1", "script.py"])
    with pytest.raises(ValueError, match="--trace_ring"):
        launch_command(bad)


def test_wizard_journal_questions_tristate(monkeypatch):
    """Declining observability leaves the journal knobs None (inherited env
    flows at launch); answering exports them like every wizard tri-state —
    and an explicit '' / 0 inside the section is a scrub, not None."""
    from accelerate_tpu.commands.config import get_user_input

    answers = {
        "configure observability": "yes",
        "telemetry journal directory": "/data/journal",
        "request-trace ring": "512",
        "flight-recorder ring": "4096",
    }

    def fake_input(prompt=""):
        for key, answer in answers.items():
            if key in prompt:
                return answer
        return ""

    monkeypatch.setattr("builtins.input", fake_input)
    cfg = get_user_input()
    assert cfg.journal_dir == "/data/journal"
    assert cfg.trace_ring == 512 and cfg.flight_ring == 4096

    def decline_journal(prompt=""):
        if "configure observability" in prompt:
            return "yes"
        return ""  # journal/ring questions take their ''/0 defaults

    monkeypatch.setattr("builtins.input", decline_journal)
    cfg = get_user_input()
    assert cfg.journal_dir == "" and cfg.trace_ring == 0  # explicit scrub

    monkeypatch.setattr("builtins.input", lambda prompt="": "")
    cfg = get_user_input()  # whole section declined → unspecified
    assert cfg.journal_dir is None
    assert cfg.trace_ring is None and cfg.flight_ring is None


# ============================================================ blackbox merge
def test_blackbox_directory_merges_dumps_with_host_labels(tmp_path, capsys):
    from accelerate_tpu.commands.profile import blackbox_command

    for host, (t0, kinds) in enumerate([
        (100.0, ["guard_trip", "restart"]),
        (100.5, ["slo_breach"]),
    ]):
        dump = {
            "reason": "test", "pid": 40 + host, "process_index": host,
            "dumped_at": t0 + 10, "events_total": len(kinds),
            "events_retained": len(kinds),
            "events": [{"kind": kind, "t_s": i * 1.0, "wall": t0 + i}
                       for i, kind in enumerate(kinds)],
        }
        (tmp_path / f"flight_{host}.json").write_text(json.dumps(dump))

    class Args:
        dump = str(tmp_path)
        last = 0

    blackbox_command(Args())
    out = capsys.readouterr().out
    assert "dump host 0" in out and "dump host 1" in out
    assert "merged timeline (3 events" in out
    lines = [line for line in out.splitlines() if "host=" in line]
    # Interleaved by wall time: host 0 @100.0, host 1 @100.5, host 0 @101.0.
    assert [line.split("host=")[1].split()[0] for line in lines] == \
        ["0", "1", "0"]
    assert "slo_breach" in lines[1]

    class Missing:
        dump = str(tmp_path / "empty")
        last = 0

    os.makedirs(Missing.dump)
    with pytest.raises(SystemExit):
        blackbox_command(Missing())


# ===================================================== zero-added-transfers
@pytest.fixture
def llama():
    from accelerate_tpu.models import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


def test_journaling_steady_state_adds_zero_blocking_transfers(
        llama, tmp_path):
    """Acceptance pin: journaling-on vs journaling-off adds ZERO device→host
    fetches and host→device puts to the traced serving steady-state loop, and
    no put blocks. Comparative per the fleet-plane precedent — identical
    waves run with the journal disarmed and armed; journal records ride host
    bookkeeping the loop already pays, so those counts must match exactly.
    Whether a fetch found its array ready (``blocking``) is the CPU's timing,
    journal or no journal, and is not compared."""
    from accelerate_tpu.serving import ContinuousBatcher
    from accelerate_tpu.utils.transfer import (
        reset_transfer_stats, transfer_stats,
    )

    prompt = np.arange(1, 6, dtype=np.int32)

    def wave(journaled: bool):
        reset_journal()
        if journaled:
            set_journal(TelemetryJournal(str(tmp_path), process_index=0))
        engine = ContinuousBatcher(
            llama, batch_slots=1, max_new_tokens=24, max_cache_len=512,
            cache_dtype=jnp.float32, bucket_sizes=(8,), sync_every=2,
            block_size=4, max_tokens_per_request=40,
        )
        rid = engine.submit(prompt)
        reset_transfer_stats()
        out = engine.run()[rid]
        stats = transfer_stats()
        if journaled:
            journal = get_journal()
            legs = [r for r in journal.tail()["records"]
                    if r["kind"] == "request_leg"]
            assert any(r["leg"] == "finish" for r in legs), legs
            reset_journal()
        return stats, out

    wave(journaled=False)  # warm the jit cache so both measured arms match

    base, base_out = wave(journaled=False)
    journaled, journaled_out = wave(journaled=True)
    np.testing.assert_array_equal(base_out, journaled_out)
    assert journaled["fetches"] == base["fetches"] > 0
    assert journaled["h2d_puts"] == base["h2d_puts"]
    assert journaled["h2d_blocking"] == 0


# ============================================================ launcher drill
def test_journal_fleet_drill_under_launcher(tmp_path):
    """Acceptance: the 3-process drill under the real launcher — every rank
    journals to the shared --journal_dir on a deliberately skewed wall
    clock, and `accelerate-tpu timeline` merges them into ONE valid
    Chrome-trace where the retried request's router/prefill/decode legs
    (incl. the handoff and handoff_failed retry leg) are causally linked
    under one rid with the skew corrected; `report --compare` exits 0 on a
    clean self-compare and 1 on an injected regression (all asserted inside
    the script)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["AT_JOURNAL_SKEW"] = "0,120,-45"
    journal_dir = str(tmp_path / "journal")
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "3", "--journal_dir", journal_dir,
            "--trace_ring", "512", "--flight_ring", "4096",
            "-m", "accelerate_tpu.test_utils.journal_script",
        ],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    assert proc.stdout.count("JOURNAL_OK") == 3, proc.stdout[-2000:]
    assert "JOURNAL_TIMELINE_OK" in proc.stdout
    assert "JOURNAL_REPORT_OK" in proc.stdout
    # The drill's artifacts are real files a human can open in Perfetto.
    with open(os.path.join(journal_dir, "trace.json"), encoding="utf-8") as fh:
        assert json.load(fh)["traceEvents"]
