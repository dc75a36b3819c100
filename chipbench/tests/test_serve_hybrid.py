"""The ``serve_hybrid`` runner and the cell ``serve-minicpm-sala-longdoc`` on
the CPU: the rehearsal of the runner's control flow, the cell's files, and the
two new readers.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_serve_hybrid.py -q
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loadgen, run  # noqa: E402

CELL = "serve-minicpm-sala-longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearse(capsys, monkeypatch, *extra):
    """``run.main --rehearse`` with this PR's own list of rehearsal cells in
    place of ``rehearse/cells.json`` (a file the benchmark already had)."""
    load_json = run.load_json

    def redirected(*parts):
        if parts[-2:] == ("rehearse", "cells.json"):
            parts = parts[:-1] + ("cells-minicpm-sala.json",)
        return load_json(*parts)

    monkeypatch.setattr(run, "load_json", redirected)
    capsys.readouterr()
    assert run.main(["--rehearse", "--workload", "rehearse-longdoc", "--seconds", "2", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def lines():
    return {}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(capsys, monkeypatch, trace, lines):
    line = rehearse(capsys, monkeypatch, "--seed", "3000000019", "--trace", str(trace))
    lines[trace] = line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values())  # never a CPU number
    checks = line["checks"]
    assert set(checks) == {
        "every_request_returned_what_it_asked_for", "no_compile_in_window",
        "admission_order_is_the_schedule_s", "free_list_full_and_state_released_after_drain",
        "reference_agrees_within_limits"}
    assert checks["free_list_full_and_state_released_after_drain"]["state_slots_in_use"] == 0
    agreement = checks["reference_agrees_within_limits"]
    # (three sampled, the last giving way to the longest: two where that one was drawn already)
    assert agreement["requests"] in (2, 3) and agreement["tokens"] > 0
    assert 0.5 < agreement["selected_blocks_shared_with_reference"] <= 1.0
    if trace:
        assert set(line["metrics"]) == {
            "device_idle_share.chat", "serve_host_ms_per_iteration",
            "sparse_attended_share", "chunk_rows_useful_share"}
    else:
        assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_int8_control_reaches_the_engine(capsys, monkeypatch):
    """``--control int8-weights`` lays the engine's ``matmul_precision`` over this
    cell's configuration as over the others'. At the rehearsal's size and sample
    (150 served tokens of a vocabulary of 512) the two precisions cannot be told
    apart (mean gap 0-0.00001 as configured, 0-0.00003 with int8 weights, four
    seeds each), so nothing is held here; that int8 moves this model's logits by
    more than bf16 does is ``tests/test_minicpm_sala.py``'s, and what the
    control reads at the cell's own size is in ``PERF.md`` section 4."""
    from accelerate_tpu import serving

    seen = []
    init = serving.ContinuousBatcher.__init__

    def spy(self, *args, **kwargs):
        seen.append(kwargs.get("matmul_precision"))
        init(self, *args, **kwargs)

    monkeypatch.setattr(serving.ContinuousBatcher, "__init__", spy)
    control = rehearse(capsys, monkeypatch, "--seed", "5", "--trace", "0", "--control", "int8-weights")
    assert seen == ["int8"] and control["failed"] == 0
    assert all(c["ok"] for name, c in control["checks"].items() if name != "reference_agrees_within_limits")


# ------------------------------------------------------------ the cell's files
def test_the_configuration_holds_every_published_key():
    config = run.load_cell(CELL, rehearse=False)["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
        assert config["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differ == {"num_hidden_layers", "mixer_types"} == set(config["reduced"])
        assert config["reduced"]["mixer_types"]["published"] == row["config"]["mixer_types"]
        published = row["config"]["mixer_types"]
        assert published.count("minicpm4") * 3 == published.count("lightning-attn")
    assert config["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 3 + config["mixer_types"][4:]
    assert config["mixer_types"] == config["mixer_types"][:4] * 3 and config["num_hidden_layers"] == 12
    assert config["reduced"]["num_hidden_layers"]["published"] == 32 == config["residual_depth"]
    assert config["engine"]["max_tokens_per_request"] == 25600
    assert 64 % config["engine"]["block_size"] == 0 or config["engine"]["block_size"] % 64 == 0


def test_the_program_builds_the_configuration_at_its_published_widths():
    from chipbench import program

    model = program.build_model(run.load_cell(CELL, rehearse=False)["config"])
    assert type(model).__name__ == "MiniCPMSALA"
    assert model.num_params() == 3_930_007_808  # 3 x 253.7M + 9 x 285.2M + 601.7M, norms included
    assert model.config.period == ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
    assert model.config.residual_depth == 32 and model.config.geometry.topk == 64


def test_the_traffic_is_the_issue_s_letter_for_letter():
    traffic = run.load_cell(CELL, rehearse=False)["traffic"]
    assert traffic["kind"] == "serve_hybrid"
    assert traffic["arrivals"] == {"law": "backlog", "requests_per_s_of_window": 2.4}
    assert traffic["prompt_tokens"] == {"law": "lognormal", "median": 12288, "sigma": 0.35,
                                        "min": 8192, "max": 24576}
    assert traffic["output_tokens"] == {"law": "lognormal", "median": 192, "sigma": 0.5,
                                        "min": 64, "max": 512}
    assert (traffic["client_threads"], traffic["drain_s"], traffic["schedule_seed"],
            traffic["reference_sample"]) == (8, 120.0, 25, 3)
    assert set(traffic["reference_limits"]) == {"mean_logit_gap", "worst_logit_gap"}
    assert max(traffic["warmup_prompt_tokens"]) > 8192
    assert set(traffic["warmup_prompt_tokens"]) >= {16, 32, 64, 128, 256, 512, 1024}
    requests = loadgen.build_schedule(traffic, 3_000_000_019, 50.0, 73448)
    assert len(requests) == 120 and all(r.counted and r.due == -traffic["lead_in_s"] for r in requests)
    prompts = np.array([r.prompt_len for r in requests])
    assert prompts.min() >= 8192 and prompts.max() <= 24576 and abs(np.median(prompts) - 12288) < 200
    assert max(r.prompt_len + r.max_new for r in requests) <= 25600


def test_the_cell_reports_what_the_issue_names():
    loaded = run.load_cell(CELL, rehearse=False)
    assert loaded["cell"]["chips"] == 1
    assert [m["name"] for m in loaded["end_to_end"]] == ["tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == [
        "device_idle_share.chat", "serve_host_ms_per_iteration",
        "sparse_attended_share", "chunk_rows_useful_share"]
    for metric in loaded["per_layer"]:
        assert callable(run.layer_metric(metric["name"]))


# ------------------------------------------------------------- the two readers
class Rec:
    def __init__(self, name, start_s, **attrs):
        self.name, self.start_s, self.duration_s, self.attrs = name, start_s, 0.01, attrs


@pytest.mark.parametrize("name,records,expected", [
    ("sparse_attended_share",
     [Rec("serve.dispatch_decode", 1.0, attended_keys=300.0, context_keys=1000.0),
      Rec("serve.dispatch_decode", 2.0, attended_keys=100.0, context_keys=1000.0),
      Rec("serve.dispatch_decode", 3.0)], 20.0),
    ("chunk_rows_useful_share",
     [Rec("serve.dispatch_chunk", 1.0, tokens=1024, rows_computed=4096),
      Rec("serve.dispatch_chunk", 2.0, tokens=512, rows_computed=2048)], 25.0),
    # A program without the counts (the parent commit): nothing to read, no raise.
    ("sparse_attended_share", [Rec("serve.dispatch_decode", 1.0, decoding=3)], None),
    ("chunk_rows_useful_share", [Rec("serve.dispatch_chunk", 1.0, tokens=1024)], None),
])
def test_the_new_readers(monkeypatch, name, records, expected):
    from chipbench import program_spans

    monkeypatch.setattr(program_spans, "serve_records", lambda record: records)
    value = run.layer_metric(name)({"kind": "serve"})
    assert value == (expected if expected is None else pytest.approx(expected))
    monkeypatch.setattr(program_spans, "serve_records", lambda record: None)
    assert run.layer_metric(name)({"kind": "serve"}) is None
