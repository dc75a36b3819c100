"""The ``serve_ref`` runner and the cell ``serve-laguna-s-codemix`` on the CPU:
the rehearsal of the runner's control flow, the cell's files, and the two new
readers.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_serve_ref.py -q
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

CELL = "serve-laguna-s-codemix"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"}


def rehearse(capsys, monkeypatch, *extra):
    """``run.main --rehearse`` with this PR's own list of rehearsal cells in
    place of ``rehearse/cells.json`` (a file the benchmark already had)."""
    load_json = run.load_json

    def redirected(*parts):
        if parts[-2:] == ("rehearse", "cells.json"):
            parts = parts[:-1] + ("cells-laguna.json",)
        return load_json(*parts)

    monkeypatch.setattr(run, "load_json", redirected)
    capsys.readouterr()
    assert run.main(["--rehearse", "--workload", "rehearse-codemix", "--seconds", "2", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(capsys, monkeypatch, trace):
    line = rehearse(capsys, monkeypatch, "--seed", "3000000019", "--trace", str(trace))
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
    # (four sampled, the last giving way to the longest: three where that one was drawn already)
    assert agreement["requests"] in (3, 4) and agreement["tokens"] > 0
    # What the reference module counts rides through under its own name, compared with nothing.
    assert 0.5 < agreement["routed_experts_shared_with_reference"] <= 1.0
    assert set(agreement) >= {"worst_logit_gap", "mean_logit_gap", "mismatch_share",
                              "worst_logit_gap_limit", "mean_logit_gap_limit"}
    if trace:
        assert set(line["metrics"]) == {
            "device_idle_share.chat", "serve_host_ms_per_iteration", "sparse_attended_share",
            "chunk_rows_useful_share", "experts_touched_share", "expert_load_max_over_mean"}
    else:
        assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_int8_control_reaches_the_engine(capsys, monkeypatch):
    """``--control int8-weights`` lays the engine's ``matmul_precision`` over this
    cell's configuration as over the others'. Nothing of the gaps is held at the
    rehearsal's size (a hundred served tokens of a vocabulary of 512); that int8
    moves this model's logits and its experts' products is
    ``tests/test_laguna.py``'s, and what the control reads at the cell's own size
    is in ``PERF.md`` section 4."""
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


def test_the_runner_names_no_model():
    with open(os.path.join(ROOT, "chipbench", "runners", "serve_ref.py")) as f:
        source = f.read().lower()
    assert not any(word in source for word in ("laguna", "minicpm", "llama", "qwen", "mistral"))
    assert 'config["reference"]' in source


# ------------------------------------------------------------ the cell's files
def test_the_configuration_holds_every_published_key():
    config = run.load_cell(CELL, rehearse=False)["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
        assert config["source"] == row["source_url"]
        published = row["config"]
        assert set(published) <= set(config)
        assert {k for k, v in published.items() if config[k] != v} == REDUCED == set(config["reduced"])
        for key in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"):
            assert config[key] == published[key][:12]
        assert config["rope_parameters"] == published["rope_parameters"]
    # Every width as published.
    assert (config["hidden_size"], config["head_dim"], config["num_key_value_heads"]) == (3072, 128, 8)
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 3
    assert (config["moe_intermediate_size"], config["shared_expert_intermediate_size"],
            config["intermediate_size"], config["num_experts_per_tok"], config["sliding_window"]) == (
        1024, 1024, 12288, 10, 512)
    assert (config["num_experts"], config["router_experts"], config["first_expert"]) == (32, 256, 0)
    assert config["reduced"]["num_experts"]["published"] == 256
    assert config["reduced"]["vocab_size"]["published"] == 100352 == 8 * config["vocab_size"]
    assert config["reduced"]["num_hidden_layers"]["published"] == 48
    assert "4,325,526,528" in config["stands_for"] and "v5e-32" in config["stands_for"]
    assert len(config["assumed"]) >= 6 and config["reference"] == "reference_laguna"
    assert config["engine"] == {"paged": True, "batch_slots": 24, "block_size": 64, "max_new_tokens": 512,
                                "prefill_chunk": 1024, "max_tokens_per_request": 4608,
                                "max_cache_len": 110592}


def test_the_program_builds_the_configuration_at_its_published_widths():
    import jax

    from chipbench import program

    model = program.build_model(run.load_cell(CELL, rehearse=False)["config"])
    assert type(model).__name__ == "Laguna"
    assert model.num_params() == 4_325_526_528
    shapes = jax.eval_shape(model.init, jax.random.key(0))  # abstract weights: nothing is allocated
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 4_325_526_528
    assert shapes["layers"]["moe"]["w_gate"].shape == (11, 32, 3072, 1024)
    assert shapes["layers"]["moe"]["router"].shape == (11, 3072, 256)
    assert shapes["layers"]["sliding"]["wq"].shape == (9, 3072, 72 * 128)
    assert shapes["layers"]["full"]["wq"].shape == (3, 3072, 48 * 128)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    traffic = run.load_cell(CELL, rehearse=False)["traffic"]
    assert traffic["kind"] == "serve_ref"
    assert traffic["arrivals"] == {"law": "backlog", "requests_per_s_of_window": 12.0}
    assert traffic["prompt_tokens"] == {"law": "lognormal", "median": 1536, "sigma": 0.7,
                                        "min": 256, "max": 4096}
    assert traffic["output_tokens"] == {"law": "lognormal", "median": 160, "sigma": 0.6,
                                        "min": 32, "max": 512}
    assert (traffic["client_threads"], traffic["drain_s"], traffic["schedule_seed"],
            traffic["reference_sample"]) == (48, 60.0, 25, 4)
    # The mean gap alone is held: the widest does not separate here, and the file says why.
    assert set(traffic["reference_limits"]) == {"mean_logit_gap"}
    assert "does not separate" in traffic["reference_limits_why"]
    assert max(traffic["warmup_prompt_tokens"]) == 4096
    assert set(traffic["warmup_prompt_tokens"]) >= {16, 32, 64, 128, 256, 512, 1024}
    requests = loadgen.build_schedule(traffic, 3_000_000_019, 50.0, 12544)
    assert len(requests) == 600 and all(r.counted and r.due == -traffic["lead_in_s"] for r in requests)
    prompts = np.array([r.prompt_len for r in requests])
    assert prompts.min() == 256 and prompts.max() == 4096 and abs(np.median(prompts) - 1536) < 20
    outputs = np.array([r.max_new for r in requests])
    assert outputs.min() == 32 and outputs.max() == 512 and abs(np.median(outputs) - 160) < 3
    assert max(r.prompt_len + r.max_new for r in requests) <= 4608
    assert max(int(r.prompt.max()) for r in requests[:20]) < 12544


def test_the_cell_reports_what_the_issue_names():
    loaded = run.load_cell(CELL, rehearse=False)
    assert loaded["cell"]["chips"] == 1 and len(loaded["cell"]["why"]) <= 200
    assert [m["name"] for m in loaded["end_to_end"]] == ["tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == [
        "device_idle_share.chat", "serve_host_ms_per_iteration", "sparse_attended_share",
        "chunk_rows_useful_share", "experts_touched_share", "expert_load_max_over_mean"]
    for metric in loaded["per_layer"]:
        assert callable(run.layer_metric(metric["name"]))
    spec = run.load_json(ROOT, "BENCHMARK.json")
    new = {m["name"]: m for m in spec["per_layer"]
           if m["name"] in ("experts_touched_share", "expert_load_max_over_mean")}
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms" and m["source"] == "program_span"
               and m["layer"] == "compiled programs" for m in new.values()) and len(new) == 2
    assert spec["workloads"][-1]["name"] == CELL and spec["configs"][-1]["name"] == "laguna-s-2.1-L12-ep8"
    assert spec["configs"][-1]["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]


# ------------------------------------------------------------- the two readers
class Rec:
    def __init__(self, name, start_s, **attrs):
        self.name, self.start_s, self.duration_s, self.attrs = name, start_s, 0.01, attrs


@pytest.mark.parametrize("name,records,expected", [
    ("experts_touched_share",
     [Rec("serve.dispatch_decode", 1.0, experts_touched=1500.0, experts_held=2816.0),
      Rec("serve.dispatch_decode", 2.0, experts_touched=1316.0, experts_held=2816.0),
      Rec("serve.dispatch_decode", 3.0)], 50.0),
    ("expert_load_max_over_mean",
     [Rec("serve.dispatch_chunk", 1.0, expert_claims_max=700.0, expert_claims_mean=440.0),
      Rec("serve.dispatch_chunk", 2.0, expert_claims_max=180.0, expert_claims_mean=110.0),
      Rec("serve.dispatch_chunk", 3.0, tokens=16)], 1.6),
    # A program without the counts (the parent commit): nothing to read, no raise.
    ("experts_touched_share", [Rec("serve.dispatch_decode", 1.0, decoding=3)], None),
    ("expert_load_max_over_mean", [Rec("serve.dispatch_chunk", 1.0, tokens=1024)], None),
])
def test_the_new_readers(monkeypatch, name, records, expected):
    from chipbench import program_spans

    monkeypatch.setattr(program_spans, "serve_records", lambda record: records)
    value = run.layer_metric(name)({"kind": "serve"})
    assert value == (expected if expected is None else pytest.approx(expected))
    monkeypatch.setattr(program_spans, "serve_records", lambda record: None)
    assert run.layer_metric(name)({"kind": "serve"}) is None
