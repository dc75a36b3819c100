"""The cell ``serve-glm5-longctx`` on the CPU: the rehearsal of the ``serve_ref``
runner over a tiny GLM-5 (every per-layer metric the cell lists reading a
number), the cell's files, and the new reader.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_glm5_cell.py -q
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loadgen, program_spans, run  # noqa: E402

CELL = "serve-glm5-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"}
PER_LAYER = ["device_idle_share.chat", "serve_host_ms_per_iteration", "sparse_attended_share",
             "chunk_rows_useful_share", "experts_touched_share", "expert_load_max_over_mean",
             "chunk_keys_useful_share"]


def rehearse(capsys, monkeypatch, *extra):
    """``run.main --rehearse`` with this PR's own list of rehearsal cells in
    place of ``rehearse/cells.json`` (a file the benchmark already had). Also
    returns what each per-layer reader read on the CPU (the line prints null
    under every metric's name off the chip)."""
    load_json, read = run.load_json, run.read_layer_metric
    values = {}

    def redirected(*parts):
        if parts[-2:] == ("rehearse", "cells.json"):
            parts = parts[:-1] + ("cells-glm5.json",)
        return load_json(*parts)

    def spy(name, record, rehearse):
        values[name] = read(name, record, rehearse)
        return values[name]

    monkeypatch.setattr(run, "load_json", redirected)
    monkeypatch.setattr(run, "read_layer_metric", spy)
    capsys.readouterr()
    argv = ["chipbench/run.py", "--rehearse", "--workload", "rehearse-longctx", "--seconds", "2", *extra]
    monkeypatch.setattr(sys, "argv", argv)  # the span readers take the window's length from it
    monkeypatch.setattr(program_spans, "process_start", lambda: run.PROCESS_START)
    assert run.main(argv[1:]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1]), values


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(capsys, monkeypatch, trace):
    line, values = rehearse(capsys, monkeypatch, "--seed", "3000000019", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values())  # never a CPU number
    checks = line["checks"]
    assert set(checks) == {
        "every_request_returned_what_it_asked_for", "no_compile_in_window",
        "admission_order_is_the_schedule_s", "free_list_full_and_state_released_after_drain",
        "reference_agrees_within_limits"}
    agreement = checks["reference_agrees_within_limits"]
    assert agreement["requests"] in (2, 3) and agreement["tokens"] > 0
    # What the reference module counts rides through under its own names, compared with nothing.
    assert 0.5 < agreement["selected_keys_shared_with_reference"] <= 1.0
    assert 0.5 < agreement["routed_experts_shared_with_reference"] <= 1.0
    assert set(agreement) >= {"worst_logit_gap", "mean_logit_gap", "mismatch_share", "mean_logit_gap_limit"}
    assert "worst_logit_gap_limit" not in agreement  # printed, not compared
    if trace:
        assert set(line["metrics"]) == set(PER_LAYER)
        # Every per-layer metric the cell lists finds something to read in this program's spans
        # (the device's idle share needs a device trace's planes, which a CPU's does not hold).
        read = {name: value for name, value in values.items() if name != "device_idle_share.chat"}
        assert set(read) == set(PER_LAYER) - {"device_idle_share.chat"}
        assert all(value is not None for value in read.values()), read
        assert 0 < read["chunk_keys_useful_share"] < 100 and 0 < read["sparse_attended_share"] < 100
    else:
        assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_int8_control_reaches_the_engine(capsys, monkeypatch):
    from accelerate_tpu import serving

    seen = []
    init = serving.ContinuousBatcher.__init__

    def spy(self, *args, **kwargs):
        seen.append(kwargs.get("matmul_precision"))
        init(self, *args, **kwargs)

    monkeypatch.setattr(serving.ContinuousBatcher, "__init__", spy)
    control, _ = rehearse(capsys, monkeypatch, "--seed", "5", "--trace", "0", "--control", "int8-weights")
    assert seen == ["int8"] and control["failed"] == 0
    assert all(c["ok"] for name, c in control["checks"].items() if name != "reference_agrees_within_limits")


# ------------------------------------------------------------ the cell's files
def test_the_configuration_holds_every_published_key():
    config = run.load_cell(CELL, rehearse=False)["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert config["source"] == row["source_url"]
        published = row["config"]
        assert set(published) <= set(config)
        assert {k for k, v in published.items() if config[k] != v} == REDUCED == set(config["reduced"])
        assert all(config["reduced"][k]["published"] == published[k] and config["reduced"][k]["here"] == config[k]
                   for k in REDUCED)
    # Every width as published.
    assert (config["hidden_size"], config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]) == (
        6144, 64, 2048, 512, 192, 64, 256)
    assert (config["index_n_heads"], config["index_head_dim"], config["index_topk"]) == (32, 128, 2048)
    assert (config["moe_intermediate_size"], config["intermediate_size"], config["num_experts_per_tok"],
            config["routed_scaling_factor"], config["max_position_embeddings"]) == (2048, 12288, 8, 2.5, 202752)
    assert (config["n_routed_experts"], config["router_experts"], config["first_expert"]) == (16, 256, 0)
    assert config["reduced"]["vocab_size"]["published"] == 154880 == 8 * config["vocab_size"]
    assert "3,909,632,768" in config["stands_for"] and "v5e-256" in config["stands_for"]
    assert len(config["assumed"]) >= 8 and config["reference"] == "reference_glm5"
    assert config["engine"] == {"paged": True, "batch_slots": 8, "block_size": 64, "max_new_tokens": 512,
                                "prefill_chunk": 1024, "max_tokens_per_request": 25088,
                                "max_cache_len": 200704}


def test_the_program_builds_the_configuration_at_its_published_widths():
    import jax

    from chipbench import program

    model = program.build_model(run.load_cell(CELL, rehearse=False)["config"])
    assert type(model).__name__ == "Glm5"
    assert model.num_params() == 3_909_632_768
    shapes = jax.eval_shape(model.init, jax.random.key(0))  # abstract weights: nothing is allocated
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 3_909_632_768
    assert shapes["layers"]["moe"]["w_gate"].shape == (4, 16, 6144, 2048)
    assert shapes["layers"]["moe"]["router"].shape == (4, 6144, 256)
    assert shapes["layers"]["attn"]["wkv_b"].shape == (5, 512, 64 * (192 + 256))
    assert shapes["layers"]["indexer"]["wq"].shape == (5, 2048, 32 * 128)
    cache = jax.eval_shape(lambda: model.init_cache(8, 64))
    assert cache["latent"].shape == (5, 8, 64, 1, 576) and cache["index_k"].shape == (5, 8, 64, 1, 128)
    # 7,040 bytes a token over the five layers, in bf16.
    assert 2 * 5 * (576 + 128) == 7040


def test_the_traffic_is_the_issue_s_letter_for_letter():
    traffic = run.load_cell(CELL, rehearse=False)["traffic"]
    assert traffic["kind"] == "serve_ref"
    assert traffic["arrivals"] == {"law": "backlog", "requests_per_s_of_window": 3.2}
    assert traffic["prompt_tokens"] == {"law": "lognormal", "median": 8192, "sigma": 0.5,
                                        "min": 4096, "max": 24576}
    assert traffic["output_tokens"] == {"law": "lognormal", "median": 192, "sigma": 0.5,
                                        "min": 64, "max": 512}
    assert (traffic["client_threads"], traffic["drain_s"], traffic["reference_sample"]) == (12, 120.0, 3)
    assert "mean_logit_gap" in traffic["reference_limits"] and traffic["reference_limits_why"]
    assert set(traffic["warmup_prompt_tokens"]) >= {16, 32, 64, 128, 256, 512, 1024}
    requests = loadgen.build_schedule(traffic, 3_000_000_019, 50.0, 19360)
    assert len(requests) == 160 and all(r.counted and r.due == -traffic["lead_in_s"] for r in requests)
    prompts = np.array([r.prompt_len for r in requests])
    assert prompts.min() == 4096 and prompts.max() == 24576 and abs(np.median(prompts) - 8192) < 120
    outputs = np.array([r.max_new for r in requests])
    assert outputs.min() == 64 and outputs.max() == 512 and abs(np.median(outputs) - 192) < 4
    assert max(r.prompt_len + r.max_new for r in requests) <= 25088
    assert max(int(r.prompt.max()) for r in requests[:10]) < 19360


def test_the_cell_reports_what_the_issue_names():
    loaded = run.load_cell(CELL, rehearse=False)
    assert loaded["cell"]["chips"] == 1 and len(loaded["cell"]["why"]) <= 200
    assert [m["name"] for m in loaded["end_to_end"]] == ["tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == PER_LAYER
    for metric in loaded["per_layer"]:
        assert callable(run.layer_metric(metric["name"]))
    spec = run.load_json(ROOT, "BENCHMARK.json")
    (new,) = [m for m in spec["per_layer"] if m["name"] == "chunk_keys_useful_share"]
    assert new == {"name": "chunk_keys_useful_share", "unit": "%", "better": "higher", "source": "program_span",
                   "layer": "compiled programs", "moves": "tpot_p95_ms", "workloads": [CELL]}
    assert spec["per_layer"][-1] is new
    assert [w["name"] for w in spec["workloads"]].count(CELL) == 1
    (entry,) = [c for c in spec["configs"] if c["name"] == "glm-5-L5-ep16"]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                                "vocab_size", "num_nextn_predict_layers"] and len(entry["why"]) <= 200


# ------------------------------------------------------------------ the reader
class Rec:
    def __init__(self, name, start_s, **attrs):
        self.name, self.start_s, self.duration_s, self.attrs = name, start_s, 0.01, attrs


@pytest.mark.parametrize("records,expected", [
    ([Rec("serve.dispatch_chunk", 1.0, keys_selected=5 * 1024 * 2048.0, keys_scored=5 * 1024 * 27200.0),
      Rec("serve.dispatch_chunk", 2.0, keys_selected=5 * 64 * 2048.0, keys_scored=5 * 64 * 26240.0),
      Rec("serve.dispatch_chunk", 3.0, tokens=16)],
     100.0 * (1024 + 64) * 2048 / (1024 * 27200 + 64 * 26240)),
    # Gathered rows would score what they selected.
    ([Rec("serve.dispatch_chunk", 1.0, keys_selected=4096.0, keys_scored=4096.0)], 100.0),
    # A program without the counts (the parent commit, another model): nothing to read, no raise.
    ([Rec("serve.dispatch_chunk", 1.0, tokens=1024, expert_claims_max=3.0)], None),
    ([Rec("serve.dispatch_decode", 1.0, decoding=3)], None),
    ([], None),
])
def test_chunk_keys_useful_share_on_recorded_spans(monkeypatch, records, expected):
    monkeypatch.setattr(program_spans, "serve_records", lambda record: records)
    value = run.layer_metric("chunk_keys_useful_share")({"kind": "serve"})
    assert value == (expected if expected is None else pytest.approx(expected))
    monkeypatch.setattr(program_spans, "serve_records", lambda record: None)
    assert run.layer_metric("chunk_keys_useful_share")({"kind": "serve"}) is None
