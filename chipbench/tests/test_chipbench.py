"""Checks of the yardstick itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

They are not part of the repository's tier-1 tests (``tests/``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, loadgen, peaks, trace_reduce  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ loadgen
@pytest.mark.parametrize("mix", ["chat", "backlog"])
def test_same_seed_same_schedule_and_clips_hold(mix):
    traffic = load("traffic", f"{mix}.json")
    a = loadgen.build_schedule(traffic, 3_000_000_019, 20.0, 1000)
    b = loadgen.build_schedule(traffic, 3_000_000_019, 20.0, 1000)
    assert [(r.due, r.prompt_len, r.max_new, r.body) for r in a] == \
           [(r.due, r.prompt_len, r.max_new, r.body) for r in b]
    for r in a:
        assert traffic["prompt_tokens"]["min"] <= r.prompt_len <= traffic["prompt_tokens"]["max"]
        assert traffic["output_tokens"]["min"] <= r.max_new <= traffic["output_tokens"]["max"]
        assert r.prompt.size == r.prompt_len and r.prompt.min() >= 1
        assert json.loads(r.body)["max_new_tokens"] == r.max_new


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    traffic = load("traffic", "chat.json")
    a = [r for r in loadgen.build_schedule(traffic, 1, 20.0, 1000) if r.counted]
    b = [r for r in loadgen.build_schedule(traffic, 2, 20.0, 1000) if r.counted]
    assert len(a) == len(b) == round(traffic["arrivals"]["rate_per_s"] * 20.0)
    assert [(r.due, r.prompt_len, r.max_new) for r in a] == [(r.due, r.prompt_len, r.max_new) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert all(0.0 <= r.due < 20.0 for r in a)
    other = [r for r in loadgen.build_schedule({**traffic, "schedule_seed": 26}, 1, 20.0, 1000)
             if r.counted]
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in other)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in other]
    gaps = lambda rs: sorted(np.diff([r.due for r in rs] + [20.0]))  # the last gap ends the window
    assert gaps(a) == pytest.approx(gaps(other), abs=1e-9)


def test_lead_in_is_uncounted_and_due_before_zero():
    traffic = load("traffic", "chat.json")
    requests = loadgen.build_schedule(traffic, 7, 10.0, 1000)
    lead = [r for r in requests if not r.counted]
    assert len(lead) == round(traffic["arrivals"]["rate_per_s"] * traffic["lead_in_s"])
    assert all(-traffic["lead_in_s"] <= r.due < 0 for r in lead)


def test_backlog_is_all_due_at_once_before_the_window():
    traffic = load("traffic", "backlog.json")
    requests = loadgen.build_schedule(traffic, 7, 10.0, 1000)
    assert len(requests) == 120 and all(r.counted for r in requests)
    assert {r.due for r in requests} == {-traffic["lead_in_s"]}


def test_lognormal_quantiles_have_the_law_s_median_and_spread():
    law = {"law": "lognormal", "median": 256, "sigma": 0.9, "min": 1, "max": 10**9}
    lengths = loadgen.sample_lengths(law, 2001)
    assert abs(np.median(lengths) - 256) <= 1
    assert np.std(np.log(lengths)) == pytest.approx(0.9, rel=0.03)


@pytest.mark.parametrize("law", [{"law": "poisson", "rate_per_s": 4.0}, {"law": "gamma", "cv": 3.0}])
def test_gaps_fill_the_window(law):
    gaps = loadgen.sample_gaps(law, 200, 50.0)
    assert gaps.sum() == pytest.approx(50.0) and (gaps >= 0).all()
    cv = gaps.std() / gaps.mean()
    assert cv == pytest.approx(1.0 if law["law"] == "poisson" else 3.0, rel=0.25)


@pytest.mark.parametrize("values,q,expected", [
    (range(1, 101), 95, 95), (range(1, 101), 50, 50), ([3.0], 95, 3.0),
    ([1, 2, 3, 4], 95, 4), ([1, 2, 3, 4], 50, 2), ([], 95, None),
])
def test_percentile_is_nearest_rank(values, q, expected):
    assert loadgen.percentile(values, q) == expected


def test_request_metrics_count_from_due_time():
    r = loadgen.Request(0, 1.0, 10, 17, True)
    r.sent, r.events = 1.002, [(1.5, 8), (1.9, 8), (2.0, 1)]
    m = loadgen.request_metrics(r)
    assert m["ttft_s"] == pytest.approx(0.5) and m["late_s"] == pytest.approx(0.002)
    assert m["ttft_from_send_s"] == pytest.approx(0.498)
    assert m["tpot_s"] == pytest.approx(0.5 / 16) and m["tokens"] == 17


def test_a_retryable_refusal_is_sent_once_more(monkeypatch):
    import http.server
    import threading

    frames = [b'event: error\ndata: {"error": "engine error", "retryable": true}\n\n',
              b'event: tokens\ndata: {"tokens": [5, 6]}\n\nevent: done\ndata: {"tokens": [5, 6, 7]}\n\n']

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(frames.pop(0))

        def log_message(self, *args):
            pass

    server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        request = loadgen.Request(0, 0.0, 2, 3, True, b'{"prompt": [1, 2]}')
        loadgen.generate(f"127.0.0.1:{server.server_port}", request, lambda: 1.0, 10.0)
    finally:
        server.shutdown()
        thread.join(5)
    assert request.error is None and request.retries == 1 and request.tokens == [5, 6, 7]
    assert [n for _, n in request.events] == [2, 1] and not frames


def test_sse_reader():
    frames = b'event: tokens\ndata: {"tokens": [1, 2]}\n\nevent: done\ndata: {"tokens": [1, 2, 3]}\n\n'
    assert [k for k, _ in loadgen.iter_sse(frames.splitlines(keepends=True))] == ["tokens", "done"]


# -------------------------------------------------------------------- flops
def test_flops_mistral_by_hand():
    cfg = load("configs", "mistral-7b-v0.3-L3.json")
    layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336
    assert flops.layer_matmul_params(cfg) == layer == 218_103_808
    assert flops.head_matmul_params(cfg) == 4096 * 32768
    assert flops.total_params(cfg) == 922_775_552
    attention = 3 * 3 * 4 * 128 * 32 * (4096 * 4097 // 2) / 4096  # layers x (fwd + 2 bwd)
    expected = 6 * (3 * layer + 4096 * 32768) + attention
    assert flops.train_flops_per_token(cfg, 4096) == pytest.approx(expected)
    assert flops.train_flops_per_token(cfg, 4096) == pytest.approx(5.03e9, rel=0.005)


def test_flops_qwen3_by_hand():
    cfg = {**load("configs", "qwen3-1.7b.json"), "qk_norm": True}
    layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144
    assert flops.layer_matmul_params(cfg) == layer == 50_331_648
    assert flops.total_params(cfg) == 1_720_574_976  # tied head: the table counts once


def test_flash_cost_and_roofline():
    fwd = flops.flash_call_cost(2, 32, 4096, 128, backward=False)
    bwd = flops.flash_call_cost(2, 32, 4096, 128, backward=True)
    assert fwd["flops"] == 4 * 128 * 32 * 2 * (4096 * 4097 // 2)
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4 * 2 * 32 * 4096 * 128 * 2 and bwd["bytes"] == 2 * fwd["bytes"]
    roof = flops.roofline_seconds(fwd, peaks.peaks_for("TPU v5 lite"))
    assert roof["bound"] == "compute" and roof["seconds"] == pytest.approx(fwd["flops"] / 197e12)


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ------------------------------------------------------------- trace_reduce
def test_interval_arithmetic():
    merged = trace_reduce.merge([[0, 2], [1, 3], [5, 6], [6, 7]])
    assert merged == [[0, 3], [5, 7]] and trace_reduce.total(merged) == 5
    assert trace_reduce.clip(merged, 2, 5.5) == [[2, 3], [5, 5.5]]
    assert trace_reduce.gaps(merged, -1, 8) == [[-1, 0], [3, 5], [7, 8]]


def test_reduce_on_a_synthetic_trace():
    ops = [("fusion.1", 0.0, 0.4), ("custom-call.2", 0.4, 0.5), ("fusion.1", 0.7, 1.0)]
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": ops}},
             "spans": [("bench.trace_window", -0.01, 1.01), ("bench.fetch_loss", 0.45, 0.72)]}
    out = trace_reduce.reduce(trace)
    assert out["window_from"] == "host_span" and out["window_s"] == pytest.approx(1.02)
    assert out["busy_s"] == pytest.approx(0.8)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.7)]
    assert dict(map(tuple, out["idle_gaps"]))["bench.fetch_loss"] == pytest.approx(0.2)


def test_reduce_on_the_recorded_trace():
    path = os.path.join(ROOT, "chipbench", "tests", "recorded_trace.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this tree")
    with open(path) as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded["trace"])
    for key, value in recorded["expected"].items():
        assert out[key] == pytest.approx(value, rel=1e-6), key
    # Two steps of about 0.3855 s, the device busy all but 0.15% of the window.
    assert out["window_from"] == "host_span" and 0.998 < out["busy_s"] / out["window_s"] < 1.0
    # Self times leave nothing out and count nothing twice (whiles hold their bodies).
    assert sum(out["op_seconds"].values()) == pytest.approx(out["busy_s"], rel=1e-9)
    flash = {k: v for k, v in out["op_counts"].items() if "flash" in k}
    assert flash == recorded["expected_flash_counts"]  # 2 steps x 3 layers of each kernel
    seconds = sum(v for k, v in out["op_seconds"].items()
                  if k.startswith(("flash_attention", "flash_mha_bwd")))
    assert seconds == pytest.approx(recorded["expected_flash_seconds"], rel=1e-6)


def test_short_names_of_device_operations():
    name = ("%fusion.3 = bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)} fusion(bf16[2,4096]{1,0} %p), "
            "kind=kLoop")
    assert trace_reduce.short_name(name) == "fusion.3 bf16[2,4096,14336]"
    assert trace_reduce.short_name("%while.1 = (s32[]{:T(128)}, bf16[2]{0}) while(...)") == "while.1 s32[]"
    assert trace_reduce.short_name("jit__step(123)") == "jit__step(123)"


# ---------------------------------------------------------------- reference
def test_reference_agrees_with_the_program_in_float32():
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import Llama, LlamaConfig
    from chipbench import reference

    cfg = LlamaConfig(vocab_size=97, hidden_size=48, intermediate_size=80, num_hidden_layers=3,
                      num_attention_heads=6, num_key_value_heads=2, head_dim=16, qk_norm=True,
                      tie_word_embeddings=True, rope_theta=1e6, rms_norm_eps=1e-6,
                      attention_impl="dense")
    model = Llama(cfg)
    params = model.init(jax.random.key(3))
    # Norm weights of 1 would hide a norm applied in the wrong place.
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(x.size), x.shape), params)
    ids = np.random.default_rng(0).integers(0, 97, (2, 24)).astype(np.int32)
    dims = {k: getattr(cfg, k) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size", "rms_norm_eps",
        "rope_theta", "tie_word_embeddings", "sliding_window", "rope_scaling", "attention_bias",
        "hidden_act")}
    with jax.default_matmul_precision("highest"):
        out = model.apply(params, input_ids=jnp.asarray(ids), labels=jnp.asarray(ids))
    for row in range(2):
        logits = reference.logits_at(params, jnp.asarray(ids[row]), 0, 24, dims)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(out["logits"][row]),
                                   rtol=2e-4, atol=2e-4)
    assert reference.next_token_loss(params, ids, dims) == pytest.approx(float(out["loss"]), abs=1e-4)


def test_reference_refuses_what_it_does_not_implement():
    from chipbench import reference

    with pytest.raises(ValueError):
        reference.check_supported({"sliding_window": 4096})


# ------------------------------------------------------------------- run.py
def run_cli(*args, devices: int = 1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run([sys.executable, os.path.join(ROOT, "chipbench", "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("workload,trace", [("rehearse-train", 1), ("rehearse-backlog", 0),
                                            ("rehearse-backlog", 1), ("rehearse-chat", 0)])
def test_rehearsal_end_to_end(workload, trace):
    done = run_cli("--workload", workload, "--seed", "3000000019", "--seconds", "2",
                   "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0
    assert line["metrics"] and all(m["value"] is None for m in line["metrics"].values())
    assert "setup_s" in line["metrics"] if not trace else "setup_s" not in line["metrics"]
    # At toy sizes the chat mix leaves the engine idle between requests, where
    # it refuses an arrival now and then (PERF.md, Open questions): the client
    # sends such a request once more, so these runs are correct all the same.
    assert line["correct"] is True and line["failed"] == 0


def test_a_four_chip_fsdp_cell_is_data_only():
    """``training.parallelism`` of the configuration file reaches
    ``ParallelismConfig``: the sharded cell needs files and no code."""
    args = ("--workload", "rehearse-train-fsdp4", "--seed", "7", "--seconds", "2",
            "--trace", "0", "--rehearse")
    done = run_cli(*args, devices=4)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    short = run_cli(*args, devices=1)  # fewer devices than the cell asks for
    assert short.returncode != 0 and "correct" not in short.stdout


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    done = run_cli("--workload", "train-mistral7b-s4096", "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert done.returncode != 0 and done.stdout.strip() == ""
