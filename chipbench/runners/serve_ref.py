"""Runner of the ``serve_ref`` kind: ``runners/serve.py``'s run for any model
whose plain reference is named by its configuration (``"reference"``: a
module of ``chipbench/``). It names no model.

``serve_hybrid.run``'s flow, with what is shared imported from
``runners/serve.py`` (``window_metrics``, ``load_in_window``,
``percentile_ms``, ``compiled_peak_bytes``) and from ``loadgen``; ``PERF.md``
section 7 asks the next ``benchmark`` PR to fold ``serve.py`` and
``serve_hybrid.py`` into this file. It differs from ``serve.py`` in this:

- the reference is ``chipbench/<config["reference"]>.py``: ``logits_at(params,
  ids, start, rows, config, watch=positions) -> (logits, seen)``, handed the
  configuration file's own keys;
- after the drain every slot's state has to be released beside the full free
  list (``pool_stats()["state_slots_in_use"]``; 0 for a model that holds none);
- where the reference module has ``checks(model, params, ids, watch, seen) ->
  {name: (part, whole)}``, what it counts at ``WATCHED`` positions of each
  sampled request is summed over the sample and printed under ``checks`` as
  ``part / whole``, compared with nothing (a near tie may flip a choice
  between bf16 and float32).

Its record says ``"kind": "serve"``, so the readers of the serving spans take
it as they take ``serve.py``'s. Everything else (set-up, warm-up, lead-in,
window, drain, the closed loop under a ``backlog``, ``correct``) is as
``runners/serve.py`` documents it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import importlib

from chipbench import loadgen, program, program_spans, trace_reduce
from chipbench.runners.serve import (STREAM_TIMEOUT_S, WARMUP_GAP_S, compiled_peak_bytes,
                                     load_in_window, percentile_ms, window_metrics)

WATCHED = 8  # positions of each sampled request at which the reference's choices are compared


def reference_check(reference, model, params, config: dict, sample: list, ref_len: int,
                    max_new: int) -> dict:
    """``serve.reference_check`` against the configuration's own reference, and
    what that reference counts at the watched positions (its ``checks``)."""
    import jax.numpy as jnp

    gaps, counted = [], {}
    for request in sample:
        answer = np.asarray(request.tokens, np.int32)
        ids = np.zeros((ref_len,), np.int32)
        seq = np.concatenate([request.prompt, answer])
        ids[: seq.size] = seq  # causal: right padding cannot reach back
        watch = np.unique(np.linspace(request.prompt.size - 1, seq.size - 1, WATCHED).astype(np.int32))
        logits, seen = reference.logits_at(
            params, jnp.asarray(ids), request.prompt.size - 1, max_new, config, watch=watch)
        logits = np.asarray(logits)
        rows = np.arange(answer.size)
        gaps.append(logits[rows].max(axis=-1) - logits[rows, answer])
        if hasattr(reference, "checks"):
            for name, (part, whole) in reference.checks(model, params, jnp.asarray(ids), watch, seen).items():
                before = counted.get(name, (0, 0))
                counted[name] = (before[0] + part, before[1] + whole)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"worst_logit_gap": float(gaps.max(initial=0.0)),
            "mean_logit_gap": float(gaps.sum() / max(gaps.size, 1)),
            "mismatch_share": float((gaps > 0).sum() / max(gaps.size, 1)),
            **{name: part / max(whole, 1) for name, (part, whole) in counted.items()},
            "requests": len(sample), "tokens": int(gaps.size)}


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher
    from accelerate_tpu.serving_net.frontend import ServingFrontend
    from accelerate_tpu.telemetry.metrics import MetricsServer

    config, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    seed, seconds, checks = ctx["seed"], ctx["seconds"], {}
    marks = program.SetupMarks(ctx["process_start"])
    mark = marks.mark

    reference = importlib.import_module("chipbench." + config["reference"])
    model = program.build_model(config)
    vocab = model.config.vocab_size
    mark("imports_and_model")
    params = program.make_params(model, seed, getattr(jnp, config["serving_dtype"]))
    jax.block_until_ready(params)
    mark("weights")

    engine = ContinuousBatcher(model, params=params, **config["engine"])
    server = MetricsServer(0, host="127.0.0.1")
    endpoint = f"127.0.0.1:{server.start()}"
    frontend = ServingFrontend(engine, stream_timeout_s=STREAM_TIMEOUT_S)
    frontend.install(server=server, endpoint=endpoint)
    requests = loadgen.build_schedule(traffic, seed, seconds, vocab)
    mark("engine_and_schedule")

    try:
        rng = np.random.default_rng(seed + 1)
        for n in traffic["warmup_prompt_tokens"]:
            warm = loadgen.make_request(rng, vocab, -1, 0.0, n, traffic["warmup_new_tokens"], False)
            t0 = time.perf_counter()
            loadgen.generate(endpoint, warm, time.perf_counter, STREAM_TIMEOUT_S + 100.0)
            say(phase="warmup", prompt_tokens=n, seconds=time.perf_counter() - t0, error=warm.error)
            if warm.error:
                raise RuntimeError(f"warm-up request failed: {warm.error}")
            time.sleep(WARMUP_GAP_S)  # the wave-end refusal (PERF.md, Open questions)
        mark("warmup")

        closed = traffic["arrivals"]["law"] == "backlog"
        lead = -min([r.due for r in requests] + [0.0])
        t0 = time.perf_counter() + lead + 0.05
        loop = loadgen.OpenLoop(endpoint, requests, t0, traffic["client_threads"],
                                stop_sending_at=seconds if closed else None)
        loop.start()
        time.sleep(max(0.0, t0 - time.perf_counter()))
        setup_s = time.perf_counter() - ctx["process_start"]
        compiles_before = ctx["compiles"].count
        with jax.profiler.TraceAnnotation("bench.window"):
            if ctx["trace_dir"]:
                time.sleep(max(0.0, t0 + traffic["trace_after_s"] - time.perf_counter()))
                with trace_reduce.capture(ctx["trace_dir"]):
                    time.sleep(min(traffic["trace_s"], max(0.5, seconds - traffic["trace_after_s"])))
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        compiles_in_window = ctx["compiles"].count - compiles_before
        unfinished = loop.wait(seconds + traffic["drain_s"])
        drained_at = loop.clock()
        pool = engine.pool_stats()
        for _ in range(50):  # the loop thread frees the last chains just after the last event
            if pool["blocks_free"] == pool["num_blocks"] and not pool["state_slots_in_use"]:
                break
            time.sleep(0.1)
            pool = engine.pool_stats()
    finally:
        frontend.uninstall()
        server.stop()

    # --------------------------------------------------------------- metrics
    window = window_metrics(requests, seconds, closed)
    counted, ok, failed = window["counted"], window["ok"], window["failed"]
    end_to_end = {**window["end_to_end"], "setup_s": setup_s}
    checks["every_request_returned_what_it_asked_for"] = {
        "ok": failed == 0 and unfinished == 0, "counted": len(counted), "failed": failed,
        "unfinished": unfinished, "retried_after_a_retryable_refusal": sum(r.retries for r in requests),
        "errors": sorted({r.error for r in counted if r.error})[:3]}
    checks["no_compile_in_window"] = {"ok": compiles_in_window == 0, "count": compiles_in_window}
    if closed:
        rids = [r.done["rid"] for r in counted if r.done is not None and not r.retries]
        checks["admission_order_is_the_schedule_s"] = {
            "ok": rids == sorted(rids), "sent": len(counted), "prepared": len(requests)}
    checks["free_list_full_and_state_released_after_drain"] = {
        "ok": pool["blocks_free"] == pool["num_blocks"] and pool["state_slots_in_use"] == 0,
        "blocks_free": pool["blocks_free"], "num_blocks": pool["num_blocks"],
        "state_slots_in_use": pool["state_slots_in_use"]}
    completed_in_window = sum(1 for r, _ in ok if r.events[-1][0] <= seconds)
    # How far the nearest token events lie from the window's two edges.
    stamps = sorted(t for r in requests for t, _ in r.events)
    clearance = {f"{side}_{edge}_s": min((abs(t - at) for t in stamps if keep(t, at)), default=None)
                 for edge, at in (("start", 0.0), ("end", seconds))
                 for side, keep in (("before", lambda t, at: t < at), ("after", lambda t, at: t >= at))}
    dispatches = list(getattr(engine, "_dispatch_log", ()))
    spans = program_spans.ring()
    say(phase="serve", setup_marks_s=marks, requests=len(requests), counted=len(counted),
        completed=len(ok), completed_in_window=completed_in_window,
        completed_in_window_per_s=completed_in_window / seconds,
        **load_in_window(requests, seconds), token_events_nearest_the_window_s_edges=clearance,
        span_records=None if spans is None else {"written": spans.total, "ring_holds": spans.capacity},
        engine_dispatches={"decode_windows": sum(d == "decode" for d in dispatches),
                           "prefill_chunks": sum(d.startswith("chunk") for d in dispatches)},
        drained_at_s=drained_at, slots=engine.B, pool_bytes=pool["pool_bytes"],
        kv_bytes=pool["kv_bytes"], state_bytes=pool["state_bytes"],
        ttft_p50_ms=percentile_ms([m["ttft_s"] for _, m in ok], 50),
        tpot_p50_ms=percentile_ms([m["tpot_s"] for _, m in ok if m["tpot_s"] is not None], 50),
        late_p95_ms=percentile_ms([m["late_s"] for _, m in ok], 95),
        prompt_tokens_mean=float(np.mean([r.prompt_len for r in counted])),
        output_tokens_mean=float(np.mean([r.max_new for r in counted])),
        end_to_end=end_to_end)

    # ------------------------------------------------------- reference check
    compiled_peak = compiled_peak_bytes(engine, say)
    peak_in_use = program.peak_bytes_in_use(ctx["devices"])
    ref_len = engine.max_tokens_per_request
    max_new = engine.max_new
    del engine, frontend, loop
    gc.collect()
    pick = list(np.random.default_rng(seed + 2).permutation(len(ok))[: traffic["reference_sample"]])
    if ok:  # the sample's last gives way to the longest finished request
        pick[-1:] = [max(range(len(ok)), key=lambda i: ok[i][0].prompt_len + ok[i][0].max_new)]
    t0 = time.perf_counter()
    agreement = reference_check(reference, model, params, config,
                                [ok[i][0] for i in dict.fromkeys(pick)], ref_len, max_new)
    say(phase="reference", seconds=time.perf_counter() - t0)
    limits = traffic["reference_limits"]
    checks["reference_agrees_within_limits"] = {
        "ok": bool(ok) and all(agreement[name] <= limit for name, limit in limits.items()),
        **agreement, **{name + "_limit": limit for name, limit in limits.items()}}

    return {
        "correct": all(c["ok"] for c in checks.values()),
        "checks": checks,
        "attempted": len(counted),
        "failed": failed,
        "end_to_end": end_to_end,
        "compiled_peak_bytes": compiled_peak,
        "peak_bytes_in_use": peak_in_use,
        "hard_exit": unfinished > 0,
        "record": {"kind": "serve", "dims": {"vocab_size": vocab}, "chips": len(ctx["devices"]),
                   "requests": [{**m, "queue_wait_s": (r.done.get("trace") or [{}])[0].get("queue_wait_s"),
                                 "engine_ttft_s": r.done.get("ttft_s")} for r, m in ok]},
    }
