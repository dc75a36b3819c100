"""Runner of the ``serve`` kind: the paged ``ContinuousBatcher`` behind
``ServingFrontend`` on a ``MetricsServer``, under an open loop on loopback.

Set-up (all of it counted in ``setup_s``): weights on the device from the
seed, in the serving dtype; the engine and its HTTP front end, built as
``chip_smoke.py``'s serve phase builds them (a copy); the schedule and every
request body; one uncounted request for each entry of the mix's
``warmup_prompt_tokens``, one after the other, so that every prefill program
the lengths can reach and the decode program are compiled or loaded; then the
uncounted lead-in at the cell's rate. The window starts when the lead-in ends.

Requests due in the window are followed to their end, for at most
``drain_s`` after it; what is unfinished then has failed. A traced run
profiles ``trace_s`` seconds of the window, starting ``trace_after_s`` in.

``correct``: every counted request returned exactly the tokens it asked for;
nothing compiled inside the window; the pool's free list is full after the
drain; and, after the engine and its pool are dropped, a seeded sample of
requests agrees with the plain reference: each generated token's float32
reference logit lies within ``logit_margin`` of that position's maximum (the
engine computes in bf16 over random weights, whose logits are nearly flat, so
a near tie may flip; the share of exact matches goes on an earlier line).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import loadgen, program, reference, trace_reduce


WARMUP_GAP_S = 0.25
# The front end's default stream timeout is sized for a warm server; the first
# warm-up request waits for its programs to compile.
STREAM_TIMEOUT_S = 1000.0


def compiled_peak_bytes(engine, say) -> int:
    """The compiler's account of the engine's largest programs (the decode
    window and the largest prefill chunk), read after the window through the
    engine's own builders, as the compile rehearsal does; the programs come
    from the compile cache."""
    peaks = {}
    try:
        chunk = engine._bucket(engine.prefill_chunk)
        programs = {"decode": (engine._decode(), engine._decode_args()),
                    f"chunk{chunk}": (engine._chunk_fn(chunk), engine._chunk_args(chunk))}
        for name, (fn, args) in programs.items():
            peaks[name] = program.compiled_peak_bytes(fn.lower(*args).compile())
    except Exception as exc:  # the engine's builders are private: fall back to memory_stats
        say(phase="compiled_peak", error=repr(exc))
    say(phase="compiled_peak", bytes=peaks)
    return max(peaks.values(), default=0)


def reference_check(params, dims: dict, sample: list, ref_len: int, max_new: int) -> dict:
    """Worst gap between the reference's best logit and the logit of the token
    the engine chose, over the sample's generated tokens."""
    import jax.numpy as jnp

    exact, total, worst = 0, 0, 0.0
    for request in sample:
        answer = np.asarray(request.tokens, np.int32)
        ids = np.zeros((ref_len,), np.int32)
        seq = np.concatenate([request.prompt, answer])
        ids[: seq.size] = seq  # causal: right padding cannot reach back
        logits = np.asarray(reference.logits_at(
            params, jnp.asarray(ids), request.prompt.size - 1, max_new, dims))
        rows = np.arange(answer.size)
        gap = logits[rows].max(axis=-1) - logits[rows, answer]
        exact += int((gap == 0).sum())
        total += int(answer.size)
        worst = max(worst, float(gap.max()))
    return {"worst_logit_gap": worst, "exact_share": exact / max(total, 1),
            "requests": len(sample), "tokens": total}


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

    model = program.build_model(config)
    dims = program.model_dims(model)
    mark("imports_and_model")
    params = program.make_params(model, seed, getattr(jnp, config["serving_dtype"]))
    jax.block_until_ready(params)
    mark("weights")

    engine = ContinuousBatcher(model, params=params, **config["engine"])
    server = MetricsServer(0, host="127.0.0.1")
    endpoint = f"127.0.0.1:{server.start()}"
    frontend = ServingFrontend(engine, stream_timeout_s=STREAM_TIMEOUT_S)
    frontend.install(server=server, endpoint=endpoint)
    requests = loadgen.build_schedule(traffic, seed, seconds, dims["vocab_size"])
    mark("engine_and_schedule")

    try:
        rng = np.random.default_rng(seed + 1)
        for n in traffic["warmup_prompt_tokens"]:
            warm = loadgen.make_request(rng, dims["vocab_size"], -1, 0.0, n,
                                        traffic["warmup_new_tokens"], False)
            t0 = time.perf_counter()
            loadgen.generate(endpoint, warm, time.perf_counter, STREAM_TIMEOUT_S + 100.0)
            say(phase="warmup", prompt_tokens=n, seconds=time.perf_counter() - t0, error=warm.error)
            if warm.error:
                raise RuntimeError(f"warm-up request failed: {warm.error}")
            # The engine's wave ends a moment after its last event; a request
            # that lands in that moment is refused (PERF.md, Open questions).
            time.sleep(WARMUP_GAP_S)
        mark("warmup")

        lead = -min([r.due for r in requests] + [0.0])
        t0 = time.perf_counter() + lead + 0.05
        loop = loadgen.OpenLoop(endpoint, requests, t0, traffic["client_threads"])
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
            if pool["blocks_free"] == pool["num_blocks"]:
                break
            time.sleep(0.1)
            pool = engine.pool_stats()
    finally:
        frontend.uninstall()
        server.stop()

    # --------------------------------------------------------------- metrics
    counted = [r for r in requests if r.counted]
    per_request = [(r, loadgen.request_metrics(r)) for r in counted]
    ok = [(r, m) for r, m in per_request
          if m and r.done is not None and len(r.tokens) == r.max_new]
    failed = len(counted) - len(ok)
    delivered = sum(n for r in requests for t, n in r.events if 0.0 <= t <= seconds)
    in_flight_at_end = sum(1 for r in requests if r.sent is not None and r.sent <= seconds
                           and (not r.events or r.done is None or r.events[-1][0] > seconds))
    ms = lambda values, q: None if not values else 1e3 * loadgen.percentile(values, q)
    end_to_end = {
        "ttft_p95_ms": ms([m["ttft_s"] for _, m in ok], 95),
        "tpot_p95_ms": ms([m["tpot_s"] for _, m in ok if m["tpot_s"] is not None], 95),
        "serve_tokens_per_s": delivered / seconds,
        "setup_s": setup_s,
    }
    checks["every_request_returned_what_it_asked_for"] = {
        "ok": failed == 0 and unfinished == 0, "counted": len(counted), "failed": failed,
        "unfinished": unfinished, "retried_after_a_retryable_refusal": sum(r.retries for r in requests),
        "errors": sorted({r.error for r in counted if r.error})[:3]}
    checks["no_compile_in_window"] = {"ok": compiles_in_window == 0, "count": compiles_in_window}
    checks["free_list_full_after_drain"] = {
        "ok": pool["blocks_free"] == pool["num_blocks"],
        "blocks_free": pool["blocks_free"], "num_blocks": pool["num_blocks"]}
    completed_in_window = sum(1 for r, _ in ok if r.events[-1][0] <= seconds)
    dispatches = list(getattr(engine, "_dispatch_log", ()))
    say(phase="serve", setup_marks_s=marks, requests=len(requests), counted=len(counted),
        completed=len(ok), completed_in_window=completed_in_window,
        completed_in_window_per_s=completed_in_window / seconds,
        in_flight_at_window_end=in_flight_at_end,
        engine_dispatches={"decode_windows": sum(d == "decode" for d in dispatches),
                           "prefill_chunks": sum(d.startswith("chunk") for d in dispatches)},
        drained_at_s=drained_at, slots=engine.B, pool_bytes=pool["pool_bytes"],
        ttft_p50_ms=ms([m["ttft_s"] for _, m in ok], 50),
        tpot_p50_ms=ms([m["tpot_s"] for _, m in ok if m["tpot_s"] is not None], 50),
        late_p95_ms=ms([m["late_s"] for _, m in ok], 95),
        prompt_tokens_mean=float(np.mean([r.prompt_len for r in counted])),
        output_tokens_mean=float(np.mean([r.max_new for r in counted])),
        end_to_end=end_to_end)

    # ------------------------------------------------------- reference check
    compiled_peak = compiled_peak_bytes(engine, say)
    ref_len = engine.max_tokens_per_request
    max_new = engine.max_new
    del engine, frontend, loop
    gc.collect()
    pick = np.random.default_rng(seed + 2).permutation(len(ok))[: traffic["reference_sample"]]
    agreement = reference_check(params, dims, [ok[i][0] for i in pick], ref_len, max_new)
    checks["reference_agrees_within_margin"] = {
        "ok": bool(ok) and agreement["worst_logit_gap"] <= traffic["logit_margin"],
        "margin": traffic["logit_margin"], **agreement}

    return {
        "correct": all(c["ok"] for c in checks.values()),
        "checks": checks,
        "attempted": len(counted),
        "failed": failed,
        "end_to_end": end_to_end,
        "compiled_peak_bytes": compiled_peak,
        # Requests the engine still works on keep its loop thread inside the
        # runtime, and the interpreter's own teardown then aborts: leave at once.
        "hard_exit": unfinished > 0,
        "record": {"kind": "serve", "dims": dims, "chips": len(ctx["devices"]),
                   "requests": [{**m, "queue_wait_s": (r.done.get("trace") or [{}])[0].get("queue_wait_s"),
                                 "engine_ttft_s": r.done.get("ttft_s")} for r, m in ok]},
    }
