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

Under a ``backlog`` (every request due at once, ``lead_in_s`` before the
window) the loop is closed: ``client_threads`` requests are with the server,
each answer's end sends the next of the schedule, in its order, and nothing
is sent once the window has ended. The requests that were sent are the ones
attempted, and all of them are drained and held to what they asked for.
``serve_tokens_per_s`` is every token that reached a client inside the window
over the window; ``tpot_p95_ms`` is taken over the requests whose last token
fell inside it (one that ends in the drain sees an engine that is emptying).

``correct``: every counted request returned exactly the tokens it asked for;
nothing compiled inside the window; the pool's free list is full after the
drain; and, after the engine and its pool are dropped, a seeded sample of the
finished requests, the longest among them, agrees with the plain reference.
At each served token the gap is the reference's best float32 logit at that
position less its logit of the token served (the engine computes in bf16 over
random weights, whose logits are nearly flat, so a near tie may flip). The
widest gap, the mean gap over the sample's tokens and the share of tokens that
are not the reference's first are printed; those that the mix's
``reference_limits`` name are held, each to its own limit (``PERF.md`` gives
the readings the limits were set from).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import loadgen, program, program_spans, reference, trace_reduce


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
    """Gaps between the reference's best logit and its logit of the token the
    engine served, over the sample's served tokens: the widest, the mean, and
    the share that are above 0."""
    import jax.numpy as jnp

    gaps = []
    for request in sample:
        answer = np.asarray(request.tokens, np.int32)
        ids = np.zeros((ref_len,), np.int32)
        seq = np.concatenate([request.prompt, answer])
        ids[: seq.size] = seq  # causal: right padding cannot reach back
        logits = np.asarray(reference.logits_at(
            params, jnp.asarray(ids), request.prompt.size - 1, max_new, dims))
        rows = np.arange(answer.size)
        gaps.append(logits[rows].max(axis=-1) - logits[rows, answer])
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"worst_logit_gap": float(gaps.max(initial=0.0)),
            "mean_logit_gap": float(gaps.sum() / max(gaps.size, 1)),
            "mismatch_share": float((gaps > 0).sum() / max(gaps.size, 1)),
            "requests": len(sample), "tokens": int(gaps.size)}


def percentile_ms(values, q: float):
    return None if not values else 1e3 * loadgen.percentile(values, q)


def window_metrics(requests: list, seconds: float, closed: bool) -> dict:
    """The window's arithmetic on the requests as the clients stamped them.

    ``counted`` (the requests attempted): those due in the window; under a
    closed loop, of those the ones that were sent. ``ok``: the counted ones
    that returned exactly what they asked for. The percentiles are over ``ok``
    (``tpot_p95_ms`` under a closed loop: over those whose last token fell
    inside the window); the rate is every token that reached a client inside
    the window, whichever request it belongs to, over the window."""
    counted = [r for r in requests if r.counted and (r.sent is not None or not closed)]
    per_request = [(r, loadgen.request_metrics(r)) for r in counted]
    ok = [(r, m) for r, m in per_request
          if m and r.done is not None and len(r.tokens) == r.max_new]
    delivered = sum(n for r in requests for t, n in r.events if 0.0 <= t <= seconds)
    paced = [(r, m) for r, m in ok if not closed or r.events[-1][0] <= seconds]
    return {"counted": counted, "ok": ok, "failed": len(counted) - len(ok), "end_to_end": {
        "ttft_p95_ms": percentile_ms([m["ttft_s"] for _, m in ok], 95),
        "tpot_p95_ms": percentile_ms([m["tpot_s"] for _, m in paced if m["tpot_s"] is not None], 95),
        "serve_tokens_per_s": delivered / seconds}}


def load_in_window(requests: list, seconds: float) -> dict:
    """How many requests were with the server (sent, last token not yet in):
    the fewest at any moment of the window, and the number at its end. On the
    ``serve`` line; neither is compared or reported as a metric."""
    steps = sorted([(r.sent, 1) for r in requests if r.sent is not None]
                   + [(r.events[-1][0], -1) for r in requests if r.done is not None])
    with_server, fewest = 0, None
    for t, step in steps:
        if 0.0 <= t <= seconds and step < 0:
            fewest = with_server - 1 if fewest is None else min(fewest, with_server - 1)
        with_server += step
    return {
        "in_flight_at_window_end": sum(
            1 for r in requests if r.sent is not None and r.sent <= seconds
            and (not r.events or r.done is None or r.events[-1][0] > seconds)),
        "fewest_with_server_in_window": fewest}


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
            if pool["blocks_free"] == pool["num_blocks"]:
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
        # The front end numbers requests as the engine's ``submit`` takes them.
        rids = [r.done["rid"] for r in counted if r.done is not None and not r.retries]
        checks["admission_order_is_the_schedule_s"] = {
            "ok": rids == sorted(rids), "sent": len(counted), "prepared": len(requests)}
    checks["free_list_full_after_drain"] = {
        "ok": pool["blocks_free"] == pool["num_blocks"],
        "blocks_free": pool["blocks_free"], "num_blocks": pool["num_blocks"]}
    completed_in_window = sum(1 for r, _ in ok if r.events[-1][0] <= seconds)
    dispatches = list(getattr(engine, "_dispatch_log", ()))
    spans = program_spans.ring()
    say(phase="serve", setup_marks_s=marks, requests=len(requests), counted=len(counted),
        completed=len(ok), completed_in_window=completed_in_window,
        completed_in_window_per_s=completed_in_window / seconds,
        **load_in_window(requests, seconds),
        span_records=None if spans is None else {"written": spans.total, "ring_holds": spans.capacity},
        engine_dispatches={"decode_windows": sum(d == "decode" for d in dispatches),
                           "prefill_chunks": sum(d.startswith("chunk") for d in dispatches)},
        drained_at_s=drained_at, slots=engine.B, pool_bytes=pool["pool_bytes"],
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
    agreement = reference_check(params, dims, [ok[i][0] for i in dict.fromkeys(pick)], ref_len, max_new)
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
        # Requests the engine still works on keep its loop thread inside the
        # runtime, and the interpreter's own teardown then aborts: leave at once.
        "hard_exit": unfinished > 0,
        "record": {"kind": "serve", "dims": dims, "chips": len(ctx["devices"]),
                   "requests": [{**m, "queue_wait_s": (r.done.get("trace") or [{}])[0].get("queue_wait_s"),
                                 "engine_ttft_s": r.done.get("ttft_s")} for r, m in ok]},
    }
