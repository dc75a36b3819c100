"""Runner of the ``train`` kind: the Accelerator's fused step under a fixed
batch shape, for as long as the window lasts.

Set-up (all of it counted in ``setup_s``): weights on the device from the
seed; ``Accelerator.prepare`` and ``build_train_step``, built as
``chip_smoke.py``'s ``build_trainer`` builds them (a copy: the yardstick may
not follow later edits of that script); the compiled step's text and memory
account; ``warmup_steps`` calls, since the step compiles at its first and at
its second call.

The window: steps are dispatched ``lookahead`` ahead and each loss is fetched
as its step completes; the window closes with the last step's fetch, so
``train_tokens_per_s`` is all tokens of all completed steps over all of the
time. A traced run then goes on for ``trace_steps`` more steps of the same
loop under the profiler, so that the rate is taken with the profiler off and
the trace is of the steady state.

The plain reference runs last, outside ``setup_s`` and the window: once the
device's peak has been read and the trainer's state dropped, the same weights
are made again from the seed (whole, in float32, on the first device) and the
reference's loss on the first batch is held against the loss of the step's
first call.
"""

from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

from chipbench import flops, program, reference, trace_reduce


def make_batches(traffic: dict, vocab: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(traffic["host_batches"]):
        ids = rng.integers(0, vocab, (traffic["batch"], traffic["seq"])).astype(np.int32)
        batches.append({"input_ids": ids, "labels": ids})
    return batches


def build_trainer(config: dict, model, devices):
    import optax

    from accelerate_tpu import Accelerator, ParallelismConfig

    training = config["training"]
    parallelism = training.get("parallelism") or {}
    accelerator = Accelerator(
        mixed_precision=training["mixed_precision"],
        parallelism_config=ParallelismConfig(**parallelism) if parallelism else None,
    )
    if not parallelism and len(accelerator.state.mesh.devices.flat) != len(devices):
        one = ParallelismConfig(dp_size=len(devices))
        accelerator.state.replace_mesh(one.build_mesh(list(devices)), one)
    optimizer = dict(training["optimizer"])
    tx = getattr(optax, optimizer.pop("name"))(**optimizer)
    pmodel, popt = accelerator.prepare(model, tx)
    model.params = None  # the whole copy on the first device; the prepared model holds the placed one
    return accelerator, pmodel, popt, accelerator.build_train_step(pmodel, popt)


def drop_trainer(accelerator, pmodel, popt) -> None:
    """Free the parameters, the optimizer state and the accumulation buffer
    (as ``chip_smoke.py``'s ``drop_training_state`` does)."""
    pmodel.handle.params = None
    popt.opt_state = popt._accum_grads = None
    accelerator.free_memory()
    gc.collect()


def steps_loop(step, batches, start_index: int, keep_going, lookahead: int):
    """Dispatch steps while ``keep_going(n_dispatched)``; returns the losses.
    Ends when the last dispatched step's loss is on the host."""
    import jax

    pending, losses, n = collections.deque(), [], 0
    while keep_going(n):
        with jax.profiler.TraceAnnotation("bench.fetch_batch"):
            batch = batches[(start_index + n) % len(batches)]
        with jax.profiler.TraceAnnotation("bench.dispatch_step"):
            pending.append(step(batch))
        n += 1
        if len(pending) > lookahead:
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                losses.append(float(pending.popleft()))
    with jax.profiler.TraceAnnotation("bench.fetch_loss"):
        losses += [float(x) for x in pending]
    return losses


def run(ctx: dict) -> dict:
    import jax

    config, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    seed, marks, checks = ctx["seed"], program.SetupMarks(ctx["process_start"]), {}
    mark = marks.mark

    model = program.build_model(config)
    dims = program.model_dims(model)
    mark("imports_and_model")
    model.params = program.make_params(model, seed)
    jax.block_until_ready(model.params)
    mark("weights")

    batches = make_batches(traffic, dims["vocab_size"], seed)
    accelerator, pmodel, popt, step = build_trainer(config, model, ctx["devices"])
    compiled = step.lower(batches[0]).compile()
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    compiled_peak = program.compiled_peak_bytes(compiled)
    mark("prepare_and_compile")

    warm = []
    for i in range(traffic["warmup_steps"]):
        t0 = time.perf_counter()
        warm.append(float(jax.device_get(step(batches[i % len(batches)]))))
        say(phase="warmup", call=i + 1, seconds=time.perf_counter() - t0, loss=warm[-1])
    mark("warmup")
    if not ctx["rehearse"]:
        from accelerate_tpu.ops.attention import resolve_auto_impl

        impl = resolve_auto_impl(traffic["seq"], dims["num_attention_heads"],
                                 dims["head_dim"], batch=traffic["batch"])
        checks["attention_impl"] = {"ok": impl == traffic["expect_attention"], "resolved": impl}
        checks["tpu_custom_call_in_compiled_step"] = {"ok": "tpu_custom_call" in text}

    # ---------------------------------------------------------------- window
    tokens_per_step = traffic["batch"] * traffic["seq"]
    compiles_before = ctx["compiles"].count
    setup_s = time.perf_counter() - ctx["process_start"]
    t0 = time.perf_counter()
    losses = steps_loop(step, batches, len(warm),
                        lambda n: time.perf_counter() - t0 < ctx["seconds"],
                        traffic["lookahead"])
    window_s = time.perf_counter() - t0
    compiles_in_window = ctx["compiles"].count - compiles_before
    tokens_per_s = len(losses) * tokens_per_step / window_s

    if ctx["trace_dir"]:
        with trace_reduce.capture(ctx["trace_dir"]):
            traced = steps_loop(step, batches, len(warm) + len(losses),
                                lambda n: n < traffic["trace_steps"], traffic["lookahead"])
        checks["traced_losses_finite"] = {"ok": all(math.isfinite(x) for x in traced)}

    bad = [x for x in losses if not math.isfinite(x)]
    checks["losses_finite"] = {"ok": not bad and bool(losses), "steps": len(losses),
                               "first": losses[0] if losses else None,
                               "last": losses[-1] if losses else None}
    checks["no_compile_in_window"] = {"ok": compiles_in_window == 0, "count": compiles_in_window}
    say(phase="train", setup_marks_s=marks, steps=len(losses), window_s=window_s,
        step_s=window_s / max(len(losses), 1), tokens_per_s=tokens_per_s,
        params=flops.total_params(dims),
        compiled_bytes={"arguments": memory.argument_size_in_bytes,
                        "temporaries": memory.temp_size_in_bytes,
                        "outputs": memory.output_size_in_bytes,
                        "aliased": memory.alias_size_in_bytes, "peak": compiled_peak})

    # ------------------------------------------------------- reference check
    peak_in_use = program.peak_bytes_in_use(ctx["devices"])
    del step
    drop_trainer(accelerator, pmodel, popt)
    t0 = time.perf_counter()
    ref_loss = reference.next_token_loss(program.make_params(model, seed),
                                         batches[0]["input_ids"], dims)
    checks["first_loss_agrees_with_reference"] = {
        "ok": abs(warm[0] - ref_loss) <= traffic["loss_tolerance"], "step": warm[0],
        "reference": ref_loss, "gap": abs(warm[0] - ref_loss), "limit": traffic["loss_tolerance"],
        "reference_seconds": time.perf_counter() - t0}

    return {
        "correct": all(c["ok"] for c in checks.values()),
        "checks": checks,
        "attempted": len(losses),
        "failed": len(bad),
        "end_to_end": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "compiled_peak_bytes": compiled_peak,
        "peak_bytes_in_use": peak_in_use,
        "record": {"kind": "train", "dims": dims, "tokens_per_s": tokens_per_s,
                   "tokens_per_step": tokens_per_step, "steps": len(losses),
                   "chips": len(ctx["devices"])},
    }
