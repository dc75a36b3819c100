"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process, the only one to touch JAX, drives the two main paths through the
entry points a user calls, at the full width of the repo's ``large`` Llama
(vocabulary 32000, hidden 2304, intermediate 9216, 7 layers, 18 heads of 128:
742M parameters; weights random, from ``--seed``):

- **train**: ``Accelerator(mixed_precision="bf16")``, ``prepare(model,
  optax.adafactor(3e-4))``, ``build_train_step``; one compile step and ten more
  on one batch of 12 x 1024 tokens.
- **serve**: the paged ``ContinuousBatcher`` behind ``ServingFrontend``; 12
  greedy requests of 32-700 prompt tokens over ``POST /v1/generate`` on
  loopback, checked against one float32 full-sequence ``model.apply``.

``--chips 4`` runs, and runs only, five steps under
``ParallelismConfig(fsdp_size=4)`` on four chips against the same five steps on
a mesh of the first chip alone. ``--rehearse`` runs the same code at a tiny
width on whatever backend is there (a rehearsal, never a chip run).

Every number printed here comes from a SMOKE RUN: it shows that the program
runs and is right, and is not a measurement of speed. The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed check
exits non-zero before that line. Unless the platform is ``tpu`` (or
``--rehearse`` is given) the script exits non-zero at once.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import math
import os
import sys
import time
import types
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
NOTE = "smoke run, not a measurement"

# Generated tokens are checked against a float32 reference over the same
# weights. The engine computes in bf16 over a bf16 KV cache and the weights are
# random, so logits are nearly flat and a near tie may flip: a generated token
# passes when its reference logit lies within this margin of the reference's
# maximum at that position. The share of exact argmax matches is printed.
LOGIT_MARGIN = 0.1
# |sharded loss - one-chip loss| per step: bf16 forward/backward with a
# different reduction order across four batch shards.
LOSS_TOLERANCE = 0.05


def smoke_config(rehearse: bool) -> dict:
    """Sizes of the run: the ``large`` bench model, or the tiny rehearsal."""
    if rehearse:
        return dict(
            model=dict(vocab_size=2048, hidden_size=256, intermediate_size=1024,
                       num_hidden_layers=2, num_attention_heads=2,
                       num_key_value_heads=2, max_position_embeddings=128),
            batch=4, seq=128, requests=4, prompt_lens=(8, 60), slots=2,
            ref_len=128, big_leaf=2**16,
        )
    return dict(
        model=dict(vocab_size=32000, hidden_size=2304, intermediate_size=9216,
                   num_hidden_layers=7, num_attention_heads=18,
                   num_key_value_heads=18, max_position_embeddings=1024),
        batch=12, seq=1024, requests=12, prompt_lens=(32, 700), slots=8,
        ref_len=768, big_leaf=2**20,
    )


class Phase:
    """One phase's checks and readings. ``finish`` prints the phase's JSON
    line and exits non-zero when any check failed: no later phase runs."""

    def __init__(self, name: str):
        self.name = name
        self.checks: dict = {}
        self.readings: dict = {}

    def check(self, name: str, ok, detail=None):
        self.checks[name] = {"ok": bool(ok), **({} if detail is None else {"detail": detail})}

    def finish(self):
        ok = all(c["ok"] for c in self.checks.values())
        print(json.dumps({"phase": self.name, "ok": ok, "note": NOTE,
                          **self.readings, "checks": self.checks}), flush=True)
        if not ok:
            failed = [k for k, c in self.checks.items() if not c["ok"]]
            print(f"chip_smoke: phase {self.name} failed: {failed}", file=sys.stderr)
            sys.exit(1)


def cache_entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def make_batch(cfg: dict, seed: int) -> dict:
    import numpy as np

    ids = np.random.default_rng(seed).integers(
        0, cfg["model"]["vocab_size"], (cfg["batch"], cfg["seq"])
    ).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def build_trainer(cfg: dict, seed: int, parallelism=None, devices=None):
    """Accelerator + prepared Llama + fused train step, as bench.py builds
    them. ``devices`` re-forms the mesh over that device list (the one-chip
    arm of ``--chips 4``) before anything is placed."""
    import jax
    import optax

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import Llama, LlamaConfig

    accelerator = Accelerator(mixed_precision="bf16", parallelism_config=parallelism)
    if devices is not None:
        one = ParallelismConfig(dp_size=len(devices))
        accelerator.state.replace_mesh(one.build_mesh(devices), one)
    model = Llama(LlamaConfig(
        **cfg["model"], remat=True, remat_policy="dots_with_no_batch_dims_saveable",
    ))
    model.init_params(jax.random.key(seed))
    pmodel, popt = accelerator.prepare(model, optax.adafactor(3e-4))
    step = accelerator.build_train_step(pmodel, popt)
    return types.SimpleNamespace(accelerator=accelerator, model=model, pmodel=pmodel,
                                 popt=popt, step=step)


def drop_training_state(trainer):
    """Free the parameters, the optimizer state and the accumulation buffer:
    at 742M they nearly fill a chip, and the next phase needs the room."""
    trainer.model.params = trainer.pmodel.handle.params = None
    trainer.popt.opt_state = trainer.popt._accum_grads = None
    trainer.accelerator.free_memory()
    gc.collect()


def run_steps(step, data, n: int) -> dict:
    """``n`` steps on one batch. The first two calls are timed alone, each to
    its device-to-host fetch: the first loads the compiled step, and the
    second compiles once more (the step's outputs come back in shardings that
    XLA chose, spelled differently from the planned ones, so jit misses its
    cache once). ``step_s`` is the mean of the calls after those."""
    import jax

    losses, seconds = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        losses.append(float(jax.device_get(step(data))))
        seconds.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    pending = [step(data) for _ in range(n - 2)]
    losses += [float(x) for x in jax.device_get(pending)]
    rest_s = time.perf_counter() - t0
    tokens = data["input_ids"].size
    return {
        "losses": losses, "first_step_s": round(seconds[0], 2),
        "second_step_s": round(seconds[1], 2), "step_s": round(rest_s / (n - 2), 4),
        "tokens_per_s": round(tokens * (n - 2) / rest_s, 1),
    }


def loss_checks(phase: Phase, losses: list, vocab: int):
    # The initialisation gives unit-variance logits, so an untrained model's
    # loss is ln V + 1/2 (10.87 at V = 32000), not ln V.
    expected = math.log(vocab) + 0.5
    phase.check("first_loss_near_ln_vocab_plus_half", abs(losses[0] - expected) <= 0.25,
                {"first": losses[0], "ln_vocab": round(math.log(vocab), 4),
                 "expected": round(expected, 4), "tolerance": 0.25})
    phase.check("losses_finite", all(math.isfinite(x) for x in losses))
    phase.check("loss_falls", losses[-1] < losses[0],
                {"first": losses[0], "last": losses[-1]})


def memory_stats(device) -> dict:
    """What the runtime reports for ``device``, as it is (a TPU reads
    ``peak_bytes_in_use`` without a running program's temporaries)."""
    stats = device.memory_stats() or {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")
    return {k: stats[k] for k in keep if k in stats}


# ------------------------------------------------------------------- train
def phase_train(cfg: dict, seed: int, on_tpu: bool):
    import jax

    from accelerate_tpu.ops.attention import resolve_auto_impl

    phase = Phase("train")
    trainer = build_trainer(cfg, seed)
    model = trainer.model
    data = make_batch(cfg, seed)

    t0 = time.perf_counter()
    compiled = trainer.step.lower(data).compile()
    compile_s = time.perf_counter() - t0
    steps = 11
    run = run_steps(trainer.step, data, steps)
    losses = run.pop("losses")

    loss_checks(phase, losses, cfg["model"]["vocab_size"])
    platform = jax.devices()[0].platform
    leaves = jax.tree_util.tree_leaves((trainer.pmodel.handle.params, trainer.popt.opt_state))
    on_device = {d.platform for leaf in leaves for d in leaf.devices()}
    phase.check("state_on_device", on_device == {platform}, sorted(on_device))
    if on_tpu:
        mcfg = model.config
        impl = resolve_auto_impl(cfg["seq"], mcfg.num_attention_heads, mcfg.head_dim,
                                 batch=cfg["batch"])
        phase.check("attention_is_flash", impl == "flash", impl)
        phase.check("tpu_custom_call_in_compiled_step",
                    "tpu_custom_call" in compiled.as_text())
        mem = compiled.memory_analysis()
        phase.readings["compiled_bytes"] = {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
        }
        phase.readings["memory_stats"] = memory_stats(jax.devices()[0])
    phase.readings.update(
        params=model.num_params(), batch=cfg["batch"], seq=cfg["seq"], steps=steps,
        losses=[round(x, 4) for x in losses], compile_s=round(compile_s, 2), **run,
    )
    phase.finish()

    # Serve in bf16 over the trained weights; everything else of training goes.
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), trainer.pmodel.handle.params
    )
    jax.block_until_ready(params)
    del leaves
    drop_training_state(trainer)
    return model, params


# ------------------------------------------------------------------- serve
def generate_over_http(endpoint: str, prompt) -> dict:
    from accelerate_tpu.serving_net.frontend import read_sse_response

    request = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(request, timeout=1100.0) as response:
        return read_sse_response(response)


def reference_logits_fn(model, ref_len: int, max_new: int):
    """float32 full-sequence forward, dense attention, no cache: the plain
    reference. Returns the logits of the ``max_new`` positions that predict
    the generated tokens."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import Llama

    plain = Llama(dataclasses.replace(model.config, attention_impl="dense", remat=False))

    @jax.jit
    def logits_at(params, ids, start):
        with jax.default_matmul_precision("highest"):
            params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
            logits = plain.apply(params, input_ids=ids)["logits"][0]
        return jax.lax.dynamic_slice_in_dim(logits.astype(jnp.float32), start, max_new)

    assert ref_len >= max_new
    return logits_at


def phase_serve(cfg: dict, seed: int, model, params, on_tpu: bool):
    import jax
    import numpy as np

    from accelerate_tpu.serving import ContinuousBatcher
    from accelerate_tpu.serving_net.frontend import ServingFrontend
    from accelerate_tpu.telemetry.metrics import MetricsServer

    phase = Phase("serve")
    max_new = 64
    engine = ContinuousBatcher(
        model, params=params, batch_slots=cfg["slots"], block_size=16,
        max_new_tokens=max_new, max_cache_len=2048,
    )
    server = MetricsServer(0, host="127.0.0.1")
    endpoint = f"127.0.0.1:{server.start()}"
    # The first stream waits for every prefill bucket and the decode program
    # to compile; the default stream timeout is sized for a warm server.
    frontend = ServingFrontend(engine, stream_timeout_s=1000.0)
    frontend.install(server=server, endpoint=endpoint)

    rng = np.random.default_rng(seed + 1)
    lo, hi = cfg["prompt_lens"]
    lens = rng.permutation(np.linspace(lo, hi, cfg["requests"]).astype(int))
    prompts = [rng.integers(1, cfg["model"]["vocab_size"], (int(n),)).astype(np.int32)
               for n in lens]
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        results = list(pool.map(lambda p: generate_over_http(endpoint, p), prompts))
    wave_s = time.perf_counter() - t0
    frontend.uninstall()
    server.stop()

    answers = [np.asarray(r["tokens"], np.int32) for r in results]
    phase.check("every_stream_done_with_64_tokens",
                all(a.size == max_new for a in answers), [int(a.size) for a in answers])
    stats = engine.pool_stats()
    phase.check("free_list_full_after_wave", stats["blocks_free"] == stats["num_blocks"],
                {"blocks_free": stats["blocks_free"], "num_blocks": stats["num_blocks"]})

    logits_at = reference_logits_fn(model, cfg["ref_len"], max_new)
    exact, worst_gap = 0, 0.0
    for prompt, answer in zip(prompts, answers):
        ids = np.zeros((1, cfg["ref_len"]), np.int32)
        seq = np.concatenate([prompt, answer])
        ids[0, : seq.size] = seq  # causal: right padding cannot reach back
        ref = np.asarray(logits_at(params, ids, prompt.size - 1))
        rows = np.arange(answer.size)
        gap = ref[rows].max(axis=-1) - ref[rows, answer]
        exact += int((gap == 0).sum())
        worst_gap = max(worst_gap, float(gap.max()))
    total = sum(a.size for a in answers)
    phase.check("reference_agrees_within_margin", worst_gap <= LOGIT_MARGIN,
                {"worst_logit_gap": round(worst_gap, 4), "margin": LOGIT_MARGIN,
                 "exact_share": round(exact / max(total, 1), 4)})
    phase.readings.update(
        requests=len(prompts), prompt_lens=[int(n) for n in lens], max_new_tokens=max_new,
        slots=cfg["slots"], wave_s_incl_compile=round(wave_s, 2),
        ttft_s=[round(r["done"]["ttft_s"], 3) for r in results if r["done"].get("ttft_s")],
        kernels=engine.kernels or "reference", pool_bytes=stats["pool_bytes"],
    )
    if on_tpu:
        phase.readings["memory_stats"] = memory_stats(jax.devices()[0])
    phase.finish()


# -------------------------------------------------------------- four chips
def phase_fsdp4(cfg: dict, seed: int, on_tpu: bool):
    import jax

    from accelerate_tpu import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState

    phase = Phase("fsdp4")
    devices = jax.devices()
    data = make_batch(cfg, seed)
    steps = 5

    # One chip first; only its losses are kept (that run nearly fills a chip).
    trainer = build_trainer(cfg, seed, devices=devices[:1])
    one_chip = run_steps(trainer.step, data, steps)["losses"]
    drop_training_state(trainer)
    del trainer
    AcceleratorState._reset_state(reset_partial_state=True)

    trainer = build_trainer(cfg, seed, parallelism=ParallelismConfig(fsdp_size=4))
    text = trainer.step.lower(data).compile().as_text()
    run = run_steps(trainer.step, data, steps)
    sharded = run.pop("losses")

    loss_checks(phase, sharded, cfg["model"]["vocab_size"])
    diffs = [abs(a - b) for a, b in zip(sharded, one_chip)]
    phase.check("losses_agree_with_one_chip", max(diffs) <= LOSS_TOLERANCE,
                {"max_abs_diff": round(max(diffs), 5), "tolerance": LOSS_TOLERANCE})
    big = [leaf for leaf in jax.tree_util.tree_leaves(trainer.pmodel.handle.params)
           if leaf.size > cfg["big_leaf"]]
    spread = [
        len({s.device for s in leaf.addressable_shards}) == 4
        and len({str(s.index) for s in leaf.addressable_shards}) == 4
        for leaf in big
    ]
    phase.check("big_param_leaves_in_four_different_shards", big and all(spread),
                {"leaves": len(big), "spread": sum(spread)})
    phase.check("all_gather_in_compiled_step", "all-gather" in text)
    phase.check("reduce_scatter_or_all_reduce_in_compiled_step",
                "reduce-scatter" in text or "all-reduce" in text)
    if on_tpu:
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices[:4]]
        phase.check("bytes_in_use_within_25_percent",
                    min(in_use) > 0 and (max(in_use) - min(in_use)) / max(in_use) <= 0.25,
                    in_use)
        phase.readings["memory_stats"] = [memory_stats(d) for d in devices[:4]]
    phase.readings.update(
        mesh={k: v for k, v in trainer.accelerator.mesh.shape.items() if v > 1},
        steps=steps, losses_one_chip=[round(x, 4) for x in one_chip],
        losses_fsdp4=[round(x, 4) for x in sharded], **run,
    )
    phase.finish()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the sharded-training phase only, on four chips")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny width on whatever backend is there")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX reports {device}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, JAX "
              f"reports {device}", file=sys.stderr)
        return 2

    import accelerate_tpu
    from accelerate_tpu.utils.environment import maybe_enable_compilation_cache

    if os.path.dirname(os.path.abspath(accelerate_tpu.__file__)) != os.path.join(
            ROOT, "accelerate_tpu"):
        print("chip_smoke: accelerate_tpu was not imported from this checkout",
              file=sys.stderr)
        return 2

    cache_dir = maybe_enable_compilation_cache(CACHE_DIR)
    entries_before = cache_entries(cache_dir)
    print(json.dumps({"phase": "setup", "note": NOTE, "device": device,
                      "rehearsal": bool(args.rehearse), "jax": jax.__version__,
                      "compile_cache_dir": cache_dir,
                      "compile_cache_entries": entries_before}), flush=True)

    cfg = smoke_config(args.rehearse)
    if args.chips == 4:
        phase_fsdp4(cfg, args.seed, on_tpu)
    else:
        model, params = phase_train(cfg, args.seed, on_tpu)
        phase_serve(cfg, args.seed, model, params, on_tpu)

    print(json.dumps({"phase": "teardown", "note": NOTE, "compile_cache_dir": cache_dir,
                      "compile_cache_entries_before": entries_before,
                      "compile_cache_entries_after": cache_entries(cache_dir)}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
