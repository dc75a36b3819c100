"""A program's start-up in the span ring (``telemetry/spans.py``): each phase
JAX times (tracing, lowering, compiling or loading from the persistent cache)
becomes a ``program.*`` record, the innermost open span sums the outermost
phases under ``trace_s`` / ``lower_s`` / ``compile_s``, and the goodput
ledger's ``compile`` bucket books them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.resilience.goodput import get_ledger
from accelerate_tpu.telemetry import get_span_ring, reset_spans, span
from accelerate_tpu.telemetry.spans import no_span

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("program.trace", "program.lower", "program.compile")


def _programs(name=None):
    return [r for r in get_span_ring().snapshot()
            if r.name.startswith("program.") and (name is None or r.attrs["program"] == name)]


def _end(rec):
    return rec.start_s + rec.duration_s


def test_first_call_pushes_three_phases_named_by_the_program_and_a_cached_call_none():
    def startup_probe_first(x):
        return jnp.cos(x) + 1.0

    fn = jax.jit(startup_probe_first)
    x = jnp.ones(5)
    reset_spans()
    fn(x)
    mine = _programs("startup_probe_first")
    assert [r.name for r in mine] == list(PHASES)  # "jit(...)" is stripped from the name
    trace, lower, compiled = mine
    assert trace.attrs["nested"] == lower.attrs["nested"] == compiled.attrs["nested"] == 0
    # an earlier test of this process may have set a persistent cache
    if compiled.attrs["cache"] == "hit":
        assert compiled.attrs["retrieval_s"] >= 0.0
    else:
        assert compiled.attrs["cache"] == ("miss" if jax.config.jax_compilation_cache_dir else "off")
        assert "retrieval_s" not in compiled.attrs
    assert _end(trace) <= lower.start_s + 1e-3 and _end(lower) <= compiled.start_s + 1e-3
    assert all(r.duration_s >= 0.0 and r.depth == 0 and r.rid is None for r in mine)
    reset_spans()
    fn(x)
    assert _programs() == []


def test_an_inner_jit_is_traced_nested_inside_its_caller():
    @jax.jit
    def startup_probe_inner(x):
        return jnp.sin(x) * 2.0

    def startup_probe_outer(x):
        return startup_probe_inner(x) + startup_probe_inner(x * 3.0).sum()

    reset_spans()
    jax.jit(startup_probe_outer)(jnp.ones(3))
    (outer,) = [r for r in _programs("startup_probe_outer") if r.name == "program.trace"]
    inner = [r for r in _programs("startup_probe_inner")]
    assert inner and all(r.name == "program.trace" for r in inner)  # no program of its own
    assert outer.attrs["nested"] == 0
    for r in inner:
        assert r.attrs["nested"] >= 1
        assert outer.start_s - 1e-3 <= r.start_s and _end(r) <= _end(outer) + 1e-3


def test_the_enclosing_span_sums_the_phases_and_no_span_gets_nothing():
    def startup_probe_span(x):
        return x * 2.0 + 1.0

    def startup_probe_quiet(x):
        return x * 3.0 - 1.0

    x = jnp.ones(4)
    reset_spans()
    with span("outer"), span("dispatch") as rec:
        jax.jit(startup_probe_span)(x)
    mine = {r.name: r for r in _programs("startup_probe_span")}
    assert set(mine) == set(PHASES)
    for name, key in zip(PHASES, ("trace_s", "lower_s", "compile_s")):
        assert rec.attrs[key] == pytest.approx(sum(
            r.duration_s for r in _programs() if r.name == name and r.attrs["nested"] == 0))
        assert (mine[name].depth, mine[name].path) == (2, "outer/dispatch/" + name)
    outer = next(r for r in get_span_ring().snapshot() if r.name == "outer")
    assert outer.attrs is None  # the innermost open span alone
    reset_spans()
    with no_span("dispatch") as quiet:
        jax.jit(startup_probe_quiet)(x)
    assert quiet.attrs == {}
    mine = _programs("startup_probe_quiet")
    assert [r.name for r in mine] == list(PHASES)
    assert all((r.depth, r.path) == (0, r.name) for r in mine)
    assert [r.name for r in get_span_ring().snapshot() if not r.name.startswith("program.")] == []


def test_the_goodput_ledger_books_the_outermost_phases_as_compile():
    def startup_probe_ledger(x):
        return jnp.tanh(x)

    ledger = get_ledger()
    ledger.reset()
    reset_spans()
    try:
        jax.jit(startup_probe_ledger)(jnp.ones(6))
        outermost = [r for r in _programs() if r.attrs["nested"] == 0]
        compiles = [r for r in outermost if r.name == "program.compile"]
        assert compiles
        assert ledger.counts["compile"] == len(compiles)  # one a program compiled or loaded
        assert ledger.seconds["compile"] == pytest.approx(sum(r.duration_s for r in outermost))
        assert ledger.summary()["compile_s"] > 0.0
    finally:
        ledger.reset()


def test_a_phase_that_raises_leaves_no_phase_open():
    def startup_probe_raises(x):
        raise ValueError("refused at trace")

    def startup_probe_after(x):
        return x + 1.0

    reset_spans()
    with pytest.raises(ValueError, match="refused at trace"):
        jax.jit(startup_probe_raises)(jnp.ones(2))
    jax.jit(startup_probe_after)(jnp.ones(2))
    (failed,) = _programs("startup_probe_raises")
    assert (failed.name, failed.attrs["nested"]) == ("program.trace", 0)
    assert [r.attrs["nested"] for r in _programs("startup_probe_after")] == [0, 0, 0]


def _thread_body(i):
    def body(x):
        return jnp.cos(x) * (i + 1)
    body.__name__ = body.__qualname__ = f"startup_probe_thread_{i}"
    return body


def test_threads_that_build_at_once_keep_their_own_phases():
    """Each thread has its own open phases and spans: a program built on one
    thread is never nested in, nor summed into, another thread's span."""
    import threading

    def build(i, out):
        x = jnp.ones(3 + i)
        barrier.wait(timeout=60)
        with span(f"thread_{i}") as rec:
            jax.jit(_thread_body(i))(x)
        out[i] = rec

    barrier = threading.Barrier(4)
    out = {}
    reset_spans()
    threads = [threading.Thread(target=build, args=(i, out)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(out) == 4
    for i, rec in out.items():
        mine = _programs(f"startup_probe_thread_{i}")
        assert [r.name for r in mine] == list(PHASES)
        assert all(r.attrs["nested"] == 0 and r.path == f"thread_{i}/{r.name}" for r in mine)
        for r, key in zip(mine, ("trace_s", "lower_s", "compile_s")):
            assert rec.attrs[key] == pytest.approx(r.duration_s)


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from accelerate_tpu.telemetry import get_span_ring, reset_spans

def startup_probe_cached(x):
    return jnp.exp(x) @ x

fn = jax.jit(startup_probe_cached)
x = jnp.ones((8, 8))
out = []
for _ in range(2):
    reset_spans()
    fn(x).block_until_ready()
    out.append([r.attrs for r in get_span_ring().snapshot()
                if r.name == "program.compile" and r.attrs["program"] == "startup_probe_cached"])
    jax.clear_caches()
print(json.dumps(out))
"""


def test_a_persistent_cache_hit_is_a_compile_record_with_its_retrieval(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(_CACHE_PROBE)
    env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    result = subprocess.run([sys.executable, str(script), str(tmp_path / "cache")],
                            capture_output=True, text=True, cwd=REPO_ROOT, timeout=300, env=env)
    assert result.returncode == 0, result.stderr[-2000:]
    (first,), (second,) = json.loads(result.stdout.strip().splitlines()[-1])
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] >= 0.0
    assert first["nested"] == second["nested"] == 0


def test_a_warm_engine_s_second_run_builds_no_program():
    from accelerate_tpu.models import Llama, LlamaConfig
    from accelerate_tpu.serving import ContinuousBatcher

    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    engine = ContinuousBatcher(model, batch_slots=2, max_new_tokens=8, max_cache_len=256,
                               cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=4,
                               block_size=4, prefill_chunk=8, max_tokens_per_request=48)

    def wave():
        rng = np.random.default_rng(3)
        for n in (5, 19, 9):
            engine.submit(rng.integers(1, 256, (n,)).astype(np.int32))
        reset_spans()
        outs = engine.run()
        return outs, get_span_ring().snapshot()

    first, records = wave()
    built = [r for r in records if r.name == "program.compile"]
    assert {"serve_decode_window", "serve_prefill_chunk_8"} <= {r.attrs["program"] for r in built}
    # the first window's dispatch span names what it built
    decode = next(r for r in records if r.name == "serve.dispatch_decode")
    assert decode.attrs["compile_s"] > 0.0 and decode.attrs["trace_s"] > 0.0
    second, records = wave()
    again = [r for r in records if r.name.startswith("program.")]
    assert not [r for r in again if r.name != "program.trace"]  # nothing lowered or compiled
    # What is traced again: the final chunk's eager ``left_align`` (a vmap of
    # ``jnp.roll``, traced anew at every call) under its dispatch span.
    assert {r.attrs["program"] for r in again} <= {"_roll_dynamic"}
    assert all(r.path.endswith("serve.dispatch_chunk/program.trace") for r in again)
    assert not any(key in (r.attrs or {}) for r in records
                   for key in ("lower_s", "compile_s"))
    for rid, tokens in first.items():
        np.testing.assert_array_equal(second[rid + len(first)], tokens)
