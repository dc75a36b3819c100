"""The only place where the benchmark builds the system under test.

Everything a cell runs comes from its configuration file: the model's class
(a name in ``accelerate_tpu.models``), its published keys, and the few fields
of the program's own config that a published file does not carry
(``model_overrides``, for example ``qk_norm`` for Qwen3).
"""

from __future__ import annotations

import dataclasses
import time


def seed_key(seed: int):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    import numpy as np

    return jax.random.key(int(np.random.SeedSequence(int(seed)).generate_state(1)[0]))


def build_model(config: dict, **extra):
    """The program's model object for a configuration file's contents."""
    import accelerate_tpu.models as models

    name = config.get("model_class", "Llama")
    model_cls = getattr(models, name)
    cfg_cls = getattr(models, config.get("config_class", name + "Config"))
    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    kwargs = {k: v for k, v in config.items() if k in fields}
    kwargs.update(config.get("model_overrides", {}))
    kwargs.update(extra)
    return model_cls(cfg_cls(**kwargs))


def model_dims(model) -> dict:
    """The sizes the reference and the shape arithmetic need, as the program
    runs them (read back from its own config object)."""
    cfg = model.config
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings", "sliding_window", "rope_scaling",
            "attention_bias", "hidden_act", "qk_norm")
    return {k: getattr(cfg, k) for k in keys if hasattr(cfg, k)}


def make_params(model, seed: int, dtype=None):
    """All weights on the device in one jitted call from the seed, in the type
    they are used in."""
    import jax

    def init(key):
        params = model.init(key)
        if dtype is not None:
            params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
        return params

    return jax.jit(init)(seed_key(seed))


def compiled_peak_bytes(compiled) -> int:
    """The compiler's own account of a compiled program's peak device memory.
    On this runtime ``memory_stats()`` leaves a running program's temporaries
    out of its peak (PERF.md, PR 22), so this is the number to trust."""
    memory = compiled.memory_analysis()
    return int(getattr(memory, "peak_memory_in_bytes", 0) or (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes))


def peak_bytes_in_use(devices) -> int:
    """The largest ``peak_bytes_in_use`` the devices report (a process's peak
    never falls again, so a runner reads it before its reference check)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


class SetupMarks(dict):
    """Seconds since the process started at which each stage of set-up ended."""

    def __init__(self, process_start: float):
        super().__init__()
        self.process_start = process_start

    def mark(self, name: str) -> None:
        self[name] = time.perf_counter() - self.process_start
