"""Environment parsing/patching helpers.

Reference parity: ``src/accelerate/utils/environment.py`` — ``parse_flag_from_env``,
``parse_choice_from_env``, ``patch_environment`` (:326), ``clear_environment`` (:291),
``purge_accelerate_environment`` (:362-420). NUMA-affinity and CUDA-P2P checks are
GPU-specific and intentionally absent; the TPU analog (megacore/ICI layout) is owned
by the XLA runtime.
"""

from __future__ import annotations

import functools
import logging
import os
from contextlib import contextmanager

from .constants import ENV_COMPILE_CACHE_DIR, ENV_COMPILE_CACHE_MIN_SECS, ENV_PREFIX

logger = logging.getLogger(__name__)


def str_to_bool(value: str) -> int:
    """Convert a string (env var) to 1/0. Accepts y/yes/t/true/on/1 and n/no/f/false/off/0."""
    value = value.lower()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0"):
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    try:
        return bool(str_to_bool(value))
    except ValueError:
        raise ValueError(f"If set, {key} must be yes/no/1/0/true/false, got {value!r}.")


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def pin_cpu_platform(n_devices: int = 8) -> None:
    """Give this process ``n_devices`` virtual CPU devices: the way tests and
    dry runs get a mesh without hardware.

    Sets ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` and
    selects the CPU platform, in the environment (so children inherit it) and
    in ``jax.config``. Must run before the process first touches a JAX
    backend; a caller that may come later checks ``len(jax.devices())``.
    Used by tests/conftest.py, __graft_entry__.py and the CLI's CPU rigs.
    """
    import re

    opt = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", opt, flags)
    else:
        flags = (flags + " " + opt).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


@functools.lru_cache(maxsize=None)
def _log_cache_override_once(jax_dir: str, requested: str) -> None:
    logger.warning(
        "JAX_COMPILATION_CACHE_DIR=%s is set: ignoring the compile cache "
        "directory %s asked for through this library.", jax_dir, requested,
    )


def maybe_enable_compilation_cache(cache_dir: str | None = None) -> str | None:
    """Single owner of JAX's persistent compilation cache. Returns the
    directory in use, or None when no cache is configured.

    Resolution order:

    1. ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other is set
       in code. ``cache_dir``, ``ACCELERATE_COMPILE_CACHE_DIR`` and the launch
       flag behind it are ignored (one log line if they name another place).
    2. ``cache_dir``, else ``ACCELERATE_COMPILE_CACHE_DIR``.
    3. Neither: nothing is touched (library users keep JAX's defaults).

    With a directory, JAX's size and time gates are opened so that every
    program is kept: the default gates skip sub-second compiles, which is all
    of a test rig's. ``ACCELERATE_COMPILE_CACHE_MIN_COMPILE_SECS`` (default 0)
    raises the time gate again.

    Idempotent; call before the first compile of the programs that should hit
    the cache. ``PartialState`` calls it on construction, so every entry point
    that builds an ``Accelerator`` gets the environment's cache for free.
    """
    requested = cache_dir or os.environ.get(ENV_COMPILE_CACHE_DIR) or None
    if requested:
        requested = os.path.abspath(os.path.expanduser(requested))
    jax_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if jax_dir:
        if requested and requested != os.path.abspath(jax_dir):
            _log_cache_override_once(jax_dir, requested)
        cache_dir = jax_dir
    elif requested:
        cache_dir = requested
        os.makedirs(cache_dir, exist_ok=True)
    else:
        return None

    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    min_secs = float(os.environ.get(ENV_COMPILE_CACHE_MIN_SECS, "0") or 0.0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def safe_donate_argnums(argnums: tuple) -> tuple:
    """Gate buffer donation on backends where it is actually safe.

    CPU executables **deserialized from the persistent compilation cache**
    have mis-handled input-output aliasing: running them with donated inputs
    corrupted the allocator heap (segfault / ``malloc(): memory corruption``
    once an orbax *restore* churned the heap: the resume-after-restart path
    the compilation cache exists to accelerate). Seen on an older jaxlib and
    not re-checked on 0.9.0. Donation on CPU buys nothing (host RAM, no HBM
    pressure), so when both features would combine — CPU backend AND an
    active persistent cache — donation is dropped; TPU/GPU always keep it,
    where it is the HBM-pressure win the fused train step is built around.
    """
    import jax

    if jax.default_backend() == "cpu" and jax.config.jax_compilation_cache_dir:
        return ()
    return tuple(argnums)


def get_int_from_env(env_keys, default: int) -> int:
    """Return the first positive int found among env_keys."""
    for key in env_keys:
        val = int(os.environ.get(key, -1))
        if val >= 0:
            return val
    return default


@contextmanager
def patch_environment(**kwargs):
    """Temporarily set environment variables; restores previous values on exit.

    Mirrors ``src/accelerate/utils/environment.py:326``.
    """
    existing = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)


@contextmanager
def clear_environment():
    """Temporarily empty ``os.environ``; restores on exit (reference :291)."""
    saved = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


def purge_accelerate_environment(fn):
    """Decorator that runs ``fn`` with all ``ACCELERATE_*`` vars removed and restores
    them afterwards (reference :362-420). Used by the test harness for state hygiene.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        saved = {k: v for k, v in os.environ.items() if k.startswith(ENV_PREFIX)}
        for k in saved:
            del os.environ[k]
        try:
            return fn(*args, **kwargs)
        finally:
            for k in list(os.environ):
                if k.startswith(ENV_PREFIX):
                    del os.environ[k]
            os.environ.update(saved)

    return wrapper
