"""The one home of ``shard_map`` and ``axis_size`` for this repo.

Call sites (parallel/pipeline.py, parallel/ring.py, parallel/ulysses.py)
import them from here, and the ``raw-shard-map`` lint rule keeps it so. The
installed JAX carries ``jax.shard_map`` (``axis_names`` / ``check_vma``) and
``jax.lax.axis_size``; these wrappers only pin the keyword surface the repo
uses.
"""

from __future__ import annotations

import jax


def has_native_shard_map() -> bool:
    """True: the installed JAX has top-level ``jax.shard_map`` with
    partial-auto manual mapping (tests/test_hlo_collectives.py keys on it)."""
    return True


def axis_size(axis_name) -> int:
    """The static size of a mapped mesh axis."""
    return jax.lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=None):
    """``jax.shard_map``. ``axis_names`` restricts MANUAL mapping to those
    mesh axes (the rest stay automatic/GSPMD); ``check_vma`` is passed only
    when given (the pipeline schedules turn it off: they produce
    stage-varying values on purpose)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, **kwargs)
