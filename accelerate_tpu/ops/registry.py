"""Kernel registry — per-op backend resolution for the Pallas kernel layer.

PAPER.md's thesis is that custom kernels land in C++-backed Pallas/Mosaic,
not Python stand-ins — but a kernel that cannot fall back is a production
liability. This module is the dispatch seam between the three hot-op
reference lowerings (``ops/paged_attention.py``'s block-table gather,
``accelerator._fused_step_body``'s optax update chain, ``ops/int8.py``'s
quantized matmul) and their ``ops/pallas/`` kernels:

- every op registers a **reference** implementation (plain XLA lowering,
  always available, the committed parity seam) and a **kernel**
  implementation (a ``pallas_call`` accepting ``interpret=``);
- :func:`resolve_backend` maps the operator's spec (call-site override >
  ``ACCELERATE_KERNELS`` env) to one of ``pallas`` / ``interpret`` /
  ``reference`` per op. On a TPU backend ``pallas`` always means the compiled
  Mosaic kernel; off the TPU the same token runs the same kernel body under
  the Pallas interpreter (``interpret``), which is what the CPU parity tests
  rely on to exercise the *kernel's* math and not a stand-in;
- specs may be a bare token (applies to every op) or a per-op map
  (``paged_decode=pallas,int8_matmul=off``); unset means ``reference``.

Backend resolution happens at **trace time**: switching the spec after a
program compiled requires a rebuild, exactly like every other compiled-in
lever (train_window, zero_sharding). The resolved per-op map rides in the
builders' ``_audit_meta["kernels"]`` so audits, fingerprints, and bench
lines record which backend actually lowered.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass

logger = logging.getLogger(__name__)

# Canonical backend names (what resolve_backend returns).
PALLAS = "pallas"
INTERPRET = "interpret"
REFERENCE = "reference"
BACKENDS = (PALLAS, INTERPRET, REFERENCE)

# Spellings accepted in specs (env / flag / call-site).
_TOKEN_ALIASES = {
    "pallas": PALLAS,
    "interpret": INTERPRET,
    "reference": REFERENCE,
    "off": REFERENCE,
    "none": REFERENCE,
    "0": REFERENCE,
    "": REFERENCE,
}


@dataclass
class KernelOp:
    """One registered hot op: its reference lowering and its Pallas kernel.

    ``kernel`` must accept the reference's exact signature plus a keyword
    ``interpret: bool`` and match the reference bit-for-bit on the committed
    test vectors (tests/test_kernels.py) — the registry guarantees dispatch,
    the kernel guarantees the seam."""

    name: str
    reference: callable
    kernel: callable
    doc: str = ""


_OPS: dict = {}
_WARNED: set = set()


def register_op(name: str, reference, kernel, doc: str = "") -> None:
    """Register (or re-register, e.g. on module reload) a kernel-backed op."""
    _OPS[name] = KernelOp(name=name, reference=reference, kernel=kernel, doc=doc)


def _ensure_registered() -> None:
    """Import the kernel modules (each self-registers) exactly once. Off the
    TPU a broken pallas import degrades every op to its reference lowering
    (logged once); on a TPU backend it raises, because there a kernel that
    was asked for must not be swapped for its reference without a word."""
    if _OPS:
        return
    try:
        from . import pallas  # noqa: F401  (self-registers on import)
    except Exception as exc:  # pragma: no cover - env-specific
        if pallas_supported():
            raise
        if "import" not in _WARNED:
            _WARNED.add("import")
            logger.warning(
                "Pallas kernel layer unavailable (%s); all ops stay on their "
                "reference lowerings.", exc,
            )


def known_ops() -> tuple:
    _ensure_registered()
    return tuple(sorted(_OPS))


def parse_kernel_spec(spec: str | None) -> dict:
    """Parse a spec string into ``{op_or_default: backend_token}``.

    A bare token (``pallas``) maps under the default key ``""``; a per-op map
    (``paged_decode=pallas,int8_matmul=off``) may mix with a bare default
    token (``pallas,int8_matmul=off``). Unknown tokens AND unknown op names
    raise — the launcher validates the flag with this same function, so a
    typo (either side of the ``=``) dies at launch instead of silently
    running reference."""
    out: dict = {}
    if spec is None:
        return out
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            op, _, token = part.partition("=")
            op, token = op.strip(), token.strip().lower()
        else:
            op, token = "", part.lower()
        if token not in _TOKEN_ALIASES:
            raise ValueError(
                f"unknown kernel backend {token!r} in ACCELERATE_KERNELS spec "
                f"{spec!r}; choose from pallas | interpret | reference | off"
            )
        if op:
            ops = known_ops()
            # Only validate when the registry actually populated (a broken
            # pallas import leaves it empty — everything degrades to
            # reference there, and dying on the spec would be worse).
            if ops and op not in ops:
                raise ValueError(
                    f"unknown kernel op {op!r} in ACCELERATE_KERNELS spec "
                    f"{spec!r}; registered ops: {', '.join(ops)}"
                )
        out[op] = _TOKEN_ALIASES[token]
    return out


def pallas_supported() -> bool:
    """Whether the compiled (Mosaic) kernel path can run: a TPU backend."""
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backendless env
        return False


def resolve_backend(op: str, spec: str | dict | None = None) -> str:
    """Resolve ``op``'s backend: call-site spec wins over ``ACCELERATE_KERNELS``.

    Returns one of ``pallas`` / ``interpret`` / ``reference``. The ``pallas``
    token degrades to ``interpret`` off-TPU (logged once per op) so the kernel
    code path stays live everywhere; ``reference`` is only ever chosen
    explicitly or by default."""
    _ensure_registered()
    if not _OPS:
        # The pallas package failed to import: every op degrades to its
        # reference lowering regardless of the requested spec (the warning
        # fired once in _ensure_registered).
        return REFERENCE
    if isinstance(spec, dict):
        tokens = spec
    else:
        if spec is None:
            from ..utils.constants import ENV_KERNELS

            spec = os.environ.get(ENV_KERNELS)
        tokens = parse_kernel_spec(spec)
    token = tokens.get(op, tokens.get("", REFERENCE))
    if token == PALLAS and not pallas_supported():
        if op not in _WARNED:
            _WARNED.add(op)
            logger.info(
                "kernels: %s=pallas requested but the backend is not TPU; "
                "running the kernel in interpret mode.", op,
            )
        return INTERPRET
    return token


def resolved_backends(spec: str | dict | None = None) -> dict:
    """{op: resolved backend} over every registered op — what builder meta,
    bench ``detail.kernels``, and the docs' tri-state examples record."""
    _ensure_registered()
    return {op: resolve_backend(op, spec) for op in sorted(_OPS)}


def dispatch(op: str, *args, backend: str | dict | None = None, **kwargs):
    """Run ``op`` on its resolved backend. ``backend`` may be a raw token, a
    spec string, or a parsed spec dict; None reads ``ACCELERATE_KERNELS``."""
    _ensure_registered()
    entry = _OPS.get(op)
    if entry is None:
        raise KeyError(f"unknown kernel op {op!r}; registered: {known_ops()}")
    if isinstance(backend, str) and backend in BACKENDS:
        resolved = backend
        if resolved == PALLAS and not pallas_supported():
            resolved = INTERPRET
    else:
        resolved = resolve_backend(op, backend)
    if resolved == REFERENCE or entry.kernel is None:
        return entry.reference(*args, **kwargs)
    return entry.kernel(*args, interpret=(resolved == INTERPRET), **kwargs)


def reference_impl(op: str):
    """The committed reference lowering for ``op`` (the parity seam)."""
    _ensure_registered()
    return _OPS[op].reference
