"""Measure the dense/flash attention crossover on the current device.

The ``impl="auto"`` dispatch in ``accelerate_tpu/ops/attention.py`` switches
from the dense einsum to the Pallas flash kernel at a per-device-kind sequence
length (``_FLASH_CROSSOVER``). This script reproduces that measurement so the
table can be re-derived on new TPU generations:

    python benchmarks/attention_crossover.py

Timing notes: each config runs ``ITERS`` attention calls chained inside one
``jit`` (a data dependency through q), so per-call host latency is amortized
away; the host round-trip is measured separately and subtracted.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp


def measure(fn, q, k, v, iters):
    @jax.jit
    def loop(q, k, v):
        def body(i, qq):
            return fn(qq, k, v, causal=True).astype(qq.dtype)

        return jax.lax.fori_loop(0, iters, body, q).sum()

    float(loop(q, k, v))  # compile + warm
    # Host round-trip floor: median of several tiny pre-compiled fetches.
    probe = jax.jit(lambda x: x.sum())
    float(probe(jnp.zeros(8)))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(probe(jnp.zeros(8)))
        rtts.append(time.perf_counter() - t0)
    rtt = sorted(rtts)[2]
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(loop(q, k, v))
        times.append(time.perf_counter() - t0)
    return (sorted(times)[1] - rtt) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--seqs", type=int, nargs="+", default=[512, 1024, 2048, 4096])
    args = ap.parse_args()

    from accelerate_tpu.ops.attention import (
        _flash_available,
        dense_attention,
        flash_attention,
    )

    kind = jax.devices()[0].device_kind
    print(f"device_kind: {kind}  flash_available: {_flash_available()}")
    crossover = None
    for S in args.seqs:
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (args.batch, S, args.heads, args.head_dim), jnp.bfloat16)
        k = jax.random.normal(ks[1], q.shape, jnp.bfloat16)
        v = jax.random.normal(ks[2], q.shape, jnp.bfloat16)
        t_dense = measure(dense_attention, q, k, v, args.iters)
        row = f"S={S:6d}  dense {t_dense * 1e3:8.3f} ms"
        if _flash_available():
            t_flash = measure(flash_attention, q, k, v, args.iters)
            row += f"  flash {t_flash * 1e3:8.3f} ms  winner: {'flash' if t_flash < t_dense else 'dense'}"
            if crossover is None and t_flash < t_dense:
                crossover = S
        print(row)
    if crossover is not None:
        print(f"suggested _FLASH_CROSSOVER[{kind!r}] = {crossover}")


if __name__ == "__main__":
    main()
