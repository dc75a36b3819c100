"""Attribute the MoE layer's time at the op level on the real chip.

Times fwd+bwd of each piece at the bench shape (E8 k2 h1024 i2816, T=B*S
tokens) so the gap between the einsum path's measured active-MFU and the
routing-free ceiling can be assigned to (a) expert matmuls themselves,
(b) dispatch/combine matmuls, (c) routing front-end, (d) the sorted path's
gather/permute glue vs lax.ragged_dot proper. Prints one JSON line per probe.

Usage: python benchmarks/moe_op_attribution.py  (runs on the default backend)
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from accelerate_tpu.ops import moe as M

E, K, H, I = 8, 2, 1024, 2816
B, S = 16, 1024
T = B * S
DTYPE = jnp.bfloat16
STEPS, WARMUP = 30, 5


def bench(name, fn, *args, flops=None):
    f = jax.jit(jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=0))
    for _ in range(WARMUP):
        out = f(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    np.asarray(jax.tree_util.tree_leaves(out)[0][..., 0:1])  # fetch: ends warm-up
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = f(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0][..., 0:1])
    dt = (time.perf_counter() - t0) / STEPS
    rec = {"probe": name, "ms": round(dt * 1e3, 3)}
    if flops:
        rec["tflops_s"] = round(flops / dt / 1e12, 1)
    print(json.dumps(rec))
    return dt


def main():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, H)), DTYPE)
    router_w = jnp.asarray(rng.standard_normal((H, E)) * 0.02, jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((E, H, I)) * 0.02, DTYPE)
    w_up = jnp.asarray(rng.standard_normal((E, H, I)) * 0.02, DTYPE)
    w_down = jnp.asarray(rng.standard_normal((E, I, H)) * 0.02, DTYPE)

    # fwd+bwd matmul FLOPs for T*K claim rows through the 3 expert matmuls
    expert_flops = 3 * 2 * (T * K) * H * I * 3  # x2 bwd => x3 total

    # (a) the three ragged_dot matmuls on PRE-SORTED contiguous rows, balanced
    # groups — lax.ragged_dot with zero routing/glue.
    sorted_rows = jnp.asarray(rng.standard_normal((T * K, H)), DTYPE)
    group_sizes = jnp.full((E,), T * K // E, jnp.int32)

    def ragged_only(rows):
        rd = lambda lhs, rhs: jax.lax.ragged_dot(lhs, rhs, group_sizes)
        return rd(jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up), w_down)

    bench("ragged_dot_3mm_presorted", ragged_only, sorted_rows, flops=expert_flops)

    # (b) the SAME three matmuls as dense per-expert einsums on capacity slots
    # shaped (E, B, C, H) with C = T*K/(B*E) (cf=1.0 equivalent, no padding).
    C = T * K // (B * E)
    slots = jnp.asarray(rng.standard_normal((E, B, C, H)), DTYPE)

    def dense_expert(slots):
        g = jax.nn.silu(jnp.einsum("ebch,ehi->ebci", slots, w_gate))
        u = jnp.einsum("ebch,ehi->ebci", slots, w_up)
        return jnp.einsum("ebci,eih->ebch", g * u, w_down)

    bench("dense_expert_3mm_slots", dense_expert, slots, flops=expert_flops)

    # (c) full layers, each back-end (fwd+bwd), cf=1.0.
    for name, fn in (("einsum", M.moe_ffn_einsum), ("sorted", M.moe_ffn_sorted),
                     ("indexed", M.moe_ffn_indexed)):
        bench(
            f"layer_{name}_cf1.0",
            lambda x, f=fn: f(x, router_w, w_gate, w_up, w_down,
                              k=K, capacity_factor=1.0)[0],
            x, flops=expert_flops,
        )

    # (d) routing front-end alone (softmax/top-k/cumsum/one-hot, no experts).
    def routing_only(x):
        logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
        d, c, aux = M.top_k_routing(logits, K, M.router_capacity(S, E, K, 1.0),
                                    dtype=x.dtype)
        return d.sum() + c.sum() + aux

    bench("routing_frontend_only", routing_only, x)

    # (e) dense FFN with k*i width — the routing-free active-FLOPs equivalent.
    wg2 = jnp.asarray(rng.standard_normal((H, K * I)) * 0.02, DTYPE)
    wu2 = jnp.asarray(rng.standard_normal((H, K * I)) * 0.02, DTYPE)
    wd2 = jnp.asarray(rng.standard_normal((K * I, H)) * 0.02, DTYPE)

    def dense_ffn(x):
        return (jax.nn.silu(x @ wg2) * (x @ wu2)) @ wd2

    bench("dense_ffn_k_times_i", dense_ffn, x, flops=expert_flops)


if __name__ == "__main__":
    main()
