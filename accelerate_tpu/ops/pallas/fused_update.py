"""Fused optimizer-update Pallas kernel — clip + moments + apply + cast, one pass.

``_fused_step_body``'s update region (``_upd_math``) is a chain of small
elementwise passes over every parameter leaf: scale by the clip factor, the
optax moment updates, bias correction, the update rule, weight decay, the
learning-rate scale, and ``apply_updates``'s cast back to the param dtype —
plus the accumulation-buffer zero-reset. On the reference path each is its
own HBM round-trip per leaf; with ZeRO active the chain runs on the 1/dp
shard between the reduce-scatter and the param all-gather, which is exactly
the window ``--xla_preset latency`` must hide (arxiv 2004.13336) — every
pass shortened here widens the overlap budget.

This module fuses the whole per-leaf chain into ONE ``pallas_call`` (param +
moments + grad stream in, param' + moments' + zeroed-buffer stream out):

- :func:`plan_fused_update` inspects an ``optax.GradientTransformation``'s
  closure chain and recovers the exact hyperparameters for the supported
  families — ``sgd`` (with or without classic momentum), ``adam``,
  ``adamw``. Anything else (schedules, nesterov, masks, custom chains)
  returns None and the reference path runs — the registry's clean-fallback
  contract, per optimizer instance.
- :func:`fused_update_apply` runs the kernel per leaf, mirroring optax's op
  order **exactly** (``(1-b)*g + b*m`` moment form, ``1 - decay**count``
  bias correction computed outside the kernel in the same precision,
  ``m / (sqrt(v + eps_root) + eps)``, ``g + wd*p``, ``-lr * u``,
  ``(p + u).astype(p.dtype)``): interpret mode is bit-exact against
  ``tx.update`` + ``optax.apply_updates`` by construction — the windowed
  ZeRO parity drill in tests/test_kernels.py pins it.

The cross-leaf global-norm clip *factor* is computed by the caller (it is a
tree-wide reduction; the kernel is per-leaf) and fused into the first
elementwise pass, identically to the reference's ``g * factor`` pre-scale.
Leaves are flattened and padded to (rows, 128) lanes; padding lanes compute
garbage that is sliced off before reshape (never NaN-propagating into real
lanes — elementwise math only). Under ZeRO the caller invokes this inside
the ``zero_update``-constrained region, so the kernel body lowers on the
dp-sharded values (shard-local math under GSPMD; see
``parallel/sharding.local_leaf_shape`` for the per-device shapes the cost
model uses).
"""

from __future__ import annotations

import inspect
import logging
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..registry import _WARNED, register_op

logger = logging.getLogger(__name__)

_LANES = 128
_MAX_BLOCK_ROWS = 512


# ------------------------------------------------------------------ planning
@dataclass(frozen=True)
class FusedUpdatePlan:
    """The recovered optimizer family + hyperparameters and where its state
    lives in the chain's state tuple. ``kind``: sgd | sgd_momentum | adam
    (adamw = adam with ``weight_decay`` not None)."""

    kind: str
    step_size: float
    b1: float = 0.0
    b2: float = 0.0
    eps: float = 0.0
    eps_root: float = 0.0
    weight_decay: float | None = None
    momentum: float = 0.0
    state_index: int | None = None  # chain position of ScaleByAdamState/TraceState

    def describe(self) -> str:
        wd = self.weight_decay is not None
        return {"adam": "adamw" if wd else "adam"}.get(self.kind, self.kind)


def _inner_update_fns(tx):
    """The chain's inner update fns (unwrapping with_extra_args_support)."""
    try:
        cells = inspect.getclosurevars(tx.update).nonlocals
    except TypeError:
        return None
    fns = cells.get("update_fns")
    if fns is None:
        return None
    out = []
    for f in fns:
        try:
            inner = inspect.getclosurevars(f).nonlocals.get("tx")
        except TypeError:
            inner = None
        out.append(inner.update if inner is not None else f)
    return out


def plan_fused_update(tx) -> FusedUpdatePlan | None:
    """Match ``tx`` against the supported optax constructions; None = run the
    reference path (unsupported chains are a fallback, never an error). Every
    caller asks only when the ``fused_update`` kernel was requested, so a
    None is logged once: the kernel that was asked for is not what runs."""
    plan = _match_fused_update(tx)
    if plan is None and "fused_update:unplanned" not in _WARNED:
        _WARNED.add("fused_update:unplanned")
        logger.warning(
            "kernels: fused_update was requested but this optimizer is not one "
            "the kernel knows (adam, adamw, sgd with a constant step size); "
            "its update runs on the reference lowering."
        )
    return plan


def _match_fused_update(tx) -> FusedUpdatePlan | None:
    fns = _inner_update_fns(tx)
    if not fns:
        return None
    kind = "sgd"
    hp: dict = {}
    state_index = None
    saw_scale = False
    for i, fn in enumerate(fns):
        qual = getattr(fn, "__qualname__", "")
        try:
            nl = inspect.getclosurevars(fn).nonlocals
        except TypeError:
            return None
        if qual.startswith("identity."):
            continue
        if qual.startswith("scale_by_adam."):
            if kind != "sgd" or saw_scale or nl.get("nesterov") or nl.get("mu_dtype") is not None:
                return None
            kind = "adam"
            state_index = i
            hp.update(b1=float(nl["b1"]), b2=float(nl["b2"]),
                      eps=float(nl["eps"]), eps_root=float(nl["eps_root"]))
            continue
        if qual.startswith("trace."):
            if kind != "sgd" or saw_scale or nl.get("nesterov") or nl.get("accumulator_dtype") is not None:
                return None
            kind = "sgd_momentum"
            state_index = i
            hp.update(momentum=float(nl["decay"]))
            continue
        if qual.startswith("add_decayed_weights."):
            if kind != "adam" or saw_scale or "weight_decay" not in nl:
                return None
            hp.update(weight_decay=float(nl["weight_decay"]))
            continue
        if qual.startswith("scale."):
            if saw_scale or not isinstance(nl.get("step_size"), (int, float)):
                return None
            saw_scale = True
            hp.update(step_size=float(nl["step_size"]))
            continue
        return None  # schedules, masks, anything unrecognized
    if not saw_scale:
        return None
    return FusedUpdatePlan(kind=kind, state_index=state_index, **hp)


# ------------------------------------------------------------------ leaf math
def _leaf_math(plan: FusedUpdatePlan, zero_buffer: bool = True):
    """The per-leaf elementwise chain, mirroring optax op-for-op. Returns a
    function of (p, g, factor, *extras) -> (p'[, zero], *new_extras).
    ``zero_buffer=False`` omits the zeroed accumulation-buffer output — the
    imperative path has no buffer to reset, and an unused pallas output is
    still a full grads-sized HBM write on the compiled path."""

    def _zero_out(g):
        return (jnp.zeros_like(g),) if zero_buffer else ()

    def adam(p, mu, nu, g, factor, bc1, bc2):
        g = g * factor
        new_mu = (1 - plan.b1) * (g ** 1) + plan.b1 * mu
        new_nu = (1 - plan.b2) * (g ** 2) + plan.b2 * nu
        mu_hat = new_mu / bc1.astype(new_mu.dtype)
        nu_hat = new_nu / bc2.astype(new_nu.dtype)
        u = mu_hat / (jnp.sqrt(nu_hat + plan.eps_root) + plan.eps)
        if plan.weight_decay is not None:
            u = u + plan.weight_decay * p
        u = plan.step_size * u
        new_p = (p + u).astype(p.dtype)
        return (new_p,) + _zero_out(g) + (new_mu, new_nu)

    def sgd(p, g, factor):
        g = g * factor
        u = plan.step_size * g
        new_p = (p + u).astype(p.dtype)
        return (new_p,) + _zero_out(g)

    def sgd_momentum(p, trace, g, factor):
        g = g * factor
        new_trace = g + plan.momentum * trace
        u = plan.step_size * new_trace
        new_p = (p + u).astype(p.dtype)
        return (new_p,) + _zero_out(g) + (new_trace,)

    return {"adam": adam, "sgd": sgd, "sgd_momentum": sgd_momentum}[plan.kind]


def _pad_rows(flat, rows, cols):
    pad = rows * cols - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, cols)


def _fused_leaf_call(math_fn, arrays, scalars, interpret: bool,
                     name: str = "fused_update_kernel",
                     local_elems: int | None = None):
    """Run the per-leaf chain as ONE pallas_call over (rows, 128) tiles.

    ``arrays`` are the leaf-shaped operands (p[, moments], g); ``scalars``
    broadcast into every tile via SMEM-style (1, 1) blocks. Output avals are
    taken from an eval_shape of the math itself, so dtype promotion follows
    the reference exactly. ``name`` is the audit/fingerprint-visible kernel
    identity (``fused_<family>_update_kernel``)."""
    shape = np.shape(arrays[0])
    size = int(np.prod(shape)) if shape else 1
    # max(1, ...): a zero-size leaf (empty bias, 0-row optional head) still
    # gets one (padded, all-discarded) tile instead of a 0//0 at trace time —
    # the reference path handles empty leaves, so the kernel lever must too.
    rows = max(1, -(-size // _LANES))
    # Tile rows are capped by the SHARD-local element count when a sharding
    # plan is declared (parallel/sharding.local_leaf_shape): under ZeRO the
    # per-leaf pass covers the 1/dp shard, and a grid block must not span
    # shard boundaries or GSPMD re-materializes the leaf to feed it.
    local_rows = rows if local_elems is None else max(1, -(-int(local_elems) // _LANES))
    block_rows = min(rows, local_rows, _MAX_BLOCK_ROWS)
    grid_rows = -(-rows // block_rows)
    padded_rows = grid_rows * block_rows
    tiles = [_pad_rows(jnp.asarray(a).reshape(-1), padded_rows, _LANES)
             for a in arrays]
    scalars = [jnp.asarray(s).reshape(1, 1) for s in scalars]
    out_avals = jax.eval_shape(
        lambda ts, ss: math_fn(*ts, *[s[0, 0] for s in ss]), tiles, scalars
    )

    n_arr = len(tiles)

    def body(*refs):
        ins, outs = refs[: n_arr + len(scalars)], refs[n_arr + len(scalars):]
        tile_vals = [r[:] for r in ins[:n_arr]]
        scalar_vals = [r[0, 0] for r in ins[n_arr:]]
        results = math_fn(*tile_vals, *scalar_vals)
        for o_ref, val in zip(outs, results):
            o_ref[:] = val.astype(o_ref.dtype)

    grid_spec = pl.GridSpec(
        grid=(grid_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
            for _ in tiles
        ] + [
            pl.BlockSpec((1, 1), lambda i: (0, 0)) for _ in scalars
        ],
        out_specs=tuple(
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
            for _ in out_avals
        ),
    )
    outs = pl.pallas_call(
        body,
        out_shape=tuple(
            jax.ShapeDtypeStruct((padded_rows, _LANES), o.dtype)
            for o in out_avals
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
    )(*tiles, *scalars)
    return tuple(o.reshape(-1)[:size].reshape(shape) for o in outs)


# ------------------------------------------------------------------ front end
def _safe_int32_increment(count):
    max_i32 = jnp.iinfo(jnp.int32).max
    return jnp.where(count < max_i32, count + jnp.array(1, jnp.int32), max_i32)


def fused_update_apply(params, opt_state, grads, *, plan: FusedUpdatePlan,
                       clip_factor, interpret: bool = False, shardings=None,
                       zero_buffer: bool = True):
    """One fused pass per leaf: returns ``(new_params, new_opt_state,
    zeroed_grads)`` matching::

        grads = tree_map(lambda g: g * clip_factor, grads)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        zero = tree_map(zeros_like, grads)

    (float-equivalent across modules, bit-deterministic within one — see
    docs/kernels.md for the exact parity contract). ``shardings`` is the
    caller's per-leaf plan (the ZeRO update-path shardings) used to size
    tile grids to the shard-local leaf, never to change values.
    ``zero_buffer=False`` skips the zeroed-grads output entirely (returns
    None in its slot) — callers with no accumulation buffer to reset (the
    imperative optimizer) must not pay its HBM write."""
    from ...parallel.sharding import local_leaf_shape

    math_fn = _leaf_math(plan, zero_buffer)
    kname = f"fused_{plan.describe()}_update_kernel"
    treedef = jax.tree_util.tree_structure(params)
    p_leaves = jax.tree_util.tree_leaves(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    if shardings is not None:
        s_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda s: hasattr(s, "spec")
        )
        local_elems = [
            int(np.prod(local_leaf_shape(np.shape(p), s)) or 1)
            for p, s in zip(p_leaves, s_leaves)
        ]
    else:
        local_elems = [None] * len(p_leaves)
    states = list(opt_state) if isinstance(opt_state, (tuple, list)) else [opt_state]

    if plan.kind == "adam":
        st = states[plan.state_index]
        count_inc = _safe_int32_increment(st.count)
        # optax.tree_bias_correction computes 1 - decay**count in full
        # precision BEFORE the per-leaf dtype cast — same here, outside the
        # kernel, broadcast into every tile.
        bc1 = 1 - plan.b1 ** count_inc
        bc2 = 1 - plan.b2 ** count_inc
        mu_leaves = jax.tree_util.tree_leaves(st.mu)
        nu_leaves = jax.tree_util.tree_leaves(st.nu)
        new_p, zeros, new_mu, new_nu = [], [], [], []
        for p, mu, nu, g, le in zip(p_leaves, mu_leaves, nu_leaves, g_leaves,
                                    local_elems):
            out = _fused_leaf_call(
                math_fn, (p, mu, nu, g), (clip_factor, bc1, bc2), interpret,
                name=kname, local_elems=le,
            )
            new_p.append(out[0])
            if zero_buffer:
                zeros.append(out[1])
            new_mu.append(out[-2]); new_nu.append(out[-1])
        states[plan.state_index] = st._replace(
            count=count_inc,
            mu=jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(st.mu), new_mu
            ),
            nu=jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(st.nu), new_nu
            ),
        )
    elif plan.kind == "sgd_momentum":
        st = states[plan.state_index]
        tr_leaves = jax.tree_util.tree_leaves(st.trace)
        new_p, zeros, new_tr = [], [], []
        for p, tr, g, le in zip(p_leaves, tr_leaves, g_leaves, local_elems):
            out = _fused_leaf_call(math_fn, (p, tr, g), (clip_factor,),
                                   interpret, name=kname, local_elems=le)
            new_p.append(out[0])
            if zero_buffer:
                zeros.append(out[1])
            new_tr.append(out[-1])
        states[plan.state_index] = st._replace(
            trace=jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(st.trace), new_tr
            )
        )
    else:  # plain sgd
        new_p, zeros = [], []
        for p, g, le in zip(p_leaves, g_leaves, local_elems):
            out = _fused_leaf_call(math_fn, (p, g), (clip_factor,),
                                   interpret, name=kname, local_elems=le)
            new_p.append(out[0])
            if zero_buffer:
                zeros.append(out[1])

    new_params = jax.tree_util.tree_unflatten(treedef, new_p)
    zero_tree = (
        jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(grads), zeros)
        if zero_buffer else None
    )
    new_state = tuple(states) if isinstance(opt_state, (tuple, list)) else states[0]
    return new_params, new_state, zero_tree


def reference_update_apply(params, opt_state, grads, *, tx, clip_factor):
    """The committed reference seam the kernel must match bit-for-bit: the
    exact op sequence of ``_fused_step_body._upd_math`` after the norm."""
    import optax

    grads = jax.tree_util.tree_map(lambda g: g * clip_factor, grads)
    updates, new_opt = tx.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    zero = jax.tree_util.tree_map(jnp.zeros_like, grads)
    return new_params, new_opt, zero


def _kernel_entry(params, opt_state, grads, *, tx=None, plan=None,
                  clip_factor, interpret: bool = False):
    if plan is None:
        plan = plan_fused_update(tx)
    if plan is None:
        return reference_update_apply(
            params, opt_state, grads, tx=tx, clip_factor=clip_factor
        )
    return fused_update_apply(
        params, opt_state, grads, plan=plan, clip_factor=clip_factor,
        interpret=interpret,
    )


def _reference_entry(params, opt_state, grads, *, tx=None, plan=None,
                     clip_factor):
    return reference_update_apply(
        params, opt_state, grads, tx=tx, clip_factor=clip_factor
    )


register_op(
    "fused_update", _reference_entry, _kernel_entry,
    doc="fused clip+moments+apply+cast optimizer update (adam/adamw/sgd)",
)
