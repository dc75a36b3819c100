"""Program auditor — static inspection of built XLA programs.

GSPMD (arxiv 2105.04663) makes partitioned-program structure statically
inspectable: the collectives the SPMD partitioner inserts, the input–output
aliases donation establishes, and every host round-trip are all visible in the
lowered StableHLO and compiled HLO text before a single chip-second is spent.
This module turns that into a gate: :func:`audit_built` takes a built train
step (or any ``jax.stages.Lowered``-producing artifact) and returns an
:class:`AuditReport` whose detectors encode the framework's program-level
invariants:

- **Collective inventory per mesh axis** — every all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all in the compiled module,
  with its replica groups mapped back onto the mesh's named axes. An
  all-gather whose groups vary along ``dp`` inside the step body means
  dp-replicated data is being re-materialized every step — the exact
  regression the zero-all-gather HLO property (tests/test_analysis.py,
  formerly hand-checked by tests/test_hlo_collectives.py) exists to block.
- **Donation effectiveness** — donated inputs are marked in the StableHLO
  entry signature (``jax.buffer_donor`` / ``tf.aliasing_output``); the
  compiled module's ``input_output_alias`` header says which ones XLA
  actually aliased. The sized difference is ``donation_misses``: buffers the
  caller believes are reused in place but are silently copied every step.
- **Host round-trips** — ``pure_callback`` / ``debug_callback`` /
  ``io_callback`` sites (custom-calls into the Python runtime) serialize the
  device stream against the host; a train step must have none.
- **Dtype upcasts** — dot_generals computing in f32 while the model's compute
  dtype is bf16: each one runs at half the MXU rate the model was cast for.
- **Large per-device intermediates** — instructions in the partitioned
  (per-device) module above a byte threshold; a tensor that should have been
  sharded but stayed replicated shows up here at its full global size.

The parsers work on the textual forms (``lowered.as_text()`` /
``compiled.as_text()``) plus an optional jaxpr walk, so they track what XLA
actually emitted, not what the Python source intended.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

_COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# HLO custom-call targets that re-enter the Python runtime (host callbacks).
_CALLBACK_TARGETS = re.compile(
    r"xla_(?:ffi_)?python_(?:cpu|gpu|tpu)_callback|xla_python_callback"
)

# jaxpr primitives that imply a host round-trip when they survive to the
# compiled program (the jaxpr walk catches them pre-partitioning too).
_CALLBACK_PRIMITIVES = ("pure_callback", "debug_callback", "io_callback", "callback")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


@dataclass
class CollectiveSite:
    """One collective instruction in the compiled (partitioned) module."""

    op: str                    # e.g. "all-gather" ("-start" variants folded in)
    axes: tuple                # mesh axis names whose coordinate varies in-group
    shape: str                 # HLO result shape text, e.g. "f32[16,64]"
    nbytes: int                # per-device result bytes
    source: str = ""           # op_name metadata when present
    # True when this collective is the ZeRO update's deliberate cross-replica
    # traffic (reduce-scatter of grads / all-gather of new params on dp) —
    # attributed by the zero_update/zero_gather_params named scopes riding in
    # op_name, or by an all-gather landing exactly on a param's base shape.
    zero: bool = False

    def to_dict(self) -> dict:
        return {
            "op": self.op,
            "axes": list(self.axes),
            "shape": self.shape,
            "nbytes": self.nbytes,
            "source": self.source,
            "zero": self.zero,
        }


@dataclass
class KernelSite:
    """One named custom kernel in the program — a ``pallas_call`` eqn in the
    jaxpr (pre-partitioning; present in interpret and compiled modes alike)
    and/or its compiled custom-call instruction (``tpu_custom_call`` on TPU —
    interpret-mode lowerings inline to plain HLO, so ``compiled`` stays
    False there). Named inventory is what keeps kernel-backed programs
    inside the zero-sync/fingerprint discipline instead of becoming opaque
    blobs (ROADMAP item 3)."""

    name: str
    count: int = 0            # pallas_call eqns in the jaxpr
    compiled_calls: int = 0   # custom-call instructions in the compiled HLO
    interpret: bool = False   # any eqn lowering via the Pallas interpreter

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "count": self.count,
            "compiled_calls": self.compiled_calls,
            "interpret": self.interpret,
        }


@dataclass
class DonationMiss:
    """A buffer marked for donation that the compiled program does not alias
    (or that an expected-donation contract says should have been donated)."""

    arg_index: int
    shape: str
    nbytes: int
    # "unaliased"    — marked donor the compiled program does not alias;
    # "never-marked" — a declared donation contract with ZERO donor marks;
    # "under-marked" — fewer donor marks than the builder's donated pytrees
    #                  flatten to (a PARTIAL donation regression: some argnums
    #                  dropped from donate_argnums while others remain).
    reason: str

    def to_dict(self) -> dict:
        return {
            "arg_index": self.arg_index,
            "shape": self.shape,
            "nbytes": self.nbytes,
            "reason": self.reason,
        }


@dataclass
class AuditReport:
    """Structured result of one program audit. ``clean`` gates on the three
    zero-tolerance invariants (dp-axis all-gathers, host callbacks, donation
    misses); everything else is inventory for trend tracking."""

    builder: str = "unknown"
    mesh_axes: dict = field(default_factory=dict)        # {axis: size}
    collectives: list = field(default_factory=list)       # [CollectiveSite]
    donated_buffers: int = 0
    aliased_buffers: int = 0
    donation_misses: list = field(default_factory=list)   # [DonationMiss]
    donation_dropped_by_policy: bool = False
    # Whether a ZeRO (cross-replica weight-update sharding) contract was
    # declared for this program — sites it claims carry ``zero=True``.
    zero_sharding: bool = False
    host_callbacks: list = field(default_factory=list)    # [str] descriptions
    # Named custom kernels (Pallas): [KernelSite] — inventory, not a gate.
    kernels: list = field(default_factory=list)
    dtype_upcasts: list = field(default_factory=list)     # [str] dot signatures
    dot_dtypes: dict = field(default_factory=dict)        # {"f32xf32": n, ...}
    large_intermediates: list = field(default_factory=list)  # [dict]
    intermediate_threshold_bytes: int = 0
    # Static memory audit (analysis/memory.py MemoryReport) when the builder's
    # meta carries the donated-pytree class join; None for foreign artifacts.
    # Inventory, not a gate: `clean` stays a program-invariant property.
    memory: object = None

    # ------------------------------------------------------------ inventories
    def collective_counts(self, axis: str | None = None) -> dict:
        """{op: count} over the whole module, or restricted to collectives
        whose replica groups vary along ``axis``. The modern spelling of the
        regex counting tests/test_hlo_collectives.py used to hand-roll."""
        counts = {op: 0 for op in _COLLECTIVE_OPS}
        for site in self.collectives:
            if axis is not None and axis not in site.axes:
                continue
            counts[site.op] = counts.get(site.op, 0) + 1
        return counts

    def collectives_by_axis(self) -> dict:
        """{axis: {op: count}} — the per-mesh-axis inventory."""
        out = {}
        for site in self.collectives:
            for axis in site.axes:
                out.setdefault(axis, {})
                out[axis][site.op] = out[axis].get(site.op, 0) + 1
        return out

    def kernel_counts(self) -> dict:
        """{kernel name: pallas_call count} — the named-kernel inventory."""
        return {k.name: k.count for k in self.kernels}

    def zero_collective_counts(self) -> dict:
        """{op: count} over the ZeRO update's claimed dp traffic."""
        counts: dict = {}
        for site in self.zero_collectives:
            counts[site.op] = counts.get(site.op, 0) + 1
        return counts

    @property
    def zero_collectives(self) -> list:
        """The ZeRO update's deliberate cross-replica traffic: the dp
        collectives the declared contract claimed (reduce-scatter of grads,
        all-gather of new params, the decomposed all-reduce forms). Inventory,
        not violations — the 1/dp opt-state savings are bought with exactly
        this traffic, and the bench carries it per JSON line so the added
        bytes are visible round-over-round."""
        return [s for s in self.collectives if s.zero]

    @property
    def dp_allgathers(self) -> list:
        """All-gathers whose replica groups vary along the ``dp`` axis — the
        flagged zero-sync violation: dp-replicated data re-materialized inside
        the step body every step. The ZeRO update's declared post-update
        param gather is deliberate traffic (``zero_collectives``), not a
        violation — forward/backward must still be dp-allgather-free."""
        return [
            s for s in self.collectives
            if s.op == "all-gather" and "dp" in s.axes and not s.zero
        ]

    @property
    def clean(self) -> bool:
        return (
            not self.dp_allgathers
            and not self.host_callbacks
            and not self.donation_misses
        )

    def to_dict(self) -> dict:
        return {
            "builder": self.builder,
            "clean": self.clean,
            "mesh_axes": dict(self.mesh_axes),
            "collectives": {
                "total": self.collective_counts(),
                "by_axis": self.collectives_by_axis(),
                "sites": [s.to_dict() for s in self.collectives],
            },
            "dp_allgathers": len(self.dp_allgathers),
            "zero_sharding": self.zero_sharding,
            "zero_collectives": self.zero_collective_counts(),
            "donation": {
                "donated_buffers": self.donated_buffers,
                "aliased_buffers": self.aliased_buffers,
                "misses": [m.to_dict() for m in self.donation_misses],
                "dropped_by_policy": self.donation_dropped_by_policy,
            },
            "host_callbacks": list(self.host_callbacks),
            "kernels": [k.to_dict() for k in self.kernels],
            "dtype_upcasts": list(self.dtype_upcasts),
            "dot_dtypes": dict(self.dot_dtypes),
            "large_intermediates": list(self.large_intermediates),
            "intermediate_threshold_bytes": self.intermediate_threshold_bytes,
            "memory": self.memory.to_dict() if self.memory is not None else None,
        }

    def summary_dict(self) -> dict:
        """Compact form for bench.py's ``detail.audit`` — counts, not sites."""
        return {
            "clean": self.clean,
            "dp_allgathers": len(self.dp_allgathers),
            "zero_sharding": self.zero_sharding,
            "zero_collectives": self.zero_collective_counts(),
            "host_callbacks": len(self.host_callbacks),
            "donation_misses": len(self.donation_misses),
            "donation_dropped_by_policy": self.donation_dropped_by_policy,
            "collectives_by_axis": self.collectives_by_axis(),
            "kernels": self.kernel_counts(),
            "dtype_upcasts": len(self.dtype_upcasts),
        }


# ------------------------------------------------------------------ HLO parse
def _shape_nbytes(shape_text: str) -> int:
    """Bytes of an HLO shape like ``f32[16,64]`` (0 for tuples/opaque)."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_text)
    if not m:
        return 0
    dtype, dims = m.groups()
    size = _DTYPE_BYTES.get(dtype, 0)
    if not size:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * size


def _parse_replica_groups(attr_text: str) -> list | None:
    """Parse an HLO ``replica_groups=`` attribute into a list of id-groups.

    Two textual forms exist:

    - explicit: ``{{0,2,4,6},{1,3,5,7}}``
    - iota: ``[2,4]<=[8]`` or ``[2,4]<=[4,2]T(1,0)`` — reshape the (optionally
      transposed) iota over all participants into (groups, group_size).

    Returns None for an empty ``{}`` (= one group of every participant).
    """
    attr_text = attr_text.strip()
    if attr_text.startswith("{"):
        inner = attr_text.strip("{}")
        if not inner.strip():
            return None
        groups = []
        for grp in re.findall(r"\{([0-9, ]*)\}", attr_text):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x != ""]
            if ids:
                groups.append(ids)
        return groups or None
    m = re.match(
        r"\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", attr_text
    )
    if not m:
        return None
    n_groups, group_size, reshape_dims, perm = m.groups()
    dims = [int(d) for d in reshape_dims.split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm:
        ids = ids.transpose([int(p) for p in perm.split(",")])
    ids = ids.reshape(int(n_groups), int(group_size))
    return [list(map(int, row)) for row in ids]


def _axes_varying(groups: list | None, mesh_shape: tuple, axis_names: tuple) -> tuple:
    """Which mesh axes have differing coordinates inside a replica group.

    Participant ids are positions in the module's device assignment, which jax
    builds from the mesh's flattened device order — so coordinates are just
    ``unravel_index(id, mesh_shape)``. An empty/absent group list means every
    participant is in one group (all axes vary, if they have size > 1).
    """
    if not axis_names:
        return ()
    if groups is None:
        return tuple(a for a, s in zip(axis_names, mesh_shape) if s > 1)
    varying = set()
    for group in groups:
        coords = np.array([np.unravel_index(i, mesh_shape) for i in group])
        for k, axis in enumerate(axis_names):
            if len(set(coords[:, k].tolist())) > 1:
                varying.add(axis)
    return tuple(a for a in axis_names if a in varying)


_RG_ATTR = re.compile(
    r"replica_groups=(\{\{[0-9,\s{}]*\}\}|\{\}|"
    r"\[\d+,\d+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)"
)


def _parse_collectives(hlo_text: str, mesh_shape: tuple, axis_names: tuple) -> list:
    sites = []
    for line in hlo_text.splitlines():
        s = line.strip()
        # Result may be a plain shape (f32[16,64]{1,0}) or a tuple for
        # variadic collectives ((f32[], f32[])); "-start" halves of async
        # pairs fold into the base op, "-done" halves (no replica_groups)
        # are skipped so each collective counts once.
        m = re.match(
            r"(?:ROOT )?%?[\w.\-]+ = (\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*) "
            r"(" + "|".join(_COLLECTIVE_OPS) + r")(-start)?\(",
            s,
        )
        if not m:
            continue
        shape_text, op, _start = m.groups()
        nbytes = sum(
            _shape_nbytes(part)
            for part in re.findall(r"[a-z0-9]+\[[0-9,]*\]", shape_text)
        )
        rg = _RG_ATTR.search(s)
        groups = _parse_replica_groups(rg.group(1)) if rg else None
        axes = _axes_varying(groups, mesh_shape, axis_names)
        src = ""
        meta = re.search(r'op_name="([^"]*)"', s)
        if meta:
            src = meta.group(1)
        sites.append(CollectiveSite(
            op=op, axes=axes, shape=re.sub(r"\{[0-9,]*\}$", "", shape_text),
            nbytes=nbytes, source=src,
        ))
    return sites


# Named scopes the builders wrap the ZeRO update region in; GSPMD-inserted
# collectives inherit the scope path in their op_name metadata.
_ZERO_SCOPE = re.compile(r"(?:^|/)zero_(?:update|gather_params|scatter_grads)\b")

# numpy dtype name -> HLO shape-text dtype, mirroring the parse direction in
# _DTYPE_BYTES/_shape_nbytes above. Produced and consumed in THIS module so
# the shape-text convention cannot drift between the two.
_NP_TO_HLO_DTYPE = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float64": "f64", "int32": "s32", "int64": "s64", "int8": "s8",
    "uint32": "u32", "uint8": "u8", "bool": "pred",
}


def zero_gather_shapes(params, shardings, mesh) -> list:
    """Per-device HLO result-shape texts of a ZeRO update's dp all-gathers:
    each param at its BASE layout (global dims divided by whatever non-dp
    axes the base spec shards), rendered in the same ``f32[16,64]`` form
    :func:`_parse_collectives` records for ``CollectiveSite.shape``. The
    builders put these in their audit meta as the shape-match fallback for
    attributing ZeRO traffic on backends that strip op_name metadata."""
    import jax

    mesh_axes = dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))
    shapes = set()
    shard_leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda s: hasattr(s, "spec")
    )
    for leaf, sharding in zip(jax.tree_util.tree_leaves(params), shard_leaves):
        shape = tuple(np.shape(leaf))
        if not shape:
            continue
        spec = tuple(getattr(sharding, "spec", ()) or ())
        dims = []
        for dim, axes in zip(shape, spec + (None,) * (len(shape) - len(spec))):
            div = 1
            for ax in (axes if isinstance(axes, (tuple, list)) else (axes,)):
                if ax is not None and ax != "dp":
                    div *= int(mesh_axes.get(ax, 1))
            dims.append(-(-dim // div))
        dtype = _NP_TO_HLO_DTYPE.get(str(np.dtype(leaf.dtype)))
        if dtype is not None:
            shapes.add(f"{dtype}[{','.join(str(d) for d in dims)}]")
    return sorted(shapes)


def _classify_zero_collectives(sites: list, zero_meta: dict) -> None:
    """Mark the ZeRO update's deliberate cross-replica traffic.

    Primary signal: the ``zero_update``/``zero_gather_params`` named scopes
    riding in op_name metadata. Fallback — ONLY for sites with no op_name at
    all (backends that strip metadata): an all-gather on the declared axis
    whose per-device result shape is exactly a param's base layout. A site
    that HAS metadata but no zero scope is never claimed: a genuine forward
    re-materialization of params lands on exactly these shapes too, and
    claiming it would mask the very violation the dp-allgather gate exists
    to catch."""
    axis = zero_meta.get("axis", "dp")
    shapes = set(zero_meta.get("param_shapes") or ())
    for site in sites:
        if axis not in site.axes:
            continue
        if _ZERO_SCOPE.search(site.source):
            site.zero = True
        elif not site.source and site.op == "all-gather" and site.shape in shapes:
            site.zero = True


def _parse_donors(stablehlo_text: str) -> tuple:
    """(donor_indices, prealised_indices, {index: (shape, nbytes)}) from the
    StableHLO entry signature: ``jax.buffer_donor = true`` marks a donated
    input whose alias decision is left to XLA; ``tf.aliasing_output = N``
    marks one already aliased at lowering time."""
    m = re.search(r"func\.func public @main\((.*?)\)\s*->", stablehlo_text, re.DOTALL)
    if not m:
        return set(), set(), {}
    donors, prealiased, sizes = set(), set(), {}
    # Arguments look like: %arg0: tensor<64x64xf32> {jax.buffer_donor = true, ...}
    # The attr dict may hold quoted strings containing braces — single-device
    # lowerings spell donation as {mhlo.sharding = "{replicated}",
    # tf.aliasing_output = N : i32}, where a naive [^}]* match stops at the
    # quoted "}" and silently drops the aliasing mark after it (the
    # under-marked false positive on 1-device backends).
    for am in re.finditer(
        r"%arg(\d+):\s*tensor<([^>]*)>\s*"
        r"(\{(?:[^{}\"]|\"[^\"]*\"|\{[^{}]*\})*\})?",
        m.group(1),
    ):
        idx = int(am.group(1))
        tensor = am.group(2)
        attrs = am.group(3) or ""
        parts = tensor.split("x")
        dims = [int(p) for p in parts[:-1] if p.isdigit()]
        dtype = parts[-1]
        nbytes = int(np.prod(dims)) if dims else 1
        nbytes *= {"f32": 4, "f64": 8, "bf16": 2, "f16": 2, "i32": 4,
                   "i64": 8, "i8": 1, "i16": 2, "ui32": 4, "i1": 1}.get(dtype, 4)
        sizes[idx] = (f"tensor<{tensor}>", nbytes)
        if "jax.buffer_donor" in attrs:
            donors.add(idx)
        if "tf.aliasing_output" in attrs:
            prealiased.add(idx)
    return donors, prealiased, sizes


def _parse_aliased_params(hlo_text: str) -> set:
    """Aliased entry-parameter numbers from the compiled module header:
    ``input_output_alias={ {0}: (0, {}, may-alias), ... }``."""
    header = hlo_text.splitlines()[0] if hlo_text else ""
    # One level of brace nesting inside the attribute: { {0}: (0, {}, may-alias), ... }
    m = re.search(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}", header)
    if not m:
        return set()
    return {int(p) for p in re.findall(r"\(\s*(\d+)\s*,", m.group(1))}


def _parse_callbacks(hlo_text: str, stablehlo_text: str) -> list:
    found = []
    for line in hlo_text.splitlines():
        if "custom-call" not in line:
            continue
        tgt = re.search(r'custom_call_target="([^"]+)"', line)
        if not tgt or not _CALLBACK_TARGETS.search(tgt.group(1)):
            continue
        src = re.search(r'op_name="([^"]*)"', line)
        found.append(src.group(1) if src else tgt.group(1))
    if not found:
        # The compiled text on some backends drops metadata; the StableHLO
        # custom_call spelling is version-stable.
        for line in stablehlo_text.splitlines():
            if "stablehlo.custom_call" in line and _CALLBACK_TARGETS.search(line):
                found.append(line.strip().split("{")[0].strip()[:120])
    return found


# Compiled custom-call targets that are Mosaic/Pallas kernel invocations, not
# host callbacks (the _CALLBACK_TARGETS regex requires a python_*_callback
# spelling, so these never misclassify — this is the positive match).
_KERNEL_TARGETS = re.compile(r"tpu_custom_call|mosaic|__gpu\$xla\.gpu\.triton")


def _kernel_name_of_eqn(eqn) -> str:
    """The kernel's bare name from a pallas_call eqn's ``name`` param (no
    source location: fingerprints must not carry file:line churn)."""
    return eqn.params.get("name") or "pallas_kernel"


def _walk_jaxpr_kernels(jaxpr) -> list:
    """Recursive jaxpr walk for ``pallas_call`` eqns → [(name, interpret)].
    The jaxpr-level walk is the backend-independent inventory: interpret-mode
    lowerings inline to plain HLO (no custom-call survives), but the eqn —
    and with it the kernel's NAME — is present in every mode."""
    found = []

    def visit(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(
                    (_kernel_name_of_eqn(eqn), bool(eqn.params.get("interpret")))
                )
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    visit(sub)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


def _parse_kernel_custom_calls(hlo_text: str) -> list:
    """Kernel custom-call instructions in the compiled module → [name]:
    the op_name metadata carries the kernel's scope path when present, else
    the raw custom-call target. Empty for interpret-mode lowerings."""
    found = []
    for line in hlo_text.splitlines():
        if "custom-call" not in line:
            continue
        tgt = re.search(r'custom_call_target="([^"]+)"', line)
        if not tgt or not _KERNEL_TARGETS.search(tgt.group(1)):
            continue
        src = re.search(r'op_name="([^"]*)"', line)
        label = src.group(1) if src else tgt.group(1)
        # op_name scope paths end in the kernel wrapper's name; keep the tail.
        found.append(label.split("/")[-1])
    return found


def _kernel_inventory(jaxpr, hlo_text: str) -> list:
    """Join the jaxpr pallas_call walk with the compiled custom-call census
    into named :class:`KernelSite` rows."""
    sites: dict = {}
    if jaxpr is not None:
        for name, interpret in _walk_jaxpr_kernels(jaxpr):
            site = sites.setdefault(name, KernelSite(name=name))
            site.count += 1
            site.interpret = site.interpret or interpret
    for label in _parse_kernel_custom_calls(hlo_text):
        match = next((s for n, s in sites.items() if n in label), None)
        if match is None:
            match = sites.setdefault(label, KernelSite(name=label))
        match.compiled_calls += 1
    return [sites[n] for n in sorted(sites)]


def _walk_jaxpr_callbacks(jaxpr) -> list:
    """Recursive jaxpr walk for callback primitives — catches host round-trips
    before partitioning (and independently of custom-call target spellings)."""
    found = []

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(cb in name for cb in _CALLBACK_PRIMITIVES):
                found.append(name)
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    visit(sub)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


def _sub_jaxprs(val):
    from jax.extend.core import ClosedJaxpr

    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def _parse_dots(stablehlo_text: str, compute_dtype: str | None) -> tuple:
    """(dot dtype census, upcast sites). A dot whose operands are f32 while
    the model's compute dtype is bf16 runs at half MXU rate — those are the
    flagged upcast sites."""
    census: dict = {}
    upcasts = []
    for m in re.finditer(
        r"stablehlo\.dot_general[^\n]*?:\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)\s*->\s*tensor<([^>]*)>",
        stablehlo_text,
    ):
        lhs, rhs, out = (t.split("x")[-1] for t in m.groups())
        key = f"{lhs}x{rhs}->{out}"
        census[key] = census.get(key, 0) + 1
        if compute_dtype in ("bf16", "bfloat16") and lhs == "f32" and rhs == "f32":
            upcasts.append(m.group(0).split(":")[0].strip()[:120] + f" ({key})")
    return census, upcasts


def _parse_large_intermediates(hlo_text: str, threshold_bytes: int) -> list:
    """Per-device instructions above the byte threshold in the partitioned
    module, largest first (top 10). Sizes are PER DEVICE after partitioning:
    an intermediate that should have been sharded but stayed replicated shows
    up here at its full global size."""
    out = []
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT )?%?([\w.\-]+) = ([a-z0-9]+\[[0-9,]*\])\S* ([\w\-]+)\(", s)
        if not m:
            continue
        name, shape_text, op = m.groups()
        if op in ("parameter", "constant"):
            continue
        nbytes = _shape_nbytes(shape_text)
        if nbytes >= threshold_bytes:
            out.append({"name": name, "op": op, "shape": shape_text, "nbytes": nbytes})
    out.sort(key=lambda d: -d["nbytes"])
    return out[:10]


# ------------------------------------------------------------------ front end
def audit_lowered(
    lowered,
    mesh=None,
    expected_donations=None,
    expected_donated_leaves: int | None = None,
    donation_dropped_by_policy: bool = False,
    compute_dtype: str | None = None,
    jaxpr=None,
    builder: str = "unknown",
    intermediate_threshold_bytes: int = 64 * 1024 * 1024,
    zero_sharding: dict | None = None,
) -> AuditReport:
    """Audit any ``jax.stages.Lowered``.

    The donation contract has two layers. ``expected_donations`` names the
    argnums the caller intends to donate: when the lowering carries ZERO
    donor marks yet donation was expected (and NOT dropped by platform
    policy), every expected argnum is a ``never-marked`` miss.
    ``expected_donated_leaves`` is the sharper count a builder can supply —
    how many flat input buffers its donated pytrees flatten to; fewer donor
    marks than that is an ``under-marked`` miss, which catches a PARTIAL
    regression (one argnum dropped from ``donate_argnums`` while others keep
    their marks) that the all-or-nothing check would wave through.
    ``donation_dropped_by_policy`` records ``safe_donate_argnums`` having
    deliberately dropped donation (CPU + persistent compile cache): expected
    donations are then waived, and the report notes the policy instead.
    """
    stablehlo_text = lowered.as_text()
    compiled = lowered.compile()
    hlo_text = compiled.as_text()

    mesh_shape: tuple = ()
    axis_names: tuple = ()
    if mesh is not None and getattr(mesh, "axis_names", None):
        axis_names = tuple(mesh.axis_names)
        mesh_shape = tuple(mesh.devices.shape)

    report = AuditReport(
        builder=builder,
        mesh_axes=dict(zip(axis_names, mesh_shape)),
        intermediate_threshold_bytes=int(intermediate_threshold_bytes),
        donation_dropped_by_policy=bool(donation_dropped_by_policy),
        zero_sharding=bool(zero_sharding),
    )
    report.collectives = _parse_collectives(hlo_text, mesh_shape, axis_names)
    if zero_sharding:
        _classify_zero_collectives(report.collectives, zero_sharding)

    donors, prealiased, sizes = _parse_donors(stablehlo_text)
    aliased = _parse_aliased_params(hlo_text)
    report.donated_buffers = len(donors | prealiased)
    report.aliased_buffers = len(aliased | prealiased)
    for idx in sorted(donors - aliased - prealiased):
        shape, nbytes = sizes.get(idx, ("?", 0))
        report.donation_misses.append(
            DonationMiss(arg_index=idx, shape=shape, nbytes=nbytes, reason="unaliased")
        )
    marked = len(donors | prealiased)
    if expected_donations and not donation_dropped_by_policy and marked == 0:
        for idx in sorted(set(int(i) for i in expected_donations)):
            shape, nbytes = sizes.get(idx, ("?", 0))
            report.donation_misses.append(
                DonationMiss(arg_index=idx, shape=shape, nbytes=nbytes,
                             reason="never-marked")
            )
    elif (
        expected_donated_leaves
        and not donation_dropped_by_policy
        and 0 < marked < int(expected_donated_leaves)
    ):
        report.donation_misses.append(DonationMiss(
            arg_index=-1,
            shape=f"{marked}/{int(expected_donated_leaves)} donated leaves marked",
            nbytes=0,
            reason="under-marked",
        ))

    report.host_callbacks = _parse_callbacks(hlo_text, stablehlo_text)
    if jaxpr is not None:
        for name in _walk_jaxpr_callbacks(jaxpr):
            entry = f"jaxpr:{name}"
            if entry not in report.host_callbacks:
                report.host_callbacks.append(entry)
    report.kernels = _kernel_inventory(jaxpr, hlo_text)

    report.dot_dtypes, report.dtype_upcasts = _parse_dots(stablehlo_text, compute_dtype)
    report.large_intermediates = _parse_large_intermediates(
        hlo_text, intermediate_threshold_bytes
    )
    # Stashed (non-field) so audit_built's memory pass reuses this executable
    # instead of paying a second XLA compile; audit_built pops it so the
    # report does not pin the executable alive for its own lifetime.
    report._compiled = compiled
    # Also stashed (non-field, plain string): the lowered StableHLO, so a
    # fingerprint extraction handed this report (bench, the tune rig) runs
    # its dtype-flow pass without re-tracing and re-lowering the program.
    report._stablehlo_text = stablehlo_text
    return report


def audit_built(built, *args, intermediate_threshold_bytes: int = 64 * 1024 * 1024,
                mesh=None, memory: bool = True, memory_budget_bytes: int | None = None,
                **kwargs) -> AuditReport:
    """Audit a built artifact — anything exposing ``.lower(*args, **kwargs)``
    (the fused builders attach one; a raw jitted function has jax's own).

    Builder metadata (``_audit_meta`` set by ``build_train_step`` /
    ``build_train_window``) supplies the mesh, the donation contract, the
    compute dtype, and a jaxpr thunk; for foreign artifacts the audit runs on
    the textual forms alone. When the meta also carries the donated-pytree
    class join (``memory_classes``) and ``memory`` is left on, the report's
    ``memory`` field is the static HBM audit (analysis/memory.py) computed
    from the SAME lowering and executable — no second compile.
    """
    lower = getattr(built, "lower", None)
    if lower is None:
        raise TypeError(
            f"{built!r} has no .lower(...); pass a built train step/window or "
            "a jitted function, or lower it yourself and call audit_lowered."
        )
    meta = getattr(built, "_audit_meta", None) or {}
    lowered = lower(*args, **kwargs)
    jaxpr = None
    jaxpr_thunk = meta.get("jaxpr_thunk")
    if jaxpr_thunk is not None:
        try:
            jaxpr = jaxpr_thunk(*args, **kwargs)
        except Exception:
            jaxpr = None
    report = audit_lowered(
        lowered,
        mesh=meta.get("mesh", mesh),
        expected_donations=meta.get("expected_donations"),
        expected_donated_leaves=meta.get("expected_donated_leaves"),
        donation_dropped_by_policy=meta.get("donation_dropped_by_policy", False),
        compute_dtype=meta.get("compute_dtype"),
        jaxpr=jaxpr,
        builder=meta.get("builder", getattr(built, "__name__", "unknown")),
        intermediate_threshold_bytes=intermediate_threshold_bytes,
        zero_sharding=meta.get("zero_sharding"),
    )
    compiled = report.__dict__.pop("_compiled", None)
    if memory and meta.get("memory_classes"):
        from .memory import memory_report_from_lowered

        report.memory = memory_report_from_lowered(
            lowered, meta=meta, mesh=meta.get("mesh", mesh),
            compiled=compiled, budget_bytes=memory_budget_bytes,
        )
    return report
