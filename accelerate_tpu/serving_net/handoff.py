"""Prefill/decode disaggregation — ship a finished KV chain between hosts.

Why this is possible at all: rope/wpe rotations are baked into K at write
time from the per-row position channel, so a chain's K/V blocks are a pure
function of (params, token prefix) — the same property that makes blocks
shareable across requests (serving.py) makes them TRANSFERABLE across
processes. A prefill host runs chunked prefill to completion
(:func:`run_prefill_only`), :func:`export_chain` lifts the written blocks
plus the slot's armed decode state into a JSON-safe payload, and
:func:`import_chain` splices both into a decode host's pool via block-table
surgery. Greedy decode then continues bit-identically to a single host that
ran the whole request (pinned by test_utils/disagg_script.py): the decode
program only ever sees (pool contents, table, state), never who wrote them.

The transfer is bounded: only the ``ceil(slot_len / block_size)`` blocks the
chain actually WROTE travel (the worst-case reservation's unwritten decode
tail is re-reserved from the importer's free list, so admission stays the
only capacity decision point on both hosts). Stale bits in the written
blocks' bucket-padding holes ride along mask-invalid, exactly as they sit in
the exporter's pool.

Clock discipline: ``time.monotonic`` is per-process, so the payload carries
WALL-clock submit/export times; the importer rebases them onto its own
monotonic clock. The router-assigned rid rides every leg, so the per-tier
tracer records (prefill: submit→chunks→handoff out; decode: handoff
in→windows→finish) join into one cross-host trace by rid.
"""

from __future__ import annotations

import base64
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.paged_attention import export_chain_blocks, import_chain_blocks
from ..utils.transfer import host_fetch, host_view

PAYLOAD_VERSION = 1

_HANDOFF_COUNTERS = None  # telemetry.metrics.cached_handles accessor


def _handoff_counters():
    """(bytes, chains, blocks) counters, labeled by transfer direction — the
    series /fleet rolls up into per-tier handoff traffic and the
    BENCH_SERVING_DISAGG lever snapshots into ``detail.serving.routing``."""
    global _HANDOFF_COUNTERS
    if _HANDOFF_COUNTERS is None:
        from ..telemetry.metrics import cached_handles

        _HANDOFF_COUNTERS = cached_handles(lambda registry: (
            registry.counter(
                "accelerate_serving_handoff_bytes_total",
                "KV chain bytes transferred between serving tiers",
                labelnames=("direction",),
            ),
            registry.counter(
                "accelerate_serving_handoff_chains_total",
                "KV chains transferred between serving tiers",
                labelnames=("direction",),
            ),
            registry.counter(
                "accelerate_serving_handoff_blocks_total",
                "KV blocks transferred between serving tiers",
                labelnames=("direction",),
            ),
        ))
    return _HANDOFF_COUNTERS()


def _book_handoff(direction: str, nbytes: int, blocks: int,
                  rid: int | None = None):
    counter_bytes, counter_chains, counter_blocks = _handoff_counters()
    counter_bytes.inc(int(nbytes), direction=direction)
    counter_chains.inc(direction=direction)
    counter_blocks.inc(int(blocks), direction=direction)
    # Durable wire-level leg (telemetry/journal.py): tracer-less engines
    # (relay tiers) still land their handoff legs in the per-host journal,
    # so a fleet timeline shows chain movement even where no RequestTracer
    # is attached. No-op when journaling is off.
    from ..telemetry.journal import journal_event

    journal_event("handoff_wire", rid=rid, direction=str(direction),
                  bytes=int(nbytes), blocks=int(blocks))


# ------------------------------------------------------------ wire encoding
def _encode(arr) -> dict:
    # host_view: a device-resident chain fetches counted; host data passes.
    arr = host_view(arr)
    return {
        "dtype": str(arr.dtype),
        "shape": list(arr.shape),
        "data": base64.b64encode(arr.tobytes()).decode("ascii"),
    }


def _decode(enc) -> np.ndarray:
    raw = base64.b64decode(enc["data"])
    # bfloat16 round-trips through ml_dtypes' registered numpy dtype (jax
    # registers it at import, so np.dtype("bfloat16") resolves here).
    arr = np.frombuffer(raw, dtype=np.dtype(enc["dtype"]))
    return arr.reshape(enc["shape"]).copy()


def _chain_nbytes(chain: dict) -> int:
    return sum(len(base64.b64decode(enc["data"])) for enc in chain.values())


# ----------------------------------------------------------------- prefill
def run_prefill_only(engine, rid: int) -> None:
    """Drive the paged engine's admission + chunk dispatch until request
    ``rid``'s prefill completes (its slot arms for decode) — WITHOUT ever
    dispatching a decode window. The prefill tier's engine loop: other
    admitted requests' chunks interleave in submit order exactly as the
    unified loop would run them, so prefill-host chunk traces match the
    single-host dispatch discipline."""
    state = engine._state_tuple()
    while True:
        target = next(
            (s for s in range(engine.B)
             if engine._slot_req[s] is not None
             and engine._slot_req[s].rid == rid),
            None,
        )
        if target is not None and engine._slot_mode[target] == "decode":
            return
        now = time.monotonic()
        engine._admit_paged(now)
        # window_pace=None: no decode runs here, so TPOT pacing (which would
        # defer chunks in decode's favor) has nothing to protect.
        s = engine._pick_chunk_slot(now, None)
        if s is None:
            if target is None and not any(
                q.rid == rid for q in engine._queue
            ):
                raise KeyError(f"request {rid} is not queued or in flight")
            if target is None:
                # Queued but unadmittable and no chunks left to dispatch:
                # every in-flight slot is armed-for-decode deadweight this
                # loop will never retire. The caller must export those
                # chains (freeing their blocks) before retrying.
                raise RuntimeError(
                    f"prefill tier stalled: request {rid} cannot admit "
                    f"({len(engine._free_blocks)} of {engine.num_blocks} "
                    "blocks free) and no prefill work remains; export "
                    "finished chains to free capacity."
                )
            continue
        state = engine._dispatch_chunk(s, state)


def _pool_layout(engine) -> dict:
    """What both tiers' pools must agree on: the first token-paged entry's
    layers, heads, dim and dtype under the names they always had, and, for a
    model that names its own entries, each entry's ``[layers, heads, dim,
    dtype]``."""
    first = engine._pool[engine._by_token[0]]
    layout = {
        "layers": int(first.shape[0]), "kv_heads": int(first.shape[3]),
        "head_dim": int(first.shape[4]), "block_size": engine.block_size,
        "dtype": str(np.dtype(first.dtype).name),
    }
    if engine._by_token != ("k", "v"):
        layout["by_token"] = {
            name: [int(x.shape[0]), int(x.shape[3]), int(x.shape[4]), str(np.dtype(x.dtype).name)]
            for name, x in ((name, engine._pool[name]) for name in engine._by_token)}
    return layout


# ------------------------------------------------------------------ export
def export_chain(engine, rid: int, endpoint: str | None = None,
                 free: bool = True) -> dict:
    """Lift request ``rid``'s finished prefill off ``engine``: the written
    chain blocks' contents, the slot's armed decode state, and the request's
    identity/controls, as one JSON-safe payload. With ``free=True`` the
    chain is refcount-freed here (blocks return to the exporter's pool the
    moment they're copied out); the relay path passes ``free=False`` and
    frees only once the importer ACKS the shipped chain
    (:func:`release_chain`) — free-on-ack, so an import that fails mid-wire
    leaves the chain intact for re-handoff to a surviving decode host. The
    tracer books the ``out`` leg either way, closing this tier's record as
    ``handed_off``."""
    if engine._stateful:
        raise ValueError(
            f"chain export ships a request's key-value blocks; "
            f"{type(engine.module).__name__} also carries recurrent state held by slot, "
            "which the handoff payload does not hold: serve it on one tier (role 'unified')")
    s = next(
        (s for s in range(engine.B)
         if engine._slot_req[s] is not None and engine._slot_req[s].rid == rid),
        None,
    )
    if s is None:
        raise KeyError(f"request {rid} holds no slot (not prefilled yet?)")
    if engine._slot_mode[s] != "decode" or engine._slot_chunks[s]:
        raise RuntimeError(
            f"request {rid} has prefill chunks outstanding; "
            "run_prefill_only() it to completion first"
        )
    req = engine._slot_req[s]
    bs = engine.block_size
    slot_len = int(engine._slot_len[s])
    n_data = -(-slot_len // bs)
    data_ids = engine._slot_blocks[s][:n_data]
    chain = export_chain_blocks(engine._pool, data_ids, engine._by_token)
    chain_enc = {name: _encode(host_fetch(chain[name])) for name in (*engine._by_token, "mask")}
    # One blocking fetch per field is fine here: export is a per-request
    # boundary event, not the steady-state decode loop.
    slot = {
        "tok": int(host_fetch(engine._tok[s])),
        "pos": int(host_fetch(engine._pos[s])),
        "n_out": int(host_fetch(engine._n_out[s])),
        "active": bool(host_fetch(engine._active[s])),
        "out_row": _encode(host_fetch(engine._out_buf[s])),
        "key_data": _encode(host_fetch(jax.random.key_data(engine._keys)[s])),
        "max": int(host_fetch(engine._slot_max[s])),
        "temp": float(host_fetch(engine._slot_temp[s])),
        "eos": int(host_fetch(engine._slot_eos[s])),
        "len": slot_len,
        "base": int(engine._slot_base[s]),
    }
    mono_now, wall_now = time.monotonic(), time.time()
    payload = {
        "version": PAYLOAD_VERSION,
        "rid": int(rid),
        "model": _pool_layout(engine),
        "chain": chain_enc,
        "data_blocks": n_data,
        "reserved_blocks": len(engine._slot_blocks[s]),
        "slot": slot,
        "tokens": _encode(engine._slot_tokens[s]),
        "request": {
            "max_new": int(req.max_new),
            "temperature": float(req.temperature),
            "eos": int(req.eos),
            "stop": [_encode(stop) for stop in req.stop],
        },
        # Wall-clock rebasing: monotonic clocks don't cross processes, so
        # the importer reconstructs submit age from wall time.
        "clock": {
            "wall_submit": wall_now - (mono_now - req.submit_t),
            "wall_export": wall_now,
        },
    }
    nbytes = _chain_nbytes(chain_enc)
    if engine.tracer is not None:
        engine.tracer.handoff(rid, "out", bytes=nbytes, blocks=n_data,
                              endpoint=endpoint)
    _book_handoff("out", nbytes, n_data, rid=rid)
    if free:
        engine.release_request(rid)
    return payload


def release_chain(engine, rid: int) -> bool:
    """Free an exported-but-retained chain (``export_chain(...,
    free=False)``): the importer acked — or every handoff target failed and
    the chain is being abandoned. Idempotent (False when ``rid`` holds no
    slot), so relay error paths can release unconditionally without
    double-free risk."""
    return bool(engine.release_request(rid))


# ------------------------------------------------------------------ import
def import_chain(engine, payload: dict, endpoint: str | None = None) -> int:
    """Splice an exported chain into ``engine``'s pool: re-reserve the full
    worst-case chain from the local free list, write the transferred blocks'
    contents (``ops.paged_attention.import_chain_blocks``), and arm the slot
    with the shipped decode state. After this, ``engine.run()`` decodes the
    request exactly as if the prefill had happened locally. Returns the rid
    (unchanged — router-assigned ids survive every hop)."""
    if payload.get("version") != PAYLOAD_VERSION:
        raise ValueError(
            f"handoff payload version {payload.get('version')!r} != "
            f"{PAYLOAD_VERSION}; tiers must run the same serving build"
        )
    model = payload["model"]
    local = _pool_layout(engine)
    if model != local:
        raise ValueError(
            f"handoff layout mismatch: exporter {model} vs importer {local} "
            "(tiers must share model config, block_size, and cache dtype)"
        )
    rid = int(payload["rid"])
    req_spec = payload["request"]
    if req_spec["max_new"] > engine.max_new:
        raise ValueError(
            f"request max_new {req_spec['max_new']} exceeds the decode "
            f"engine's output buffer ({engine.max_new})"
        )
    reserved = int(payload["reserved_blocks"])
    n_data = int(payload["data_blocks"])
    if reserved > engine.max_blocks_per_slot:
        raise ValueError(
            f"chain reservation {reserved} blocks exceeds the decode "
            f"engine's static table ({engine.max_blocks_per_slot}); raise "
            "max_tokens_per_request to match the prefill tier"
        )
    s = next((s for s in range(engine.B) if engine._slot_mode[s] == "free"), None)
    if s is None:
        raise RuntimeError("no free slot to import into; drain a wave first")
    if reserved > len(engine._free_blocks):
        raise RuntimeError(
            f"KV pool capacity exhausted ({len(engine._free_blocks)} of "
            f"{engine.num_blocks} blocks free; the imported chain needs "
            f"{reserved})"
        )
    fresh = [engine._free_blocks.pop(0) for _ in range(reserved)]
    for blk in fresh:
        engine._block_ref[blk] += 1
    chain = {name: jnp.asarray(_decode(payload["chain"][name]))
             for name in (*engine._by_token, "mask")}
    engine._pool = import_chain_blocks(engine._pool, fresh[:n_data], chain, engine._by_token)
    slot = payload["slot"]
    prompt = _decode(payload["tokens"])
    engine._tables_np[s, :] = 0
    engine._tables_np[s, :reserved] = fresh
    engine._slot_blocks[s] = fresh
    engine._slot_len[s] = int(slot["len"])
    engine._slot_base[s] = int(slot["base"])
    engine._slot_chunks[s] = []
    engine._slot_tokens[s] = prompt
    engine._slot_mode[s] = "decode"
    # Rebase the exporter's wall-clock submit onto this process's monotonic
    # clock, so queue-wait/TTFT attribution spans the whole cross-tier
    # journey (transfer latency included) instead of restarting at import.
    mono_now, wall_now = time.monotonic(), time.time()
    submit_t = mono_now - max(0.0, wall_now - payload["clock"]["wall_submit"])
    from ..serving import _Request

    req = _Request(
        rid, prompt, int(req_spec["max_new"]), float(req_spec["temperature"]),
        int(req_spec["eos"]),
        tuple(_decode(stop) for stop in req_spec["stop"]),
        submit_t,
    )
    engine._slot_req[s] = req
    engine._next_rid = max(engine._next_rid, rid + 1)
    engine._req_times[rid] = {"submit": submit_t}
    out_row = _decode(slot["out_row"])
    if out_row.size < engine.max_new:
        out_row = np.concatenate([
            out_row,
            np.full((engine.max_new - out_row.size,), engine.pad, np.int32),
        ])
    key = jax.random.wrap_key_data(jnp.asarray(_decode(slot["key_data"])))
    engine._tok = engine._tok.at[s].set(slot["tok"])
    engine._pos = engine._pos.at[s].set(slot["pos"])
    engine._n_out = engine._n_out.at[s].set(slot["n_out"])
    engine._active = engine._active.at[s].set(slot["active"])
    engine._out_buf = engine._out_buf.at[s].set(jnp.asarray(out_row[: engine.max_new]))
    engine._keys = engine._keys.at[s].set(key)
    engine._slot_max = engine._slot_max.at[s].set(slot["max"])
    engine._slot_temp = engine._slot_temp.at[s].set(slot["temp"])
    engine._slot_eos = engine._slot_eos.at[s].set(slot["eos"])
    nbytes = _chain_nbytes(payload["chain"])
    if engine.tracer is not None:
        engine.tracer.submit(rid, int(prompt.size), submit_t=submit_t,
                             tier="decode")
        engine.tracer.handoff(rid, "in", bytes=nbytes, blocks=n_data,
                              endpoint=endpoint)
    _book_handoff("in", nbytes, n_data, rid=rid)
    engine._peak_consumed_slots = max(
        engine._peak_consumed_slots, engine.blocks_in_use * engine.block_size
    )
    engine._publish_pool_gauges()
    return rid
