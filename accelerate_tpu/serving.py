"""Continuous batching — a slot-based serving engine over a paged KV cache.

The reference serves through transformers' ``generate`` one batch at a time:
a batch runs until its LAST row finishes, so short requests pay for long ones
(head-of-line blocking). ``ContinuousBatcher`` keeps a fixed number of slots
decoding together and refills a slot the moment its sequence finishes — the
scheduling idea of vLLM/Orca, shaped for XLA's static-compilation model:

- **One decode program plus one prefill program per chunk bucket**: the
  decode window covers all B slots at once, and a chunk program prefills one
  slot's prompt chunk at batch 1. No shape ever depends on which requests
  are in flight, so nothing recompiles as traffic changes.
- **A block pool** (ops/paged_attention.py): ``num_blocks`` blocks of
  ``block_size`` token slots shared by every slot through per-slot block
  tables of static ``max_blocks_per_slot`` width, so HBM is consumed per
  *chain* and ``max_cache_len`` (the pool's token capacity) sizes to the
  working set of concurrently LIVE tokens, not the whole queue. A
  genuinely-too-small pool raises an actionable error instead of corrupting
  state.
- **Per-row validity**: rows that didn't really produce a token mask the
  written slot out of their ``kv_mask``. Attention needs only
  slot-causality + validity, both hole-tolerant; rope positions ride the
  separate per-row ``positions`` channel, so absolute- and rotary-position
  models are exact.
- **Allocation is host free-list surgery**: a request reserves its whole
  worst-case chain at admission (the only capacity decision point), and a
  retired request's chain frees when its report is read — no device
  permutation ever runs. Stale bits of reused blocks are masked by a
  chain-frontier comparison, so the free list never needs device-side
  scrubbing.
- **Cross-request prefix sharing**: hole-free full blocks are indexed by
  their chain-prefix tokens and any request whose prompt starts with an
  indexed chain ALIASES those blocks (refcounted) — K/V are pure functions
  of (params, token prefix) because rope/wpe ride the position channel,
  which is exactly what makes the bits shareable. ``set_prefix`` stores a
  prompt prefix shared by every request (system prompt, few-shot block, a
  long document): requests then submit only their suffixes, the first one
  prefills the prefix's blocks and the later ones alias them.
- **Chunked prefill** interleaves with decode: ``submit()`` splits prompts
  into ``prefill_chunk``-token chunks and each engine iteration dispatches at
  most ONE chunk between decode windows, bounding per-step decode stall by a
  chunk's compute instead of a prompt's. Prompts may exceed the largest
  bucket (up to ``max_tokens_per_request``).
- **SLO-aware admission** (``slo=SLOTargets(...)``): per-request TTFT/TPOT
  accounting in the goodput-ledger idiom decides whether to admit, chunk,
  defer, or escalate a prefill (``slo_report()``); TTFT/TPOT histograms and
  pool gauges publish to the MetricsRegistry (docs/observability.md).
- **The decode/chunk/verify programs** gather each slot's chain once into a
  contiguous READ-ONLY view and give the model forward a two-part cache:
  that view plus an empty write window the size of what the program writes
  (``_paged_view_cache``). A chunk program belongs to one slot and runs at
  batch 1: that slot's chain, state and row, no other. The decode window's
  view is as wide as the longest chain among the rows that decode, rounded
  up to a half, three quarters or the whole of the table: one program holds
  the three widths as the branches of a ``lax.switch`` and computes the index
  itself (``_decode``). Every layer attends
  both parts under one softmax and writes the window alone
  (``ops/attention.py`` ``cached_attention(prefix=...)``); the decode
  window's scan carries the window, never the view, and
  the window is scattered onto chain tails as it is — nothing the size of
  the view is copied per step. The engine loop runs one window AHEAD of its sync:
  each window's (active, n_out, out_buf) report is read only after the next
  window is dispatched, so the steady-state loop performs zero blocking
  transfers (pinned by tests).

**Per-request generation controls** (``submit`` kwargs): each request may
carry its own ``max_new_tokens``, ``temperature``, ``eos_token_id``, and
``stop_sequences``, heterogeneously within one wave. Per-slot scalars ride the
engine state through the same compiled programs — nothing recompiles as the
mix changes. Length/temperature/eos act on-device per slot; multi-token stop
sequences are detected host-side at the sync cadence (the slot frees at most
``sync_every - 1`` steps late) and the OUTPUT is truncated exactly at the
first stop occurrence, so results never depend on cadence.

Correctness contract (pinned by tests/test_serving.py): in greedy mode each
request's output is EXACTLY ``generate(model, prompt, temperature=0)`` for
that prompt alone (with a prefix set: for ``prefix + suffix``), regardless of
how requests interleave. In sampling mode
each request draws from its own stream — ``fold_in(engine_rng, request_id)``
folded again by step index — so a request's sampled tokens depend only on
(engine rng, request id), not on traffic or slot assignment; they are
reproducible but not bit-equal to a solo ``generate()`` (whose split chain
differs).

Sliding-window models serve exactly: ``cached_attention`` measures windows in
VALID-slot distance, so masked holes don't stretch the window
(ops/attention.py — on the solo cache of ``generate()`` the two distances
coincide, which is what makes engine output == solo output).
"""

from __future__ import annotations

import functools
import os
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .generation import _unwrap, left_align, mask_positions
from .ops.int8 import quantize_kv
from .ops.paged_attention import (PLAIN_CACHE_LAYOUT, cache_layout, gather_block_mask, gather_view,
                                  init_kv_pool, pool_bytes, pool_is_quantized, token_bytes)
from .utils.environment import safe_donate_argnums
from .utils.transfer import host_fetch


_SERVING_COUNTERS = None  # telemetry.metrics.cached_handles accessor
_SERVING_SLO_METRICS = None
_SERVING_SPEC_METRICS = None


def _serving_counters():
    """(submitted, completed, tokens, prefix lookups refused) telemetry
    counters — the per-request paths pay only the .inc() (cached_handles
    hoists the registry lookup)."""
    global _SERVING_COUNTERS
    if _SERVING_COUNTERS is None:
        from .telemetry.metrics import cached_handles

        _SERVING_COUNTERS = cached_handles(lambda registry: (
            registry.counter(
                "accelerate_serving_requests_total",
                "Requests submitted to the engine",
            ),
            registry.counter(
                "accelerate_serving_requests_completed_total", "Requests finished"
            ),
            registry.counter(
                "accelerate_serving_tokens_total", "Tokens generated by the engine"
            ),
            registry.counter(
                "accelerate_serving_prefix_lookups_refused_total",
                "Prefix-cache lookups refused because the model carries recurrent state",
            ),
        ))
    return _SERVING_COUNTERS()


def _slo_metrics():
    """(ttft_hist, tpot_hist, blocks_free_gauge, pool_util_gauge,
    cache_bytes_gauge) — the
    serving SLO/telemetry handles (docs/observability.md), hoisted like the
    request counters so the per-request paths pay only the observe/set."""
    global _SERVING_SLO_METRICS
    if _SERVING_SLO_METRICS is None:
        from .telemetry.metrics import cached_handles

        _SERVING_SLO_METRICS = cached_handles(lambda registry: (
            registry.histogram(
                "accelerate_serving_ttft_seconds",
                "Observed time-to-first-token per request (sync-cadence granularity)",
            ),
            registry.histogram(
                "accelerate_serving_tpot_seconds",
                "Observed time-per-output-token per request (finish-ttft over tokens)",
            ),
            registry.gauge(
                "accelerate_serving_kv_pool_blocks_free",
                "Free blocks in the paged KV pool",
            ),
            registry.gauge(
                "accelerate_serving_kv_pool_utilization",
                "Allocated fraction of the paged KV pool's blocks",
            ),
            registry.gauge(
                "accelerate_serving_cache_bytes",
                "Persistent device bytes of the paged cache by kind: kv (blocks paged by "
                "token, scales included) and state (recurrent state held by slot)",
                labelnames=("kind",),
            ),
        ))
    return _SERVING_SLO_METRICS()


def _spec_metrics():
    """(proposed_total, accepted_total, acceptance_gauge) — the speculative-
    decoding telemetry handles (docs/observability.md): cumulative draft
    tokens proposed/accepted plus the running acceptance-rate gauge, hoisted
    like the request counters so each verify round pays only the inc/set."""
    global _SERVING_SPEC_METRICS
    if _SERVING_SPEC_METRICS is None:
        from .telemetry.metrics import cached_handles

        _SERVING_SPEC_METRICS = cached_handles(lambda registry: (
            registry.counter(
                "accelerate_spec_proposed_tokens_total",
                "Draft tokens proposed by the speculative decoder",
            ),
            registry.counter(
                "accelerate_spec_accepted_tokens_total",
                "Draft tokens accepted by the target verifier",
            ),
            registry.gauge(
                "accelerate_spec_acceptance_rate",
                "Cumulative accepted/proposed draft-token ratio",
            ),
        ))
    return _SERVING_SPEC_METRICS()


@dataclass
class SLOTargets:
    """Per-request latency targets the paged engine's admission loop steers
    by (the goodput-ledger idiom applied to serving: classify every scheduling
    decision, account per-request TTFT/TPOT against explicit targets).

    ``ttft_s``: target time-to-first-token. A queued request whose projected
    TTFT is at risk gets its remaining prefill escalated to bigger chunks
    (fewer interleave gaps — prefill completes sooner at the cost of larger
    per-step decode stalls). ``tpot_s``: target time-per-output-token for
    in-flight decoders. While the recent decode-window pace is over budget,
    prefill chunks are deferred (decode keeps priority) unless that would put
    a waiting request's TTFT at risk — TTFT outranks TPOT on conflict, the
    standard serving trade. ``None`` disables a dimension."""

    ttft_s: float | None = None
    tpot_s: float | None = None


def _first_stop_end(row: np.ndarray, stops: tuple) -> int | None:
    """End index (exclusive) of the earliest-ending completed stop-sequence
    occurrence in ``row``, or None. Earliest END, so a later-starting shorter
    stop that completes first wins — the order generation actually stops in."""
    best = None
    for s in stops:
        L = int(s.size)
        if L > row.size:
            continue
        win = np.lib.stride_tricks.sliding_window_view(row, L)
        hits = np.nonzero((win == s).all(axis=1))[0]
        if hits.size:
            end = int(hits[0]) + L
            if best is None or end < best:
                best = end
    return best


# Ring bound on per-request latency samples and the dispatch trace a
# long-lived engine retains (the Prometheus histograms keep the full
# distributions; these only back slo_report()'s recent view and the tests'
# structural pins).
_SLO_HISTORY = 4096

# A decode window gathers a view as wide as its longest decoding chain, rounded
# up to a half, three quarters or the whole of the table (``_decode``). A width
# under this many columns is not made: such a view is a few megabytes a layer,
# and a step's read of the weights dwarfs it.
_MIN_VIEW_COLS = 512
# Nor is any narrower width made where the whole view is under this share of
# what a decode step reads (the weights and the view): each further body of
# the program costs seconds of every start (tracing and loading it), which a
# narrower view cannot pay back where the weights are most of the step. On the
# chip (PERF.md, PR 37): Qwen3-1.7B's 12 slots of 1,568 columns are 39% of a
# step and three widths cut a window by a fifth for 1.4 s of set-up; Laguna's
# view is 16% and MiniCPM-SALA's 4%, and three widths cost them 8 and 4 s of
# set-up for 2% of their token time.
_MIN_VIEW_SHARE = 0.25


def _view_ladder(max_blocks: int, block_size: int, view_share: float) -> tuple:
    """The widths, in table entries and ascending, that a decode window's view
    may take; the last is the whole table. One width means one program body.
    ``view_share``: the whole view's share of a decode step's bytes."""
    if view_share < _MIN_VIEW_SHARE:
        return (max_blocks,)
    widths = sorted({-(-max_blocks // 2), -(-3 * max_blocks // 4), max_blocks})
    return tuple(nb for nb in widths
                 if nb == max_blocks or nb * block_size >= _MIN_VIEW_COLS)


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray  # (P,) real tokens, no padding
    max_new: int
    temperature: float
    eos: int  # -1 = none
    stop: tuple  # tuple of np.int32 arrays; () = none
    submit_t: float = 0.0  # monotonic submit time (TTFT/TPOT accounting)


class ContinuousBatcher:
    """Slot-based continuous batching over a decoder-only cached model.

    Usage::

        engine = ContinuousBatcher(model, batch_slots=4, max_new_tokens=64,
                                   max_cache_len=4096, eos_token_id=eos)
        ids = [engine.submit(p) for p in prompts]       # any ragged lengths
        outputs = engine.run()                           # {rid: np.ndarray}

    ``run()`` drives admits + decode steps until every submitted request has
    finished; ``submit`` may be called again afterwards, wave after wave: a
    finished request's blocks return to the free list as its report is read.
    ``max_cache_len`` is the pool's token capacity (``num_blocks`` defaults
    to ``max_cache_len // block_size``); ``reset()`` drops every resident
    block. ``paged`` selects nothing: ``True`` is accepted because the
    benchmark's configuration files still pass it, ``False`` is refused.
    """

    def __init__(
        self,
        model,
        *,
        batch_slots: int,
        max_new_tokens: int,
        max_cache_len: int,
        params=None,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        rng=None,
        eos_token_id: int | None = None,
        pad_token_id: int = 0,
        cache_dtype=jnp.bfloat16,
        bucket_sizes: tuple = (16, 32, 64, 128, 256, 512, 1024),
        sync_every: int = 8,
        paged: bool = True,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefill_chunk: int | None = None,
        max_tokens_per_request: int | None = None,
        slo: SLOTargets | None = None,
        kernels: str | None = None,
        speculative_k: int = 0,
        draft_model=None,
        kv_quant: str | None = None,
        matmul_precision: str | None = None,
        trace_requests: bool = True,
    ):
        if paged is not True:
            raise ValueError(
                "paged=False: the contiguous engine is gone; ContinuousBatcher serves "
                "through the paged KV pool alone (drop the argument)")
        module, mparams = _unwrap(model)
        # Weight-quantized serving (opt-in dtype policy): swap the model's
        # matmul primitive for the kernel-backed int8 path (ops/int8.py) via a
        # memoized config variant — the params are untouched (dynamic
        # quantization happens inside the matmul), so the SAME checkpoint
        # serves both precisions.
        if matmul_precision in ("", "default"):
            matmul_precision = None
        if matmul_precision is not None:
            from .generation import _precision_variant

            module = _precision_variant(module, matmul_precision)
        self.matmul_precision = matmul_precision
        self.module = module
        self.params = params if params is not None else mparams
        if self.params is None:
            raise ValueError("Model has no params; pass params= or init the model first.")
        if hasattr(module, "encode"):
            raise ValueError("ContinuousBatcher supports decoder-only cached models.")
        self.B = batch_slots
        self.max_new = max_new_tokens
        self.C = max_cache_len
        self.temperature = temperature
        self.top_k, self.top_p = top_k, top_p
        self.eos = -1 if eos_token_id is None else eos_token_id
        self.pad = pad_token_id
        self.cache_dtype = cache_dtype
        self.buckets = tuple(sorted(bucket_sizes))
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        # How many decode steps to enqueue between host checks. The host
        # round-trip (detecting finished slots) is the serving loop's only
        # sync; batching K steps per check amortizes it — finished slots idle
        # at most K-1 extra steps and the cache consumes at most K-1 extra
        # columns per wave, both accounted for in the capacity reservation.
        self.sync_every = sync_every
        # ----------------------------------------------- decode-speed levers
        # Speculative decoding + int8 KV blocks (ISSUE 20): constructor args
        # win; unset values resolve from the launcher env contract
        # (ACCELERATE_SPECULATIVE_K / _DRAFT_MODEL / _KV_QUANT) so a serving
        # tier picks them up with zero code, like kernels/SLO targets.
        from .utils.constants import ENV_KV_QUANT, ENV_SPECULATIVE_K

        if not speculative_k:
            speculative_k = int(os.environ.get(ENV_SPECULATIVE_K, "0") or 0)
        self.speculative_k = int(speculative_k)
        if self.speculative_k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {speculative_k}")
        if kv_quant is None:
            kv_quant = os.environ.get(ENV_KV_QUANT) or None
        if kv_quant in ("", "none", "off"):
            kv_quant = None
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
        self.kv_quant = kv_quant
        # What the model's cache holds (ops/paged_attention.py cache_layout):
        # entries paged by token, entries held by slot (a recurrent state),
        # and whether its chains must stay dense. State held by slot cannot
        # be shared or truncated block by block, so prefix aliasing stands
        # down and speculative decoding is refused for such a model.
        self._layout = cache_layout(module)
        self._by_token = self._layout["by_token"]
        self._stateful = bool(self._layout["by_slot"])
        if self._stateful and self.speculative_k:
            raise ValueError(
                f"speculative decoding rolls a rejected draft back by block-table "
                f"truncation, which cannot roll back the recurrent state "
                f"{type(module).__name__} holds by slot; run it with speculative_k=0")
        if self.speculative_k and not self._layout["speculative"]:
            raise ValueError(
                f"the speculative verify round compares the logits of every position of "
                f"its window; {type(module).__name__}'s cached forward returns the last "
                f"position's alone (cache_layout 'speculative'): run it with speculative_k=0")
        # ----------------------------------------------------- the KV pool
        # A block pool (ops/paged_attention.py): `num_blocks` blocks of
        # `block_size` token slots shared by all slots via per-slot block
        # tables (static max_blocks_per_slot, so every program stays
        # compiled-once). `max_cache_len` is the pool's total token capacity
        # (num_blocks defaults to max_cache_len // block_size);
        # `prefill_chunk` bounds each prefill dispatch so long prompts
        # interleave with decode instead of stalling it.
        self.block_size = int(block_size)
        if slo is None:
            # The launcher's SLO env contract reaches a serving tier with
            # zero code: ACCELERATE_SLO_TTFT/TPOT resolve here unless the
            # caller pinned targets (or their absence) explicitly.
            from .telemetry.slo import serving_slo_from_env

            slo = serving_slo_from_env()
        self.slo = slo
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks is None:
            num_blocks = max(1, self.C // self.block_size)
        self.num_blocks = int(num_blocks)
        if prefill_chunk is None:
            # Largest block-aligned chunk within the biggest bucket: full
            # (non-final) chunks stay hole-free and block-aligned, which
            # is what makes their blocks registrable for cross-request
            # sharing. Clamped to the largest bucket for the degenerate
            # block_size > buckets[-1] case (chunks then just aren't
            # block-aligned, so they skip share registration).
            prefill_chunk = min(self.buckets[-1], max(
                self.block_size,
                (self.buckets[-1] // self.block_size) * self.block_size,
            ))
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1 or self.prefill_chunk > self.buckets[-1]:
            raise ValueError(
                f"prefill_chunk must be in [1, largest bucket "
                f"{self.buckets[-1]}], got {prefill_chunk}"
            )
        # Per-request token ceiling (prompt incl. any shared prefix +
        # output). Sizes the static per-slot block table: the chain may
        # additionally hold the final chunk's bucket padding and up to
        # ~3 windows of post-finish slack (finish detection + the
        # one-window sync lookahead), all block-rounded.
        if max_tokens_per_request is None:
            max_tokens_per_request = self.buckets[-1] + self.max_new
        self.max_tokens_per_request = int(max_tokens_per_request)
        # The final chunk is BUCKET-padded, and _bucket rounds a
        # <=prefill_chunk remainder up to at most _bucket(prefill_chunk)
        # (coarse bucket lists round far past prefill_chunk itself), so
        # that is the padding the static table must budget for.
        # Spec-decode verify rounds write (k+1)-token windows instead of
        # sync_every-token ones, so the post-finish slack is measured in
        # the LARGER of the two window widths.
        self._decode_slack = 3 * max(self.sync_every, self.speculative_k + 1)
        worst_chain = (
            self.max_tokens_per_request + self._bucket(self.prefill_chunk)
            + self._decode_slack
        )
        self.max_blocks_per_slot = -(-worst_chain // self.block_size)
        # Pallas kernel-layer spec for the engine's compiled programs
        # (ops/registry.py; docs/kernels.md): None = the launcher contract
        # (ACCELERATE_KERNELS) resolved at trace time; an explicit string
        # (e.g. "pallas" / "paged_gather=off") pins the engine regardless of
        # env. The chain-view assembly dispatches through op
        # ``paged_gather`` — the Pallas chain-walk skips bucket-padded slots
        # and never materializes the intermediate (B, M, bs, ...) gather;
        # token output is bit-identical either way (tests/test_kernels.py).
        if kernels is not None:
            from .ops.registry import parse_kernel_spec

            parse_kernel_spec(kernels)  # validate eagerly
        self.kernels = kernels
        # Speculative decoding: resolve the draft model. Its paged pool
        # mirrors the target pool's block geometry exactly, so ONE set of
        # host block tables / free-list bookkeeping indexes both.
        self._draft_module = None
        self._draft_params = None
        if self.speculative_k:
            if draft_model is None:
                from .utils.constants import ENV_DRAFT_MODEL

                draft_model = self._build_draft_from_preset(
                    os.environ.get(ENV_DRAFT_MODEL) or "tiny"
                )
            d_module, d_params = _unwrap(draft_model)
            if d_params is None:
                raise ValueError(
                    "draft model has no params; init it first or pass a "
                    "prepared/initialized model as draft_model="
                )
            self._draft_module = d_module
            self._draft_params = d_params
        elif draft_model is not None:
            raise ValueError("draft_model requires speculative_k > 0")
        self._rng = rng if rng is not None else jax.random.key(0)
        self._queue: deque[_Request] = deque()
        self._next_rid = 0
        self._results: dict[int, np.ndarray] = {}
        self._chunk_fns: dict[int, object] = {}
        self._decode_fn = None
        self._verify_fn = None
        # Cumulative speculative-decoding ledger (host side, both exposed via
        # spec_report() and the accelerate_spec_* metrics handles).
        self._spec_proposed = 0
        self._spec_accepted = 0
        # SLO/throughput accounting: per-request wall-clock
        # marks and the admission loop's decision tallies. Both ring-bounded
        # (_SLO_HISTORY): a long-lived engine serves unbounded requests, and
        # the histograms already hold the full distribution — the dicts only
        # back slo_report()'s recent-sample view.
        self._req_times: dict[int, dict] = {}
        self._slo_decisions = {
            "admitted": 0, "chunked_prefills": 0, "deferred_prefills": 0,
            "escalated_monolithic": 0, "aliased_blocks": 0,
        }
        self._peak_consumed_slots = 0
        # Host-side trace of dispatches ("chunk:<P>" / "decode"):
        # the structural evidence behind the bounded-stall contract (tests
        # pin that no two prefill chunks ever run back-to-back while a
        # decoder is active, and that every chunk is <= prefill_chunk's
        # bucket — so a decode step waits on at most one chunk's compute).
        self._dispatch_log: list[str] = []
        self._prefix_tokens: np.ndarray | None = None
        # Per-request lifecycle tracing (telemetry/requests.py): every hook
        # fires from host bookkeeping the loop performs anyway, so tracing
        # adds zero device transfers (pinned by tests/test_fleet.py). A TTFT
        # breach books accelerate_slo_breaches_total + a flight event and can
        # arm a trace capture via the installed profile trigger.
        # The same switch arms the loop's spans (telemetry/spans.py:
        # serve.run / serve.iteration / serve.admit / serve.dispatch_* /
        # serve.report_wait / serve.process_report — docs/observability.md);
        # every attribute they carry is host bookkeeping too.
        from .telemetry.spans import no_span, span

        if trace_requests:
            from .telemetry.requests import RequestTracer

            self.tracer: RequestTracer | None = RequestTracer(slo=self.slo)
            self._span = span
        else:
            self.tracer = None
            self._span = no_span
        # Token-streaming sink (serving_net/frontend.py installs one):
        # ``stream(rid, tokens, final)`` — per-window deltas from the report
        # the loop already reads, then ONE final call carrying the
        # authoritative (eos/stop-truncated) output. None = no streaming and
        # no extra report fetches.
        self.stream = None
        self._streamed: dict[int, int] = {}
        self.reset()
        # A view column's bytes over the by_token entries (a quantized pool's
        # view is dequantized to the cache's dtype).
        column_bytes = sum(
            x.shape[0] * x.shape[3] * x.shape[4]
            * jnp.dtype(self.cache_dtype if self.kv_quant else x.dtype).itemsize
            for x in (self._pool[name] for name in self._by_token))
        view_bytes = self.B * self.max_blocks_per_slot * self.block_size * column_bytes
        weight_bytes = sum(x.size * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(self.params))
        self._view_ladder = _view_ladder(self.max_blocks_per_slot, self.block_size,
                                         view_bytes / (view_bytes + weight_bytes))
        if trace_requests:
            self._record_cache_layout()

    def _record_cache_layout(self):
        """One ``serve.cache_layout`` record at engine start: what a token
        costs in the entries paged by token (together and by name), what a
        slot costs in recurrent state, and how many layers hold each
        (docs/observability.md)."""
        from .telemetry.spans import record_span

        pool, now = self._pool, time.perf_counter()
        held = {name: pool[name] for name in self._layout["by_slot"]}
        record_span(
            "serve.cache_layout", now, now,
            kv_bytes_per_token=self._pool_bytes["kv"] // (pool["mask"].shape[0] * self.block_size),
            state_bytes_per_slot=self._pool_bytes["state"] // self.B,
            kv_layers=int(pool[self._by_token[0]].shape[0]),
            state_layers=sum(int(x.shape[0]) for x in held.values()),
            slot_bytes={name: int(x.nbytes) // self.B for name, x in held.items()},
            # A model that names its own token-paged entries: each one's bytes a token.
            **({"token_bytes": token_bytes(pool, self._layout)}
               if self._by_token != PLAIN_CACHE_LAYOUT["by_token"] else {}),
        )

    def _build_draft_from_preset(self, preset: str):
        """Materialize the env-named draft model (``ACCELERATE_DRAFT_MODEL``,
        default ``tiny``): a zoo config preset re-shaped to the target's
        vocabulary and position budget, deterministically initialized (fixed
        seed) so every host of a serving fleet builds the SAME draft weights.
        Checkpointed drafts pass ``draft_model=`` directly instead."""
        from .models.llama import Llama, LlamaConfig

        factory = getattr(LlamaConfig, preset, None)
        if factory is None or not callable(factory):
            raise ValueError(
                f"unknown draft-model preset {preset!r} (a LlamaConfig "
                "classmethod name like 'tiny')"
            )
        overrides = {}
        tcfg = getattr(self.module, "config", None)
        if tcfg is not None and hasattr(tcfg, "vocab_size"):
            overrides["vocab_size"] = tcfg.vocab_size
        if tcfg is not None and hasattr(tcfg, "max_position_embeddings"):
            overrides["max_position_embeddings"] = tcfg.max_position_embeddings
        d_module = Llama(factory(**overrides))
        d_module.init_params(jax.random.key(0))
        return d_module

    # ------------------------------------------------------------- lifecycle
    def reset(self, keep_prefix: bool = True):
        """Fresh pool, tables, free-list, and slot state. Queued
        (not-yet-admitted) requests and already-finished results survive;
        in-flight slots are wiped — the capacity-error path re-queues them
        first, so catch + ``reset()`` + ``run()`` retries everything. The
        shared-prefix TOKENS (``set_prefix``) survive ``keep_prefix=True`` so
        the retry flow stays exact (prefix caching is lazy: the first request
        of the next wave re-prefills the prefix blocks and later requests
        alias them), but all resident blocks are dropped; pass
        ``keep_prefix=False`` to drop the prefix too."""
        B = self.B
        self._streamed.clear()
        self._chunk_counts = None  # the last chunk's counts, where the model names any
        if self.tracer is not None:
            # In-flight slots are about to be wiped: their lifecycle records
            # close as cancelled (queued requests survive and stay queued).
            for req in getattr(self, "_slot_req", []):
                if req is not None:
                    self.tracer.cancel(req.rid)
        self._pool = init_kv_pool(
            self.module, self.num_blocks, self.block_size,
            dtype=self.cache_dtype, quant=self.kv_quant, slots=B,
        )
        # The pool's bytes by kind are fixed once it is allocated.
        self._pool_bytes = pool_bytes(self._pool, self._layout)
        for kind, nbytes in self._pool_bytes.items():
            _slo_metrics()[4].set(float(nbytes), kind=kind)
        # The draft pool mirrors the target pool's block geometry (same
        # num_blocks/block_size/max_blocks_per_slot), so a chain's block i
        # holds target KV in self._pool AND draft KV in self._draft_pool
        # under the SAME host table entry. It stays unquantized: the draft is
        # tiny, its pool a rounding error next to the target's.
        self._draft_pool = (
            init_kv_pool(self._draft_module, self.num_blocks, self.block_size,
                         dtype=self.cache_dtype)
            if self.speculative_k else None
        )
        self._draft_by_token = (cache_layout(self._draft_module)["by_token"]
                                if self.speculative_k else ())
        self._tok = jnp.full((B,), self.pad, jnp.int32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._n_out = jnp.zeros((B,), jnp.int32)
        self._active = jnp.zeros((B,), bool)
        self._out_buf = jnp.full((B, self.max_new), self.pad, jnp.int32)
        self._keys = jnp.broadcast_to(self._rng, (B,))
        # Per-slot generation controls (heterogeneous per request; traced
        # values, so the compiled programs are shared across any mix).
        self._slot_max = jnp.full((B,), self.max_new, jnp.int32)
        self._slot_temp = jnp.full((B,), float(self.temperature or 0.0), jnp.float32)
        self._slot_eos = jnp.full((B,), self.eos, jnp.int32)
        self._slot_req: list[_Request | None] = [None] * B
        # Host-side pool bookkeeping. Block 0 is the reserved trash block
        # (ops/paged_attention.py): never allocated, never mask-valid.
        self._tables_np = np.zeros((B, self.max_blocks_per_slot), np.int32)
        self._slot_len = np.zeros((B,), np.int64)      # chain slots (incl holes)
        self._slot_base = np.zeros((B,), np.int64)     # real tokens in chain
        self._slot_mode = ["free"] * B                  # free | prefill | decode
        self._slot_chunks: list[list] = [[] for _ in range(B)]
        self._slot_blocks: list[list[int]] = [[] for _ in range(B)]
        self._slot_tokens: list[np.ndarray | None] = [None] * B
        self._free_blocks = list(range(1, self.num_blocks + 1))
        self._block_ref = np.zeros((self.num_blocks + 1,), np.int64)
        self._share_index: dict[bytes, int] = {}
        self._block_key: dict[int, bytes] = {}
        if not keep_prefix:
            self._prefix_tokens = None

    def set_prefix(self, prefix_ids) -> int:
        """Shared-prefix caching: store a prompt prefix common to every
        request (a system prompt, few-shot examples, a long document).
        Subsequent ``submit()`` calls pass only each request's *suffix*;
        outputs are exactly ``generate(model, prefix + suffix)`` per request
        (pinned by tests/test_serving.py). A special case of cross-request
        block aliasing: the stored prefix is prepended to every submit()'s
        prompt, the FIRST request prefills it into blocks, and every later
        request whose chain starts with those full blocks aliases them
        (refcounted — they stay resident while any chain uses them), so the
        prefix's full blocks are held, and prefilled, once instead of once
        per admitted request.

        Must be called on a fresh engine (right after construction or
        ``reset(keep_prefix=False)``); ``reset()`` keeps the tokens so the
        capacity-retry flow stays exact. Returns the prefix length."""
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        if any(m != "free" for m in self._slot_mode) or self._prefix_tokens is not None:
            raise RuntimeError(
                "set_prefix needs a fresh cache (no admitted requests, no "
                "prior prefix): call reset(keep_prefix=False) first."
            )
        P = int(prefix.size)
        if P + self.buckets[0] + self.max_new > self.max_tokens_per_request:
            raise ValueError(
                f"prefix length {P} leaves no room for even one "
                f"smallest-bucket request within max_tokens_per_request="
                f"{self.max_tokens_per_request}"
            )
        self._prefix_tokens = prefix
        return P

    @property
    def cache_columns_used(self) -> int:
        """Pool token-slots currently allocated to chains (out of
        ``num_blocks * block_size``); they return to the free list as
        requests finish."""
        return self.blocks_in_use * self.block_size

    @property
    def blocks_in_use(self) -> int:
        """Pool blocks currently owned by at least one chain."""
        return self.num_blocks - len(self._free_blocks)

    @property
    def kv_cache_bytes(self) -> int:
        """Persistent device bytes of the cache — the pool (trash block
        included) with, for a model that carries recurrent state, the state
        it holds by slot (``pool_stats()`` names the two kinds). The
        denominator of the serving bench's admitted-tokens-per-cache-byte capacity
        metric, and the quantity ``accelerate-tpu memcheck --serving`` gates
        against the HBM budget. A quantized pool (``kv_quant="int8"``) prices
        its per-token scale planes too; speculative decoding adds the draft
        pool's blocks — both layouts the memcheck gate must cover."""
        total = sum(self._pool_bytes.values())
        return total + self._draft_pool_bytes()

    def _draft_pool_bytes(self) -> int:
        if self._draft_pool is None:
            return 0
        return sum(int(self._draft_pool[name].nbytes) for name in self._draft_by_token)

    @property
    def kv_consumed_slots_peak(self) -> int:
        """Peak token-slots of KV storage the engine has had allocated to
        chains at once."""
        return self._peak_consumed_slots

    def pool_stats(self) -> dict:
        """Host-side pool snapshot (no device readback)."""
        return {
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "blocks_free": len(self._free_blocks),
            "blocks_in_use": self.blocks_in_use,
            "shared_blocks": len(self._block_key),
            "max_blocks_per_slot": self.max_blocks_per_slot,
            "pool_bytes": self.kv_cache_bytes,
            # The two kinds of bytes (target pool): blocks paged by token,
            # and recurrent state held by slot (0 for a model without one).
            "kv_bytes": self._pool_bytes["kv"],
            "state_bytes": self._pool_bytes["state"],
            "state_slots_in_use": (
                sum(m != "free" for m in self._slot_mode) if self._stateful else 0),
            "kv_quant": self.kv_quant,
            "speculative_k": self.speculative_k,
            "draft_pool_bytes": self._draft_pool_bytes(),
        }

    def spec_report(self) -> dict:
        """Cumulative speculative-decoding acceptance ledger (host-side, no
        device readback beyond what verify rounds already paid): draft tokens
        proposed/accepted and the acceptance rate — the serving analog of
        slo_report(), consumed by bench.py's BENCH_SPEC cell and the journal
        run_summary's accepted-tokens/s fields."""
        proposed, accepted = self._spec_proposed, self._spec_accepted
        return {
            "speculative_k": self.speculative_k,
            "proposed_tokens": proposed,
            "accepted_tokens": accepted,
            "acceptance_rate": accepted / proposed if proposed else None,
        }

    def slo_report(self) -> dict:
        """Per-request TTFT/TPOT accounting + the admission loop's decision
        tallies (the goodput-ledger idiom for serving): what was admitted,
        chunked, deferred, or escalated, and the observed latency samples
        behind the ``accelerate_serving_ttft/tpot_seconds`` histograms."""
        ttft = [
            t["first_token"] - t["submit"]
            for t in self._req_times.values() if "first_token" in t
        ]
        tpot = [t["tpot"] for t in self._req_times.values() if "tpot" in t]
        return {
            "targets": {
                "ttft_s": self.slo.ttft_s if self.slo else None,
                "tpot_s": self.slo.tpot_s if self.slo else None,
            },
            "decisions": dict(self._slo_decisions),
            "ttft_s": ttft,
            "tpot_s": tpot,
            "requests": len(self._req_times),
        }

    @property
    def cache_utilization(self) -> float:
        """Valid tokens over allocated pool slots — the engine's capacity
        honesty metric. Holes are only bucket padding in final prefill
        chunks and masked inactive-step decode writes, and whole chains free
        at retirement, so it does not decay across waves."""
        used = sorted(set(range(1, self.num_blocks + 1)) - set(self._free_blocks))
        if not used:
            return 1.0
        mask = host_fetch(self._pool["mask"])
        return float(mask[np.asarray(used, np.int64)].mean())

    def submit(
        self,
        prompt_ids,
        *,
        max_new_tokens: int | None = None,
        temperature: float | None = None,
        eos_token_id: int | None = None,
        stop_sequences=None,
        request_id: int | None = None,
        tier: str = "unified",
    ) -> int:
        """Queue one prompt (1-D array of token ids). Returns a request id.

        Per-request overrides (engine defaults when omitted):
        ``max_new_tokens`` (must be <= the engine's, which sizes the output
        buffer), ``temperature`` (0 = greedy; rows mix freely within one
        wave), ``eos_token_id``, and ``stop_sequences`` — an iterable of
        token-id sequences; generation stops at the first completed
        occurrence, which is INCLUDED in the returned ids (like eos). Stop
        detection runs host-side at the sync cadence, but the returned output
        is truncated at the exact first occurrence, so results are
        cadence-independent.

        ``request_id`` threads an EXTERNAL id (the serving_net router assigns
        one per fleet request) through this engine instead of the local
        counter, so the request's lifecycle records carry the SAME rid on
        every tier it crosses (router admission → prefill chunks → chain
        handoff → decode) and /fleet rollups join them into one trace;
        ``tier`` labels this engine's tracer record with the serving role
        that made it. The local counter jumps past any external id, so
        auto-assigned and router-assigned ids never collide."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        # Chunked prefill lifts the one-bucket prompt bound: the chain
        # just has to fit the per-request token ceiling (prompt incl.
        # prefix + output buffer). The prefix is prepended HERE so the
        # whole downstream path sees one logical token stream — block
        # aliasing then recovers the shared-prefix capacity win.
        if self._prefix_tokens is not None:
            prompt = np.concatenate([self._prefix_tokens, prompt])
        limit = self.max_tokens_per_request - (
            self.max_new if max_new_tokens is None else int(max_new_tokens)
        )
        if prompt.size > limit:
            raise ValueError(
                f"prompt length {prompt.size} (incl. prefix) exceeds "
                f"max_tokens_per_request={self.max_tokens_per_request} "
                f"minus the output reservation; raise max_tokens_per_request."
            )
        max_new = self.max_new if max_new_tokens is None else int(max_new_tokens)
        if not (1 <= max_new <= self.max_new):
            raise ValueError(
                f"per-request max_new_tokens must be in [1, {self.max_new}] "
                f"(the engine's max_new_tokens sizes the output buffer), got {max_new}"
            )
        temp = float(self.temperature or 0.0) if temperature is None else float(temperature)
        eos = self.eos if eos_token_id is None else int(eos_token_id)
        stop = ()
        if stop_sequences:
            stop = tuple(np.asarray(s, np.int32).reshape(-1) for s in stop_sequences)
            if any(s.size == 0 for s in stop):
                raise ValueError("empty stop sequence")
        if request_id is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            rid = int(request_id)
            if rid < 0:
                raise ValueError(f"request_id must be >= 0, got {request_id}")
            if (
                rid in self._results
                or any(q.rid == rid for q in self._queue)
                or any(r is not None and r.rid == rid for r in self._slot_req)
            ):
                raise ValueError(f"request_id {rid} is already in use")
            self._next_rid = max(self._next_rid, rid + 1)
        now = time.monotonic()
        self._queue.append(_Request(rid, prompt, max_new, temp, eos, stop, now))
        self._req_times[rid] = {"submit": now}
        if self.tracer is not None:
            self.tracer.submit(rid, int(prompt.size), submit_t=now, tier=tier)
        while len(self._req_times) > _SLO_HISTORY:
            # Insertion-ordered: evict the oldest sample (a still-in-flight
            # old rid just loses its latency SAMPLE, never its result).
            self._req_times.pop(next(iter(self._req_times)))
        _serving_counters()[0].inc()
        return rid

    # ------------------------------------------------------------- sampling
    def _sample_rows(self, logits, keys, step_idx, temps):
        """Per-row draw from per-request streams: row r's key folded by its
        own step index — sampled tokens depend only on (engine rng, request
        id, step), never on traffic or slot assignment. ``temps`` (B,) is the
        per-request temperature; 0 rows take the raw argmax (exact greedy),
        so greedy and sampled requests mix inside one compiled program."""
        from .generation import _warp_scores

        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Per-row temperature is a traced value, so _warp_scores' scalar
        # temperature short-circuit can't apply — divide by a safe temp here,
        # then reuse _warp_scores (at T=1) for the top-k/top-p chain so the
        # masking semantics can never diverge from generate()'s. top_k/top_p
        # stay engine-global (static).
        safe_t = jnp.where(temps > 0.0, temps, 1.0)
        scores = _warp_scores(logits.astype(jnp.float32) / safe_t[:, None],
                              1.0, self.top_k, self.top_p)

        def one(lg, k, n):
            return jax.random.categorical(jax.random.fold_in(k, n), lg).astype(jnp.int32)

        sampled = jax.vmap(one)(scores, keys, step_idx)
        return jnp.where(temps > 0.0, sampled, greedy)

    # ------------------------------------------------------------- compiled
    def _paged_view_cache(self, pool, tables, lens, write_cols: int, names=None):
        """The two-part cache of a paged program, as ``(view, window)``.
        ``names``: the pool's entries paged by token (the engine's own model's
        by default; the draft's for its pool), written ``"k"``, ``"v"`` below.

        ``B`` is the number of rows of ``tables`` and ``lens``: every slot
        for the decode window and the verify round, the one slot that
        prefills for a chunk program. ``view`` (``{"k", "v"}`` of
        (L, B, T, Hkv, D) and a ``"kv_mask"`` of (B, T)) is those rows' block
        chains gathered once, and is read-only: a program closes over it,
        every layer attends it, nothing writes it. ``T`` is the width of the
        ``tables`` handed in: the whole table for a chunk program and the
        verify round, one of the ladder's widths for a decode window
        (``_decode`` chooses, and hands in the table's leading entries), so a
        row whose chain is longer than ``T`` gets a view cut short. The view
        also carries ``"capacity"``, a static int: the columns of the WHOLE
        table. A model that needs one length for a whole generation (a
        length-dependent rope) takes it from there and never from
        ``view["k"].shape``. ``window`` is an ordinary
        empty cache ``write_cols`` wide (``"k"``, ``"v"`` of
        (L, B, write_cols, Hkv, D), ``"kv_mask"``, ``"pos"`` 0) whose columns
        follow the view's: what the program writes lands there, at one
        uniform offset for all rows, and ``_scatter_pool`` takes it as it
        is. For a model that holds state by slot the window also carries
        ``pool["state"]`` as handed in (the caller gives the state of the
        same rows as ``tables``), and the programs write back the rows they
        own. The model forward gets ``{**window, "view": view}`` and
        returns the advanced window (``Llama._apply_cached``). The frontier
        comparison masks stale bits of reused (freed→reallocated) blocks, so
        the free-list never needs device-side scrubbing."""
        bs = self.block_size
        t = tables.shape[1] * bs
        # Registry-dispatched assembly (op `paged_gather`): the Pallas
        # chain-walk kernel skips slots with an empty chain (bucket padding /
        # drained slots — their view rows are masked garbage on the reference
        # path and zeros on the kernel path; attention provably ignores both).
        active = lens > 0
        # int8 pools (kv_quant) dequantize HERE, at view assembly: the Pallas
        # gather kernel folds the per-token rescale into its DMA-to-VMEM step
        # (ops/pallas/paged_decode.py), the reference path multiplies after
        # the gather — bit-identical either way (the registry parity seam).
        names = self._by_token if names is None else names
        view = {}
        for name in names:
            scales = pool.get(name + "_scale")
            view[name] = gather_view(
                pool[name], tables, active=active, scales=scales,
                out_dtype=self.cache_dtype if scales is not None else None,
                backend=self.kernels)                     # (L, B, T, H, D)
        vmask = gather_block_mask(pool["mask"], tables)  # (B, T)
        b = vmask.shape[0]
        vmask = jnp.where(jnp.arange(t)[None] < lens[:, None], vmask, 0)
        zeros = {}  # entries of one shape and dtype share one
        window = {}
        for name in names:
            x = view[name]
            shape = x.shape[:2] + (write_cols,) + x.shape[3:]
            if (shape, x.dtype) not in zeros:
                zeros[shape, x.dtype] = jnp.zeros(shape, x.dtype)
            window[name] = zeros[shape, x.dtype]
        view.update(kv_mask=vmask, capacity=self.max_blocks_per_slot * bs)
        window.update(pos=jnp.int32(0), kv_mask=jnp.zeros((b, write_cols), jnp.int32))
        if self._stateful:
            # State held by slot rides in the window (it is read AND written);
            # what a model derives from the view once a program (compressed
            # keys) is made here, before any step reads it.
            for name in self._layout["by_slot"]:
                window[name] = pool[name]
            view = self.module.prepare_view(view)
        return view, window

    def _view_rung(self, lens, commit):
        """Which of the ladder's widths a decode window takes, computed by the
        program from its own arguments: the first that covers the longest
        chain among the rows that decode (a view holds columns ``[0, len)``;
        the window's new columns live beside it, not in it)."""
        longest = jnp.max(jnp.where(commit, lens, 0))
        narrower = [nb * self.block_size for nb in self._view_ladder[:-1]]
        return jnp.sum(longest > jnp.asarray(narrower, jnp.int32)).astype(jnp.int32)

    def _view_cols(self) -> int:
        """The host's account of the same choice, from its own bookkeeping and
        free (the ``view_cols`` of ``serve.dispatch_decode``): the columns of
        the view that a window dispatched now gathers. The verify round
        gathers the whole table."""
        bs = self.block_size
        longest = max((int(self._slot_len[s]) for s, mode in enumerate(self._slot_mode)
                       if mode == "decode"), default=0)
        ladder = self._view_ladder[-1:] if self.speculative_k else self._view_ladder
        return next((nb for nb in ladder if longest <= nb * bs), ladder[-1]) * bs

    def _scatter_pool(self, pool, blk, off, written: dict, mask_new):
        """Append freshly written view columns onto chain tails — the single
        pool write point shared by the chunk / decode-window / spec-verify
        programs. ``written``: the new rows of each entry paged by token, by
        name. An int8 pool (``kv_quant``) quantizes the written rows here,
        one (int8 payload, f32 scale) pair per token row (ops/int8.quantize_kv
        — a committed row is never rescaled, which is what lets blocks fill
        incrementally), and dequantizes at view assembly, so the quantization
        seam is invisible to the model forward."""
        if pool_is_quantized(pool):
            quantized = {name: quantize_kv(new) for name, new in written.items()}
            written = {**{name: q for name, (q, _) in quantized.items()},
                       **{name + "_scale": scale for name, (_, scale) in quantized.items()}}
        out = dict(pool)
        for name, new in written.items():  # payloads, then their scales
            out[name] = pool[name].at[:, blk, off].set(new)
        out["mask"] = pool["mask"].at[blk, off].set(mask_new)
        return out

    def _chunk_fn(self, P: int):
        """Compiled prefill of ONE ``P``-token chunk of one slot's prompt
        against the paged pool, at batch 1: gather the target slot's chain
        alone (``slot`` is a traced scalar, so shapes stay
        request-independent), run the (1, P) chunk against that one-row
        view, scatter the written columns onto the slot's chain tail, and on
        the FINAL chunk sample the request's first token and arm the slot
        for decode. No other slot's chain or state is read or computed. One
        program per chunk bucket, shared by mid-prompt and final chunks
        (``is_final`` is a traced scalar; the state writes are harmless for
        mid chunks — the slot stays inactive and the final chunk rewrites
        them)."""
        if P in self._chunk_fns:
            return self._chunk_fns[P]
        module = self.module
        d_module = self._draft_module
        pad = self.pad
        bs = self.block_size
        spec = bool(self.speculative_k)
        by_slot, dense_chain = self._layout["by_slot"], self._layout["dense_chain"]
        counted = self._layout["counters"].get("chunk", ())

        def body(params, pool, state, tables, lens, slot, chunk_row, mask_row,
                 base_pos, is_final, rid, base_rng, req_max, req_temp, req_eos,
                 d_params=None, d_pool=None):
            (tok, pos, n_out, active, out_buf, keys,
             slot_max, slot_temp, slot_eos) = state
            # The slot's own row of the host's tables and lengths: the view,
            # the write window and the forward below are all batch 1.
            table, length = tables[slot], lens[slot]
            # A request's first chunk starts from a zero state whatever the
            # slot's last occupant left (no aliasing: an empty chain IS the
            # first chunk).
            own = {name: jnp.where(length > 0, pool[name][:, slot], 0)[:, None]
                   for name in by_slot}
            view, window = self._paged_view_cache(
                {**pool, **own}, table[None], length[None], P)
            ids, mask = chunk_row[None], mask_row[None]
            # Token positions continue the slot's REAL-token count (holes
            # from bucket padding never shift positions), so rope/wpe are
            # exact across chunk boundaries and identical to a monolithic
            # prefill of the same prompt.
            out = module.apply(params, input_ids=ids, attention_mask=mask,
                               cache={**window, "view": view},
                               positions=mask_positions(mask) + base_pos)
            if dense_chain:
                # Real tokens alone join the chain, in order, so that a key's
                # column is its token's position; bucket padding goes to the
                # trash block.
                idx = length + jnp.cumsum(mask_row) - 1
                blk = jnp.where(mask_row > 0, table[idx // bs], 0)
            else:
                idx = length + jnp.arange(P)
                blk = table[idx // bs]
            off = idx % bs
            pool = self._scatter_pool(
                pool, blk, off, {name: out["cache"][name][:, 0] for name in self._by_token},
                jnp.where(blk != 0, mask_row, 0),
            )
            for name in by_slot:  # the prefilled slot's state alone is written
                pool[name] = pool[name].at[:, slot].set(out["cache"][name][:, 0])
            if spec:
                # Speculative mode: the draft model prefills the SAME chunk
                # into its mirrored pool inside this program, so every
                # resident chain (including aliased shared-prefix blocks,
                # which are written exactly once, here) carries draft KV by
                # the time the first verify round needs it.
                d_view, d_window = self._paged_view_cache(
                    d_pool, table[None], length[None], P, self._draft_by_token)
                d_out = d_module.apply(
                    d_params, input_ids=ids, attention_mask=mask,
                    cache={**d_window, "view": d_view},
                    positions=mask_positions(mask) + base_pos)
                d_pool = self._scatter_pool(
                    d_pool, blk, off,
                    {name: d_out["cache"][name][:, 0] for name in self._draft_by_token},
                    jnp.where(blk != 0, mask_row, 0),
                )
            real = jnp.sum(mask_row).astype(jnp.int32)
            key = jax.random.fold_in(base_rng, rid)  # the request's own stream
            keys = keys.at[slot].set(key)
            slot_max = slot_max.at[slot].set(req_max)
            slot_temp = slot_temp.at[slot].set(req_temp)
            slot_eos = slot_eos.at[slot].set(req_eos)
            first = self._sample_rows(
                out["logits"][0, -1][None], key[None],
                jnp.zeros((1,), jnp.int32), req_temp[None],
            )[0]
            tok = tok.at[slot].set(first)
            pos = pos.at[slot].set(base_pos + real)
            n_out = n_out.at[slot].set(1)
            out_buf = out_buf.at[slot].set(jnp.full((self.max_new,), pad, jnp.int32))
            out_buf = out_buf.at[slot, 0].set(first)
            done0 = (first == req_eos) | (req_max <= 1)
            active = active.at[slot].set(is_final & ~done0)
            state = (tok, pos, n_out, active, out_buf, keys,
                     slot_max, slot_temp, slot_eos)
            # What the model counted over this chunk (cache_layout "counters").
            counts = (jnp.stack([jnp.sum(out[name]) for name in counted]),) if counted else ()
            if spec:
                return (pool, d_pool, state) + counts
            return (pool, state) + counts

        if spec:
            def chunk(params, d_params, pool, d_pool, state, tables, lens, slot,
                    chunk_row, mask_row, base_pos, is_final, rid, base_rng,
                    req_max, req_temp, req_eos):
                return body(params, pool, state, tables, lens, slot, chunk_row,
                            mask_row, base_pos, is_final, rid, base_rng,
                            req_max, req_temp, req_eos, d_params, d_pool)

            donations = (2, 3, 4)
            donated_leaves = (
                len(jax.tree_util.tree_leaves(self._pool))
                + len(jax.tree_util.tree_leaves(self._draft_pool))
                + len(jax.tree_util.tree_leaves(self._state_tuple()))
            )
        else:
            def chunk(params, pool, state, tables, lens, slot, chunk_row,
                    mask_row, base_pos, is_final, rid, base_rng, req_max,
                    req_temp, req_eos):
                return body(params, pool, state, tables, lens, slot, chunk_row,
                            mask_row, base_pos, is_final, rid, base_rng,
                            req_max, req_temp, req_eos)

            donations = (1, 2)
            donated_leaves = len(jax.tree_util.tree_leaves(self._pool)) + len(
                jax.tree_util.tree_leaves(self._state_tuple())
            )
        effective_donate = safe_donate_argnums(donations)
        chunk.__name__ = f"serve_prefill_chunk_{P}"
        fn = jax.jit(chunk, donate_argnums=effective_donate)
        param_leaves = jax.tree_util.tree_leaves(self.params)
        from .ops.registry import resolved_backends

        # The prefill-tier analog of the decode window's audit metadata: a
        # prefill-ONLY host (serving_net roles) never builds the decode
        # program, so memcheck --serving --serving-role prefill and the
        # `prefill_paged` fingerprint golden price/pin THIS program instead.
        memory_classes = {
            "kv_pool": (lambda: self._pool, lambda: None),
            "params": (lambda: self.params, lambda: None),
        }
        if spec:
            memory_classes["draft_pool"] = (lambda: self._draft_pool, lambda: None)
            memory_classes["draft_params"] = (lambda: self._draft_params, lambda: None)
        fn._audit_meta = {
            "builder": "serving_prefill_chunk",
            "compute_dtype": (
                str(np.dtype(param_leaves[0].dtype).name) if param_leaves else None
            ),
            "expected_donations": donations,
            "expected_donated_leaves": donated_leaves,
            "donation_dropped_by_policy": not effective_donate,
            "kernels": {"spec": self.kernels,
                        "backends": resolved_backends(self.kernels)},
            "jaxpr_thunk": lambda *a, **k: jax.make_jaxpr(chunk)(*a, **k),
            "memory_classes": memory_classes,
        }
        self._chunk_fns[P] = fn
        return fn

    def _decode(self):
        """Compiled ``sync_every``-token window over block tables: ONE gather
        of every slot's chain (the reference block-table lowering —
        ops/paged_attention.py), a ``lax.scan`` of decode steps writing into
        a uniform view window, then one scatter of the written columns onto
        each committed slot's chain tail. Returns ``(pool, state, report)``
        where ``report`` is an optimization-barrier'd (active, n_out,
        out_buf) copy the host can read AFTER donating ``state`` to the next
        window — the one-window-lookahead handle that makes the steady-state
        engine loop's sync non-blocking.

        The view is as wide as the longest chain among the rows that decode,
        rounded up to one of the ladder's widths (``_view_ladder``): the
        program holds the gather and the steps once a width, as the branches
        of one ``lax.switch`` whose index it computes itself from ``lens`` and
        ``commit`` (``_view_rung``), so the host decides nothing and one
        executable serves every window. The pool is read-only inside the
        branches; the scatter and the by-slot write-back follow the switch
        and read the whole table. A row that does not decode (free, or
        mid-prefill with a chain longer than the width) gets a view cut
        short: it is inactive, commits nothing and keeps its state. An engine
        whose ladder is one width (a small table, or a view that is a small
        share of a step's bytes: ``_view_ladder``) has no switch."""
        if self._decode_fn is not None:
            return self._decode_fn
        module = self.module
        pad = self.pad
        bs = self.block_size
        w = self.sync_every
        by_slot = self._layout["by_slot"]
        counted = self._layout["counters"].get("decode", ())
        row_mask = self._layout["row_mask"]
        ladder = self._view_ladder

        def serve_decode_window(params, pool, tables, lens, commit, force_stop, state):
            (tok, pos, n_out, active, out_buf, keys,
             slot_max, slot_temp, slot_eos) = state
            B = tok.shape[0]
            # Host-side stop-sequence verdicts from the previous window's
            # report land here.
            active = active & ~force_stop
            state = (tok, pos, n_out, active, out_buf, keys,
                     slot_max, slot_temp, slot_eos)

            def one_step(view, carry, _):
                # The carry is the window alone; the view is constant across
                # the steps.
                window, state = carry[:2]
                (tok, pos, n_out, active, out_buf, keys,
                 slot_max, slot_temp, slot_eos) = state
                col = window["pos"]  # window column this step writes
                feed = jnp.where(active, tok, pad)
                # A model that asks (cache_layout "row_mask") is told which
                # rows decode; for the others the program is what it was.
                rows = {"attention_mask": active[:, None].astype(jnp.int32)} if row_mask else {}
                out = module.apply(params, input_ids=feed[:, None],
                                   cache={**window, "view": view},
                                   positions=pos[:, None], **rows)
                nxt = self._sample_rows(out["logits"][:, -1], keys, n_out, slot_temp)
                nxt = jnp.where(active, nxt, pad)
                window2 = out["cache"]
                window2 = {
                    **window2,
                    "kv_mask": window2["kv_mask"].at[:, col].set(
                        jnp.where(active, window2["kv_mask"][:, col], 0)
                    ),
                }
                emit_idx = jnp.clip(n_out, 0, self.max_new - 1)
                cur = out_buf[jnp.arange(B), emit_idx]
                out_buf = out_buf.at[jnp.arange(B), emit_idx].set(
                    jnp.where(active, nxt, cur)
                )
                n_out = n_out + active.astype(jnp.int32)
                still = active & (nxt != slot_eos) & (n_out < slot_max)
                state = (nxt, pos + 1, n_out, still, out_buf, keys,
                         slot_max, slot_temp, slot_eos)
                if counted:
                    # What the model counted this step (cache_layout
                    # "counters"): a count a row over the rows that decode,
                    # a count for the batch as it is.
                    seen = carry[2] + jnp.stack([
                        jnp.sum(jnp.where(active, out[name], 0.0)) if out[name].ndim
                        else out[name] for name in counted])
                    return (window2, state, seen), None
                return (window2, state), None

            def steps(nb):
                """The window's steps over a view of the tables' first ``nb``
                entries: ``(window, state)`` and, where the model counts, what
                it counted. Reads the pool, writes none of it."""
                view, window = self._paged_view_cache(pool, tables[:, :nb], lens, w)
                carry = (window, state) + (
                    (jnp.zeros((len(counted),), jnp.float32),) if counted else ())
                carry, _ = jax.lax.scan(functools.partial(one_step, view), carry, None, length=w)
                return carry

            if len(ladder) == 1:
                carry = steps(ladder[0])
            else:
                carry = jax.lax.switch(
                    self._view_rung(lens, commit),
                    [functools.partial(steps, nb) for nb in ladder])
            window, state = carry[:2]
            # Persist the window: committed slots append their written
            # columns (valid or holed: a committed chain advances by the
            # whole window whatever its row produced); everything
            # else lands in the trash block with a forced-zero mask, so
            # block 0 is provably never attendable.
            idx = lens[:, None] + jnp.arange(w)[None]
            blk = jnp.where(
                commit[:, None],
                jnp.take_along_axis(tables, (idx // bs).astype(jnp.int32), axis=1),
                0,
            )
            off = (idx % bs).astype(jnp.int32)
            pool = self._scatter_pool(
                pool, blk, off, {name: window[name] for name in self._by_token},
                jnp.where(blk != 0, window["kv_mask"], 0),
            )
            for name in by_slot:
                # Rows that decode keep their stepped state; every other row
                # (free, or mid-prefill) keeps what it had, bit for bit.
                own = commit.reshape((1, B) + (1,) * (pool[name].ndim - 2))
                pool[name] = jnp.where(own, window[name], pool[name])
            report = jax.lax.optimization_barrier(
                (state[3], state[2], state[4]) + tuple(carry[2:]))
            return pool, state, report

        effective_donate = safe_donate_argnums((1, 6))
        self._decode_fn = jax.jit(serve_decode_window, donate_argnums=effective_donate)
        donated_leaves = len(jax.tree_util.tree_leaves(self._pool)) + len(
            jax.tree_util.tree_leaves(self._state_tuple())
        )
        param_leaves = jax.tree_util.tree_leaves(self.params)
        compute_dtype = (
            str(np.dtype(param_leaves[0].dtype).name) if param_leaves else None
        )
        from .ops.registry import resolved_backends

        self._decode_fn._audit_meta = {
            "builder": "serving_decode_paged",
            "compute_dtype": compute_dtype,
            "expected_donations": (1, 6),
            "expected_donated_leaves": donated_leaves,
            "donation_dropped_by_policy": not effective_donate,
            # Which kernel backend each registered op resolved to at build
            # time, so audits/fingerprints record the engine's kernel config
            # (the paged path dispatches `paged_gather`), plus a jaxpr thunk
            # so the auditor's pallas_call inventory sees the kernel eqns
            # pre-partitioning.
            "kernels": {"spec": self.kernels,
                        "backends": resolved_backends(self.kernels)},
            "jaxpr_thunk": lambda *a, **k: jax.make_jaxpr(serve_decode_window)(*a, **k),
            # The static-memory join for `accelerate-tpu memcheck --serving`:
            # the persistent pool is the class the per-device KV budget gate
            # prices (the gathered view and the write window land in XLA's temp
            # workspace via memory_analysis, not here).
            "memory_classes": {
                "kv_pool": (lambda: self._pool, lambda: None),
                "params": (lambda: self.params, lambda: None),
            },
        }
        return self._decode_fn

    def _spec_verify(self):
        """Compiled speculative verify round (``speculative_k`` = k > 0): ONE
        program that (1) runs k+1 greedy single-token draft steps over the
        draft pool's chain view — the tokens it FEEDS are exactly
        ``[current_token, d_0 .. d_{k-1}]``, so after the scan the draft
        cache holds KV for every window column — then (2) verifies all k
        proposals in ONE target forward over a (k+1)-token window (the
        chunked-prefill multi-token machinery), sampling the target's choice
        at every position with the SAME per-request stream indices
        (``fold_in(key, n_out + j)``) the plain decode window would use.

        Acceptance is the longest matched prefix of (choices, drafts); the
        fix-up token at the first mismatch is the target's own choice, so for
        every EMITTED position the logits are conditioned on exactly the
        tokens the non-speculative engine would have fed — greedy output is
        bit-identical to non-speculative BY CONSTRUCTION, and sampled output
        stays traffic-independent (tests/test_speculative.py pins both).

        Rejection is block-table truncation: rejected window columns' writes
        land in the trash block with a zero mask and the host simply does not
        advance the chain frontier past them — no device scrub. Returns ``(pool, d_pool, state,
        produced, report)``: ``produced`` (tokens committed per slot, current
        + accepted drafts) is fetched eagerly — the one blocking (B,)
        readback a verify round pays for k-fold fewer target passes —
        while ``report`` is the usual barrier'd (active, n_out, out_buf)
        handle processed one round late."""
        if self._verify_fn is not None:
            return self._verify_fn
        module = self.module
        d_module = self._draft_module
        pad = self.pad
        bs = self.block_size
        k = self.speculative_k
        S = k + 1

        def serve_spec_verify(params, d_params, pool, d_pool, tables, lens, commit,
                              force_stop, state):
            (tok, pos, n_out, active, out_buf, keys,
             slot_max, slot_temp, slot_eos) = state
            B = tok.shape[0]
            active = active & ~force_stop & commit
            # --- draft leg: k+1 greedy steps. The last proposal (fed
            # nothing) is discarded, but feeding k+1 steps means the last
            # ACCEPTED draft token's draft-KV is written too — without it a
            # fully-accepted round would leave the draft chain one column
            # short of the target chain.
            d_view, d_window = self._paged_view_cache(d_pool, tables, lens, S,
                                                      self._draft_by_token)

            def d_step(carry, _):
                d_window, d_tok, d_pos = carry
                feed = jnp.where(active, d_tok, pad)
                d_out = d_module.apply(d_params, input_ids=feed[:, None],
                                       cache={**d_window, "view": d_view},
                                       positions=d_pos[:, None])
                nxt = jnp.argmax(d_out["logits"][:, -1], axis=-1).astype(jnp.int32)
                return (d_out["cache"], nxt, d_pos + 1), feed

            (d_window, _, _), fed = jax.lax.scan(
                d_step, (d_window, tok, pos), None, length=S
            )
            ids = fed.T  # (B, S): [cur, d_0 .. d_{k-1}] per row
            # --- target leg: ONE forward over the whole window.
            view, window = self._paged_view_cache(pool, tables, lens, S)
            mask = jnp.broadcast_to(active[:, None], (B, S)).astype(jnp.int32)
            out = module.apply(
                params, input_ids=jnp.where(active[:, None], ids, pad),
                attention_mask=mask, cache={**window, "view": view},
                positions=pos[:, None] + jnp.arange(S)[None],
            )
            choices = jnp.stack(
                [self._sample_rows(out["logits"][:, j], keys, n_out + j, slot_temp)
                 for j in range(S)], axis=1)            # (B, S)
            # --- acceptance: longest matched prefix; position j (if emitted)
            # emits choices[:, j]. n_acc = index of first mismatch (k when
            # every draft matched), so positions 0..n_acc are emittable.
            match = choices[:, :k] == ids[:, 1:]        # (B, k)
            n_acc = jnp.argmin(
                jnp.concatenate([match, jnp.zeros((B, 1), bool)], axis=1)
                .astype(jnp.int32), axis=1)
            j_idx = jnp.arange(S)[None]
            noteos = choices != slot_eos[:, None]
            # Every emission cutoff (mismatch, per-request length, prior eos)
            # is monotone in j, so the emit mask is a per-row prefix and
            # `produced` is its length (>= 1 for active rows: position 0 is
            # the non-spec step the window subsumes).
            prior_ok = jnp.concatenate(
                [jnp.ones((B, 1), bool),
                 jnp.cumprod(noteos[:, :-1].astype(jnp.int32), axis=1).astype(bool)],
                axis=1)
            em = (active[:, None] & (j_idx <= n_acc[:, None])
                  & (n_out[:, None] + j_idx < slot_max[:, None]) & prior_ok)
            produced = jnp.sum(em.astype(jnp.int32), axis=1)  # (B,)
            rows = jnp.arange(B)
            for j in range(S):
                emit_idx = jnp.clip(n_out + j, 0, self.max_new - 1)
                cur_v = out_buf[rows, emit_idx]
                out_buf = out_buf.at[rows, emit_idx].set(
                    jnp.where(em[:, j], choices[:, j], cur_v))
            n_out2 = n_out + produced
            last = choices[rows, jnp.clip(produced - 1, 0, S - 1)]
            tok2 = jnp.where(produced > 0, last, tok)
            eos_hit = jnp.any(em & ~noteos, axis=1)
            still = active & ~eos_hit & (n_out2 < slot_max)
            state = (tok2, pos + produced, n_out2, still, out_buf, keys,
                     slot_max, slot_temp, slot_eos)
            # --- commit: window column j holds the KV of INPUT token j (cur
            # at j=0, accepted draft = emitted choice after). Exactly the
            # first `produced` columns belong to the final sequence — the
            # round's last emitted choice becomes the next current token,
            # whose KV is written next round — so everything past them never
            # commits (trash block, zero mask): rejection without a scrub.
            idx = lens[:, None] + jnp.arange(S)[None]
            wvalid = active[:, None] & (jnp.arange(S)[None] < produced[:, None])
            blk = jnp.where(
                wvalid,
                jnp.take_along_axis(
                    tables,
                    jnp.clip(idx // bs, 0, tables.shape[1] - 1).astype(jnp.int32),
                    axis=1),
                0)
            off = (idx % bs).astype(jnp.int32)
            window = out["cache"]
            pool = self._scatter_pool(
                pool, blk, off, {name: window[name] for name in self._by_token},
                jnp.where(blk != 0, window["kv_mask"], 0),
            )
            d_pool = self._scatter_pool(
                d_pool, blk, off, {name: d_window[name] for name in self._draft_by_token},
                jnp.where(blk != 0, d_window["kv_mask"], 0),
            )
            report = jax.lax.optimization_barrier((state[3], state[2], state[4]))
            return pool, d_pool, state, produced, report

        effective_donate = safe_donate_argnums((2, 3, 8))
        self._verify_fn = jax.jit(serve_spec_verify, donate_argnums=effective_donate)
        donated_leaves = (
            len(jax.tree_util.tree_leaves(self._pool))
            + len(jax.tree_util.tree_leaves(self._draft_pool))
            + len(jax.tree_util.tree_leaves(self._state_tuple()))
        )
        param_leaves = jax.tree_util.tree_leaves(self.params)
        from .ops.registry import resolved_backends

        self._verify_fn._audit_meta = {
            "builder": "serving_spec_verify",
            "compute_dtype": (
                str(np.dtype(param_leaves[0].dtype).name) if param_leaves else None
            ),
            "expected_donations": (2, 3, 8),
            "expected_donated_leaves": donated_leaves,
            "donation_dropped_by_policy": not effective_donate,
            "kernels": {"spec": self.kernels,
                        "backends": resolved_backends(self.kernels)},
            "jaxpr_thunk": lambda *a, **kw: jax.make_jaxpr(serve_spec_verify)(*a, **kw),
            "memory_classes": {
                "kv_pool": (lambda: self._pool, lambda: None),
                "draft_pool": (lambda: self._draft_pool, lambda: None),
                "params": (lambda: self.params, lambda: None),
                "draft_params": (lambda: self._draft_params, lambda: None),
            },
        }
        return self._verify_fn

    # ---------------------------------------------------------------- audit
    def _state_tuple(self):
        return (self._tok, self._pos, self._n_out, self._active, self._out_buf,
                self._keys, self._slot_max, self._slot_temp, self._slot_eos)

    def _decode_args(self):
        """The decode program's full argument tuple against the engine's
        CURRENT cache/state — what audit_decode/fingerprint_decode lower
        with. Program contracts are value-independent, so live host
        bookkeeping values are fine."""
        return (
            self.params, self._pool, jnp.asarray(self._tables_np),
            jnp.asarray(self._slot_len, dtype=jnp.int32),
            jnp.asarray([m == "decode" for m in self._slot_mode]),
            jnp.zeros((self.B,), bool), self._state_tuple(),
        )

    def audit_decode(self, **kwargs):
        """Statically audit the compiled ``sync_every``-token decode window
        (analysis/audit.py) against the engine's current cache/state:
        collective inventory, donation aliasing (pool+state are donated —
        the KV-footprint halving must actually alias), host callbacks.
        Lowers and compiles but never decodes a token."""
        from .analysis import audit_built

        return audit_built(self._decode(), *self._decode_args(), **kwargs)

    def fingerprint_decode(self, config: str = "decode_paged", **kwargs):
        """Canonical :class:`~.analysis.fingerprint.ProgramFingerprint` of
        the compiled decode window — the serving entry in the drift-gate
        matrix (``accelerate-tpu fingerprint``). Lowers and compiles but
        never decodes a token."""
        from .analysis.fingerprint import fingerprint_built

        return fingerprint_built(
            self._decode(), *self._decode_args(), config=config, **kwargs
        )

    def _verify_args(self):
        """The spec-verify program's full argument tuple against the engine's
        current pools/state (value-independent, like ``_decode_args``)."""
        if not self.speculative_k:
            raise ValueError(
                "the spec-verify program exists only with speculative_k > 0"
            )
        return (
            self.params, self._draft_params, self._pool, self._draft_pool,
            jnp.asarray(self._tables_np),
            jnp.asarray(self._slot_len, dtype=jnp.int32),
            jnp.asarray([m == "decode" for m in self._slot_mode]),
            jnp.zeros((self.B,), bool), self._state_tuple(),
        )

    def audit_verify(self, **kwargs):
        """Statically audit the compiled speculative verify round (donation
        aliasing over both pools + state, kernel inventory, memory classes).
        Lowers and compiles but never decodes a token."""
        from .analysis import audit_built

        return audit_built(self._spec_verify(), *self._verify_args(), **kwargs)

    def fingerprint_verify(self, config: str = "spec_verify", **kwargs):
        """Canonical fingerprint of the compiled speculative verify round —
        the spec-decoding entry in the drift-gate matrix (a silently vanished
        draft leg or dequant seam classifies as violation). Lowers and
        compiles but never decodes a token."""
        from .analysis.fingerprint import fingerprint_built

        return fingerprint_built(
            self._spec_verify(), *self._verify_args(), config=config, **kwargs
        )

    def _chunk_args(self, P: int):
        """The ``P``-token chunk program's full argument tuple against the
        engine's current pool/state — what the prefill-tier audit/fingerprint
        lower with (value-independent, like ``_decode_args``)."""
        tail = (
            jnp.asarray(self._tables_np),
            jnp.asarray(self._slot_len, dtype=jnp.int32), jnp.int32(0),
            jnp.zeros((P,), jnp.int32), jnp.ones((P,), jnp.int32),
            jnp.int32(0), jnp.asarray(True), jnp.int32(0), self._rng,
            jnp.int32(self.max_new), jnp.float32(0.0), jnp.int32(self.eos),
        )
        if self.speculative_k:
            return (self.params, self._draft_params, self._pool,
                    self._draft_pool, self._state_tuple()) + tail
        return (self.params, self._pool, self._state_tuple()) + tail

    def fingerprint_prefill(self, config: str = "prefill_paged", **kwargs):
        """Canonical fingerprint of the compiled ``prefill_chunk``-token
        prefill program — the prefill-ONLY tier's entry in the drift-gate
        matrix (a disaggregated prefill host never runs the decode window,
        so the decode golden cannot cover its program contract). Lowers and
        compiles but never prefills a token."""
        from .analysis.fingerprint import fingerprint_built

        P = self.prefill_chunk
        return fingerprint_built(
            self._chunk_fn(P), *self._chunk_args(P), config=config, **kwargs
        )

    # ----------------------------------------------------------------- loop
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError  # guarded in submit()

    def _finish(self, req: _Request, row: np.ndarray):
        """Bank one finished request's output: exact eos/stop truncation —
        tokens decoded past the stop (host scan lags by the sync cadence) are
        discarded, so output is cadence-independent — plus completion
        counters and the TTFT/TPOT histogram observations."""
        row = row.copy()
        if req.eos >= 0 and (row == req.eos).any():
            row = row[: int(np.argmax(row == req.eos)) + 1]
        end = _first_stop_end(row, req.stop)
        if end is not None:
            row = row[:end]
        self._results[req.rid] = row
        times = self._req_times.get(req.rid)
        if times is not None:
            times["finish"] = time.monotonic()
            ft = times.get("first_token")
            if ft is not None:
                ttft_hist, tpot_hist = _slo_metrics()[:2]
                ttft_hist.observe(max(0.0, ft - times["submit"]))
                if row.size > 1:
                    times["tpot"] = (times["finish"] - ft) / (row.size - 1)
                    tpot_hist.observe(max(0.0, times["tpot"]))
        _, completed, tokens, _ = _serving_counters()
        completed.inc()
        tokens.inc(int(row.size))
        if self.tracer is not None:
            self.tracer.finish(
                req.rid, int(row.size),
                tpot_s=(times or {}).get("tpot"),
                at=(times or {}).get("finish"),
            )
        if self.stream is not None:
            self._streamed.pop(req.rid, None)
            self._emit_stream(req.rid, row, True)

    def _emit_stream(self, rid: int, tokens: np.ndarray, final: bool):
        """Deliver one streaming event best-effort: a broken sink (a client
        that hung up mid-stream) must never take the engine loop down."""
        try:
            self.stream(rid, tokens, final)
        except Exception:
            pass

    def _sync(self, state):
        (self._tok, self._pos, self._n_out, self._active, self._out_buf,
         self._keys, self._slot_max, self._slot_temp, self._slot_eos) = state

    def _alias_lookup(self, prompt: np.ndarray):
        """Longest resident block chain whose tokens prefix ``prompt``:
        cross-request prefix sharing as refcounted aliasing. Capped one token
        short of the whole prompt so the final token always runs through a
        prefill chunk (its logits seed the first sampled token)."""
        if self._stateful:
            # A resident chain's blocks say nothing of the recurrent state
            # that went with them: no sharing for a model that carries one.
            _serving_counters()[3].inc()
            return []
        bs = self.block_size
        blocks = []
        for k in range(1, (prompt.size - 1) // bs + 1):
            blk = self._share_index.get(prompt[: k * bs].tobytes())
            if blk is None:
                break
            blocks.append(blk)
        return blocks

    def prefix_match_tokens(self, prompt_ids) -> int:
        """How many leading tokens of ``prompt_ids`` are already resident in
        this engine's shared-block index — the prefix-cache affinity answer
        behind GET /v1/prefixes (serving_net: the router sends each worker a
        prompt's chain prefix and routes to the longest match, so cache-hit
        routing is a host-side lookup, never a device touch). A configured
        shared prefix counts exactly as submit() would prepend it."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if self._prefix_tokens is not None:
            prompt = np.concatenate([self._prefix_tokens, prompt])
        return len(self._alias_lookup(prompt)) * self.block_size

    def in_flight(self) -> int:
        """Requests queued or occupying a slot — the least-loaded routing
        signal GET /v1/stats publishes (host bookkeeping only)."""
        return len(self._queue) + sum(r is not None for r in self._slot_req)

    def release_request(self, rid: int) -> bool:
        """Retire request ``rid``'s slot and refcount-free its chain —
        host-side bookkeeping only. This is the free half of the handoff
        tiers' free-on-ack discipline (serving_net/handoff.py): an exporter
        keeps the chain resident until the importer acks, then releases it
        here; a failed handoff releases it too, so pool blocks never leak.
        Idempotent — returns False when ``rid`` no longer holds a slot."""
        s = next(
            (s for s in range(self.B)
             if self._slot_req[s] is not None and self._slot_req[s].rid == rid),
            None,
        )
        if s is None:
            return False
        self._req_times.pop(rid, None)
        self._free_chain(s)
        self._publish_pool_gauges()
        return True

    def _plan_chunks(self, remainder: np.ndarray, chunk_size: int) -> list:
        """Split the un-aliased prompt tail into prefill chunks: exact
        ``chunk_size`` pieces (hole-free, block-aligned — registrable for
        sharing) plus one final ragged piece in (0, chunk_size]."""
        final = (remainder.size - 1) % chunk_size + 1
        n_full = (remainder.size - final) // chunk_size
        return [
            remainder[i * chunk_size:(i + 1) * chunk_size] for i in range(n_full)
        ] + [remainder[n_full * chunk_size:]]

    def _register_shared(self, s: int, c0: int, p: int):
        """After a hole-free block-aligned chunk lands, index its full blocks
        by their chain-prefix tokens so later requests alias them. First
        writer wins: a key already mapping to another chain's block leaves
        this chain's copy private."""
        bs = self.block_size
        if c0 % bs or p % bs or self._stateful:
            return
        toks = self._slot_tokens[s]
        for j in range(p // bs):
            end = c0 + (j + 1) * bs
            blk = self._slot_blocks[s][end // bs - 1]
            key = toks[:end].tobytes()
            if key not in self._share_index:
                self._share_index[key] = blk
                self._block_key[blk] = key

    def _free_chain(self, s: int):
        """Retire slot ``s``'s chain: refcount-decrement every block, return
        rc-0 blocks to the free list (unregistering their share keys):
        reclaiming a finished request's cache is block-table surgery on the
        host, no device program."""
        for blk in self._slot_blocks[s]:
            self._block_ref[blk] -= 1
            if self._block_ref[blk] == 0:
                self._free_blocks.append(blk)
                key = self._block_key.pop(blk, None)
                if key is not None:
                    self._share_index.pop(key, None)
        self._slot_blocks[s] = []
        self._tables_np[s, :] = 0
        self._slot_len[s] = 0
        self._slot_base[s] = 0
        self._slot_tokens[s] = None
        self._slot_req[s] = None
        self._slot_chunks[s] = []
        self._slot_mode[s] = "free"

    def _log_dispatch(self, event: str):
        self._dispatch_log.append(event)
        if len(self._dispatch_log) > 2 * _SLO_HISTORY:
            del self._dispatch_log[:_SLO_HISTORY]

    def _publish_pool_gauges(self):
        _, _, free_gauge, util_gauge, _ = _slo_metrics()
        free_gauge.set(float(len(self._free_blocks)))
        util_gauge.set(1.0 - len(self._free_blocks) / max(1, self.num_blocks))

    def _admit_paged(self, now: float):
        """Fill free slots from the queue: alias resident prefix blocks,
        reserve the WHOLE request's worst-case chain up front (prompt chunks
        with bucket padding + max_new - 1 decode slots + 3 windows of
        finish-detection slack), and stage the chunk plan. Up-front
        reservation makes admission the only capacity decision point — decode
        windows can never strand mid-request."""
        free_slots = [s for s in range(self.B) if self._slot_mode[s] == "free"]
        bs = self.block_size
        while free_slots and self._queue:
            req = self._queue[0]
            blocks = self._alias_lookup(req.prompt)
            k = len(blocks)
            remainder = req.prompt[k * bs:]
            chunk_size, escalated = self.prefill_chunk, False
            if (
                self.slo is not None and self.slo.ttft_s is not None
                and now - req.submit_t > 0.5 * self.slo.ttft_s
                and self.buckets[-1] > self.prefill_chunk
            ):
                # TTFT at risk: escalate to the biggest chunk the buckets
                # allow — prefill completes in fewer interleave gaps at the
                # cost of larger per-step decode stalls.
                chunk_size, escalated = self.buckets[-1], True
            chunks = self._plan_chunks(remainder, chunk_size)
            aligned = k * bs + sum(
                c.size if i + 1 < len(chunks) else self._bucket(c.size)
                for i, c in enumerate(chunks)
            )
            need = aligned + (req.max_new - 1) + self._decode_slack
            if escalated and need > self.max_blocks_per_slot * bs:
                # Escalation's extra bucket padding would overflow the static
                # table; fall back to the standard chunk plan.
                chunks = self._plan_chunks(remainder, self.prefill_chunk)
                escalated = False
                aligned = k * bs + sum(
                    c.size if i + 1 < len(chunks) else self._bucket(c.size)
                    for i, c in enumerate(chunks)
                )
                need = aligned + (req.max_new - 1) + self._decode_slack
            if need > self.max_blocks_per_slot * bs:
                raise AssertionError(
                    f"internal: chain need {need} exceeds the static table "
                    f"({self.max_blocks_per_slot} x {bs}) — submit() validation out of sync"
                )
            need_blocks = -(-need // bs) - k
            if need_blocks > len(self._free_blocks):
                break  # backpressure; the loop dead-ends loudly if nothing can free
            self._queue.popleft()
            s = free_slots.pop(0)
            fresh = [self._free_blocks.pop(0) for _ in range(need_blocks)]
            chain = blocks + fresh
            for blk in chain:
                self._block_ref[blk] += 1
            self._tables_np[s, :] = 0
            self._tables_np[s, : len(chain)] = chain
            self._slot_blocks[s] = chain
            self._slot_len[s] = k * bs
            self._slot_base[s] = k * bs  # aliased region is all real tokens
            self._slot_chunks[s] = chunks
            self._slot_tokens[s] = req.prompt
            self._slot_req[s] = req
            self._slot_mode[s] = "prefill"
            self._slo_decisions["admitted"] += 1
            self._slo_decisions["aliased_blocks"] += k
            if len(chunks) > 1:
                self._slo_decisions["chunked_prefills"] += 1
            if escalated:
                self._slo_decisions["escalated_monolithic"] += 1
            if self.tracer is not None:
                self.tracer.admit(
                    req.rid, "escalate" if escalated else "admit",
                    aliased_blocks=k, chunks=len(chunks),
                )
            self._peak_consumed_slots = max(
                self._peak_consumed_slots, self.blocks_in_use * bs
            )

    def _pick_chunk_slot(self, now: float, window_pace: float | None):
        """At most ONE prefill chunk interleaves per engine iteration — the
        bounded-decode-stall contract. SLO pacing: while the observed decode
        window pace is over the TPOT budget, prefill defers (decode keeps
        priority) unless the oldest waiting request's TTFT is itself at
        risk — TTFT outranks TPOT on conflict."""
        slots = [
            s for s in range(self.B)
            if self._slot_mode[s] == "prefill" and self._slot_chunks[s]
        ]
        if not slots:
            return None
        slots.sort(key=lambda s: self._slot_req[s].submit_t)
        s = slots[0]
        if (
            self.slo is not None and self.slo.tpot_s is not None
            and window_pace is not None
            and window_pace > self.slo.tpot_s * self.sync_every
            and any(m == "decode" for m in self._slot_mode)
        ):
            ttft_risk = (
                self.slo.ttft_s is not None
                and now - self._slot_req[s].submit_t > 0.5 * self.slo.ttft_s
            )
            if not ttft_risk:
                self._slo_decisions["deferred_prefills"] += 1
                if self.tracer is not None:
                    self.tracer.defer(self._slot_req[s].rid)
                return None
        return s

    def _next_chunk(self, s: int):
        """``(p, real tokens, final)`` of the chunk slot ``s`` dispatches
        next: a final chunk pads to its bucket, the others are exact."""
        chunks = self._slot_chunks[s]
        size, final = int(chunks[0].size), len(chunks) == 1
        return (self._bucket(size) if final else size), size, final

    def _dispatch_chunk(self, s: int, state):
        p, _, final = self._next_chunk(s)
        chunk = self._slot_chunks[s].pop(0)
        if final:
            row = np.full((p,), self.pad, np.int32)
            mrow = np.zeros((p,), np.int32)
            row[: chunk.size] = chunk
            mrow[: chunk.size] = 1
            # left-align inside the bucket so the last real token sits at
            # p-1 (its logits row seeds the first sampled token)
            row_j, mrow_j = left_align(row[None], mrow[None])
            row_j, mrow_j = row_j[0], mrow_j[0]
        else:  # exact: hole-free, registrable
            row_j = jnp.asarray(chunk)
            mrow_j = jnp.ones((p,), jnp.int32)
        req = self._slot_req[s]
        c0 = int(self._slot_len[s])
        tail = (
            jnp.asarray(self._tables_np),
            jnp.asarray(self._slot_len, dtype=jnp.int32), jnp.int32(s),
            row_j, mrow_j, jnp.int32(self._slot_base[s]), jnp.asarray(final),
            jnp.int32(req.rid), self._rng, jnp.int32(req.max_new),
            jnp.float32(req.temperature), jnp.int32(req.eos),
        )
        if self.speculative_k:
            self._pool, self._draft_pool, state, *counts = self._chunk_fn(p)(
                self.params, self._draft_params, self._pool, self._draft_pool,
                state, *tail,
            )
        else:
            self._pool, state, *counts = self._chunk_fn(p)(
                self.params, self._pool, state, *tail,
            )
        # The model's counts of this chunk (if it names any) stay on the
        # device until a report that was dispatched after them has been read.
        self._chunk_counts = counts[0] if counts else None
        self._sync(state)  # instance fields track the LIVE (post-donation) buffers
        self._log_dispatch(f"chunk:{p}")
        if self.tracer is not None:
            self.tracer.prefill_chunk(req.rid, p, final)
        if not final:
            self._register_shared(s, c0, p)
        # A dense chain took the real tokens alone (its frontier is the
        # token count); otherwise the bucket's padding holds columns too.
        self._slot_len[s] += int(chunk.size) if self._layout["dense_chain"] else p
        self._slot_base[s] += int(chunk.size)
        if final:
            self._slot_mode[s] = "decode"
        return state

    def _dispatch_decode(self, state, force_stop: np.ndarray):
        commit = np.asarray([m == "decode" for m in self._slot_mode], bool)
        window = (self.speculative_k + 1) if self.speculative_k else self.sync_every
        for s in np.nonzero(commit)[0]:
            if self._slot_len[s] + window > len(self._slot_blocks[s]) * self.block_size:
                raise AssertionError(
                    "internal: slot chain reservation exhausted mid-request"
                )
        produced_np = None
        if self.speculative_k:
            (self._pool, self._draft_pool, state, produced,
             report) = self._spec_verify()(
                self.params, self._draft_params, self._pool, self._draft_pool,
                jnp.asarray(self._tables_np),
                jnp.asarray(self._slot_len, dtype=jnp.int32),
                jnp.asarray(commit), jnp.asarray(force_stop), state,
            )
            self._sync(state)
            # The one blocking readback a verify round pays (traded for
            # k-fold fewer target passes): each chain's frontier advances by
            # the slot's COMMITTED count — not advancing past rejected
            # columns IS the block-table truncation.
            produced_np = np.asarray(host_fetch(produced), np.int64)
            self._slot_len += produced_np
            live = produced_np > 0
            proposed = int(live.sum()) * self.speculative_k
            if proposed:
                accepted = int((produced_np[live] - 1).sum())
                self._spec_proposed += proposed
                self._spec_accepted += accepted
                prop_c, acc_c, rate_g = _spec_metrics()
                prop_c.inc(proposed)
                acc_c.inc(accepted)
                rate_g.set(self._spec_accepted / max(1, self._spec_proposed))
            self._log_dispatch(f"verify:{self.speculative_k}")
        else:
            self._pool, state, report = self._decode()(
                self.params, self._pool, jnp.asarray(self._tables_np),
                jnp.asarray(self._slot_len, dtype=jnp.int32), jnp.asarray(commit),
                jnp.asarray(force_stop), state,
            )
            self._sync(state)
            self._slot_len[commit] += self.sync_every
            self._log_dispatch("decode")
        # Tag the report with the occupants it describes: by the time it is
        # processed (one window later), a collected slot may already host a
        # NEW request — its rows in this report belong to the old one.
        req_map = [
            self._slot_req[s].rid if commit[s] and self._slot_req[s] is not None
            else None
            for s in range(self.B)
        ]
        if self.tracer is not None:
            for s, rid in enumerate(req_map):
                if rid is None:
                    continue
                self.tracer.decode_window(rid)
                if produced_np is not None and produced_np[s] > 0:
                    self.tracer.spec_round(
                        rid, proposed=self.speculative_k,
                        accepted=int(produced_np[s] - 1),
                    )
        return state, (report, req_map)

    def _process_report(self, report, force_stop: np.ndarray):
        """Consume one decode window's report (active, n_out, out_buf):
        record first-token times, run the host-side stop-sequence scan
        (verdicts ride ``force_stop`` into the NEXT window), collect finished
        requests, and free their chains. The report was optimization-
        barrier'd out of the donated state, so reading it here — after the
        next window was already dispatched — is the non-blocking sync.
        ``serve.report_wait`` is that first read alone: how long the host
        was blocked on the device (its end is the moment the window's report
        became ready, where the host waited at all)."""
        report, req_map, dispatched, chunks = report
        with self._span("serve.report_wait"):
            active_np = host_fetch(report[0]).copy()
        # What the model counted (cache_layout "counters"), on the spans that
        # dispatched the programs (the ring holds the records themselves):
        # read here, with the report, and the chunks' that ran before it.
        counters = self._layout["counters"]
        if len(report) > 3:
            self._set_counts(dispatched, counters["decode"], report[3])
        for rec, counts in chunks:
            self._set_counts(rec, counters["chunk"], counts)
        with self._span("serve.process_report") as rec:
            rec.attrs["tokens"], rec.attrs["finished"] = self._consume_report(
                report, req_map, active_np, force_stop)

    @staticmethod
    def _set_counts(rec, names, counts):
        rec.attrs.update(zip(names, map(float, host_fetch(counts))))

    def _consume_report(self, report, req_map, active_np, force_stop: np.ndarray):
        """``_process_report`` past its first read. Returns the tokens this
        report handed out (stream deltas, and at a finish the rest of the
        truncated output, so over a request they sum to what it returned)
        and the requests it finished."""
        n_np = host_fetch(report[1])
        out_np = None
        emitted = finished = 0
        now = time.monotonic()
        for s in range(self.B):
            req = self._slot_req[s]
            if (
                req is None or self._slot_mode[s] != "decode"
                or req_map[s] != req.rid
            ):
                # Slot was empty at dispatch, or has been refilled since —
                # this report's row describes the previous occupant.
                continue
            times = self._req_times.get(req.rid)
            if times is not None and "first_token" not in times and n_np[s] >= 1:
                times["first_token"] = now
                if self.tracer is not None:
                    self.tracer.first_token(req.rid, at=now)
            if self.stream is not None and active_np[s]:
                # Per-window token deltas for the SSE front end, read off the
                # SAME one-window-late report the stop scan and collection
                # already fetch — streaming adds no sync point. Deltas are
                # pre-truncation (a multi-token stop lands one window late,
                # the cadence caveat submit() documents); the FINAL event
                # from _finish carries the authoritative output.
                if out_np is None:
                    out_np = host_fetch(report[2])
                done = self._streamed.get(req.rid, 0)
                n = int(n_np[s])
                if n > done:
                    self._emit_stream(req.rid, out_np[s][done:n].copy(), False)
                    self._streamed[req.rid] = n
                    emitted += n - done
            if active_np[s] and req.stop:
                if out_np is None:
                    out_np = host_fetch(report[2])
                if _first_stop_end(out_np[s][: int(n_np[s])], req.stop) is not None:
                    force_stop[s] = True
            if not active_np[s]:
                if out_np is None:
                    out_np = host_fetch(report[2])
                streamed = self._streamed.get(req.rid, 0)
                self._finish(req, out_np[s][: int(n_np[s])])
                self._free_chain(s)
                emitted += int(self._results[req.rid].size) - streamed
                finished += 1
        self._publish_pool_gauges()
        return emitted, finished

    def run(self) -> dict[int, np.ndarray]:
        """Drive admits + decode until the queue drains and all slots finish.
        Returns THIS wave's results only: {request_id: generated token ids
        (eos included, no pads)} for every request finished during the call.

        The engine loop: per iteration, admit; dispatch at most ONE
        prefill chunk; dispatch one decode window; then process the
        PREVIOUS window's report — a one-window lookahead, so the window
        just dispatched overlaps all host work including the report fetch
        (zero blocking transfers in steady state, pinned by tests). Decode
        stall per iteration is bounded by one chunk's compute instead of one
        prompt's — the chunked-prefill contract. The wave is one
        ``serve.run`` span and each turn one ``serve.iteration`` span whose
        children are the steps above."""
        with self._span("serve.run") as wave:
            state = self._state_tuple()
            pending = None
            counted_chunks = []  # (span record, counts on the device) since the last window
            force_stop = np.zeros((self.B,), bool)
            last_dispatch_t = None
            window_pace = None
            window = (self.speculative_k + 1) if self.speculative_k else self.sync_every
            view_cols_full = self.max_blocks_per_slot * self.block_size
            while True:
                with self._span("serve.iteration") as turn:
                    now = time.monotonic()
                    with self._span("serve.admit") as rec:
                        admitted = self._slo_decisions["admitted"]
                        self._admit_paged(now)
                        rec.attrs["admitted"] = self._slo_decisions["admitted"] - admitted
                    chunk_slot = self._pick_chunk_slot(now, window_pace)
                    chunk_p = 0
                    if chunk_slot is not None:
                        chunk_p, tokens, final = self._next_chunk(chunk_slot)
                        with self._span("serve.dispatch_chunk", rid=self._slot_req[chunk_slot].rid,
                                        p=chunk_p, tokens=tokens, final=final,
                                        rows_computed=chunk_p) as rec:
                            state = self._dispatch_chunk(chunk_slot, state)
                        if self._chunk_counts is not None:
                            counted_chunks.append((rec, self._chunk_counts))
                    decoding = sum(m == "decode" for m in self._slot_mode)
                    turn.attrs.update(
                        chunk=chunk_p, decoding=decoding,
                        prefilling=sum(m == "prefill" for m in self._slot_mode),
                        queued=len(self._queue), free_blocks=len(self._free_blocks))
                    new_pending = None
                    if decoding:
                        with self._span("serve.dispatch_decode", decoding=decoding,
                                        slots=self.B, window=window,
                                        view_cols=self._view_cols(),
                                        view_cols_full=view_cols_full) as rec:
                            state, new_pending = self._dispatch_decode(state, force_stop)
                        new_pending += (rec, counted_chunks)
                        counted_chunks = []
                        force_stop[:] = False
                        t = time.monotonic()
                        if last_dispatch_t is not None:
                            dt = t - last_dispatch_t
                            window_pace = dt if window_pace is None else 0.5 * window_pace + 0.5 * dt
                        last_dispatch_t = t
                    if pending is not None:
                        self._process_report(pending, force_stop)
                    pending = new_pending
                    if pending is None and chunk_slot is None and not decoding:
                        if self._queue:
                            if any(m != "free" for m in self._slot_mode):
                                continue
                            raise RuntimeError(
                                f"KV pool capacity exhausted ({len(self._free_blocks)} of "
                                f"{self.num_blocks} blocks free; the next request needs "
                                "more); raise max_cache_len/num_blocks, or catch this, "
                                "reset(), and run() again."
                            )
                        if all(m == "free" for m in self._slot_mode):
                            break
            self._sync(state)
            for rec, counts in counted_chunks:  # chunks that no window followed
                self._set_counts(rec, self._layout["counters"]["chunk"], counts)
            self._publish_pool_gauges()
            results, self._results = self._results, {}
            wave.attrs["finished"] = len(results)
        return {rid: results[rid] for rid in sorted(results)}
