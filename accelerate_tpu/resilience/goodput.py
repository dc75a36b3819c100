"""Goodput accounting — where did the wall-clock go?

At pod scale the question that decides cost is not "how fast is a step" but
"what fraction of the job's wall-clock was spent stepping". Everything else —
XLA compiles, checkpoint saves, restores after a preemption, restart backoff —
is *badput*: time the chips were reserved but no tokens were trained. This
module keeps one process-wide ledger that the rest of the framework feeds
(``checkpointing`` times saves/restores, ``run_resilient`` times restart
downtime, ``telemetry/spans.py`` books each program's trace, lowering and
compile as JAX reports them, ``bench.py`` times steps) and that surfaces in two
places: ``Accelerator.log_goodput()`` pushes the breakdown through the normal
tracker path, and ``bench.py`` embeds it in its JSON lines. The telemetry
registry (telemetry/metrics.py) additionally exports the summary as
``accelerate_goodput_*``/``accelerate_badput_seconds`` gauges via a
scrape-time collector, so the Prometheus endpoint and ``log_telemetry`` see
the same numbers with zero per-step cost.

The categories follow the goodput decomposition used by large TPU trainers
(productive step time vs program-acquisition and checkpoint overheads): one
goodput bucket (``step``) and nine badput buckets — ``compile``, ``ckpt_save``,
``ckpt_restore``, ``restart``, the health subsystem's ``rollback``
(last-known-good restores after a NaN/loss-spike trip, health/rollback.py) and
``hang`` (time a wedged run sat before the watchdog fired, health/hang.py),
plus ``reshard`` (elastic world-size transitions, resilience/elastic.py),
``profile`` (trace-capture start/stop/parse overhead, telemetry/profiler.py),
and ``tune`` (the autotuner's short-bench trials, tune/trials.py — reserved
chip time spent measuring candidate configs, not training).  Wall-clock not
attributed to any bucket is reported as ``other_s`` (data feeding, host-side
logging, eval, idle).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

GOODPUT_CATEGORY = "step"
# ``reshard`` is the elastic world-size transition (resilience/elastic.py):
# re-forming the mesh at a new dp degree and redistributing params/opt-state
# onto it — voluntary downtime, booked separately from crash ``restart``s.
# ``profile`` is trace-capture overhead (telemetry/profiler.py): starting/
# stopping an XLA trace and parsing it into the attribution report — booked so
# a profiled run's goodput/MFU accounting stays honest about what the
# diagnosis itself cost.
# ``tune`` is autotuner trial time (tune/trials.py): the whole wall-clock of a
# candidate's short-bench — build, compile, warmup, and measured steps — so
# trial steps never count as productive training and can't inflate MFU/goodput.
BADPUT_CATEGORIES = (
    "compile", "ckpt_save", "ckpt_restore", "restart", "rollback", "hang",
    "reshard", "profile", "tune",
)
CATEGORIES = (GOODPUT_CATEGORY,) + BADPUT_CATEGORIES


class GoodputLedger:
    """Wall-clock classifier. All methods are thread-safe (orbax background
    writers and async hosts may report concurrently with the train loop)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        """Start a fresh accounting window (bench.py calls this per config)."""
        with self._lock:
            self._t0 = time.perf_counter()
            self.seconds = {c: 0.0 for c in CATEGORIES}
            self.counts = {c: 0 for c in CATEGORIES}
            self.restarts = 0

    # ------------------------------------------------------------- recording
    def add(self, category: str, seconds: float, count: int = 1):
        if category not in CATEGORIES:
            raise ValueError(f"unknown goodput category {category!r}; choose from {CATEGORIES}")
        with self._lock:
            self.seconds[category] += float(seconds)
            self.counts[category] += count
        # Durable delta (telemetry/journal.py): badput transitions (compiles,
        # checkpoint saves/restores, resharding, profiling overhead) land in
        # the per-host journal as they happen, so the fleet timeline renders
        # where the wall-clock went. ``step`` is excluded — the telemetry
        # hook journals every step boundary already, richer.
        if category != GOODPUT_CATEGORY:
            try:
                from ..telemetry.journal import journal_event

                journal_event("goodput", category=category,
                              seconds=round(float(seconds), 6), count=count)
            except Exception:
                pass

    @contextmanager
    def track(self, category: str):
        """Attribute the wall-clock of a ``with`` block to ``category``."""
        if category not in CATEGORIES:
            raise ValueError(f"unknown goodput category {category!r}; choose from {CATEGORIES}")
        t = time.perf_counter()
        try:
            yield
        finally:
            self.add(category, time.perf_counter() - t)

    def record_step(self, seconds: float, steps: int = 1):
        self.add(GOODPUT_CATEGORY, seconds, count=steps)

    def record_restart(self, downtime_s: float = 0.0):
        with self._lock:
            self.restarts += 1
            self.seconds["restart"] += float(downtime_s)
            self.counts["restart"] += 1
        try:
            from ..telemetry.journal import journal_event

            journal_event("goodput", category="restart",
                          seconds=round(float(downtime_s), 6), count=1)
        except Exception:
            pass

    def mark_process_start(self, attempt: int = 0):
        """Called by ``PartialState`` at process birth: a nonzero
        ACCELERATE_RESTART_ATTEMPT means the launcher relaunched the gang —
        count those incarnations even though their downtime was paid in a
        previous process we cannot measure from here."""
        if attempt > 0:
            with self._lock:
                self.restarts = max(self.restarts, int(attempt))

    # --------------------------------------------------------------- reading
    @property
    def wall_s(self) -> float:
        return time.perf_counter() - self._t0

    def summary(self) -> dict:
        """Flat goodput/badput breakdown — the schema shared by
        ``Accelerator.log_goodput()`` and ``bench.py``'s JSON lines."""
        with self._lock:
            wall = max(time.perf_counter() - self._t0, 1e-9)
            productive = self.seconds[GOODPUT_CATEGORY]
            badput = sum(self.seconds[c] for c in BADPUT_CATEGORIES)
            out = {
                "goodput_fraction": round(min(productive / wall, 1.0), 4),
                "badput_fraction": round(min(badput / wall, 1.0), 4),
                "wall_s": round(wall, 3),
                "productive_s": round(productive, 3),
                "badput_s": round(badput, 3),
                "other_s": round(max(wall - productive - badput, 0.0), 3),
                "steps": self.counts[GOODPUT_CATEGORY],
                "restarts": self.restarts,
            }
            for c in BADPUT_CATEGORIES:
                out[f"{c}_s"] = round(self.seconds[c], 3)
            return out


_LEDGER = GoodputLedger()


def get_ledger() -> GoodputLedger:
    """The process-wide ledger every layer reports into."""
    return _LEDGER
