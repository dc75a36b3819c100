"""chipbench: the benchmark's one command.

    python3 chipbench/run.py --workload W --seed N --seconds S --trace 0|1

Everything about a cell is data that this file finds by name:

- the cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
  traffic mix;
- the configuration's file (``configs[].file``) holds the model's sizes and how
  the program is set up for it;
- ``chipbench/traffic/<traffic>.json`` holds the mix; its ``kind`` picks
  ``chipbench/runners/<kind>.py``;
- each per-layer metric of the cell is ``chipbench/layer_metrics/<name>.py``
  with ``compute(record) -> float | None``.

A later PR adds a cell by adding files and entries, and edits nothing here.

The last line of standard output is the one JSON object the contract asks
for (its last key, ``checks``, holds each number that ``correct`` compared
beside its limit; the same goes, one check a line, last on standard error);
everything else goes on earlier lines. Off the TPU, or with fewer chips
than the cell asks for, the command exits non-zero and prints no result.
``--rehearse`` takes its cells from ``chipbench/rehearse/`` instead, runs on
whatever backend is there, and prints every metric as ``null``: a rehearsal of
control flow, never a measurement.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def say(**fields):
    """One line of commentary (never the last line of output)."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"chipbench: no {what} named {name!r}")


def load_cell(workload: str, rehearse: bool) -> dict:
    """The cell's entry, its configuration, its traffic mix and the metrics it
    reports, from ``BENCHMARK.json`` (a rehearsal's from ``rehearse/cells.json``)."""
    traffic_dir = os.path.join(HERE, "rehearse" if rehearse else "", "traffic")
    spec = (load_json(HERE, "rehearse", "cells.json") if rehearse
            else load_json(ROOT, "BENCHMARK.json"))
    cell = find(spec["workloads"], workload, "workload")
    config_entry = find(spec["configs"], cell["config"], "configuration")
    in_cell = lambda m: "workloads" not in m or workload in m["workloads"]
    return {
        "cell": cell,
        "config": load_json(ROOT, config_entry["file"]),
        "traffic": load_json(traffic_dir, cell["traffic"] + ".json"),
        "end_to_end": [m for m in spec["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in spec["per_layer"] if in_cell(m)],
    }


def layer_metric(name: str):
    """``compute`` of ``chipbench/layer_metrics/<name>.py`` (names may hold
    dots, so the file is loaded by path)."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def read_layer_metric(name: str, record: dict, rehearse: bool):
    """The reader's value, or None where it finds nothing to read. A rehearsal
    runs the reader for its control flow and forgives a device it cannot know."""
    try:
        return layer_metric(name)(record)
    except KeyError:
        if rehearse:
            return None
        raise


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed place: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``. Every
    program is kept, however quick its compile."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


class CompileCounter:
    """Counts the programs JAX compiles or loads, by its own monitoring event,
    so that a runner can show that none came inside the measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="with --trace 1: also copy the profiler's .xplane.pb into DIR")
    parser.add_argument("--control", default=None, metavar="NAME",
                        help="lay chipbench/controls/NAME.json over the cell's configuration: a run "
                             "that has to come out not correct, never one of the benchmark's own")
    parser.add_argument("--rehearse", action="store_true",
                        help="cells of chipbench/rehearse/ on any backend; metrics print as null")
    args = parser.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    loaded = load_cell(args.workload, args.rehearse)
    cell, traffic = loaded["cell"], loaded["traffic"]
    if args.control:
        for key, over in load_json(HERE, "controls", args.control + ".json")["config"].items():
            loaded["config"][key] = {**loaded["config"].get(key, {}), **over}

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chipbench: needs a TPU, JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: the cell needs {cell['chips']} chips, JAX reports "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[: cell["chips"]]

    import accelerate_tpu

    if os.path.dirname(os.path.abspath(accelerate_tpu.__file__)) != os.path.join(ROOT, "accelerate_tpu"):
        print("chipbench: accelerate_tpu was not imported from this checkout", file=sys.stderr)
        return 2

    cache_dir = enable_compile_cache()
    cache_before = len(os.listdir(cache_dir))
    say(phase="start", workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=args.rehearse, control=args.control, device=device, jax=jax.__version__,
        compile_cache_dir=cache_dir, compile_cache_entries=cache_before)

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace else None
    runner = importlib.import_module(f"chipbench.runners.{traffic['kind']}")
    try:
        result = runner.run({
            "config": loaded["config"], "traffic": traffic, "cell": cell,
            "seed": args.seed, "seconds": args.seconds, "trace_dir": trace_dir,
            "rehearse": args.rehearse, "devices": devices, "say": say,
            "process_start": PROCESS_START, "compiles": CompileCounter(),
        })
        record = result["record"]
        record.update(config=loaded["config"], traffic=traffic, device=device,
                      end_to_end=result["end_to_end"], trace=None)
        if trace_dir:
            from chipbench import trace_reduce

            xplane = trace_reduce.find_xplane(trace_dir)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(xplane, args.keep_trace)
            record["trace"] = trace_reduce.reduce(trace_reduce.load(xplane))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # A runner reads the devices' peak itself where its reference check runs after the window.
    from chipbench import program

    in_use = result.get("peak_bytes_in_use") or program.peak_bytes_in_use(devices)
    device["memory_peak_bytes"] = int(max(result.get("compiled_peak_bytes", 0), in_use))
    units = {m["name"]: m["unit"] for m in loaded["end_to_end"] + loaded["per_layer"]}
    if args.trace:
        values = {m["name"]: read_layer_metric(m["name"], record, args.rehearse)
                  for m in loaded["per_layer"]}
        reduced = record["trace"]
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
    else:
        values = {m["name"]: result["end_to_end"].get(m["name"]) for m in loaded["end_to_end"]}
    if args.rehearse:  # a CPU number never stands under a device metric's name
        metrics = {name: {"value": None, "unit": units[name]} for name in values}
    else:
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in values.items() if value is not None}

    say(phase="end", checks=result["checks"], total_s=time.perf_counter() - PROCESS_START,
        compile_cache_entries_before=cache_before,
        compile_cache_entries_after=len(os.listdir(cache_dir)))
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                             "idle_gaps": record["trace"]["idle_gaps"]}
    # What ``correct`` compared, each number beside its limit: last on the line
    # and, one check a line, last on standard error.
    line["checks"] = result["checks"]
    for name, check in result["checks"].items():
        print(f"check {name}: {json.dumps(check, default=str)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, default=str), flush=True)
    if result.get("hard_exit"):
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
