"""Process/device state singletons — the L2 layer.

Reference parity (``src/accelerate/state.py``):

- ``PartialState`` (:124) — joins the distributed job, discovers rank/world, selects
  the device, and offers process-control helpers (``wait_for_everyone`` :366,
  ``split_between_processes`` :414, ``main_process_first`` :505, on_*_process
  decorators). There the collective world is a torch.distributed process group
  chosen at :743-809 (nccl/gloo/xla/...); here it is the JAX distributed runtime
  (``jax.distributed.initialize``) plus a ``jax.sharding.Mesh`` whose named axes
  carry every parallelism strategy (see ``parallel/mesh.py``).
- ``AcceleratorState`` (:860) — layers mixed-precision and parallelism config on
  top, mutating ``distributed_type`` the way the reference does for
  DEEPSPEED/FSDP/MEGATRON/TP (:957-989).
- ``GradientState`` (:1204) — gradient-accumulation bookkeeping shared between
  ``Accelerator``, dataloaders, optimizer and scheduler wrappers. The reference's
  ``xm.mark_step`` XLA flush (:1297-1306) has no JAX analog: step boundaries are
  the jitted-function boundary.

All three use the borg pattern (``self.__dict__ = self._shared_state``, reference
:163,179) so every constructor call observes one process-wide state.

A note on "process": in the reference one rank == one GPU. In JAX one *process*
(host) owns many local devices, and arrays are global across all processes. Process
helpers here therefore operate at host granularity — the correct unit for host-side
work (data feeding, logging, checkpoint I/O) — while per-device work is expressed
through shardings on the mesh, not per-rank Python.
"""

from __future__ import annotations

import logging
import os
import weakref
from contextlib import contextmanager
from enum import Enum
from functools import wraps
from typing import Callable

import numpy as np

import jax

from .parallel.mesh import ParallelismConfig, batch_sharding_size
from .utils.constants import (
    ENV_COORDINATOR,
    ENV_CPU,
    ENV_DEBUG_MODE,
    ENV_FLEET_METRICS,
    ENV_HANDLE_PREEMPTION,
    ENV_HANG_TIMEOUT,
    ENV_METRICS_PORT,
    ENV_MIXED_PRECISION,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
    ENV_RESTART_ATTEMPT,
)
from .utils.environment import (
    maybe_enable_compilation_cache,
    parse_choice_from_env,
    parse_flag_from_env,
)

logger = logging.getLogger(__name__)


class DistributedType(str, Enum):
    """Topology/engine marker, mirroring the reference enum's role
    (``utils/dataclasses.py:554-589``) with TPU-native values.

    ``JAX_TPU``/``JAX_GPU``/``MULTI_CPU`` describe the launch topology; plugin
    configuration mutates ``AcceleratorState.distributed_type`` to the strategy
    values (``FSDP``/``TP``/``MEGATRON_STYLE``) exactly like the reference mutates
    to DEEPSPEED/FSDP/MEGATRON_LM/TP at ``state.py:957-989``.
    """

    NO = "NO"
    MULTI_CPU = "MULTI_CPU"
    JAX_TPU = "JAX_TPU"
    JAX_GPU = "JAX_GPU"
    FSDP = "FSDP"  # fsdp axis > 1 (≈ FSDP2 full-shard / ZeRO-3)
    TP = "TP"  # tp axis > 1
    MEGATRON_STYLE = "MEGATRON_STYLE"  # composed tp×pp×dp (3-D)


def is_initialized() -> bool:
    """Whether ``PartialState`` has been constructed (reference ``PartialState().initialized``)."""
    return PartialState._shared_state != {}


def _maybe_init_jax_distributed() -> None:
    """Join the multi-host job if the launcher set the env contract.

    The reference's analog is ``init_process_group`` at ``state.py:233,274`` (the
    NCCL/gloo rendezvous). Here the coordinator is the JAX distributed service;
    collectives themselves are compiled by XLA onto ICI/DCN, not brokered by this
    process group.
    """
    coordinator = os.environ.get(ENV_COORDINATOR)
    num_processes = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
    if coordinator is None or num_processes <= 1:
        return
    if jax._src.distributed.global_state.client is not None:  # already initialized
        return
    process_id = int(os.environ.get(ENV_PROCESS_ID, "0"))
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


class PartialState:
    """Singleton owning process/device discovery and the default mesh.

    Reference: ``state.py:124`` (ctor :178-317).
    """

    _shared_state: dict = {}
    _known_attrs = [
        "_cpu",
        "backend",
        "device",
        "debug",
        "distributed_type",
        "fork_launched",
        "local_process_index",
        "num_processes",
        "process_index",
        "_mesh",
        "_parallelism_config",
        "_metrics_endpoint",
    ]

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        self._cpu = cpu or parse_flag_from_env(ENV_CPU)
        self.debug = parse_flag_from_env(ENV_DEBUG_MODE)
        if self._cpu:
            # Force the host platform BEFORE any backend/distributed init so
            # multi-process rendezvous aggregates CPU devices, not accelerator
            # plugins (reference `cpu=True` semantics, state.py:295-307).
            try:
                jax.config.update("jax_platforms", "cpu")
            except Exception:
                logger.warning("cpu=True requested but platform switch failed")
        # XLA latency-hiding preset (ACCELERATE_XLA_PRESET): merged into
        # LIBTPU_INIT_ARGS before ANY backend creation below — libtpu reads
        # the variable once at init, so this must precede the compilation
        # cache config, the distributed rendezvous, and default_backend().
        from .utils.xla_flags import install_preset_from_env

        install_preset_from_env()
        # Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else
        # ACCELERATE_COMPILE_CACHE_DIR): configured before the first compile
        # so restarted jobs load their programs instead of re-building them.
        maybe_enable_compilation_cache()
        _maybe_init_jax_distributed()
        # Resilience wiring (resilience/): count this gang incarnation in the
        # goodput ledger (the launcher increments ACCELERATE_RESTART_ATTEMPT on
        # every relaunch), and install the preemption watcher EARLY when the
        # launch contract asks for it — a SIGTERM during the first compile or
        # data-loader warmup must set the sticky flag, not kill the process.
        from .resilience.goodput import get_ledger

        get_ledger().mark_process_start(
            attempt=int(os.environ.get(ENV_RESTART_ATTEMPT, "0") or 0)
        )
        if parse_flag_from_env(ENV_HANDLE_PREEMPTION):
            from .resilience.preemption import get_default_watcher

            get_default_watcher(install=True)
        # Hang watchdog (health/hang.py): started here so it guards the whole
        # process life; it only arms on the first step heartbeat, so a long
        # first compile cannot false-positive.
        hang_timeout = os.environ.get(ENV_HANG_TIMEOUT, "").strip()
        if hang_timeout:
            from .health.hang import install_default_watchdog

            try:
                install_default_watchdog(float(hang_timeout))
            except ValueError:
                raise ValueError(
                    f"{ENV_HANG_TIMEOUT}={hang_timeout!r} must be a positive "
                    "number of seconds"
                ) from None
        platform = jax.default_backend()
        if self._cpu and platform != "cpu":
            logger.warning(
                "cpu=True requested but backend resolved to %s; "
                "set jax.config jax_platforms='cpu' before any backend use.",
                platform,
            )
        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        # Host-local index: with one process per host this equals process_index
        # modulo per-node layout; JAX does not expose a node rank, so launchers set
        # ACCELERATE_LOCAL_PROCESS_ID when it differs.
        self.local_process_index = int(
            os.environ.get("ACCELERATE_LOCAL_PROCESS_ID", self.process_index)
        )
        self.device = jax.local_devices()[0]
        self.backend = platform
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED", 0)
        if platform == "tpu":
            self.distributed_type = DistributedType.JAX_TPU
        elif platform == "gpu":
            self.distributed_type = DistributedType.JAX_GPU
        elif jax.device_count() > 1 or self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_CPU
        else:
            self.distributed_type = DistributedType.NO
        self._mesh = None
        self._parallelism_config = None
        # Telemetry wiring (telemetry/): the opt-in Prometheus endpoint starts
        # at init — like the watchdog, it must serve for the whole process
        # life, including a multi-minute first compile — while the timeline/
        # straggler pieces build lazily on first Accelerator.telemetry access.
        # After process discovery so co-located workers (the CPU-sim gang)
        # offset the port by their local rank instead of fighting for one
        # bind; the shared helper degrades a bind failure to a warning.
        self._metrics_endpoint = None
        # Disaggregated-serving tier membership (serving_net/roles.py): the
        # role is a launch-time property of the HOST — resolved here once so
        # commands, the serving frontend, and the fleet plane all agree —
        # and published as a labeled gauge so /fleet rows carry the tier
        # before any engine or frontend exists (warmup is visible per tier).
        from .serving_net.roles import resolve_serving_role

        self.serving_role = resolve_serving_role()
        if os.environ.get(ENV_METRICS_PORT, "").strip():
            from .telemetry import start_endpoint_from_env

            server = start_endpoint_from_env(self.local_process_index)
            if server is not None:
                # Publish the ACTUALLY bound host:port (the local-rank port
                # offset and ephemeral binds included) into the fleet KV
                # registry, so the aggregator, straggler warnings, and
                # operators read the real address instead of guessing it
                # from the env contract (telemetry/fleet.py).
                from .telemetry.fleet import publish_metrics_endpoint

                self._metrics_endpoint = publish_metrics_endpoint(
                    process_index=self.process_index, server=server
                )
                if self.serving_role.name != "unified":
                    from .telemetry.metrics import get_registry

                    get_registry().gauge(
                        "accelerate_serving_role",
                        "Serving tier this process runs (1 = the labeled role)",
                        labelnames=("role",),
                    ).set(1, role=self.serving_role.name)
                # Fleet aggregation plane (ACCELERATE_FLEET_METRICS): the
                # lead host scrapes every registered endpoint and serves the
                # joined series + rollups at /fleet on this same server.
                if parse_flag_from_env(ENV_FLEET_METRICS) and self.process_index == 0:
                    from .telemetry.fleet import (
                        FleetAggregator,
                        install_fleet_provider,
                    )

                    install_fleet_provider(FleetAggregator(state=self))

    def __repr__(self) -> str:
        return (
            f"Distributed environment: {self.distributed_type.value}  Backend: {self.backend}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local process index: {self.local_process_index}\n"
            f"Device: {self.device}\n"
            f"Local devices: {jax.local_device_count()}  Global devices: {jax.device_count()}\n"
        )

    @classmethod
    def _reset_state(cls):
        """Reset singleton state — for testing (reference ``state.py:1188``)."""
        cls._shared_state.clear()

    @property
    def initialized(self) -> bool:
        return self._shared_state != {}

    # ---------------------------------------------------------------- topology
    @property
    def use_distributed(self) -> bool:
        """True when more than one device participates (reference :334-340 checks
        num_processes > 1; a single JAX process driving 8 chips is distributed in
        every sense that matters here)."""
        return self.num_devices > 1

    @property
    def num_devices(self) -> int:
        return jax.device_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def metrics_endpoint(self) -> str | None:
        """The metrics endpoint this worker ACTUALLY serves (``host:port``,
        bound port — ephemeral binds and the co-located-worker port offset
        included), published into the fleet KV registry at init; None when no
        endpoint is configured (telemetry/fleet.py)."""
        return self.__dict__.get("_metrics_endpoint")

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # ------------------------------------------------------------------- mesh
    @property
    def mesh(self):
        """The default mesh: all devices on the ``dp`` axis. ``AcceleratorState``
        replaces this with the plugin-configured mesh."""
        if self._mesh is None:
            self._mesh = ParallelismConfig().build_mesh()
        return self._mesh

    def set_mesh(self, mesh, parallelism_config: ParallelismConfig | None = None):
        self._mesh = mesh
        self._parallelism_config = parallelism_config

    @property
    def parallelism_config(self) -> ParallelismConfig | None:
        return self._parallelism_config

    # -------------------------------------------------------- process control
    def wait_for_everyone(self):
        """Cross-host barrier (reference :366-402). No-op single-process; on a pod
        this synchronizes via a tiny global collective, the multihost_utils idiom."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    def _goes_first(self, is_main: bool):
        if not is_main:
            self.wait_for_everyone()
        yield
        if is_main:
            self.wait_for_everyone()

    @contextmanager
    def main_process_first(self):
        """Main process runs the block first, others wait (reference :505)."""
        yield from self._goes_first(self.is_main_process)

    @contextmanager
    def local_main_process_first(self):
        yield from self._goes_first(self.is_local_main_process)

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array evenly across processes (reference :414-504).

        When the length does not divide evenly, the first ``length % num_processes``
        processes receive one extra element. With ``apply_padding``, short shards
        are padded with the *global* final element so every process gets the same
        length (needed before global collectives with static shapes).
        """
        if self.num_processes == 1:
            yield inputs
            return
        if isinstance(inputs, dict):
            # Split each value's rows, not the dict's keys (reference :447-455).
            lengths = {k: len(v) for k, v in inputs.items()}
            if not lengths:
                yield inputs
                return
            if len(set(lengths.values())) > 1:
                raise ValueError(
                    f"All dict values must share a length to split; got {lengths}"
                )
            length = next(iter(lengths.values()))
        else:
            length = len(inputs)
        split_sizes = [length // self.num_processes] * self.num_processes
        for i in range(length % self.num_processes):
            split_sizes[i] += 1
        start = sum(split_sizes[: self.process_index])
        end = start + split_sizes[self.process_index]

        if isinstance(inputs, dict):
            shard = {k: v[start:end] for k, v in inputs.items()}
        else:
            shard = inputs[start:end]
        if apply_padding and split_sizes[self.process_index] < max(split_sizes):
            pad = max(split_sizes) - split_sizes[self.process_index]
            if isinstance(inputs, dict):
                # Pad with the global last row so even empty shards become rectangular.
                shard = {k: _pad_with_last(shard[k], pad, fallback=inputs[k]) for k in inputs}
            else:
                shard = _pad_with_last(shard, pad, fallback=inputs)
        yield shard

    def on_main_process(self, function: Callable = None):
        """Decorator: run only on the main process (reference :531)."""

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable = None):
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable):
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None):
        if function is None:
            return lambda f: self.on_process(f, process_index)

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def on_local_process(self, function: Callable = None, local_process_index: int = None):
        if function is None:
            return lambda f: self.on_local_process(f, local_process_index)

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return wrapper

    def print(self, *args, **kwargs):
        if self.is_local_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self):
        """Leave the distributed job (reference ``destroy_process_group`` :747)."""
        if jax._src.distributed.global_state.client is not None:
            jax.distributed.shutdown()

    def __getattr__(self, name: str):
        if name in self._known_attrs:
            raise AttributeError(
                f"`PartialState` object has no attribute `{name}`. "
                "This happens if `PartialState._reset_state()` was called and "
                "an `Accelerator` or `PartialState` was not reinitialized."
            )
        raise AttributeError(f"'PartialState' object has no attribute '{name}'")


def _pad_with_last(seq, pad: int, fallback=None):
    """Pad ``seq`` with ``pad`` copies of its last element; an empty shard borrows
    the last element of ``fallback`` (the full input) so it still pads."""
    source = seq if len(seq) else fallback
    if isinstance(seq, np.ndarray) or hasattr(seq, "shape"):
        reps = [np.asarray(source[-1:])] * pad
        return np.concatenate([np.asarray(seq), *reps], axis=0) if len(seq) else np.concatenate(reps, axis=0)
    return list(seq) + [source[-1]] * pad


class AcceleratorState:
    """Adds mixed precision + parallelism configuration on top of ``PartialState``.

    Reference: ``state.py:860`` (ctor :890-1008). The distributed_type mutation for
    FSDP/TP/Megatron (:957-989) is mirrored: a non-trivial ``ParallelismConfig``
    rewrites ``distributed_type`` so downstream code can branch the same way user
    code does in the reference ecosystem.
    """

    _shared_state: dict = {}
    _known_attrs = PartialState._known_attrs + [
        "mixed_precision",
        "dynamo_plugin",
        "use_ipex",
        "parallelism_config",
    ]

    def __init__(
        self,
        mixed_precision: str | None = None,
        cpu: bool = False,
        parallelism_config: ParallelismConfig | None = None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if parallelism_config is not None and self.parallelism_config != parallelism_config:
                raise ValueError(
                    "AcceleratorState already initialized with a different parallelism_config; "
                    "call AcceleratorState._reset_state() first."
                )
            if mixed_precision is not None and mixed_precision != self._mixed_precision:
                logger.warning(
                    "AcceleratorState already initialized; mixed_precision=%s ignored "
                    "(currently %s).",
                    mixed_precision,
                    self._mixed_precision,
                )
            return
        # Validate everything fallible BEFORE touching the borg shared dict, so a
        # failed construction doesn't leave a half-initialized singleton behind.
        mixed_precision = (
            parse_choice_from_env(ENV_MIXED_PRECISION, "no")
            if mixed_precision is None
            else str(mixed_precision)
        )
        if mixed_precision not in ("no", "bf16", "fp16", "fp8"):
            raise ValueError(
                f"Unknown mixed_precision mode: {mixed_precision!r}; choose from no/bf16/fp16/fp8"
            )
        if mixed_precision == "fp8":
            logger.warning(
                "fp8 requested: TPU generations through v5p have no fp8 ALUs; falling "
                "back to int8-quantized matmuls where configured, bf16 elsewhere."
            )
        if parallelism_config is None:
            parallelism_config = ParallelismConfig.from_env()
        # Build everything in locals first: mesh-shape validation errors must not
        # leave a half-initialized AcceleratorState singleton behind.
        partial = PartialState(cpu=cpu, **kwargs)
        mesh = parallelism_config.build_mesh()
        # Read sizes off the built mesh: it is the source of truth once slice
        # auto-detection (dcn) has resolved against the real device set.
        sizes = dict(mesh.shape)

        self._partial = partial
        # Share the dict contents: expose PartialState attrs through this object.
        for key, value in self._partial.__dict__.items():
            if key not in self.__dict__:
                self.__dict__[key] = value
        self._mixed_precision = mixed_precision
        self.parallelism_config = parallelism_config
        self._partial.set_mesh(mesh, parallelism_config)
        self.__dict__["_mesh"] = mesh

        # distributed_type mutation, mirroring reference state.py:957-989
        if sizes["tp"] > 1 and (sizes["pp"] > 1 or sizes["fsdp"] > 1):
            self.distributed_type = DistributedType.MEGATRON_STYLE
        elif sizes["fsdp"] > 1:
            self.distributed_type = DistributedType.FSDP
        elif sizes["tp"] > 1:
            self.distributed_type = DistributedType.TP
        else:
            self.distributed_type = self._partial.distributed_type

    def __repr__(self):
        return self._partial.__repr__() + f"Mixed precision type: {self.mixed_precision}\n"

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False):
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()

    @property
    def initialized(self) -> bool:
        return self._shared_state != {}

    @property
    def mixed_precision(self) -> str:
        return self._mixed_precision

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self._mixed_precision in ("bf16", "fp8") else (
            jnp.float16 if self._mixed_precision == "fp16" else jnp.float32
        )

    @property
    def mesh(self):
        return self._partial.mesh

    def replace_mesh(self, mesh, parallelism_config: ParallelismConfig | None = None):
        """Swap the process mesh after an elastic world-size change
        (``resilience/elastic.py``): every property reading the mesh live —
        batch placement, ``global_batch_divisor``, the sharding planner —
        sees the new world immediately. The caller owns moving live arrays
        onto it (``reshard_accelerator``)."""
        self._partial.set_mesh(mesh, parallelism_config)
        self.__dict__["_mesh"] = mesh
        if parallelism_config is not None:
            self.parallelism_config = parallelism_config

    @property
    def global_batch_divisor(self) -> int:
        """How many ways the global batch is sharded (dp*fsdp axes)."""
        return batch_sharding_size(self.mesh)

    # Delegate everything else to PartialState.
    def __getattr__(self, name: str):
        if name in ("_partial",) or name.startswith("__"):
            raise AttributeError(name)
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(type(partial), name):
            return getattr(partial, name)
        if partial is not None and name in partial.__dict__:
            return partial.__dict__[name]
        if name in self._known_attrs:
            raise AttributeError(
                f"`AcceleratorState` object has no attribute `{name}`. "
                "This happens if `AcceleratorState._reset_state()` was called and "
                "an `Accelerator` or `AcceleratorState` was not reinitialized."
            )
        raise AttributeError(f"'AcceleratorState' object has no attribute '{name}'")


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference ``state.py:1204``).

    ``sync_gradients`` is True on accumulation boundaries — in the fused jitted
    train step this flag is carried as data (a traced boolean) rather than causing
    retraces; this mirror exists for the imperative facade and for the scheduler/
    optimizer wrappers. Registered dataloaders are tracked by weakref exactly like
    the reference (:1308-1339) so `end_of_dataloader`/`remainder` reflect the
    currently-iterating loader.
    """

    _shared_state: dict = {}

    def __init__(self, gradient_accumulation_plugin=None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self._dataloader_refs = []
            self.plugin_kwargs = {}
            self._is_xla_gradients_synced = False  # parity slot; always True in JAX
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def initialized(self) -> bool:
        return GradientState._shared_state != {}

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def active_dataloader(self):
        refs = [r() for r in self._dataloader_refs]
        refs = [r for r in refs if r is not None]
        return refs[-1] if refs else None

    @property
    def dataloader_references(self):
        return [r() for r in self._dataloader_refs]

    @property
    def end_of_dataloader(self) -> bool:
        dl = self.active_dataloader
        return getattr(dl, "end_of_dataloader", False) if dl is not None else False

    @property
    def remainder(self) -> int:
        dl = self.active_dataloader
        return getattr(dl, "remainder", -1) if dl is not None else -1

    def _set_sync_gradients(self, sync: bool):
        self.sync_gradients = sync

    def _add_dataloader(self, dataloader):
        self._dataloader_refs.append(weakref.ref(dataloader))

    def _remove_dataloader(self, dataloader):
        self._dataloader_refs = [
            r for r in self._dataloader_refs if r() is not None and r() is not dataloader
        ]

    @classmethod
    def _reset_state(cls):
        cls._shared_state.clear()

    def __repr__(self):
        return (
            f"Sync Gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation plugin: {self.plugin_kwargs}\n"
        )
