"""Accelerator — the orchestration facade (L5).

Reference parity: ``src/accelerate/accelerator.py`` (3,952 LoC, class at :180).
The public surface is kept — ``prepare`` (:1289), ``backward`` (:2502),
``accumulate`` (:1122), ``gather``/``gather_for_metrics`` (:2719/:2751),
``clip_grad_norm_`` (:2630), ``save_state``/``load_state`` (:3260/:3426),
``autocast`` (:3770), ``set_trigger``/``check_trigger`` (:2536-2593) — but the
engine is inverted:

- The reference wraps live modules (DDP/FSDP/engine wrappers) and lets backward
  hooks fire NCCL collectives. Here ``prepare`` lowers the model into a **pure
  function + sharded param pytree** on the state's mesh, and every forward/backward
  is a cached, jitted XLA program in which GSPMD has already inserted the
  cross-device reductions. ``backward(loss)`` therefore doesn't *run* autodiff —
  gradients were produced by the same compiled call that produced ``loss``
  (``jax.value_and_grad``) — it *banks* them into the optimizer's accumulation
  buffer (the explicit-pytree analog of ``.grad +=``).
- DDP's ``no_sync`` dance (:1007-1045) vanishes: gradient accumulation is a
  device-side buffer add; the cross-device reduce rides each compiled step.
- The fused path ``build_train_step`` goes further and compiles forward+backward+
  accumulation+update into ONE XLA program with donated buffers — that is the
  shape the hardware wants, and what ``bench.py`` measures.

Imperative-compat contract (SURVEY.md §7 hard part 1): the pattern

    model, optimizer, loader, scheduler = accelerator.prepare(...)
    for batch in loader:
        with accelerator.accumulate(model):
            outputs = model(**batch)
            accelerator.backward(outputs.loss)
            optimizer.step(); scheduler.step(); optimizer.zero_grad()

works unmodified: prepared models in train mode compute grads at forward time
(same cost as torch's fwd+bwd — one fwd, one bwd, fused by XLA), and the loss
object returned carries the association to those banked grads.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .modules import Module, ModelOutput, as_module, default_loss_extractor
from .optimizer import AcceleratedOptimizer, GradScalerState
from .parallel.mesh import ParallelismConfig
from .parallel.sharding import (
    apply_shardings,
    batch_sharding,
    make_global_batch,
    plan_param_shardings,
)
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, DistributedType, GradientState, PartialState
from .utils.dataclasses import (
    AutocastKwargs,
    DataLoaderConfiguration,
    Fp8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    JaxShardingKwargs,
    KwargsHandler,
    ProfileKwargs,
    MegatronStylePlugin,
    PipelineParallelPlugin,
    ProjectConfiguration,
    SequenceParallelPlugin,
    TensorParallelPlugin,
)
from .utils import operations as ops

logger = logging.getLogger(__name__)


class TrainHandle:
    """Shared mutable cell binding a PreparedModel to its optimizer(s): holds the
    *current* sharded params so ``optimizer.step()`` visibly updates what
    ``model(...)`` uses next — the stateful shim over the functional core."""

    def __init__(self, module: Module, params, param_shardings, mesh, compute_dtype, rng,
                 pipeline_spec=None):
        self.module = module
        self.params = params
        self.param_shardings = param_shardings
        self.mesh = mesh
        self.compute_dtype = compute_dtype
        self.rng = rng
        # GPipe schedule over the pp axis (parallel/pipeline.py); None = the
        # GSPMD layer-dim sharding fallback (or no pp axis at all).
        self.pipeline_spec = pipeline_spec
        self.step_counter = 0
        self.last_grad_norm = None
        self.pending = None  # (loss jax.Array, grads pytree) from last train forward


def _grad_reduce_barrier(params, shardings, reduce_dtype):
    """Identity on the forward; on the backward, each leaf's cotangent is cast
    to ``reduce_dtype`` and pinned to the parameter's sharding — GSPMD then
    materializes the gradient reduction (all-reduce for dp, reduce-scatter for
    fsdp) at the reduced precision, halving the bytes on the wire. The cast
    back to the original dtype is local. TPU-native analog of the reference's
    fp16/bf16 gradient-compression comm hooks
    (``DistributedDataParallelKwargs``, reference dataclasses.py:130-226)."""

    def one(leaf, sharding):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf

        @jax.custom_vjp
        def bridge(x):
            return x

        def fwd(x):
            return x, None

        def bwd(_, g):
            gc = jax.lax.with_sharding_constraint(g.astype(reduce_dtype), sharding)
            return (gc.astype(g.dtype),)

        bridge.defvjp(fwd, bwd)
        return bridge(leaf)

    return jax.tree_util.tree_map(one, params, shardings)


class PreparedModel:
    """The object handed back by ``prepare`` in a model's slot (reference returns
    the DDP/FSDP-wrapped module, ``accelerator.py:1515``)."""

    def __init__(self, handle: TrainHandle, accelerator: "Accelerator", loss_fn=None):
        self.handle = handle
        self.accelerator = accelerator
        self.loss_fn = loss_fn or default_loss_extractor
        self.training = True
        self._train_call = None
        self._eval_call = None

    # ------------------------------------------------------------------ modes
    def train(self, mode: bool = True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    # ------------------------------------------------------------- unwrapping
    @property
    def module(self) -> Module:
        return self.handle.module

    @property
    def params(self):
        return self.handle.params

    @params.setter
    def params(self, value):
        self.handle.params = value

    def state_dict(self):
        return self.handle.params

    def load_state_dict(self, params):
        self.handle.params = apply_shardings(params, self.handle.param_shardings)

    # ------------------------------------------------------------------ loss
    def training_loss_fn(self, extract=None):
        """The canonical ``loss_of(params, batch, rng)`` used by every compiled
        training path (fused step, LocalSGDTrainer) — one definition so the
        forward contract (train flag, rng collections, loss extraction) cannot
        diverge between them. ``extract`` overrides the model's loss extractor."""
        module = self.handle.module
        cast = self._cast
        extract = extract or self.loss_fn
        if self._uses_1f1b():
            # training_loss_fn consumers (LocalSGDTrainer, custom loops) drive
            # their own value_and_grad — they cannot honor the 1F1B schedule,
            # and silently running GPipe would deliver O(M) activation
            # liveness the user opted out of.
            raise ValueError(
                "schedule='1f1b' trains through build_train_step or the "
                "imperative prepared-model forward only; use "
                "PipelineParallelPlugin(schedule='gpipe') with this training path."
            )
        pipe = {"pipeline": self.handle.pipeline_spec} if self.handle.pipeline_spec is not None else {}

        def loss_of(params, batch, rng):
            outputs = module.apply(cast(params), train=True, rngs={"dropout": rng}, **pipe, **batch)
            return extract(outputs, batch)

        return loss_of

    # ---------------------------------------------------------------- compile
    def _cast(self, params):
        dtype = self.handle.compute_dtype
        if dtype != jnp.float32:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
        rd = self._grad_reduce_dtype()
        if rd is not None:
            params = _grad_reduce_barrier(params, self.handle.param_shardings, rd)
        return params

    def _grad_reduce_dtype(self):
        sk = getattr(self.accelerator, "sharding_kwargs", None)
        name = getattr(sk, "grad_reduce_dtype", None)
        if name is None:
            return None
        return {"bf16": jnp.bfloat16, "fp16": jnp.float16}[name]

    def _uses_1f1b(self):
        spec = self.handle.pipeline_spec
        return spec is not None and spec.schedule == "1f1b"

    def _check_1f1b_loss_fn(self, extract):
        if extract is not None and extract is not default_loss_extractor:
            raise ValueError(
                "schedule='1f1b' computes the loss on the last pipeline stage "
                "via the model's own head (labels in the batch) — a custom "
                "loss_fn cannot be honored. Drop set_loss_fn/loss_fn or use "
                "PipelineParallelPlugin(schedule='gpipe')."
            )

    def _build_calls(self):
        module = self.handle.module
        loss_fn = self.loss_fn
        cast = self._cast
        handle = self.handle
        # Training forwards route through the pipeline schedule when one
        # resolved; eval keeps the GSPMD path (eval batch sizes need not
        # divide the microbatch grid, and eval throughput is not
        # pipeline-bound).
        pipe = {"pipeline": handle.pipeline_spec} if handle.pipeline_spec is not None else {}

        def fwd(params, args, kwargs, rng):
            return module.apply(cast(params), *args, train=False, rngs=None, **kwargs)

        if self._uses_1f1b():
            self._check_1f1b_loss_fn(self.loss_fn)
            spec = handle.pipeline_spec

            def train_fwd(params, args, kwargs, rng, loss_scale):
                # The 1F1B schedule produces loss AND grads in one pass; the
                # outputs carry loss (and aux) but no logits — the same
                # contract as fused_loss. Positional args follow the model
                # apply() convention (input_ids, labels, attention_mask, ...).
                batch = dict(zip(("input_ids", "labels", "attention_mask", "positions"), args))
                batch.update(kwargs)
                loss, grads, aux = spec.train_grads(
                    module, params, batch,
                    compute_dtype=handle.compute_dtype, loss_scale=loss_scale,
                    param_shardings=handle.param_shardings,
                )
                outputs = ModelOutput(loss=loss)
                if aux:
                    outputs["aux_loss"] = sum(aux.values())
                return loss, outputs, grads
        else:

            def loss_and_out(params, args, kwargs, rng, loss_scale):
                outputs = module.apply(
                    cast(params), *args, train=True, rngs={"dropout": rng}, **pipe, **kwargs
                )
                loss = loss_fn(outputs, kwargs if kwargs else args)
                return loss * loss_scale, outputs

            def train_fwd(params, args, kwargs, rng, loss_scale):
                (scaled_loss, outputs), grads = jax.value_and_grad(loss_and_out, has_aux=True)(
                    params, args, kwargs, rng, loss_scale
                )
                return scaled_loss / loss_scale, outputs, grads

        self._eval_call = jax.jit(fwd)
        self._train_call = jax.jit(train_fwd)

    def __call__(self, *args, **kwargs):
        if self._train_call is None:
            self._build_calls()
        handle = self.handle
        handle.step_counter += 1
        rng = jax.random.fold_in(handle.rng, handle.step_counter)
        args, kwargs = self.accelerator._place_batch((args, kwargs))
        if self.training:
            scaler = self.accelerator.scaler
            if scaler is not None:
                # The previous step's deferred overflow outcome must land
                # before its scale seeds this forward (optimizer.py keeps the
                # hot path async by resolving found_inf lazily, here).
                opt = self.accelerator._optimizer_for_handle(handle)
                if opt is not None:
                    opt._resolve_pending_finite()
            loss_scale = jnp.float32(scaler.scale if scaler is not None else 1.0)
            loss, outputs, grads = self._train_call(handle.params, args, kwargs, rng, loss_scale)
            handle.pending = (loss, grads)
            if isinstance(outputs, dict) and "loss" in outputs:
                # Hand the *differentiated* loss object out so backward() can match it.
                outputs = ModelOutput(outputs)
                outputs["loss"] = loss
            return outputs
        return self._eval_call(handle.params, args, kwargs, rng)

    def forward(self, *args, **kwargs):
        return self(*args, **kwargs)


class Accelerator:
    """See module docstring. Constructor mirrors reference ``accelerator.py:271``."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: str | None = None,
        gradient_accumulation_steps: int | None = None,  # None -> env, then 1
        cpu: bool = False,
        dataloader_config: DataLoaderConfiguration | None = None,
        fsdp_plugin: FullyShardedDataParallelPlugin | None = None,
        tp_plugin: TensorParallelPlugin | None = None,
        pp_plugin: PipelineParallelPlugin | None = None,
        sp_plugin: SequenceParallelPlugin | None = None,
        megatron_plugin: MegatronStylePlugin | None = None,
        parallelism_config: ParallelismConfig | None = None,
        rng_types: list | None = None,
        log_with=None,
        project_dir: str | os.PathLike | None = None,
        project_config: ProjectConfiguration | None = None,
        gradient_accumulation_plugin: GradientAccumulationPlugin | None = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: list | None = None,
        dynamo_backend=None,  # parity slot: XLA always compiles
    ):
        # Env contract extensions written by `accelerate-tpu config`'s guided
        # wizard and exported by the launcher (reference cluster.py:57 flow):
        # explicit constructor arguments always win over the env.
        from .utils.environment import parse_flag_from_env

        if project_config is None and project_dir is None:
            env_pdir = os.environ.get("ACCELERATE_PROJECT_DIR")
            if env_pdir:
                project_config = ProjectConfiguration(
                    project_dir=env_pdir,
                    automatic_checkpoint_naming=parse_flag_from_env(
                        "ACCELERATE_CHECKPOINT_AUTO_NAMING"
                    ),
                    total_limit=(
                        int(os.environ["ACCELERATE_CHECKPOINT_TOTAL_LIMIT"])
                        if os.environ.get("ACCELERATE_CHECKPOINT_TOTAL_LIMIT")
                        else None
                    ),
                )
        if fsdp_plugin is None and (
            os.environ.get("ACCELERATE_FSDP_MIN_SHARD_SIZE")
            or os.environ.get("ACCELERATE_FSDP_CPU_OFFLOAD")
        ):
            # Axis size comes from the mesh-shape env (the wizard writes both);
            # only the per-feature options live in these variables. fsdp_size
            # 1 stays 1 (disabled); 0/unset means full-shard (-1).
            env_mesh_fsdp = ParallelismConfig.from_env().fsdp_size
            fsdp_plugin = FullyShardedDataParallelPlugin(
                fsdp_size=env_mesh_fsdp or -1,
                min_shard_size=int(os.environ.get("ACCELERATE_FSDP_MIN_SHARD_SIZE", 2**14)),
                cpu_offload=parse_flag_from_env("ACCELERATE_FSDP_CPU_OFFLOAD"),
            )
        if pp_plugin is None and os.environ.get("ACCELERATE_PP_SCHEDULE"):
            # The pp axis size ALSO comes from the mesh-shape env; defaulting
            # the plugin's pp_size would override (and silently disable) it.
            pp_plugin = PipelineParallelPlugin(
                pp_size=max(ParallelismConfig.from_env().pp_size, 1),
                schedule=os.environ["ACCELERATE_PP_SCHEDULE"],
            )
        if log_with is None and os.environ.get("ACCELERATE_LOG_WITH"):
            log_with = [t.strip() for t in os.environ["ACCELERATE_LOG_WITH"].split(",") if t.strip()]

        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)
        self.sharding_kwargs = JaxShardingKwargs()
        self.autocast_handler = None
        self.profile_handler = None
        self.fp8_recipe_handler = None
        seen_handler_classes = set()
        for handler in kwargs_handlers or []:
            assert isinstance(handler, KwargsHandler), (
                f"Unsupported kwargs handler passed: {handler}, must be one that "
                "inherits `accelerate_tpu.utils.KwargsHandler`."
            )
            if type(handler) in seen_handler_classes:
                raise ValueError(
                    f"You can only pass one {type(handler).__name__} in `kwargs_handlers`."
                )
            seen_handler_classes.add(type(handler))
            if isinstance(handler, JaxShardingKwargs):
                self.sharding_kwargs = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, Fp8RecipeKwargs):
                self.fp8_recipe_handler = handler

        if parallelism_config is None:
            parallelism_config = self._resolve_parallelism(
                fsdp_plugin, tp_plugin, pp_plugin, sp_plugin, megatron_plugin
            )
        self.fsdp_plugin = fsdp_plugin
        self.sp_plugin = sp_plugin
        self.pp_plugin = pp_plugin
        self.state = AcceleratorState(
            mixed_precision=mixed_precision, cpu=cpu, parallelism_config=parallelism_config
        )

        if gradient_accumulation_plugin is None:
            # The env is a default, not an override: any explicit constructor
            # value (including 1, via the None sentinel) wins over the
            # wizard's env.
            steps = gradient_accumulation_steps
            if steps is None:
                steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", 1))
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=steps)
        elif gradient_accumulation_steps is not None and gradient_accumulation_steps > 1:
            raise ValueError(
                "You can only pass one of `gradient_accumulation_steps` and "
                "`gradient_accumulation_plugin`. Please only pass in the created "
                "`GradientAccumulationPlugin` object."
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.split_batches = split_batches
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["generator"]

        self.scaler = GradScalerState() if self.state.mixed_precision == "fp16" else None
        # FSDP plugin cpu_offload: optimizer state parks in host RAM (the
        # ZeRO-Offload trade — HBM for step latency). Applies to the imperative
        # optimizer path; the fused build_train_step keeps state device-resident
        # by design (donated buffers, zero host round-trips).
        self._offload_opt_state = bool(fsdp_plugin.cpu_offload) if fsdp_plugin is not None else False
        self.step = 0
        self.flag_tensor = None
        self._train_window = None  # lazy: ACCELERATE_TRAIN_WINDOW, then 1
        self._zero_sharding = None  # lazy: ACCELERATE_ZERO_SHARDING, then off
        self._kernels = None  # lazy: ACCELERATE_KERNELS, then reference
        self._resilience_step = 0
        # Bumped by every elastic reshard (resilience/elastic.py): fused
        # programs built before a transition compiled for a mesh that no
        # longer exists and must refuse to run.
        self._mesh_epoch = 0
        self._preemption_watcher = None
        self._health_guard = None
        self._telemetry = None
        self._models: list[PreparedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list = []
        self._custom_objects: list = []
        self._loss_fn = None
        self._rng_seed_counter = 0

        self.log_with = []
        self.trackers = []
        if log_with is not None:
            from .tracking import filter_trackers

            self.log_with = filter_trackers(log_with, self.logging_dir)

    # ------------------------------------------------------------- properties
    def _resolve_parallelism(self, fsdp_plugin, tp_plugin, pp_plugin, sp_plugin, megatron_plugin):
        if megatron_plugin is not None:
            return ParallelismConfig(
                fsdp_size=megatron_plugin.fsdp_size,
                tp_size=megatron_plugin.tp_size,
                pp_size=megatron_plugin.pp_size,
                sp_size=megatron_plugin.sp_size,
            )
        cfg = ParallelismConfig.from_env()
        if fsdp_plugin is not None:
            # -1 = full-shard over all remaining devices; ParallelismConfig
            # resolves it against the device count at mesh-build time.
            cfg.fsdp_size = fsdp_plugin.fsdp_size if fsdp_plugin.fsdp_size > 0 else -1
        if tp_plugin is not None:
            cfg.tp_size = tp_plugin.tp_size
        if pp_plugin is not None:
            cfg.pp_size = pp_plugin.pp_size
        if sp_plugin is not None:
            cfg.sp_size = sp_plugin.sp_size
        return cfg

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def device(self):
        return self.state.device

    @property
    def num_processes(self):
        return self.state.num_processes

    @property
    def process_index(self):
        return self.state.process_index

    @property
    def local_process_index(self):
        return self.state.local_process_index

    @property
    def is_main_process(self):
        return self.state.is_main_process

    @property
    def is_local_main_process(self):
        return self.state.is_local_main_process

    @property
    def is_last_process(self):
        return self.state.is_last_process

    @property
    def mixed_precision(self):
        return self.state.mixed_precision

    @property
    def gradient_accumulation_steps(self):
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def train_window(self) -> int:
        """Dispatch-amortization window K: how many full train steps
        ``build_train_window`` fuses into ONE compiled program (1 = one
        dispatch per step, the ``build_train_step`` shape). Default comes from
        the launcher contract (``--train_window`` → ACCELERATE_TRAIN_WINDOW),
        else 1; ``build_train_window(window=K)`` pins it."""
        if self._train_window is None:
            from .utils.constants import ENV_TRAIN_WINDOW

            raw = os.environ.get(ENV_TRAIN_WINDOW, "").strip()
            try:
                value = int(raw) if raw else 1
            except ValueError:
                raise ValueError(
                    f"{ENV_TRAIN_WINDOW}={raw!r} is not an integer"
                ) from None
            if value < 1:
                raise ValueError(f"{ENV_TRAIN_WINDOW} must be >= 1, got {value}")
            self._train_window = value
        return self._train_window

    @train_window.setter
    def train_window(self, value):
        value = int(value)
        if value < 1:
            raise ValueError(f"train_window must be >= 1, got {value}")
        self._train_window = value

    @property
    def zero_sharding(self) -> bool:
        """Cross-replica (ZeRO-style) sharding of optimizer state and the
        weight update along the dp axis (arxiv 2004.13336; ROADMAP item 2):
        opt-state leaves take each param's layout further partitioned over
        ``dp``, and the fused update lowers as reduce-scatter(grads) →
        sharded clip+update → all-gather(new params), cutting dp-replicated
        opt-state HBM to ~1/dp (the ``memcheck --replicated-opt-gib`` gate).
        Default comes from the launcher contract (``--zero_sharding`` →
        ACCELERATE_ZERO_SHARDING), else off; set it before ``prepare()`` —
        prepared optimizers snapshot it."""
        if self._zero_sharding is None:
            from .utils.constants import ENV_ZERO_SHARDING
            from .utils.environment import parse_flag_from_env

            self._zero_sharding = parse_flag_from_env(ENV_ZERO_SHARDING)
        return self._zero_sharding

    @zero_sharding.setter
    def zero_sharding(self, value):
        self._zero_sharding = bool(value)
        # Propagate to optimizers prepared BEFORE the flip whose sharding
        # plan hasn't been realized yet (opt_state still None): once state
        # arrays exist on a plan, the flag is pinned for that optimizer.
        for opt in self._optimizers:
            if opt.opt_state is None:
                opt.zero_sharding = self._zero_sharding

    @property
    def kernels(self) -> str:
        """The Pallas kernel-layer backend spec (docs/kernels.md): a bare
        token (``pallas`` / ``interpret`` / ``reference``) or a per-op map
        (``paged_decode=pallas,int8_matmul=off``) resolved per op by
        ``ops/registry.py`` at build/trace time. Default comes from the
        launcher contract (``--kernels`` → ACCELERATE_KERNELS), else the
        reference lowerings. Set before building — compiled programs bake
        the resolved backend in (rebuild to switch, like train_window)."""
        if self._kernels is None:
            from .utils.constants import ENV_KERNELS

            self._kernels = os.environ.get(ENV_KERNELS, "") or ""
        return self._kernels

    @kernels.setter
    def kernels(self, value):
        from .ops.registry import parse_kernel_spec

        value = "" if value is None else str(value)
        parse_kernel_spec(value)  # validate eagerly: a typo dies here
        self._kernels = value
        # Propagate to optimizers prepared BEFORE the flip whose imperative
        # update hasn't been built yet (the zero_sharding precedent): once
        # _update_fn exists, the resolved backend is compiled in.
        for opt in self._optimizers:
            if opt._update_fn is None:
                opt.kernels = value

    @property
    def fp8_backend(self):
        """Which low-precision backend serves ``mixed_precision='fp8'`` (reference
        ``fp8_backend`` property :3939-3952): "INT8" (QAT matmuls) or "BF16"
        (cast-only fallback); None when fp8 isn't requested."""
        if self.state.mixed_precision != "fp8":
            return None
        recipe = self.fp8_recipe_handler or Fp8RecipeKwargs()
        return recipe.backend.upper()

    @property
    def sync_gradients(self):
        return self.gradient_state.sync_gradients

    @property
    def use_distributed(self):
        return self.state.use_distributed

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    # --------------------------------------------------------------- plumbing
    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def on_main_process(self, f):
        return self.state.on_main_process(f)

    def on_local_main_process(self, f):
        return self.state.on_local_main_process(f)

    def on_process(self, f=None, process_index=None):
        return self.state.on_process(f, process_index)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def _place_batch(self, batch):
        """Ensure host arrays in a forward call are global mesh arrays."""
        return self._place_with(batch, make_global_batch)

    def _place_with(self, batch, placer):
        """Host ndarray leaves → ``placer(x, mesh)``; device-resident leaves
        (and non-arrays) pass through untouched."""
        if not self.device_placement:
            return batch

        mesh = self.mesh

        def _one(x):
            if isinstance(x, jax.Array):
                return x
            if isinstance(x, np.ndarray):
                return placer(x, mesh)
            return x

        return jax.tree_util.tree_map(_one, batch)

    # ---------------------------------------------------------------- prepare
    def prepare(self, *args, device_placement=None):
        """Classify & lower each object (reference ``prepare`` :1289-1443).

        models → ``PreparedModel`` (sharded params), optax transforms →
        ``AcceleratedOptimizer``, dataloaders → sharded device-feeding loaders,
        schedules → ``AcceleratedScheduler``. Order is preserved.
        """
        from .telemetry import span

        with span("prepare"):
            return self._prepare(*args, device_placement=device_placement)

    def _prepare(self, *args, device_placement=None):
        import optax

        result = []
        prepared_model = None
        prepared_opts = []
        for obj in args:
            kind = self._classify(obj)
            if kind == "model":
                prepared = self.prepare_model(obj)
                prepared_model = prepared
            elif kind == "optimizer":
                prepared = AcceleratedOptimizer(
                    obj, scaler=self.scaler, host_offload=self._offload_opt_state,
                    zero_sharding=self.zero_sharding, kernels=self.kernels,
                )
                prepared_opts.append(prepared)
                self._optimizers.append(prepared)
            elif kind == "dataloader":
                prepared = self.prepare_data_loader(obj)
            elif kind == "scheduler":
                prepared = obj  # bound after optimizers exist
            else:
                prepared = obj
            result.append((kind, obj, prepared))

        # Bind optimizers to the model handle (single-model case; multi-model users
        # call prepare separately per pair, as in the reference's deepspeed guard).
        if prepared_model is not None:
            for opt in prepared_opts:
                opt.handle = prepared_model.handle
        elif prepared_opts and self._models:
            for opt in prepared_opts:
                opt.handle = self._models[-1].handle

        final = []
        for kind, obj, prepared in result:
            if kind == "scheduler":
                opts = prepared_opts or self._optimizers
                prepared = AcceleratedScheduler(
                    obj,
                    opts,
                    step_with_optimizer=self.step_scheduler_with_optimizer,
                    split_batches=self.dataloader_config.split_batches,
                )
                self._schedulers.append(prepared)
            final.append(prepared)
        return final[0] if len(final) == 1 else tuple(final)

    def _classify(self, obj) -> str:
        import optax

        if isinstance(obj, optax.GradientTransformation):
            return "optimizer"
        if isinstance(obj, (PreparedModel,)):
            return "model"
        if isinstance(obj, Module) or type(obj).__module__.startswith("flax"):
            return "model"
        if isinstance(obj, tuple) and len(obj) == 2 and (
            isinstance(obj[0], Module) or hasattr(obj[0], "apply")
        ):
            return "model"
        if hasattr(obj, "init") and hasattr(obj, "apply"):
            return "model"
        from .modules import is_torch_module

        if is_torch_module(obj):
            # Route to prepare_model → as_module, whose error points at from_hf.
            return "model"
        if hasattr(obj, "__iter__") and not callable(obj):
            return "dataloader"
        if _is_torch_dataloader(obj):
            return "dataloader"
        if callable(obj):
            # Only a schedule (int step -> lr, optax convention) belongs here.
            # Anything else callable — a loss function, a metric, a model
            # factory — must not be silently wrapped in AcceleratedScheduler.
            if _looks_like_schedule(obj):
                return "scheduler"
            raise TypeError(
                f"prepare() received a callable ({getattr(obj, '__name__', type(obj).__name__)}) "
                "that does not look like an LR schedule (a schedule takes a single "
                "integer step count, e.g. optax.cosine_decay_schedule(...)). Loss "
                "functions are registered with accelerator.set_loss_fn(...), and "
                "models must expose init/apply (see accelerate_tpu.modules.as_module)."
            )
        return "other"

    def prepare_model(self, model, device_placement=None, evaluation_mode: bool = False):
        """Lower a model to (module, sharded params) and wrap (reference
        ``prepare_model`` :1515-1800 — where DDP/FSDP wrapping happened, here the
        param pytree is placed onto the mesh by the sharding planner)."""
        if isinstance(model, PreparedModel):
            return model
        params = None
        if isinstance(model, tuple) and len(model) == 2:
            model, params = model
        module = as_module(model)
        if params is None:
            params = getattr(model, "params", None)
        if params is None:
            raise ValueError(
                "Model has no parameters: pass `(module, params)` to prepare(), or set "
                "`model.params` (model-zoo modules do this via `model.init_params(rng, ...)`)."
            )
        rules = None
        if isinstance(module, Module):
            rules = module.sharding_rules()
        # fp8 mixed precision: swap eligible model matmuls to the int8 QAT path
        # (reference routes fp8 through TE/AO module conversion at prepare time,
        # accelerator.py:1802-1830 there; see Fp8RecipeKwargs for the TPU story).
        # Config-driven compute routing. replace() (not mutation) gives the
        # module its own config copy: a config shared with other models (or
        # serialized later) must not silently change precision or attention.
        import dataclasses as _dc

        model_cfg = getattr(module, "config", None)
        if self.fp8_backend == "INT8":
            if model_cfg is not None and getattr(model_cfg, "matmul_precision", None) == "default":
                model_cfg = _dc.replace(model_cfg, matmul_precision="int8")
        # Sequence parallelism: with an sp axis in the mesh, route the model's
        # attention through the sequence-parallel op — ppermute ring (default)
        # or Ulysses all-to-all (SequenceParallelPlugin(ring_attention=False)).
        if self.mesh.shape.get("sp", 1) > 1:
            lw = getattr(model_cfg, "layer_windows", None) if model_cfg is not None else None
            if lw is not None and any(w is not None for w in lw):
                raise ValueError(
                    "Sequence parallelism (sp>1) does not support per-layer "
                    "windowed attention (layer_windows); train with sp=1 or use "
                    "fsdp/tp for memory."
                )
            if model_cfg is not None and (
                getattr(model_cfg, "attn_logit_softcap", None) is not None
                or getattr(model_cfg, "query_pre_attn_scalar", None) is not None
            ):
                # Gemma-2 score shaping is dense-only; fail at prepare, not at
                # trace time inside the first compiled step.
                raise ValueError(
                    "Sequence parallelism (sp>1) does not support attention "
                    "softcapping / query_pre_attn_scalar (Gemma-2); train with "
                    "sp=1 and use fsdp/tp for memory."
                )
            if model_cfg is not None and getattr(model_cfg, "sliding_window", None):
                # Fail here, not deep inside the first compiled step: the
                # sequence-parallel attention paths reject window masks
                # (advisor r2 — windowed Mistral/Qwen2 checkpoints under sp).
                raise ValueError(
                    "Sequence parallelism (sp>1) does not support sliding-window "
                    f"attention (sliding_window={model_cfg.sliding_window}). Train "
                    "this model with sp=1 (use fsdp/tp for memory), or clear "
                    "config.sliding_window to use full attention."
                )
            if model_cfg is not None and getattr(model_cfg, "attention_impl", None) == "auto":
                ring = self.sp_plugin.ring_attention if self.sp_plugin is not None else True
                model_cfg = _dc.replace(model_cfg, attention_impl="ring" if ring else "ulysses")
        if model_cfg is not None and model_cfg is not getattr(module, "config", None):
            module.config = model_cfg
        min_shard = self.fsdp_plugin.min_shard_size if self.fsdp_plugin is not None else 2**14
        shardings = plan_param_shardings(params, self.mesh, rules=rules, min_shard_size=min_shard)
        params = apply_shardings(params, shardings)
        rng = jax.random.key(int(os.environ.get("ACCELERATE_SEED", 0)) + 7919)
        # AutocastKwargs(enabled=False) pins fp32 compute regardless of the
        # mixed-precision setting (reference autocast ctx with enabled=False).
        compute_dtype = self.state.compute_dtype
        if self.autocast_handler is not None and not self.autocast_handler.enabled:
            compute_dtype = jnp.float32
        # Pipeline-parallel training: with a pp axis and a stage-protocol model,
        # swap the GSPMD layer-dim sharding (which all-gathers stage weights)
        # for the GPipe schedule with stationary weights + ppermuted activations.
        from .parallel.pipeline import resolve_pipeline_spec

        mbs = self.pp_plugin.num_microbatches if self.pp_plugin is not None else 0
        if mbs <= 0:
            env_mbs = os.environ.get("ACCELERATE_PP_MICROBATCHES", "").strip()
            try:
                mbs = int(env_mbs) if env_mbs else 0
            except ValueError:
                raise ValueError(
                    f"ACCELERATE_PP_MICROBATCHES={env_mbs!r} is not an integer"
                ) from None
        schedule = self.pp_plugin.schedule if self.pp_plugin is not None else "gpipe"
        pipeline_spec = resolve_pipeline_spec(module, params, self.mesh, mbs, schedule=schedule)
        handle = TrainHandle(
            module, params, shardings, self.mesh, compute_dtype, rng,
            pipeline_spec=pipeline_spec,
        )
        prepared = PreparedModel(handle, self, loss_fn=self._loss_fn)
        prepared.train(not evaluation_mode)
        self._models.append(prepared)
        # Keep the user's handle usable: reflect params back onto the original
        # object so `model.params` stays meaningful after prepare.
        try:
            model.params = params
        except (AttributeError, TypeError):
            pass
        return prepared

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            self._dataloaders.append(data_loader)
            return data_loader
        cfg = self.dataloader_config
        prepared = prepare_data_loader(
            data_loader,
            device=self.device,
            split_batches=cfg.split_batches,
            put_on_device=self.device_placement if device_placement is None else device_placement,
            rng_types=self.rng_types if _is_torch_dataloader(data_loader) else None,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            slice_fn_for_dispatch=slice_fn_for_dispatch,
            use_seedable_sampler=cfg.use_seedable_sampler,
            data_seed=cfg.data_seed,
            non_blocking=cfg.non_blocking,
            use_stateful_dataloader=cfg.use_stateful_dataloader,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer, device_placement=None):
        prepared = AcceleratedOptimizer(
            optimizer, scaler=self.scaler, host_offload=self._offload_opt_state,
            zero_sharding=self.zero_sharding, kernels=self.kernels,
        )
        if self._models:
            prepared.handle = self._models[-1].handle
        self._optimizers.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler):
        prepared = AcceleratedScheduler(
            scheduler,
            self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        self._schedulers.append(prepared)
        return prepared

    def set_loss_fn(self, loss_fn: Callable):
        """Register a custom loss: ``loss_fn(outputs, batch) -> scalar`` (jittable).
        Needed when the model returns logits and the loss lives in user code —
        the analog of computing ``F.cross_entropy`` outside the model in torch."""
        self._loss_fn = loss_fn
        for m in self._models:
            m.loss_fn = loss_fn
            m._train_call = None  # force recompile with the new loss

    # ------------------------------------------------------- training facade
    def backward(self, loss, **kwargs):
        """Bank the gradients already produced with ``loss`` (see module docstring;
        reference ``backward`` :2502-2534 divides by accum steps — we fold that
        into the accumulation scale)."""
        model = self._find_model_for_loss(loss)
        if model is None or model.handle.pending is None:
            raise RuntimeError(
                "backward() found no gradients: call it with the loss from a train-mode "
                "forward of a prepared model (or use build_train_step for the fused path)."
            )
        _, grads = model.handle.pending
        model.handle.pending = None
        opt = self._optimizer_for_handle(model.handle)
        if opt is None:
            raise RuntimeError("No prepared optimizer is bound to this model.")
        opt._accumulate(grads, scale=1.0 / self.gradient_accumulation_steps)

    def _find_model_for_loss(self, loss):
        for m in self._models:
            if m.handle.pending is not None and m.handle.pending[0] is loss:
                return m
        pending = [m for m in self._models if m.handle.pending is not None]
        if len(pending) == 1:
            return pending[0]
        return None

    def _optimizer_for_handle(self, handle):
        for opt in self._optimizers:
            if opt.handle is handle:
                return opt
        return self._optimizers[-1] if self._optimizers else None

    def _do_sync(self):
        """Reference ``_do_sync`` :1096-1103."""
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
            )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Reference ``accumulate`` :1122-1166."""
        self._do_sync()
        yield

    @contextlib.contextmanager
    def no_sync(self, model):
        """DDP ``no_sync`` parity (:1007-1045). Under GSPMD the grad reduction is
        part of the compiled step, so there is nothing to suppress — accumulation
        correctness comes from the buffer add, and this context is a no-op."""
        yield

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """DDP-join parity (:1167-1265): uneven tails never reach the mesh — the
        data layer pads to static shapes and records ``remainder`` — so joining is
        a no-op context."""
        yield

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Parity context (:3770). The dtype policy is baked into compiled calls
        when a model is prepared, so this context cannot retroactively retune an
        already-compiled model; a handler passed here (or via ``kwargs_handlers``)
        governs models prepared inside the context."""
        prev = self.autocast_handler
        if autocast_handler is not None:
            self.autocast_handler = autocast_handler
        try:
            yield
        finally:
            self.autocast_handler = prev

    def _optimizer_for_parameters(self, parameters):
        """Resolve which prepared optimizer owns ``parameters`` (a PreparedModel,
        its params pytree, or None). With one optimizer None is unambiguous; with
        several it is an error — the reference clips exactly the tensors you pass
        (``accelerator.py:2630``), so silently picking one would clip the wrong
        model."""
        if parameters is None:
            if len(self._optimizers) > 1:
                raise ValueError(
                    "Multiple optimizers are prepared; pass the model (or its "
                    "params) whose gradients should be clipped."
                )
            return self._optimizers[-1] if self._optimizers else None
        handle = getattr(parameters, "handle", None)  # PreparedModel
        for opt in self._optimizers:
            if opt.handle is handle and handle is not None:
                return opt
            if opt.handle is not None and opt.handle.params is parameters:
                return opt
        # Match by pytree identity of any leaf (covers params trees that were
        # rebuilt but share buffers) before giving up.
        param_ids = {id(l) for l in jax.tree_util.tree_leaves(parameters)}
        for opt in self._optimizers:
            if opt.handle is None:
                continue
            opt_ids = {id(l) for l in jax.tree_util.tree_leaves(opt.handle.params)}
            if param_ids & opt_ids:
                return opt
        raise ValueError(
            "clip_grad_norm_ received parameters that do not belong to any "
            "prepared optimizer; pass a model returned by prepare()."
        )

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: int = 2):
        """Register clipping for the pending update and return the pre-clip global
        norm of the currently-banked grads (reference :2630-2690; the XLA branch
        there hand-rolls all_reduce — GSPMD already made our grads global)."""
        if norm_type != 2:
            raise NotImplementedError("only the L2 global norm is supported on TPU")
        opt = self._optimizer_for_parameters(parameters)
        if opt is None or opt.grads is None:
            return jnp.float32(0.0)
        opt._pending_clip_norm = float(max_norm)
        from .optimizer import _global_norm

        return _global_norm(opt.grads)

    def clip_grad_value_(self, parameters, clip_value: float):
        opt = self._optimizer_for_parameters(parameters)
        if opt is None or opt.grads is None:
            return
        opt._accum_grads = jax.tree_util.tree_map(
            lambda g: jnp.clip(g, -clip_value, clip_value), opt._accum_grads
        )

    # ----------------------------------------------------------- fused step
    def _fused_value_and_grads(self, model: PreparedModel, loss_fn=None):
        """The ``(params, batch, rng) -> (loss, grads)`` core shared by
        ``build_train_step`` and ``build_train_window`` — one definition so
        the 1F1B/GSPMD routing and loss contract cannot diverge between the
        per-step and windowed programs."""
        handle = model.handle
        spec = handle.pipeline_spec
        if model._uses_1f1b():
            model._check_1f1b_loss_fn(loss_fn if loss_fn is not None else model.loss_fn)

            def value_and_grads(params, batch, rng):
                loss, grads, _aux = spec.train_grads(
                    handle.module, params, batch, compute_dtype=handle.compute_dtype,
                    param_shardings=handle.param_shardings,
                )
                return loss, grads
        else:
            loss_of = model.training_loss_fn(loss_fn)

            def value_and_grads(params, batch, rng):
                return jax.value_and_grad(loss_of)(params, batch, rng)

        return value_and_grads

    def _fused_step_body(self, model: PreparedModel, optimizer: AcceleratedOptimizer,
                         accum: int, loss_fn=None):
        """``(params, opt_state, accum_grads, count, batch, rng, clip_norm) ->
        (params, opt_state, accum_grads, count, loss)`` — the per-step math
        both fused programs compile: forward+backward via
        :meth:`_fused_value_and_grads`, grad accumulation at ``1/accum``
        scale, global-norm clip, conditional ``tx.update``/apply, buffer
        zero-reset. One definition so ``build_train_window``'s bit-exactness
        vs K sequential ``build_train_step`` calls is structural, not
        maintained by hand."""
        import optax

        tx = optimizer.tx
        value_and_grads = self._fused_value_and_grads(model, loss_fn)
        # ZeRO (cross-replica weight-update sharding, arxiv 2004.13336): when
        # the optimizer's dp plan is active, the update region is constrained
        # to it — GSPMD turns the gradient all-reduce + slice into a
        # reduce-scatter, runs clip+update on 1/dp of every param, and
        # all-gathers the new params back to their base layout. Inside a
        # K-step window the gather is async-schedulable against the NEXT
        # step's compute (the xla_flags latency presets overlap it). The
        # named scopes ride into collective op_name metadata so the program
        # auditor attributes the deliberate dp all-gather as ZeRO traffic.
        zero_specs = optimizer.zero_param_shardings
        base_specs = model.handle.param_shardings if zero_specs is not None else None
        # Pallas fused-update kernel (ops/pallas/fused_update.py): when the
        # registry resolves the `fused_update` op away from reference AND the
        # optimizer matches a supported optax family (adam/adamw/sgd — the
        # closure-introspected plan), the update region's per-leaf chain
        # (clip-scale + moments + apply + cast + buffer zero) runs as ONE
        # pallas pass per leaf. With ZeRO on it executes inside the
        # zero_update-constrained region, i.e. on the 1/dp shard between the
        # reduce-scatter and the param all-gather. An unsupported optimizer
        # falls back to the reference chain silently — per-instance, the
        # registry's clean-fallback contract.
        from .ops.registry import resolve_backend

        kernel_backend = resolve_backend("fused_update", self.kernels)
        fused_plan = None
        if kernel_backend != "reference":
            from .ops.pallas.fused_update import plan_fused_update

            fused_plan = plan_fused_update(tx)

        def step_body(params, opt_state, accum_grads, count, batch, rng, clip_norm):
            if zero_specs is not None:
                # GSPMD gives each HLO value ONE sharding: without this pin,
                # the update branch's dp constraint propagates back through
                # the shared `params` value into the forward/backward, which
                # would both re-materialize params every step AND change the
                # gradient reduction order (breaking bit-exactness vs the
                # replicated path). The pin anchors the value the forward
                # consumes at its base layout; the update-region constraint
                # below then lowers as a local slice at the region edge.
                params = jax.lax.with_sharding_constraint(params, base_specs)
                accum_grads = jax.lax.with_sharding_constraint(
                    accum_grads, base_specs
                )
            loss, grads = value_and_grads(params, batch, rng)
            accum_grads = jax.tree_util.tree_map(
                lambda a, g: a + g / accum, accum_grads, grads
            )
            if zero_specs is not None:
                # Same propagation block on the gradient side: the update
                # region's dp constraint must not reach back through this add
                # into the backward (which would re-partition the transpose
                # ops and change the gradient reduction order).
                accum_grads = jax.lax.with_sharding_constraint(
                    accum_grads, base_specs
                )
            count = count + 1
            do_update = (count % accum) == 0

            def upd(operand):
                params, opt_state, grads = operand
                if zero_specs is not None:
                    with jax.named_scope("zero_update"):
                        return _zero_upd(params, opt_state, grads)
                return _upd_math(params, opt_state, grads)

            def _zero_upd(params, opt_state, grads):
                # Entering the region: replicated → dp-sharded constraints
                # lower as local slices of the (already all-reduced) grads —
                # XLA's all-reduce+slice fusion turns the pair into the
                # reduce-scatter of the ZeRO schedule where profitable.
                grads = jax.lax.with_sharding_constraint(grads, zero_specs)
                params = jax.lax.with_sharding_constraint(params, zero_specs)
                new_params, new_opt, zero = _upd_math(params, opt_state, grads)
                with jax.named_scope("zero_gather_params"):
                    new_params = jax.lax.with_sharding_constraint(
                        new_params, base_specs
                    )
                # The accumulation buffer keeps its base layout (it was
                # seeded as zeros_like(params)): a constant, no traffic —
                # this just stops the donated buffer's alias from drifting
                # onto the dp-sharded layout across iterations.
                zero = jax.lax.with_sharding_constraint(zero, base_specs)
                return new_params, new_opt, zero

            def _upd_math(params, opt_state, grads):
                # With ZeRO on this is per-shard partial sums + ONE scalar
                # cross-replica reduce; the clip factor (and with it every
                # downstream op) stays elementwise either way, which is what
                # keeps the sharded path bit-exact vs the replicated one
                # whenever clipping is off (clip_norm <= 0 → factor == 1.0).
                gnorm = jnp.sqrt(
                    sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads))
                )
                factor = jnp.where(
                    (clip_norm > 0) & (gnorm > clip_norm),
                    clip_norm / (gnorm + 1e-6), 1.0,
                )
                if fused_plan is not None:
                    from .ops.pallas.fused_update import fused_update_apply

                    return fused_update_apply(
                        params, opt_state, grads, plan=fused_plan,
                        clip_factor=factor,
                        interpret=(kernel_backend == "interpret"),
                        # Under ZeRO the kernel covers the 1/dp shard: the
                        # plan sizes its shard-local tile grid.
                        shardings=zero_specs,
                    )
                grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                zero = jax.tree_util.tree_map(jnp.zeros_like, grads)
                return new_params, new_opt, zero

            def keep(operand):
                return operand

            params, opt_state, accum_grads = jax.lax.cond(
                do_update, upd, keep, (params, opt_state, accum_grads)
            )
            return params, opt_state, accum_grads, count, loss

        return step_body

    def _fused_build_prologue(self, handle, optimizer: AcceleratedOptimizer,
                              accum: int, builder: str):
        """Shared scaffolding for both fused builders: lazily zero the donated
        accumulation buffer, seed the device-resident micro-step count, feed
        the model's flop count to the timeline, and return ``(count_box,
        check_stale_accum)`` — the stale-accumulation guard each wrapper calls
        per dispatch. One definition so the builders' build-time contract
        cannot drift apart."""
        # A (re)build restarts the compiled program's accumulation state: the
        # device micro-step count seeds at 0 below, so the buffer must start
        # zeroed too — a partially-filled buffer left by a prior build (or by
        # imperative backward() calls) would desynchronize the boundary and
        # silently fold extra microbatches into the first update.
        optimizer._accum_grads = jax.tree_util.tree_map(jnp.zeros_like, handle.params)
        count_box = [jnp.int32(0)]
        # The MFU estimate needs the model's flop count; the zoo models expose
        # it, anything else leaves the timeline at tokens/s only.
        flops_fn = getattr(handle.module, "flops_per_token", None)
        if self.telemetry.enabled and callable(flops_fn):
            try:
                self.telemetry.timeline.set_model_flops(float(flops_fn()))
            except Exception:
                pass

        build_epoch = self._mesh_epoch

        def check_stale_accum():
            if self._mesh_epoch != build_epoch:
                # The program compiled for shardings on a mesh an elastic
                # transition has since replaced; running it would feed the
                # dead layout. run_resilient re-enters train_fn so the
                # rebuild is one call away.
                raise RuntimeError(
                    f"The device mesh was resharded (elastic world-size "
                    f"change) after {builder}; call {builder} again so the "
                    "program compiles for the new mesh and sharding layout."
                )
            if self.gradient_accumulation_steps != accum:
                # The compiled program bakes the accumulation scale in; a
                # mid-run change would silently diverge from the imperative
                # path (which reads GradientState live) — fail instead.
                raise RuntimeError(
                    f"gradient_accumulation_steps changed from {accum} to "
                    f"{self.gradient_accumulation_steps} after {builder}; "
                    f"call {builder} again to pick up the new value."
                )

        return count_box, check_stale_accum

    def build_train_step(self, model: PreparedModel, optimizer: AcceleratedOptimizer, loss_fn=None):
        """ONE compiled XLA program per microbatch: forward + backward + buffer
        accumulation + (conditional) optimizer update, with params/opt-state/grad
        buffers donated. This is the TPU-shaped hot loop — no host round-trips, no
        retraces across accumulation boundaries (SURVEY.md §7 hard part 3).

        Returns ``step(batch) -> loss`` operating on the shared handle state.
        """
        handle = model.handle
        optimizer._ensure_initialized()
        accum = self.gradient_accumulation_steps
        step_body = self._fused_step_body(model, optimizer, accum, loss_fn)

        from .utils.environment import safe_donate_argnums

        donate = safe_donate_argnums((0, 1, 2, 3))

        @partial(jax.jit, donate_argnums=donate)
        def train_step(params, opt_state, accum_grads, count, batch, rng, clip_norm):
            return step_body(params, opt_state, accum_grads, count, batch, rng, clip_norm)

        from .telemetry import span
        from .telemetry.timeline import batch_token_count

        count_box, check_stale_accum = self._fused_build_prologue(
            handle, optimizer, accum, "build_train_step"
        )

        def _step_args(batch, rng, clip_norm):
            return (
                handle.params, optimizer.opt_state, optimizer._accum_grads,
                count_box[0], self._place_batch(batch), rng, jnp.float32(clip_norm),
            )

        def step(batch, clip_norm: float = 0.0):
            check_stale_accum()
            handle.step_counter += 1
            rng = jax.random.fold_in(handle.rng, handle.step_counter)
            # self.telemetry (not a build-time capture) so a later
            # configure_telemetry() redirects the feed, and ACCELERATE_
            # TELEMETRY=0 strips the per-step instrumentation entirely.
            telemetry = self.telemetry
            if not telemetry.enabled:
                (handle.params, optimizer.opt_state, optimizer._accum_grads,
                 count_box[0], loss) = train_step(*_step_args(batch, rng, clip_norm))
                return loss
            with span("train_step"):
                (handle.params, optimizer.opt_state, optimizer._accum_grads,
                 count_box[0], loss) = train_step(*_step_args(batch, rng, clip_norm))
            # Per-step timeline sample: a clock read + deque append; the loss
            # scalar is retained (never fetched) so the dispatch stays async.
            telemetry.on_fused_step(tokens=batch_token_count(batch), loss=loss)
            return loss

        def lower(batch, clip_norm: float = 0.0):
            """Lower (without running) the fused step for HLO inspection — used
            by the collective-count tests to pin each plan's communication
            pattern without multi-chip hardware."""
            return train_step.lower(*_step_args(batch, handle.rng, clip_norm))

        step.lower = lower
        step._audit_meta = self._builder_audit_meta(
            "build_train_step", handle, optimizer, donate, (0, 1, 2, 3),
            lambda batch, clip_norm=0.0: jax.make_jaxpr(step_body)(
                *_step_args(batch, handle.rng, clip_norm)
            ),
        )
        return step

    # --------------------------------------------------------- fused windows
    def build_train_window(self, model: PreparedModel, optimizer: AcceleratedOptimizer,
                           window: int | None = None, loss_fn=None):
        """ONE compiled XLA program per K steps: ``lax.scan`` of K full train
        steps (forward + backward + accumulation + conditional update, buffers
        donated) over a K-stacked device-resident batch window — the
        dispatch-amortized hot loop (docs/performance.md "Dispatch
        amortization"). Each launch pays ONE program dispatch where
        ``build_train_step`` pays K, which matters where the host's control
        path is slow next to a step; the per-step math — accumulation scale,
        clip, RNG fold-in sequence — is bit-identical to K sequential fused
        steps.

        ``window`` defaults to (and pins) :attr:`train_window`
        (ACCELERATE_TRAIN_WINDOW / ``launch --train_window``); ``window=1``
        is exactly the ``build_train_step`` program with a leading length-1
        batch axis. Composes with gradient accumulation (K in-window
        micro-steps advance the same accumulation counter), the health guard
        (``guard_step(losses, step=..., window=K)`` dispatches one windowed
        verdict, quarantines the exact in-window step, and snapshots at
        window boundaries), preemption hooks
        (``checkpoint_on_preemption(window=K)``), and the 1F1B/fused-loss
        paths via the shared forward core.

        Returns ``step_window(window_batch) -> losses`` where ``window_batch``
        has a leading K axis on every leaf (``DeviceBatchPrefetcher(...,
        window=K)`` builds these, already on device, for K > 1; at
        ``window=1`` the prefetcher deliberately yields PLAIN batches shaped
        for ``build_train_step`` — the unwindowed async-prefetch pairing —
        so stack a length-1 leading axis yourself to feed a K=1 window
        program) and ``losses`` is the retained per-step K-vector — drain it
        through the timeline's no-blocking-fetch discipline, never
        ``float()`` it mid-loop.
        """
        window = self.train_window if window is None else int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        # Pin the accelerator-level knob so the stale-config check below has
        # one source of truth (mirrors gradient_accumulation_steps semantics).
        self.train_window = window
        handle = model.handle
        optimizer._ensure_initialized()
        accum = self.gradient_accumulation_steps
        step_body = self._fused_step_body(model, optimizer, accum, loss_fn)

        from .utils.environment import safe_donate_argnums

        donate = safe_donate_argnums((0, 1, 2, 3))

        @partial(jax.jit, donate_argnums=donate)
        def _window(params, opt_state, accum_grads, count, batches, counters,
                    base_rng, clip_norm):
            def body(carry, xs):
                params, opt_state, accum_grads, count = carry
                batch, counter = xs
                # Same stream as the per-step program: fold_in of the handle
                # key at this step's counter value.
                rng = jax.random.fold_in(base_rng, counter)
                params, opt_state, accum_grads, count, loss = step_body(
                    params, opt_state, accum_grads, count, batch, rng, clip_norm
                )
                return (params, opt_state, accum_grads, count), loss

            (params, opt_state, accum_grads, count), losses = jax.lax.scan(
                body, (params, opt_state, accum_grads, count), (batches, counters)
            )
            return params, opt_state, accum_grads, count, losses

        from .telemetry import span
        from .telemetry.timeline import batch_token_count

        count_box, check_stale_accum = self._fused_build_prologue(
            handle, optimizer, accum, "build_train_window"
        )

        def _check_leading_axis(batch):
            for leaf in jax.tree_util.tree_leaves(batch):
                if hasattr(leaf, "shape") and np.ndim(leaf) > 0:
                    if leaf.shape[0] != window:
                        hint = (
                            "Use DeviceBatchPrefetcher(..., window=K) or "
                            "np.stack K batches."
                            if window > 1 else
                            "Stack a length-1 leading axis (np.expand_dims, "
                            "axis=0) — DeviceBatchPrefetcher(window=1) yields "
                            "PLAIN batches shaped for build_train_step."
                        )
                        raise ValueError(
                            f"build_train_window(window={window}) expects every "
                            f"batch leaf stacked on a leading K axis; got leading "
                            f"dim {leaf.shape[0]} (shape {tuple(leaf.shape)}). "
                            + hint
                        )

        def _window_args(batch, clip_norm: float = 0.0):
            """The exact argument tuple the compiled window consumes — shared
            by step_window, lower(), and the audit jaxpr thunk so the audited
            program can never diverge from the program that actually runs.
            Counters derive from the CURRENT step_counter (callers advance it
            after assembling args)."""
            counters = jnp.arange(
                handle.step_counter + 1, handle.step_counter + window + 1,
                dtype=jnp.int32,
            )
            return (
                handle.params, optimizer.opt_state, optimizer._accum_grads,
                count_box[0], self._place_window_batch(batch), counters,
                handle.rng, jnp.float32(clip_norm),
            )

        def step_window(batch, clip_norm: float = 0.0):
            check_stale_accum()
            if self.train_window != window:
                raise RuntimeError(
                    f"train_window changed from {window} to {self.train_window} "
                    "after build_train_window; the compiled program scans exactly "
                    f"{window} steps per dispatch — call build_train_window again "
                    "to pick up the new value."
                )
            _check_leading_axis(batch)
            args = _window_args(batch, clip_norm)
            handle.step_counter += window
            telemetry = self.telemetry
            if not telemetry.enabled:
                (handle.params, optimizer.opt_state, optimizer._accum_grads,
                 count_box[0], losses) = _window(*args)
                return losses
            with span("train_window"):
                (handle.params, optimizer.opt_state, optimizer._accum_grads,
                 count_box[0], losses) = _window(*args)
            # One boundary, K steps: the timeline splits wall time and tokens
            # per step and retains the K-vector of losses (no fetch here).
            telemetry.on_fused_step(
                tokens=batch_token_count(batch), loss=losses, steps=window
            )
            return losses

        def lower(batch, clip_norm: float = 0.0):
            """Lower (without running) the fused window for HLO inspection /
            auditing — the window-builder analog of build_train_step's lower."""
            _check_leading_axis(batch)
            return _window.lower(*_window_args(batch, clip_norm))

        step_window.window = window
        step_window.lower = lower
        step_window._audit_meta = self._builder_audit_meta(
            "build_train_window", handle, optimizer, donate, (0, 1, 2, 3),
            lambda batch, clip_norm=0.0: jax.make_jaxpr(_window)(
                *_window_args(batch, clip_norm)
            ),
            window=window,
        )
        return step_window

    # ------------------------------------------------------------- audit
    def _builder_audit_meta(self, builder: str, handle, optimizer,
                            effective_donate: tuple, intended_donate: tuple,
                            jaxpr_thunk, window: int = 1):
        """Audit metadata the fused builders attach to their returned step fn:
        the donation contract (what was intended vs what safe_donate_argnums
        left after platform gating, plus how many flat buffers the donated
        pytrees flatten to — the count that catches PARTIAL donation
        regressions), the mesh for collective attribution, the compute dtype
        for upcast detection, a jaxpr thunk for the pre-partitioning walk, and
        the donated-pytree class join (``memory_classes``) the static memory
        auditor (analysis/memory.py) uses to attribute flat input buffers to
        param / opt-state / accum classes with their shardings. The class
        thunks read the LIVE handle/optimizer state so an audit after steps
        (donated buffers replaced) still sees current shapes."""
        try:
            compute_dtype = np.dtype(handle.compute_dtype).name
        except Exception:
            compute_dtype = None
        # Donated argnums (0,1,2,3) = params, opt_state, accum buffer, count.
        donated_leaves = (
            len(jax.tree_util.tree_leaves(handle.params))
            + len(jax.tree_util.tree_leaves(optimizer.opt_state))
            + len(jax.tree_util.tree_leaves(optimizer._accum_grads))
            + 1  # the device-resident micro-step count scalar
        )
        zero_meta = None
        if getattr(optimizer, "zero_active", False):
            from .analysis.audit import zero_gather_shapes

            zero_meta = {
                "axis": "dp",
                "param_shapes": zero_gather_shapes(
                    handle.params, handle.param_shardings, self.mesh
                ),
            }
        from .ops.registry import resolved_backends

        kernels_meta = {"spec": self.kernels,
                        "backends": resolved_backends(self.kernels)}
        try:
            from .ops.pallas.fused_update import plan_fused_update

            plan = (plan_fused_update(optimizer.tx)
                    if kernels_meta["backends"].get("fused_update") != "reference"
                    else None)
            kernels_meta["fused_update_plan"] = plan.describe() if plan else None
        except Exception:
            kernels_meta["fused_update_plan"] = None
        return {
            "builder": builder,
            "mesh": self.mesh,
            "compute_dtype": compute_dtype,
            "kernels": kernels_meta,
            "expected_donations": tuple(intended_donate),
            "expected_donated_leaves": donated_leaves,
            "donation_dropped_by_policy": (
                bool(intended_donate) and not effective_donate
            ),
            "jaxpr_thunk": jaxpr_thunk,
            "window": int(window),
            # Non-None when the optimizer's cross-replica plan engaged: the
            # auditor classifies the update's deliberate dp collectives
            # (zero_update / zero_gather_params scopes, or an all-gather
            # landing exactly on a param's base per-device shape) as ZeRO
            # traffic instead of zero-sync violations.
            "zero_sharding": zero_meta,
            "memory_classes": {
                "params": (lambda: handle.params,
                           lambda: handle.param_shardings),
                "opt_state": (lambda: optimizer.opt_state,
                              lambda: optimizer.opt_shardings),
                # The accumulation buffer is zeros_like(params): same
                # structure, same shardings.
                "accum": (lambda: optimizer._accum_grads,
                          lambda: handle.param_shardings),
            },
        }

    def audit(self, built, batch, clip_norm: float = 0.0,
              intermediate_threshold_bytes: int = 64 * 1024 * 1024,
              memory: bool = True):
        """Statically audit a built artifact (``build_train_step`` /
        ``build_train_window`` output, or any jitted fn exposing ``.lower``)
        against the framework's program-level invariants: collective inventory
        per mesh axis (dp-axis all-gathers flagged), donation effectiveness
        via input–output aliasing, host callbacks, dtype upcasts, and
        oversized per-device intermediates. Returns
        :class:`~.analysis.AuditReport`; see docs/analysis.md for the schema.

        For the fused builders the report additionally carries the static
        memory audit as ``report.memory`` (a
        :class:`~.analysis.MemoryReport`): per-device HBM bytes by class
        (param / opt-state / accum / batch / activation-workspace), the
        sharded-vs-replicated split per named mesh axis, implicit resharding
        copies, and the OOM-before-launch verdict. ``memory=False`` skips it.

        ``batch`` must be shaped as the artifact expects (window-stacked for a
        window program). Auditing lowers and compiles but never executes — no
        training state is touched."""
        from .analysis import audit_built

        report = audit_built(
            built, batch, clip_norm,
            mesh=self.mesh,
            intermediate_threshold_bytes=intermediate_threshold_bytes,
            memory=memory,
        )
        # Feed the trace attributor's axis join: a later profile capture can
        # then attribute measured collective time to the NAMED mesh axes this
        # program's inventory established (telemetry/traceview.py).
        from .telemetry.traceview import attach_collective_axes, attach_kernel_names

        attach_collective_axes(report)
        # Same join for named Pallas kernels: captured custom-call time then
        # attributes to the kernels this program's inventory established.
        attach_kernel_names(report)
        if report.memory is not None:
            # Arm the timeline's predicted-vs-observed peak cross-check: the
            # next summary() compares this static prediction to the live
            # memory_stats() peak on backends that report one.
            self.telemetry.timeline.set_predicted_peak(
                report.memory.predicted_peak_bytes
            )
        return report

    def fingerprint(self, built, batch, clip_norm: float = 0.0,
                    config: str = "unknown", report=None):
        """Canonical :class:`~.analysis.fingerprint.ProgramFingerprint` of a
        built artifact — the drift-gate identity (per-axis collective
        inventory with ZeRO attribution, donation contract + misses,
        per-class replication split, dtype-flow census/flags). ``report``
        reuses an :meth:`audit` already run on the SAME program so only a
        fresh lowering is paid; without it the program is lowered, compiled,
        and audited here. Never executes a step."""
        from .analysis.fingerprint import fingerprint_built

        return fingerprint_built(
            built, batch, clip_norm, config=config, mesh=self.mesh, report=report,
        )

    def memory_report(self, built, batch, clip_norm: float = 0.0,
                      budget_bytes: int | None = None):
        """Static HBM audit of a built artifact without the full program
        audit: returns the :class:`~.analysis.MemoryReport` directly (see
        :meth:`audit` for what it contains). ``budget_bytes`` overrides the
        per-generation HBM × headroom budget the OOM verdict gates on —
        the ``accelerate-tpu memcheck --budget-gib`` path."""
        from .analysis import memory_report_from_built

        report = memory_report_from_built(
            built, batch, clip_norm, mesh=self.mesh, budget_bytes=budget_bytes,
        )
        self.telemetry.timeline.set_predicted_peak(report.predicted_peak_bytes)
        return report

    def _place_window_batch(self, batch):
        """Host leaves of a K-stacked window → global mesh arrays (window axis
        replicated, batch axis — dim 1 — on the data axes). Device-resident
        leaves (the prefetcher's output) pass through untouched."""
        from .parallel.sharding import make_global_window_batch

        return self._place_with(batch, make_global_window_batch)

    # ------------------------------------------------------------ collectives
    def gather(self, tensor):
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather and drop the duplicated tail samples of the final batch
        (reference :2751-2823).

        Non-tensor payloads (strings, object-dtype arrays, arbitrary
        picklables) route through ``gather_object`` *by detection*, not by
        catching everything: a genuine collective failure (shape mismatch,
        dead host, backend error) on tensor data must surface, not silently
        degrade to the pickle path."""
        from .telemetry import span

        if not use_gather_object and self.num_processes > 1:
            use_gather_object = _has_object_leaves(input_data)
        with span("gather_for_metrics"):
            if use_gather_object:
                all_tensors = ops.gather_object(input_data)
            else:
                all_tensors = ops.gather(input_data)
        if not self.gradient_state.end_of_dataloader:
            return all_tensors
        remainder = self.gradient_state.remainder
        if remainder is None or remainder <= 0:
            return all_tensors
        if use_gather_object:
            return all_tensors[:remainder]

        def _trim(t):
            return t[:remainder] if hasattr(t, "shape") and np.ndim(t) > 0 else t

        return ops.recursively_apply(_trim, all_tensors)

    def reduce(self, tensor, reduction="sum", scale=1.0):
        return ops.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        return ops.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # ------------------------------------------------------------ early stop
    def set_trigger(self):
        """Cross-process early-stop flag (reference :2536-2563)."""
        self.flag_tensor = np.ones((), dtype=np.int32)

    def check_trigger(self) -> bool:
        local = self.flag_tensor if self.flag_tensor is not None else np.zeros((), dtype=np.int32)
        total = ops.reduce(local, reduction="sum")
        from .utils.transfer import host_fetch

        if float(host_fetch(total)) >= 1:
            self.flag_tensor = None
            return True
        return False

    # -------------------------------------------------------------- unwrap &c
    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """Return (module, params) behind a PreparedModel (reference
        ``extract_model_from_parallel``, utils/other.py:197)."""
        if isinstance(model, PreparedModel):
            return model.module
        return model

    def get_state_dict(self, model, unwrap: bool = True):
        """Full (host) state dict — always gatherable here because params are
        global arrays (the zero3/FSDP special-casing at :3661 dissolves)."""
        if isinstance(model, PreparedModel):
            params = model.params
        else:
            params = getattr(model, "params", model)
        from .utils.transfer import host_fetch

        return jax.tree_util.tree_map(host_fetch, params)

    def free_memory(self, *objects):
        """Release prepared references & buffers (reference :3570-3608)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        import gc

        gc.collect()
        try:
            jax.clear_caches()
        except Exception:
            pass
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # ------------------------------------------------------- trackers / log
    def init_trackers(self, project_name: str, config: dict | None = None, init_kwargs: dict | None = None):
        from .tracking import init_trackers as _init

        self.trackers = _init(self.log_with, project_name, self.logging_dir, config, init_kwargs, self)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not found: available {[t.name for t in self.trackers]}")

    def log(self, values: dict, step: int | None = None, log_kwargs: dict | None = None):
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(values, step=step, **((log_kwargs or {}).get(tracker.name, {})))

    def log_goodput(self, step: int | None = None):
        """Push the goodput/badput wall-clock breakdown (resilience/goodput.py)
        through the active trackers as ``goodput/*`` series — productive step
        time vs compile / checkpoint save / restore / restart downtime."""
        from .resilience.goodput import get_ledger

        self.log({f"goodput/{k}": v for k, v in get_ledger().summary().items()}, step=step)

    # -------------------------------------------------------------- telemetry
    @property
    def telemetry(self):
        """The process-wide :class:`~.telemetry.Telemetry` — always-on step
        timeline, span ring, metrics registry, straggler monitor — built from
        the launcher's env contract (ACCELERATE_TELEMETRY /
        ACCELERATE_METRICS_PORT / ACCELERATE_STRAGGLER_THRESHOLD) on first
        access; ``configure_telemetry`` overrides it."""
        if self._telemetry is None:
            from .telemetry import get_telemetry

            self._telemetry = get_telemetry()
        return self._telemetry

    def configure_telemetry(self, **kwargs):
        """Build the telemetry stack explicitly (kwargs go to
        :class:`~.telemetry.Telemetry`); replaces the lazy/env default for
        this process so framework-internal hooks see the same instance."""
        from .telemetry import Telemetry, set_telemetry

        previous = self._telemetry
        self._telemetry = Telemetry(**kwargs)
        # A fused step built before this call keeps feeding the (now current)
        # instance via the self.telemetry indirection; carry the model flop
        # count over so its MFU estimate survives the swap.
        if previous is not None and self._telemetry.timeline._flops_per_token is None:
            self._telemetry.timeline._flops_per_token = previous.timeline._flops_per_token
        set_telemetry(self._telemetry)
        return self._telemetry

    def log_telemetry(self, step: int | None = None):
        """Push the step-timeline summary and the metrics-registry snapshot
        through the active trackers — ``telemetry/*`` for the timeline schema
        (docs/observability.md) and ``metrics/*`` for every registered
        counter/gauge (goodput classes, health trips, restarts, ...)."""
        telemetry = self.telemetry
        values: dict = {}

        def flatten(prefix, value):
            if isinstance(value, dict):
                for key, inner in value.items():
                    flatten(f"{prefix}/{key}", inner)
            else:
                values[prefix] = value

        flatten("telemetry", telemetry.summary())
        for name, val in telemetry.registry.snapshot().items():
            values[f"metrics/{name}"] = val
        self.log(values, step=step if step is not None else self.step)

    def end_training(self):
        """Flush trackers AND join queued async checkpoint writes: a script
        that returns right after a non-blocking ``save_state`` must not drop
        shard writes still draining on orbax's background thread (an atexit
        hook in ``checkpointing`` is the backstop for scripts that never call
        this)."""
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.finish()
        self.finish_pending_saves()
        self.wait_for_everyone()

    # ----------------------------------------------------------- checkpointing
    def register_for_checkpointing(self, *objects):
        """Objects with state_dict/load_state_dict saved in save_state (reference :3733)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(f"Objects lack state_dict/load_state_dict: {invalid}")
        self._custom_objects.extend(objects)

    def save_state(self, output_dir: str | None = None, **save_model_func_kwargs):
        """``blocking=False`` queues the array writes in the background and
        returns immediately (training continues while HBM drains to disk);
        join with ``finish_pending_saves()`` or let ``load_state`` join."""
        from .checkpointing import save_accelerator_state
        from .telemetry import span

        with span("checkpoint_save"):
            return save_accelerator_state(self, output_dir, **save_model_func_kwargs)

    def finish_pending_saves(self):
        from .checkpointing import finish_pending_saves

        finish_pending_saves()

    def load_state(self, input_dir: str | None = None, **load_model_func_kwargs):
        from .checkpointing import load_accelerator_state
        from .telemetry import span

        with span("checkpoint_restore"):
            return load_accelerator_state(self, input_dir, **load_model_func_kwargs)

    def save_model(self, model, save_directory, max_shard_size="10GB", safe_serialization=True):
        from .checkpointing import save_model as _save_model

        return _save_model(self, model, save_directory, max_shard_size, safe_serialization)

    # -------------------------------------------------------------- resilience
    @property
    def preemption_watcher(self):
        """The process-wide :class:`~.resilience.preemption.PreemptionWatcher`,
        installed on first access (or earlier, by ``PartialState`` when the
        launcher exported ACCELERATE_HANDLE_PREEMPTION)."""
        if self._preemption_watcher is None:
            from .resilience.preemption import get_default_watcher

            self._preemption_watcher = get_default_watcher(install=True)
        return self._preemption_watcher

    def checkpoint_on_preemption(self, output_dir: str | None = None,
                                 step: int | None = None, window: int = 1) -> bool:
        """Call once per training step: emergency-checkpoint if preempted.

        Three things happen, in order: (1) the deterministic fault plan
        (ACCELERATE_FAULT_PLAN, resilience/faults.py) fires any fault scheduled
        for this step; (2) the preemption watcher's per-host flags (SIGTERM/
        SIGINT, maintenance poller) are combined into an all-host agreement —
        one scalar collective, so every process must call this at the same step
        boundary; (3) on agreement, a SYNCHRONOUS ``save_state`` runs (queued
        async writes joined too — the grace window is short and a half-written
        emergency checkpoint is worse than none) and True is returned so the
        training loop can exit cleanly for ``run_resilient`` / the launcher to
        restart-and-resume.

        ``step`` defaults to an internal once-per-call counter; pass the loop's
        own global step when resuming mid-plan so fault steps stay aligned.
        Windowed loops (``build_train_window``) call this once per window with
        ``window=K`` so the internal counter keeps per-STEP numbering (fault
        plans and resume positions stay window-size-independent); kill-style
        faults scheduled anywhere inside the window fire at its boundary — the
        earliest point host control returns from the fused program.
        """
        from .health.hang import beat_default
        from .resilience.faults import active_plan
        from .resilience.goodput import get_ledger

        window = max(int(window), 1)
        self._resilience_step += window
        step = self._resilience_step if step is None else step
        # A completed step boundary is a heartbeat: loops that only call this
        # hook (no guard_step) still keep the hang watchdog fed.
        beat_default(step)
        # ...and a telemetry boundary — but only when no health guard is in
        # play: guard_step is then the designated timeline feeder, and its
        # numbering (self.step) can diverge from the private resilience
        # counter here (resumes restore self.step; accumulation counts
        # micro-steps), which would defeat the per-step dedupe and
        # double-sample every step. Guard-less resilient loops keep their
        # timeline through this hook with its own consistent numbering.
        if self._health_guard is None:
            self.telemetry.on_step(step, state=self.state, window=window)
        # Install the watcher BEFORE the fault plan can deliver a signal: a
        # 'sigterm' fault at the first hooked step must hit the sticky-flag
        # handler, not the default disposition (process death).
        watcher = self.preemption_watcher
        plan = active_plan()
        if plan is not None:
            # Windowed loops: a kill/sigterm/stall scheduled at ANY in-window
            # step fires at this boundary, where host control first returns.
            for in_window in range(step - window + 1, step + 1):
                plan.maybe_fire(in_window)
        if not watcher.sync(self.state):
            return False
        logger.warning(f"Preemption agreed at step {step}: taking an emergency checkpoint.")
        self.save_state(output_dir)  # ckpt_save time recorded by checkpointing
        with get_ledger().track("ckpt_save"):
            self.finish_pending_saves()
        self.wait_for_everyone()
        return True

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def reshard(self, devices=None, min_data_parallel: int = 1):
        """Re-form the mesh over a different device set (elastic world-size
        change) and redistribute all prepared state onto it — see
        :func:`~.resilience.elastic.reshard_accelerator` and
        docs/resilience.md "Elastic world size". Only the dp axis resizes;
        gradient accumulation rescales to preserve the global batch. Every
        fused program built before this call must be rebuilt (stale ones
        raise pointedly). Normally driven by ``run_resilient(elastic=True)``
        rather than called directly."""
        from .resilience.elastic import reshard_accelerator

        return reshard_accelerator(
            self, devices=devices, min_data_parallel=min_data_parallel
        )

    # -------------------------------------------------------------- health
    @property
    def health_guard(self):
        """The :class:`~.health.guard.HealthGuard` driven by ``guard_step``,
        built lazily from the env contract (ACCELERATE_GUARD_NUMERICS /
        ACCELERATE_SPIKE_ZSCORE — the launcher's --guard_numerics /
        --spike_zscore flags); ``configure_health`` overrides it."""
        if self._health_guard is None:
            self._health_guard = self._build_health_guard()
        return self._health_guard

    def configure_health(self, **kwargs):
        """Build the health guard explicitly (kwargs go to
        :class:`~.health.guard.HealthGuard`); replaces any lazy/env guard."""
        from .health.guard import HealthGuard

        self._health_guard = HealthGuard(**kwargs)
        return self._health_guard

    def _build_health_guard(self):
        from .health.guard import HealthGuard
        from .utils.constants import ENV_GUARD_NUMERICS, ENV_SPIKE_ZSCORE

        # The sentinel is always-on by default; the env can only widen or
        # disable it ("0"/"false"), mirroring the launch-flag semantics.
        kwargs: dict = {
            "numerics": os.environ.get(ENV_GUARD_NUMERICS, "").strip().lower()
            not in ("0", "false", "no")
        }
        zscore = os.environ.get(ENV_SPIKE_ZSCORE, "").strip()
        if zscore:
            kwargs["spike_zscore"] = float(zscore)
        return HealthGuard(**kwargs)

    def guard_step(self, loss=None, step: int | None = None, window: int = 1):
        """Call once per training step, after the optimizer step: run the
        training-health protocol (docs/health.md) on this step's ``loss``.

        Windowed loops (``build_train_window``) call this once per WINDOW:
        ``loss`` is the retained K-vector the window returned, ``step`` the
        last in-window step, and ``window=K`` — one verdict dispatch covers
        all K losses, a trip quarantines the exact in-window step, and
        last-known-good snapshots are captured at window boundaries.

        Heartbeats the hang watchdog, consumes any ``nan``/``loss_spike``
        fault scheduled for this step, folds the numerics + spike verdict
        into one on-device dispatch, drains prior verdicts without blocking,
        agrees any trip across hosts, and applies the recovery action —
        rollback to the last-known-good snapshot (quarantining the poisoned
        step so ``health_guard.should_skip`` excludes it on replay) or
        skip+quarantine. Returns a :class:`~.health.guard.HealthVerdict`;
        after ``verdict.rolled_back`` the loop must re-read ``self.step``.

        ``step`` defaults to ``self.step`` — the 1-based count the resilient
        loop convention maintains (the same numbering fault plans use).
        """
        from .health.hang import beat_default

        step = self.step if step is None else step
        beat_default(step)
        # Same-step telemetry sample BEFORE any rollback rewinds the count;
        # the straggler exchange inside is collective, and guard_step already
        # carries the every-host-same-step contract it needs. (Under windowed
        # dispatch the fused boundary already fed the timeline; the hook's
        # boundary-watermark dedupe makes this a no-op sample then.)
        # The K-vector rides through unchanged: step_end retains it unfetched
        # (drain takes the last element), and when build_train_window already
        # fed this boundary the dedupe watermark skips the fallback entirely.
        self.telemetry.on_step(step, state=self.state, loss=loss, window=window)
        return self.health_guard.guard_step(self, loss, step, window=window)

    # ---------------------------------------------------------------- profile
    @contextlib.contextmanager
    def profile(self, profile_handler=None):
        """Manual trace capture (reference ``profile`` :3797-3856 builds
        torch.profiler; output opens in TensorBoard/perfetto).

        Built on the same :class:`~.telemetry.profiler.ProfileManager` as the
        triggered captures (``--profile_steps``, the slow-step z-score, POST
        /profile), so a manual capture gets identical treatment: the covered
        step range is recorded from the boundaries observed inside the block,
        start/stop/parse overhead books as ``profile`` badput, the capture
        lands in the flight recorder and the
        ``accelerate_profile_captures_total{trigger="manual"}`` counter, and
        the parsed attribution report surfaces in
        ``telemetry.timeline.summary()["profile"]``. Manual captures are
        exempt from the triggered-capture budget. Yields the trace directory;
        yields None — and the block runs untraced — when no
        ``output_trace_dir`` is configured (reference parity) or when a
        triggered capture is already in flight (jax has one global trace;
        stealing it would cut the triggered range short)."""
        handler = profile_handler or self.profile_handler or ProfileKwargs()
        trace_dir = handler.output_trace_dir
        if trace_dir is None:
            yield None
            return
        from .telemetry.profiler import get_profile_manager

        with get_profile_manager().manual_capture(trace_dir) as capture_dir:
            yield capture_dir

    def __repr__(self):
        return f"Accelerator(state={self.state!r})"


def _looks_like_schedule(obj) -> bool:
    """Heuristic for optax-style LR schedules: a callable whose signature
    accepts exactly one required positional argument (the step count).
    Unsignaturable callables (C extensions) pass — AcceleratedScheduler's own
    ``schedule(0)`` probe is the backstop there."""
    import inspect

    try:
        sig = inspect.signature(obj)
    except (TypeError, ValueError):
        return True
    required = [
        p for p in sig.parameters.values()
        if p.default is inspect.Parameter.empty
        and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    has_varargs = any(p.kind == p.VAR_POSITIONAL for p in sig.parameters.values())
    return len(required) == 1 or (len(required) == 0 and has_varargs)


def _has_object_leaves(data) -> bool:
    """True when ``data`` contains a leaf the tensor all-gather cannot carry:
    an object/string-dtype array, or any non-array leaf (str, None, dataclass,
    ...) other than plain numbers inside the nested containers."""
    if isinstance(data, (list, tuple)):
        return any(_has_object_leaves(v) for v in data)
    if isinstance(data, dict):
        return any(_has_object_leaves(v) for v in data.values())
    if ops.is_tensor_like(data):
        from .utils.transfer import host_view

        dtype = host_view(data).dtype if not hasattr(data, "dtype") else data.dtype
        return dtype == object or np.issubdtype(dtype, np.str_) or np.issubdtype(dtype, np.bytes_)
    return not isinstance(data, (int, float, complex, bool, np.number))


def _is_torch_dataloader(obj) -> bool:
    try:
        import torch.utils.data as tud

        return isinstance(obj, tud.DataLoader)
    except ImportError:
        return False
