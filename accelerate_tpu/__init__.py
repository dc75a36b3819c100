"""accelerate_tpu — a TPU-native (JAX/XLA/pjit) training & inference framework with
the capabilities of HuggingFace Accelerate.

Public surface mirrors the reference facade (``src/accelerate/__init__.py:16-46``):
``Accelerator``, ``PartialState``, big-modeling helpers, utils — re-architected
around one ``jax.sharding.Mesh`` and compiled train steps instead of wrapped
torch modules.
"""

__version__ = "0.1.0"

from .state import AcceleratorState, DistributedType, GradientState, PartialState
from .parallel.mesh import ParallelismConfig
from .utils.dataclasses import (
    AutocastKwargs,
    Fp8RecipeKwargs,
    DataLoaderConfiguration,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    JaxShardingKwargs,
    MegatronStylePlugin,
    PipelineParallelPlugin,
    ProfileKwargs,
    SequenceParallelPlugin,
    TensorParallelPlugin,
)
# Registers the listener that records each program's start-up into the span
# ring (telemetry/spans.py), so that every program built after this import is
# recorded, the model's weights among them.
from .telemetry import spans as _spans  # noqa: F401


def __getattr__(name):
    # Lazy imports keep `import accelerate_tpu` light and avoid circulars.
    if name == "Accelerator":
        from .accelerator import Accelerator

        return Accelerator
    if name in ("notebook_launcher", "debug_launcher"):
        from . import launchers

        return getattr(launchers, name)
    if name in (
        "init_empty_weights",
        "init_on_device",
        "dispatch_model",
        "load_checkpoint_and_dispatch",
        "cpu_offload",
        "disk_offload",
    ):
        from . import big_modeling

        return getattr(big_modeling, name)
    if name == "infer_auto_device_map":
        from .utils.modeling import infer_auto_device_map

        return infer_auto_device_map
    if name in ("load_and_quantize_model", "QuantizationConfig"):
        from .utils import quantization

        return getattr(quantization, name)
    if name == "find_executable_batch_size":
        from .utils.memory import find_executable_batch_size

        return find_executable_batch_size
    if name == "skip_first_batches":
        from .data_loader import skip_first_batches

        return skip_first_batches
    if name == "DeviceBatchPrefetcher":
        from .data_loader import DeviceBatchPrefetcher

        return DeviceBatchPrefetcher
    if name == "prepare_pippy":
        from .inference import prepare_pippy

        return prepare_pippy
    if name in ("LocalSGD", "LocalSGDTrainer"):
        from . import local_sgd

        return getattr(local_sgd, name)
    if name in ("generate", "sample_logits", "beam_search", "assisted_generate"):
        from . import generation

        return getattr(generation, name)
    if name == "ContinuousBatcher":
        from .serving import ContinuousBatcher

        return ContinuousBatcher
    if name in ("from_hf", "from_hf_checkpoint"):
        from .models import convert

        return getattr(convert, name)
    if name in ("GPTTrainStep", "BertTrainStep", "T5TrainStep", "get_train_step"):
        from . import train_steps

        return getattr(train_steps, name)
    if name in (
        "run_resilient",
        "PreemptionWatcher",
        "FaultPlan",
        "SimulatedFault",
        "GoodputLedger",
    ):
        from . import resilience

        return getattr(resilience, name)
    if name in (
        "Telemetry",
        "StepTimeline",
        "StragglerMonitor",
        "MetricsRegistry",
        "get_registry",
        "get_telemetry",
        "span",
        "ProfileManager",
        "FlightRecorder",
        "get_profile_manager",
        "get_flight_recorder",
    ):
        from . import telemetry

        return getattr(telemetry, name)
    raise AttributeError(f"module 'accelerate_tpu' has no attribute {name!r}")
