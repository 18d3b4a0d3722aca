"""accelerate_tpu — a TPU-native training-acceleration framework.

Brand-new JAX/XLA/Pallas re-design with the capability surface of the
reference HuggingFace-Accelerate fork (see SURVEY.md): a user writes a plain
training step; the framework supplies device meshes, GSPMD sharding (DP/FSDP/
HSDP/TP/CP/SP/EP), mixed precision, data sharding, checkpointing,
observability, and a launcher CLI.
"""

__version__ = "0.1.0"

from .parallelism_config import MESH_AXIS_ORDER, ParallelismConfig
from .state import AcceleratorState, GradientState, PartialState
from .utils.dataclasses import (
    AutocastKwargs,
    ContextParallelConfig,
    DataLoaderConfiguration,
    DistributedOperationException,
    DistributedType,
    ExpertParallelConfig,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradSyncKwargs,
    InitProcessGroupKwargs,
    MixedPrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
    ResiliencePlugin,
    SequenceParallelConfig,
    ServingPlugin,
    ShardingStrategy,
    TelemetryPlugin,
    TensorParallelConfig,
)

from .accelerator import Accelerator
from .data_loader import prepare_data_loader, skip_first_batches
from .big_modeling import (
    abstract_init,
    cpu_offload,
    disk_offload,
    dispatch_model,
    infer_auto_device_map,
    infer_auto_placement,
    init_empty_weights,
    load_checkpoint_and_dispatch,
    load_checkpoint_in_model,
    offload_state_dict,
    offload_store_params,
    offloaded_apply,
)
from .utils.memory import find_executable_batch_size
from .utils.random import set_seed, synchronize_rng_states
from .launchers import debug_launcher, notebook_launcher
from .parallel.pipeline_parallel import PipelinedModel, prepare_pipeline
from .local_sgd import LocalSGD
from .utils.other import extract_model_from_parallel
from .hooks import (
    AlignDevicesHook,
    ModelHook,
    SequentialHook,
    add_hook_to_apply,
    attach_align_device_hook,
    remove_hook_from_apply,
)
from .utils.quantization import (
    QuantizationConfig,
    load_and_quantize_model,
    quantize_params,
    quantized_apply,
)
from .generation import (
    GenerationConfig,
    beam_search,
    generate,
    generate_seq2seq,
    generate_streamed,
    place_params_host,
    sample_logits,
)
from .ops.streaming import LayerPrefetcher, StreamStats
from .telemetry import (
    SLOMonitor,
    SpanRecorder,
    TrainTimeline,
    TwinRegistry,
    twin_registry,
)
