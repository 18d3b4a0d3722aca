"""The single registry of file names and option lists (reference
``utils/constants.py:107`` — the reference centralizes weights/index file
names, rng-state patterns, and launcher option lists; modules were carrying
their own copies here until round 4).

Checkpoint-layout names are imported by ``checkpointing.py`` /
``big_modeling.py``; option lists back CLI ``choices=`` and config
validation so the questionnaire, the launcher, and the dataclasses cannot
drift apart.
"""

from __future__ import annotations

# -- checkpoint layout (save_state/load_state, save_model) -------------------
MODEL_NAME = "model"
TRAIN_STATE_DIR = "train_state"
RNG_STATE_NAME = "random_states_{}.pkl"
CUSTOM_STATES_NAME = "custom_checkpoint_{}.pkl"
SAMPLER_STATES_NAME = "sampler_states.json"
SCHEDULER_STATES_NAME = "scheduler_states.json"
METADATA_NAME = "accelerate_metadata.json"
CHECKPOINT_DIR_PREFIX = "checkpoint"
CHECKPOINT_DIR_PATTERN = r"checkpoint_\d+"
# verified atomic checkpoints (checkpointing.py): every file stages under
# <dir>.tmp, the manifest (per-file sizes + crc32) is written last, and one
# os.replace publishes the directory — the pattern above intentionally does
# NOT match *.tmp, so scans/GC never see a half-written checkpoint
CHECKPOINT_TMP_SUFFIX = ".tmp"
CHECKPOINT_MANIFEST_NAME = "checkpoint_manifest.json"

# -- unified weights files (save_model / load_checkpoint_in_model) -----------
SAFE_WEIGHTS_NAME = "model.safetensors"
SAFE_WEIGHTS_INDEX_NAME = "model.safetensors.index.json"
SAFE_WEIGHTS_SHARD_PATTERN = "model-{:05d}-of-{:05d}.safetensors"

# -- option lists (CLI choices / config validation / plugin env parsing) -----
MIXED_PRECISION_CHOICES = ["no", "bf16", "fp16"]
# what a request for the reference's fourth choice is told, wherever it arrives
FP8_REFUSED = "the chips this package runs on have no fp8 matmul units; use 'bf16'"
SHARDING_STRATEGY_CHOICES = ["FULL_SHARD", "SHARD_GRAD_OP", "NO_SHARD", "HYBRID_SHARD"]
REMAT_POLICY_CHOICES = ["full", "dots", "offload"]
GRAD_ACCUM_MODE_CHOICES = ["in_step", "across_steps"]
RNG_TYPES = ["python", "numpy", "jax", "torch", "generator"]
QUANTIZATION_SCHEMES = ["int8", "nf4"]
