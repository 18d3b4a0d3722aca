"""Lazy, cached availability probes for optional dependencies.

TPU-native analog of the reference's ``utils/imports.py`` (~60 ``is_*_available``
probes, reference utils/imports.py:1-518).  On the JAX stack the probe list is
much shorter: the heavy engines (DeepSpeed/Megatron/TE/bnb) have no meaning
here — their *capabilities* are native to XLA — so we only probe genuinely
optional integrations (trackers, torch interop, datasets).
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
from functools import lru_cache


@lru_cache
def _is_package_available(pkg_name: str, metadata_name: str | None = None) -> bool:
    exists = importlib.util.find_spec(pkg_name) is not None
    if exists and metadata_name is not None:
        try:
            importlib.metadata.version(metadata_name)
        except importlib.metadata.PackageNotFoundError:
            return False
    return exists


def is_jax_available() -> bool:
    return _is_package_available("jax")


def is_flax_available() -> bool:
    return _is_package_available("flax")


def is_optax_available() -> bool:
    return _is_package_available("optax")


def is_orbax_available() -> bool:
    return _is_package_available("orbax")


def is_chex_available() -> bool:
    return _is_package_available("chex")


def is_torch_available() -> bool:
    """Torch is only used for interop (DataLoader sources, weight import)."""
    return _is_package_available("torch")


def is_transformers_available() -> bool:
    return _is_package_available("transformers")


def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


def is_datasets_available() -> bool:
    return _is_package_available("datasets")


def is_einops_available() -> bool:
    return _is_package_available("einops")


def is_numpy_available() -> bool:
    return _is_package_available("numpy")


def is_pallas_available() -> bool:
    """Pallas ships inside jax.experimental on every supported jax."""
    return _is_package_available("jax") and importlib.util.find_spec("jax.experimental.pallas") is not None


# --------------------------------------------------------------------------
# Tracker backends (reference tracking.py registers 10; we probe the same set)
# --------------------------------------------------------------------------

def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboardX") or _is_package_available("tensorboard") or _is_package_available(
        "torch.utils.tensorboard"
    )


def is_wandb_available() -> bool:
    return _is_package_available("wandb")


def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


def is_aim_available() -> bool:
    return _is_package_available("aim")


def is_clearml_available() -> bool:
    return _is_package_available("clearml")


def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


def is_swanlab_available() -> bool:
    return _is_package_available("swanlab")


def is_trackio_available() -> bool:
    return _is_package_available("trackio")


def is_rich_available() -> bool:
    return _is_package_available("rich")


def is_tqdm_available() -> bool:
    return _is_package_available("tqdm")


def is_pynvml_available() -> bool:
    return _is_package_available("pynvml")


def is_psutil_available() -> bool:
    return _is_package_available("psutil")


def is_matplotlib_available() -> bool:
    return _is_package_available("matplotlib")


# --------------------------------------------------------------------------
# Hardware probes
# --------------------------------------------------------------------------

@lru_cache
def is_tpu_available(check_device: bool = True) -> bool:
    """True when the JAX backend is a TPU.  A backend that fails to
    initialize (e.g. a chip held by another process) raises — it is never
    reported as "no TPU"."""
    if not is_jax_available():
        return False
    if not check_device:
        return True
    import jax

    return any(d.platform == "tpu" for d in jax.devices())


@lru_cache
def is_multihost_available() -> bool:
    if not is_jax_available():
        return False
    import jax

    return jax.process_count() > 1


def is_bf16_available() -> bool:
    """bf16 is native on every TPU generation we target; always true on JAX."""
    return is_jax_available()
