"""HuggingFace-format checkpoint interop for the in-tree model families.

The reference framework's users hold HF checkpoints (torch ``state_dict``
naming, ``Linear.weight`` stored [out, in]); this module supplies the
``key_map``/``tensor_map`` pair that lets :func:`load_checkpoint_in_model`
stream those files straight into this framework's Llama-family param trees —
renamed, transposed, sharded, and cast on the fly (reference parity:
transformers ``from_pretrained`` + modeling.py:load_checkpoint_in_model,
which the reference big-model path composes the same way).

Correctness note: HF Llama applies rotary embeddings with the
``rotate_half`` (half-split) convention, which matches ``apply_rope`` here,
so weights need no permutation beyond the [out, in] -> [in, out] kernel
transpose.  Verified end-to-end by a golden logits-parity test against
``transformers.LlamaForCausalLM`` (tests/test_hf_interop.py).
"""

from __future__ import annotations

import re
from typing import Optional

import numpy as np

# (hf-name regex) -> our dot-path template.  Group refs use \1-style.
_LLAMA_RULES: list[tuple[str, str]] = [
    (r"^model\.embed_tokens\.weight$", r"params.embed_tokens.embedding"),
    (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
     r"params.layers_\1.self_attn.\2_proj.kernel"),
    (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
     r"params.layers_\1.mlp.\2_proj.kernel"),
    (r"^model\.layers\.(\d+)\.input_layernorm\.weight$",
     r"params.layers_\1.input_layernorm.scale"),
    (r"^model\.layers\.(\d+)\.post_attention_layernorm\.weight$",
     r"params.layers_\1.post_attention_layernorm.scale"),
    (r"^model\.norm\.weight$", r"params.norm.scale"),
    (r"^lm_head\.weight$", r"params.lm_head.kernel"),
]

# Mixtral's HF layout stores per-expert w1/w2/w3 tensors while this
# framework keeps experts STACKED [E, d, f] (GShard dispatch); the router
# renames directly, the experts go through the E-way stacking pass in
# :func:`load_hf_mixtral`.
_MIXTRAL_ROUTER_RULE = (
    r"^model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight$",
    r"params.layers_\1.block_sparse_moe.router.kernel",
)
_MIXTRAL_EXPERT_RE = re.compile(
    r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w([123])\.weight$"
)
_EXPERT_PROJ = {"1": "gate_proj", "2": "down_proj", "3": "up_proj"}

# HF buffers with no param here (recomputed from config at trace time)
_SKIP = re.compile(r"rotary_emb\.inv_freq$")


def hf_llama_key_map(name: str) -> Optional[str]:
    """HF **Llama-family** ``state_dict`` name -> this framework's param
    path (dot-separated, as load_checkpoint_in_model normalizes), or None
    for buffers that should be skipped.  Mixtral checkpoints go through
    :func:`load_hf_mixtral`, which adds the router rename and the E-way
    expert stacking pass."""
    if _SKIP.search(name):
        return None
    for pattern, template in _LLAMA_RULES:
        if re.match(pattern, name):
            return re.sub(pattern, template, name)
    return name  # unknown names pass through and surface as `unexpected`


def hf_llama_tensor_map(our_key: str, arr: np.ndarray) -> np.ndarray:
    """torch ``Linear.weight`` is [out, in]; flax kernels are [in, out].
    Embeddings ([vocab, hidden] both sides) and norm scales pass through."""
    if our_key.endswith("/kernel") and arr.ndim == 2:
        return arr.T
    return arr


def load_hf_llama(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                  sample_args=(), strict: bool = True, **kwargs):
    """One call: stream an HF-format Llama checkpoint (a safetensors
    file, an index.json, or a directory of shards) into ``model``'s param
    tree — renamed, transposed, optionally sharded over ``mesh``, cast to
    ``dtype``, and auto-tiered to host/disk when over HBM (thin wrapper
    over load_checkpoint_and_dispatch).  Returns (params, offload_store)."""
    from ..big_modeling import load_checkpoint_and_dispatch

    if getattr(model.config, "scan_layers", False):
        raise ValueError(
            "load_hf_llama needs the unrolled layout (HF names map to "
            "layers_{i}); load with scan_layers=False, then convert via "
            "stack_layer_params(params, scan_block_size)."
        )
    if not sample_args:
        import jax.numpy as jnp

        sample_args = (jnp.ones((1, 8), jnp.int32),)
    key_map = hf_llama_key_map
    if getattr(model.config, "tie_word_embeddings", False):
        # tied model: the head reuses embed_tokens, so the param tree has no
        # lm_head leaf — a stored lm_head.weight (some exporters keep one)
        # would otherwise surface as `unexpected` under strict
        def key_map(name):
            return None if name == "lm_head.weight" else hf_llama_key_map(name)

    try:
        return load_checkpoint_and_dispatch(
            model, checkpoint, rng=rng, sample_args=sample_args, mesh=mesh,
            dtype=dtype, strict=strict,
            key_map=key_map, tensor_map=hf_llama_tensor_map, **kwargs,
        )
    except ValueError as e:
        if "missing" in str(e) and "lm_head" in str(e):
            raise ValueError(
                "This checkpoint stores no lm_head.weight — it was saved with "
                "tied word embeddings (tie_word_embeddings=True, e.g. "
                "TinyLlama/Gemma-style exports). Build the model with "
                "tie_word_embeddings=True so the head reuses embed_tokens, or "
                "pass strict=False to leave lm_head abstract."
            ) from e
        raise


def hf_mixtral_key_map(name: str) -> Optional[str]:
    """Like :func:`hf_llama_key_map` plus the MoE router and the synthetic
    ``experts_stacked`` names that :func:`_stack_expert_stream` emits."""
    m = re.match(
        r"^model\.layers\.(\d+)\.block_sparse_moe\.experts_stacked\.(\w+)$", name
    )
    if m:
        return f"params.layers_{m.group(1)}.block_sparse_moe.experts.{m.group(2)}"
    if re.match(_MIXTRAL_ROUTER_RULE[0], name):
        return re.sub(*_MIXTRAL_ROUTER_RULE, name)
    return hf_llama_key_map(name)


def _stack_expert_stream(checkpoint, num_experts: int, expert_re=None, proj_of=None,
                         stacked: str = "model.layers.{layer}.block_sparse_moe.experts_stacked.{proj}"):
    """Adapt a raw HF Mixtral tensor stream: per-expert w1/w2/w3 [out, in]
    tensors are transposed and buffered per (layer, proj); as soon as all
    ``num_experts`` arrive, ONE stacked [E, ...] tensor is yielded under a
    synthetic ``experts_stacked`` name and the buffer entry is freed (HF
    files are layer-ordered, so at most ~one layer's projections are ever
    buffered).  Non-expert tensors pass through untouched, so the normal
    loader applies sharding plans / placement / dtype / strictness
    uniformly in a single read of the checkpoint."""
    from ..big_modeling import _iter_checkpoint_tensors

    expert_re = expert_re or _MIXTRAL_EXPERT_RE
    proj_of = proj_of or _EXPERT_PROJ.__getitem__
    buf: dict[tuple[str, str], dict[int, np.ndarray]] = {}
    for name, tensor in _iter_checkpoint_tensors(checkpoint):
        m = expert_re.match(name)
        if not m:
            yield name, tensor
            continue
        layer, eidx, w = m.group(1), int(m.group(2)), m.group(3)
        key = (layer, proj_of(w))
        buf.setdefault(key, {})[eidx] = np.asarray(tensor).T
        if len(buf[key]) == num_experts:
            group = buf.pop(key)
            yield (
                stacked.format(layer=layer, proj=key[1]),
                np.stack([group[i] for i in range(num_experts)]),
            )
    if buf:
        raise ValueError(
            "incomplete expert groups in checkpoint: "
            + ", ".join(
                f"layer {l} {p}: have {sorted(g)} of {num_experts}"
                for (l, p), g in buf.items()
            )
        )


def load_hf_mixtral(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                    sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format Mixtral checkpoint in one pass: shared weights
    stream like Llama; per-expert w1/w2/w3 tensors are transposed and
    stacked into this framework's [E, d, f] / [E, f, d] expert arrays by a
    stream adapter, so mesh sharding plans, device_map placement, dtype
    casting, and ``strict`` checking all apply to the experts exactly as to
    every other weight.  Returns (params, offload_store)."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    if getattr(model.config, "scan_layers", False):
        raise ValueError(
            "load_hf_mixtral needs the unrolled layout; load with "
            "scan_layers=False, then convert via stack_layer_params."
        )
    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    stream = _stack_expert_stream(checkpoint, model.config.num_local_experts)
    return load_checkpoint_and_dispatch(
        model, stream, rng=rng, sample_args=sample_args, mesh=mesh,
        dtype=dtype, strict=strict,
        key_map=hf_mixtral_key_map, tensor_map=hf_llama_tensor_map, **kwargs,
    )


# -- Keye-VL-2.0 (language model: sparse attention + dropless experts) -------
# The language model's leaves only: the vision tower's tensors are skipped.
# Per-expert gate/up/down tensors are stacked [E, in, out] like Mixtral's.
_KEYE_LM = r"(?:model\.language_model\.|language_model\.model\.|model\.)"
_KEYE_PREFIX = re.compile("^" + _KEYE_LM)
_KEYE_SKIP = re.compile(r"^(model\.)?(visual|vision_tower|mlp_AR|multi_modal_projector)\.|rotary_emb\.inv_freq$")
_KEYE_EXPERT_RE = re.compile(
    "^" + _KEYE_LM + r"layers\.(\d+)\.mlp\.experts\.(\d+)\.(gate|up|down)_proj\.weight$")
_KEYE_RULES: list[tuple[str, str]] = [
    (r"^embed_tokens\.weight$", r"params.embed_tokens.embedding"),
    (r"^layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$", r"params.layers_\1.self_attn.\2_proj.kernel"),
    (r"^layers\.(\d+)\.self_attn\.(q|k)_norm\.weight$", r"params.layers_\1.self_attn.\2_norm.scale"),
    (r"^layers\.(\d+)\.self_attn\.indexer\.(q_proj|k_proj|weights_proj)\.weight$",
     r"params.layers_\1.self_attn.indexer.\2.kernel"),
    (r"^layers\.(\d+)\.(input|post_attention)_layernorm\.weight$", r"params.layers_\1.\2_layernorm.scale"),
    (r"^layers\.(\d+)\.mlp\.gate\.weight$", r"params.layers_\1.mlp.gate.kernel"),
    (r"^layers\.(\d+)\.mlp\.experts_stacked\.(gate|up|down)_proj$", r"params.layers_\1.mlp.experts_\2_proj"),
    (r"^norm\.weight$", r"params.norm.scale"),
]


def hf_keye_vl2_key_map(name: str) -> Optional[str]:
    """HF ``Keye-VL-2.0`` ``state_dict`` name -> ``KeyeVL2ForCausalLM``'s
    param path, for the language model's leaves (``model.language_model.*``,
    ``language_model.model.*`` or bare ``model.*`` prefixes); None for the
    vision tower's tensors and rotary buffers.  The per-expert tensors arrive
    stacked under ``experts_stacked`` from :func:`load_hf_keye_vl2`'s stream."""
    if _KEYE_SKIP.search(name):
        return None
    if name == "lm_head.weight" or name.endswith(".lm_head.weight"):
        return "params.lm_head.kernel"
    inner = _KEYE_PREFIX.sub("", name)
    for pattern, template in _KEYE_RULES:
        if re.match(pattern, inner):
            return re.sub(pattern, template, inner)
    return name  # unknown names pass through and surface as `unexpected`


def load_hf_keye_vl2(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                     sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format Keye-VL-2.0 checkpoint's LANGUAGE MODEL into
    ``KeyeVL2ForCausalLM``'s param tree (the vision tower is not built and
    its tensors are skipped); experts stacked as in :func:`load_hf_mixtral`."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    stream = _stack_expert_stream(
        checkpoint, model.config.num_experts, _KEYE_EXPERT_RE, lambda w: f"{w}_proj",
        "model.layers.{layer}.mlp.experts_stacked.{proj}")
    return load_checkpoint_and_dispatch(
        model, stream, rng=rng, sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_keye_vl2_key_map, tensor_map=hf_llama_tensor_map, **kwargs)


# -- K-EXAONE (window + full attention, sigmoid-routed experts + a shared expert) ---
# Names follow the DeepSeek-V3-style block whose keys the published config
# carries (``mlp.gate.e_score_correction_bias``, ``mlp.shared_experts.*``);
# per-expert tensors are stacked [E, in, out] like Mixtral's.  The
# next-token-prediction module's tensors (``model.mtp.*``) map to ``params.mtp``.
_K_EXAONE_EXPERT_RE = re.compile(
    r"^model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.(gate|up|down)_proj\.weight$")
_K_EXAONE_BLOCK: list[tuple[str, str]] = [
    (r"self_attn\.(q|k|v|o)_proj\.weight$", r"self_attn.\1_proj.kernel"),
    (r"self_attn\.(q|k)_norm\.weight$", r"self_attn.\1_norm.scale"),
    (r"(input|post_attention)_layernorm\.weight$", r"\1_layernorm.scale"),
    (r"mlp\.gate\.weight$", r"mlp.gate.kernel"),
    (r"mlp\.gate\.e_score_correction_bias$", r"mlp.e_score_correction_bias"),
    (r"mlp\.experts_stacked\.(gate|up|down)_proj$", r"mlp.experts_\1_proj"),
    (r"mlp\.shared_experts\.(gate|up|down)_proj\.weight$", r"mlp.shared_experts.\1_proj.kernel"),
    (r"mlp\.(gate|up|down)_proj\.weight$", r"mlp.\1_proj.kernel"),
]
_K_EXAONE_TOP = {"model.embed_tokens.weight": "params.embed_tokens.embedding",
                 "model.norm.weight": "params.norm.scale", "lm_head.weight": "params.lm_head.kernel",
                 "model.mtp.hnorm.weight": "params.mtp.hnorm.scale",
                 "model.mtp.enorm.weight": "params.mtp.enorm.scale",
                 "model.mtp.eh_proj.weight": "params.mtp.eh_proj.kernel"}


def hf_k_exaone_key_map(name: str) -> Optional[str]:
    """HF ``exaone_moe`` ``state_dict`` name -> ``KExaoneForCausalLM``'s param
    path (the whole model: a share's slices are the caller's to cut); None
    for rotary buffers."""
    if name.endswith("rotary_emb.inv_freq"):
        return None
    if name in _K_EXAONE_TOP:
        return _K_EXAONE_TOP[name]
    m = re.match(r"^model\.(?:layers\.(\d+)|mtp\.block)\.(.+)$", name)
    if m:
        scope = "mtp.block" if m.group(1) is None else f"layers_{m.group(1)}"
        for pattern, template in _K_EXAONE_BLOCK:
            if re.match(pattern, m.group(2)):
                return f"params.{scope}." + re.sub(pattern, template, m.group(2))
    return name  # unknown names pass through and surface as `unexpected`


def load_hf_k_exaone(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                     sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format K-EXAONE checkpoint into ``KExaoneForCausalLM``'s
    param tree; experts stacked as in :func:`load_hf_mixtral`."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    stream = _stack_expert_stream(
        checkpoint, model.config.num_experts, _K_EXAONE_EXPERT_RE, lambda w: f"{w}_proj",
        "model.layers.{layer}.mlp.experts_stacked.{proj}")
    return load_checkpoint_and_dispatch(
        model, stream, rng=rng, sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_k_exaone_key_map, tensor_map=hf_llama_tensor_map, **kwargs)


# -- JoyAI-LLM-Flash (latent attention, sigmoid-routed experts + a shared expert) ---
# ASSUMED: DeepSeek-V3's tensor names, which every key of the published config
# is one of (``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj``,
# ``kv_a_proj_with_mqa`` / ``kv_a_layernorm`` / ``kv_b_proj``); the MLP, router
# and next-token-prediction names are K-EXAONE's rules above.  ``kv_b_proj``
# stays ONE tensor ``[kv_lora_rank, heads x (nope + v)]``, head-major, each
# head's columns ``[W_UK,h ; W_UV,h]``: the model cuts it per head.
_JOYAI_BLOCK: list[tuple[str, str]] = [
    (r"self_attn\.(q_a|q_b|kv_a_proj_with_mqa|o)(_proj)?\.weight$", r"self_attn.\1\2.kernel"),
    (r"self_attn\.(q_a|kv_a)_layernorm\.weight$", r"self_attn.\1_layernorm.scale"),
    (r"self_attn\.kv_b_proj\.weight$", r"self_attn.kv_b_proj"),
] + _K_EXAONE_BLOCK[2:]


def hf_joyai_flash_key_map(name: str) -> Optional[str]:
    """HF ``joyai_llm_flash`` ``state_dict`` name -> ``JoyAIFlashForCausalLM``'s
    param path (the whole model: a share's slices are the caller's to cut);
    None for rotary buffers."""
    if name.endswith("rotary_emb.inv_freq"):
        return None
    if name in _K_EXAONE_TOP:
        return _K_EXAONE_TOP[name]
    m = re.match(r"^model\.(?:layers\.(\d+)|mtp\.block)\.(.+)$", name)
    if m:
        scope = "mtp.block" if m.group(1) is None else f"layers_{m.group(1)}"
        for pattern, template in _JOYAI_BLOCK:
            if re.match(pattern, m.group(2)):
                return f"params.{scope}." + re.sub(pattern, template, m.group(2))
    return name  # unknown names pass through and surface as `unexpected`


def load_hf_joyai_flash(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                        sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format JoyAI-LLM-Flash checkpoint into
    ``JoyAIFlashForCausalLM``'s param tree; experts stacked as in
    :func:`load_hf_mixtral`.  ``rope_interleave``: the checkpoint's rotary
    dims pair as ``(2i, 2i + 1)`` and the program rotates halves, so the last
    ``qk_rope_head_dim`` columns of ``kv_a_proj_with_mqa`` and of every head
    of ``q_b_proj`` are de-interleaved on the way in
    (``models/joyai_flash.deinterleave_rope``)."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch
    from .joyai_flash import deinterleave_rope_columns

    cfg = model.config
    dr = cfg.qk_rope_head_dim

    def tensor_map(our_key: str, arr: np.ndarray) -> np.ndarray:
        if our_key.endswith("/kv_b_proj"):
            return arr.T
        arr = hf_llama_tensor_map(our_key, arr)
        if not cfg.rope_interleave:
            return arr
        if our_key.endswith("q_b_proj/kernel"):
            return deinterleave_rope_columns(arr, cfg.qk_nope_head_dim + dr, dr)
        if our_key.endswith("kv_a_proj_with_mqa/kernel"):
            return deinterleave_rope_columns(arr, arr.shape[-1], dr)
        return arr

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    stream = _stack_expert_stream(
        checkpoint, cfg.num_experts, _K_EXAONE_EXPERT_RE, lambda w: f"{w}_proj",
        "model.layers.{layer}.mlp.experts_stacked.{proj}")
    return load_checkpoint_and_dispatch(
        model, stream, rng=rng, sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_joyai_flash_key_map, tensor_map=tensor_map, **kwargs)


# -- Qwen3-Next (Gated DeltaNet + gated attention, softmax-routed experts + a gated shared expert) ---
# The published modelling code's names.  ``linear_attn.in_proj_qkvz`` and
# ``in_proj_ba`` stay FUSED, their outputs a key head's group after another
# (``[q; k; v x r; z x r]``, ``[b x r; a x r]``): the model cuts them per
# group, and a tensor-parallel share is a run of whole groups
# (``tests/test_qwen3_next.py`` cuts four and adds them up).  ``conv1d.weight`` ``[C, 1, K]``
# becomes ``[K, C]``; ``A_log``, ``dt_bias`` and the gated norm's weight are
# bare leaves.  The multi-token-prediction module (``mtp.*``) is not built.
_QWEN3_NEXT_BLOCK: list[tuple[str, str]] = [
    (r"linear_attn\.(in_proj_qkvz|in_proj_ba|out_proj)\.weight$", r"linear_attn.\1.kernel"),
    (r"linear_attn\.conv1d\.weight$", r"linear_attn.conv1d"),
    (r"linear_attn\.(A_log|dt_bias)$", r"linear_attn.\1"),
    (r"linear_attn\.norm\.weight$", r"linear_attn.norm"),
    (r"self_attn\.(q|k|v|o)_proj\.weight$", r"self_attn.\1_proj.kernel"),
    (r"self_attn\.(q|k)_norm\.weight$", r"self_attn.\1_norm.weight"),
    (r"(input|post_attention)_layernorm\.weight$", r"\1_layernorm.weight"),
    (r"mlp\.gate\.weight$", r"mlp.gate.kernel"),
    (r"mlp\.experts_stacked\.(gate|up|down)_proj$", r"mlp.experts_\1_proj"),
    (r"mlp\.shared_expert\.(gate|up|down)_proj\.weight$", r"mlp.shared_expert.\1_proj.kernel"),
    (r"mlp\.shared_expert_gate\.weight$", r"mlp.shared_expert_gate.kernel"),
]
_QWEN3_NEXT_TOP = {"model.embed_tokens.weight": "params.embed_tokens.embedding",
                   "model.norm.weight": "params.norm.weight",
                   "lm_head.weight": "params.lm_head.kernel"}


def hf_qwen3_next_key_map(name: str) -> Optional[str]:
    """HF ``qwen3_next`` ``state_dict`` name -> ``Qwen3NextForCausalLM``'s param
    path (the whole model: a share's slices are the caller's to cut); None
    for rotary buffers and the multi-token-prediction module."""
    if name.endswith("rotary_emb.inv_freq") or name.startswith("mtp."):
        return None
    if name in _QWEN3_NEXT_TOP:
        return _QWEN3_NEXT_TOP[name]
    m = re.match(r"^model\.layers\.(\d+)\.(.+)$", name)
    if m:
        for pattern, template in _QWEN3_NEXT_BLOCK:
            if re.match(pattern, m.group(2)):
                return f"params.layers_{m.group(1)}." + re.sub(pattern, template, m.group(2))
    return name  # unknown names pass through and surface as `unexpected`


def load_hf_qwen3_next(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                       sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format Qwen3-Next checkpoint into ``Qwen3NextForCausalLM``'s
    param tree; experts stacked as in :func:`load_hf_mixtral`, the depthwise
    conv's ``[C, 1, K]`` weight laid out ``[K, C]``."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    def tensor_map(our_key: str, arr: np.ndarray) -> np.ndarray:
        if our_key.endswith("linear_attn/conv1d"):
            return arr[:, 0, :].T
        return hf_llama_tensor_map(our_key, arr)

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    stream = _stack_expert_stream(
        checkpoint, model.config.num_experts, _K_EXAONE_EXPERT_RE, lambda w: f"{w}_proj",
        "model.layers.{layer}.mlp.experts_stacked.{proj}")
    return load_checkpoint_and_dispatch(
        model, stream, rng=rng, sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_qwen3_next_key_map, tensor_map=tensor_map, **kwargs)


# -- Olmo-Hybrid (Gated DeltaNet + full attention, post-norm, dense MLP) ----------------
# The names are ASSUMED (the checkpoint was not read): the OLMo 2 / OLMo 3
# modelling code's for what that family has (``self_attn.{q,k,v,o}_proj``,
# full-width ``q_norm`` / ``k_norm``, ``post_attention_layernorm`` and
# ``post_feedforward_layernorm`` on the sublayers' outputs, ``mlp``) and the
# published Gated DeltaNet layer's for the rest (``linear_attn.{q,k,v,a,b,g,o}_proj``,
# one depthwise conv each for q, k and v, ``A_log``, ``dt_bias``, ``o_norm``).
# A conv's ``[C, 1, K]`` weight becomes ``[K, C]``; rotary buffers have no
# counterpart (``rope_theta: null``).
_OLMO_HYBRID_BLOCK: list[tuple[str, str]] = [
    (r"linear_attn\.(q|k|v|a|b|g|o)_proj\.weight$", r"linear_attn.\1_proj.kernel"),
    (r"linear_attn\.(q|k|v)_conv1d\.weight$", r"linear_attn.\1_conv1d"),
    (r"linear_attn\.(A_log|dt_bias)$", r"linear_attn.\1"),
    (r"linear_attn\.o_norm\.weight$", r"linear_attn.o_norm.scale"),
    (r"self_attn\.(q|k|v|o)_proj\.weight$", r"self_attn.\1_proj.kernel"),
    (r"self_attn\.(q|k)_norm\.weight$", r"self_attn.\1_norm.scale"),
    (r"(post_attention|post_feedforward)_layernorm\.weight$", r"\1_layernorm.scale"),
    (r"mlp\.(gate|up|down)_proj\.weight$", r"mlp.\1_proj.kernel"),
]
_OLMO_HYBRID_TOP = {"model.embed_tokens.weight": "params.embed_tokens.embedding",
                    "model.norm.weight": "params.norm.scale",
                    "lm_head.weight": "params.lm_head.kernel"}


def hf_olmo_hybrid_key_map(name: str) -> Optional[str]:
    """HF ``olmo_hybrid`` ``state_dict`` name -> ``OlmoHybridForCausalLM``'s
    param path; None for rotary buffers."""
    if name.endswith("rotary_emb.inv_freq"):
        return None
    if name in _OLMO_HYBRID_TOP:
        return _OLMO_HYBRID_TOP[name]
    m = re.match(r"^model\.layers\.(\d+)\.(.+)$", name)
    if m:
        for pattern, template in _OLMO_HYBRID_BLOCK:
            if re.match(pattern, m.group(2)):
                return f"params.layers_{m.group(1)}." + re.sub(pattern, template, m.group(2))
    return name  # unknown names pass through and surface as `unexpected`


def load_hf_olmo_hybrid(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                        sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format Olmo-Hybrid checkpoint into ``OlmoHybridForCausalLM``'s
    param tree; a depthwise conv's ``[C, 1, K]`` weight laid out ``[K, C]``."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    def tensor_map(our_key: str, arr: np.ndarray) -> np.ndarray:
        if our_key.endswith("_conv1d"):
            return arr[:, 0, :].T
        return hf_llama_tensor_map(our_key, arr)

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32),)
    return load_checkpoint_and_dispatch(
        model, checkpoint, rng=rng, sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_olmo_hybrid_key_map, tensor_map=tensor_map, **kwargs)


# -- BERT (encoder classifier) -----------------------------------------------
_BERT_RULES: list[tuple[str, str]] = [
    (r"^bert\.embeddings\.word_embeddings\.weight$", r"params.word_embeddings.embedding"),
    (r"^bert\.embeddings\.position_embeddings\.weight$", r"params.position_embeddings.embedding"),
    (r"^bert\.embeddings\.LayerNorm\.weight$", r"params.embeddings_norm.scale"),
    (r"^bert\.embeddings\.LayerNorm\.bias$", r"params.embeddings_norm.bias"),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.self\.(query|key|value)\.(weight|bias)$",
     r"params.layer_\1.attention.\2.\3"),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.dense\.(weight|bias)$",
     r"params.layer_\1.attention.dense.\2"),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.LayerNorm\.weight$",
     r"params.layer_\1.attention_norm.scale"),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.LayerNorm\.bias$",
     r"params.layer_\1.attention_norm.bias"),
    (r"^bert\.encoder\.layer\.(\d+)\.intermediate\.dense\.(weight|bias)$",
     r"params.layer_\1.intermediate.\2"),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.dense\.(weight|bias)$",
     r"params.layer_\1.output.\2"),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.LayerNorm\.weight$",
     r"params.layer_\1.output_norm.scale"),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.LayerNorm\.bias$",
     r"params.layer_\1.output_norm.bias"),
    (r"^bert\.pooler\.dense\.(weight|bias)$", r"params.pooler.\1"),
    (r"^classifier\.(weight|bias)$", r"params.classifier.\1"),
]


# non-parameter buffers (position_ids in pre-4.31 transformers exports) and
# the token-type table the stream adapter folds away
_BERT_SKIP = re.compile(
    r"^bert\.embeddings\.(position_ids|token_type_ids|token_type_embeddings\.weight)$"
)


def hf_bert_key_map(name: str) -> Optional[str]:
    """HF BERT ``state_dict`` name -> this framework's param path.  torch
    ``.weight`` on Dense layers becomes ``.kernel`` via the shared tensor
    map; embeddings/norms keep their names."""
    if _BERT_SKIP.match(name):
        return None
    for pattern, template in _BERT_RULES:
        if re.match(pattern, name):
            out = re.sub(pattern, template, name)
            # norms map to .scale and embeddings to .embedding explicitly in
            # the rules, so any remaining .weight IS a Dense kernel
            if out.endswith(".weight"):
                out = out[: -len(".weight")] + ".kernel"
            return out
    return name


def _fold_bert_token_types(checkpoint):
    """This framework's BERT has no token-type embedding (single-segment
    inputs); transformers adds ``token_type_embeddings[0]`` to every
    position, which folds exactly into the position-embedding table."""
    from ..big_modeling import _iter_checkpoint_tensors

    pos, typ, pos_name = None, None, None
    for name, tensor in _iter_checkpoint_tensors(checkpoint):
        if name == "bert.embeddings.position_embeddings.weight":
            pos, pos_name = np.asarray(tensor), name
        elif name == "bert.embeddings.token_type_embeddings.weight":
            typ = np.asarray(tensor)
        else:
            yield name, tensor
        if pos is not None and typ is not None:
            yield pos_name, pos + typ[0][None, :]
            pos, typ = None, None
    if pos is not None:  # checkpoint without token types: pass through
        yield pos_name, pos


def load_hf_bert(model, checkpoint, *, mesh=None, dtype=None, rng=None,
                 sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format BERT sequence-classification checkpoint into the
    in-tree model (token-type embeddings folded into positions — inputs are
    single-segment).  Returns (params, offload_store)."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32))
    return load_checkpoint_and_dispatch(
        model, _fold_bert_token_types(checkpoint), rng=rng,
        sample_args=sample_args, mesh=mesh, dtype=dtype, strict=strict,
        key_map=hf_bert_key_map, tensor_map=hf_llama_tensor_map, **kwargs,
    )


# -- T5 (encoder-decoder) ----------------------------------------------------
# HF layout: shared embedding + per-block numbered sub-layers (layer.0 self
# attention, layer.1 cross attention [decoder], last layer DenseReluDense);
# the relative-attention bias table lives only in block 0 of each stack.
_T5_RULES: list[tuple[str, str]] = [
    (r"^shared\.weight$", r"params.shared_embedding.embedding"),
    (r"^encoder\.block\.(\d+)\.layer\.0\.SelfAttention\.(q|k|v|o)\.weight$",
     r"params.enc_layers_\1.self_attn.\2_proj.kernel"),
    (r"^encoder\.block\.0\.layer\.0\.SelfAttention\.relative_attention_bias\.weight$",
     r"params.enc_rel_bias.rel_embedding"),
    (r"^encoder\.block\.(\d+)\.layer\.0\.layer_norm\.weight$",
     r"params.enc_layers_\1.ln_attn.scale"),
    (r"^encoder\.block\.(\d+)\.layer\.1\.DenseReluDense\.wi_0\.weight$",
     r"params.enc_layers_\1.mlp.wi_gate.kernel"),
    (r"^encoder\.block\.(\d+)\.layer\.1\.DenseReluDense\.wi_1\.weight$",
     r"params.enc_layers_\1.mlp.wi_up.kernel"),
    (r"^encoder\.block\.(\d+)\.layer\.1\.DenseReluDense\.wo\.weight$",
     r"params.enc_layers_\1.mlp.wo_mlp.kernel"),
    (r"^encoder\.block\.(\d+)\.layer\.1\.layer_norm\.weight$",
     r"params.enc_layers_\1.ln_mlp.scale"),
    (r"^encoder\.final_layer_norm\.weight$", r"params.enc_norm.scale"),
    (r"^decoder\.block\.(\d+)\.layer\.0\.SelfAttention\.(q|k|v|o)\.weight$",
     r"params.dec_layers_\1.self_attn.\2_proj.kernel"),
    (r"^decoder\.block\.0\.layer\.0\.SelfAttention\.relative_attention_bias\.weight$",
     r"params.dec_rel_bias.rel_embedding"),
    (r"^decoder\.block\.(\d+)\.layer\.0\.layer_norm\.weight$",
     r"params.dec_layers_\1.ln_self.scale"),
    (r"^decoder\.block\.(\d+)\.layer\.1\.EncDecAttention\.(q|k|v|o)\.weight$",
     r"params.dec_layers_\1.cross_attn.\2_proj.kernel"),
    (r"^decoder\.block\.(\d+)\.layer\.1\.layer_norm\.weight$",
     r"params.dec_layers_\1.ln_cross.scale"),
    (r"^decoder\.block\.(\d+)\.layer\.2\.DenseReluDense\.wi_0\.weight$",
     r"params.dec_layers_\1.mlp.wi_gate.kernel"),
    (r"^decoder\.block\.(\d+)\.layer\.2\.DenseReluDense\.wi_1\.weight$",
     r"params.dec_layers_\1.mlp.wi_up.kernel"),
    (r"^decoder\.block\.(\d+)\.layer\.2\.DenseReluDense\.wo\.weight$",
     r"params.dec_layers_\1.mlp.wo_mlp.kernel"),
    (r"^decoder\.block\.(\d+)\.layer\.2\.layer_norm\.weight$",
     r"params.dec_layers_\1.ln_mlp.scale"),
    (r"^decoder\.final_layer_norm\.weight$", r"params.dec_norm.scale"),
    (r"^lm_head\.weight$", r"params.lm_head.kernel"),
]

# aliases of `shared.weight` and buffers with no param here
_T5_SKIP = re.compile(r"^(encoder|decoder)\.embed_tokens\.weight$")


def hf_t5_key_map(name: str) -> Optional[str]:
    """HF T5 ``state_dict`` name -> this framework's T5 param path (see
    ``models/t5.py``; v1.1 gated-gelu MLP layout: wi_0 gate / wi_1 up)."""
    if _T5_SKIP.match(name):
        return None
    if re.match(r"^(encoder|decoder)\.block\.\d+\.layer\.\d\.DenseReluDense\.wi\.weight$", name):
        raise ValueError(
            "This T5 checkpoint uses the original ungated relu MLP "
            "(DenseReluDense.wi); the in-tree T5 implements the v1.1 "
            "gated-gelu layout (wi_0/wi_1). Load a t5-v1_1-* / flan-t5-* "
            "style export instead."
        )
    for pattern, template in _T5_RULES:
        if re.match(pattern, name):
            return re.sub(pattern, template, name)
    return name  # unknown names surface as `unexpected`


def load_hf_t5(model, checkpoint, *, mesh=None, dtype=None, rng=None,
               sample_args=(), strict: bool = True, **kwargs):
    """Stream an HF-format T5 checkpoint into the in-tree encoder-decoder
    (names remapped, kernels transposed; the relative-attention bias tables
    pass through — both sides store [num_buckets, num_heads]).  Tied
    (v1.0-style) checkpoints need ``T5Config(tie_word_embeddings=True)``
    (no ``lm_head`` param exists); untied v1.1 exports need ``False``.
    Returns (params, offload_store)."""
    import jax.numpy as jnp

    from ..big_modeling import load_checkpoint_and_dispatch

    if not sample_args:
        sample_args = (jnp.ones((1, 8), jnp.int32), jnp.ones((1, 4), jnp.int32))
    key_map = hf_t5_key_map
    if getattr(model.config, "tie_word_embeddings", True):
        # tied model: a stored lm_head.weight (some exporters keep the alias)
        # has no param to land in
        def key_map(name):
            return None if name == "lm_head.weight" else hf_t5_key_map(name)

    try:
        return load_checkpoint_and_dispatch(
            model, checkpoint, rng=rng, sample_args=sample_args, mesh=mesh,
            dtype=dtype, strict=strict,
            key_map=key_map, tensor_map=hf_llama_tensor_map, **kwargs,
        )
    except ValueError as e:
        if "missing" in str(e) and "lm_head" in str(e):
            raise ValueError(
                "This T5 checkpoint stores no lm_head.weight — it ties the "
                "head to the shared embedding (original T5). Build the model "
                "with T5Config(tie_word_embeddings=True), or pass "
                "strict=False to leave lm_head abstract."
            ) from e
        raise
