"""Model families.

Decoders and what carries ``head_dim``: ``LlamaConfig`` (Llama, Mistral, Yi;
``MixtralConfig`` extends it) DERIVES ``head_dim = hidden_size /
num_attention_heads``; ``KeyeVL2Config`` takes an explicit ``head_dim``
(32 heads x 128 over a hidden size of 2048) and is the family for a published
config whose ``num_attention_heads * head_dim != hidden_size``.
``KExaoneConfig`` (window and full-attention layers, sigmoid-routed experts
beside a shared expert) does too, and adds what of a layer a chip HOLDS.
``JoyAIFlashConfig`` (latent attention: a token's key and value are one row
of ``kv_lora_rank + qk_rope_head_dim`` values shared by every head) has no
``head_dim`` to carry: a head is ``qk_nope_head_dim + qk_rope_head_dim`` wide
where it is scored and ``v_head_dim`` where it is summed.
``Qwen3NextConfig`` (three Gated DeltaNet layers, whose state is one matrix a
value head and sequence, to every gated-attention layer of ``head_dim`` 256)
carries ``head_dim`` for the attention layers and ``linear_key_head_dim`` /
``linear_value_head_dim`` for the others.
``OlmoHybridConfig`` (the same 3 : 1 interleave in a dense, post-norm decoder:
Gated DeltaNet heads of 96 x 192 with a write strength up to 2, full attention
with no rotary and QK-norm over all channels) DERIVES ``head_dim`` as Llama's
does, and is the one family whose linear-attention layers TRAIN
(``make_olmo_hybrid_loss_fn``; it is not served).
``docs/supported_models.md`` has the table of what each family trains,
serves and refuses."""

from .bert import BertConfig, BertForSequenceClassification, make_bert_loss_fn
from .hf_interop import (
    hf_bert_key_map,
    hf_joyai_flash_key_map,
    hf_k_exaone_key_map,
    hf_keye_vl2_key_map,
    hf_llama_key_map,
    hf_llama_tensor_map,
    hf_mixtral_key_map,
    hf_olmo_hybrid_key_map,
    hf_qwen3_next_key_map,
    hf_t5_key_map,
    load_hf_bert,
    load_hf_joyai_flash,
    load_hf_k_exaone,
    load_hf_keye_vl2,
    load_hf_llama,
    load_hf_mixtral,
    load_hf_olmo_hybrid,
    load_hf_qwen3_next,
    load_hf_t5,
)
from .joyai_flash import JoyAIFlashConfig, JoyAIFlashForCausalLM
from .k_exaone import KExaoneConfig, KExaoneForCausalLM
from .keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM
from .llama import (
    LlamaConfig,
    LlamaForCausalLM,
    causal_lm_loss,
    count_params,
    flops_per_token,
    make_llama_loss_fn,
)
from .mixtral import (
    MixtralConfig,
    MixtralForCausalLM,
    count_active_params,
    make_mixtral_loss_fn,
)
from .olmo_hybrid import OlmoHybridConfig, OlmoHybridForCausalLM, make_olmo_hybrid_loss_fn
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM
from .resnet import ResNet, ResNetConfig, make_resnet_loss_fn
from .t5 import T5Config, T5ForConditionalGeneration, make_t5_loss_fn
