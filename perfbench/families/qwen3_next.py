"""Family adapter ``qwen3_next``: how the benchmark reaches the system under
test for Qwen3-Next's language model (``accelerate_tpu/models/qwen3_next.py``:
Gated DeltaNet layers whose recurrent state lives per slot beside the
full-attention layers' pages, gated attention, softmax-routed experts beside a
gated shared expert), served as ONE chip's share of a layer that four chips
divide.  Serving only.  The plain reference is
``perfbench/reference/qwen3_next.py``; ``families/qwen3_next.md`` says what
this family had to solve.

The share is the configuration's, as in ``families/k_exaone.py``: the
top-level ``num_attention_heads``, ``num_key_value_heads``,
``linear_num_key_heads``, ``linear_num_value_heads``, ``num_experts`` and
``vocab_size`` are what this chip HOLDS, ``published`` has the model's own
counts and ``share.experts_held`` the global ids.

The benchmark's leaves are zero-mean seeded normals (or ones), so ``A_log`` is
handed to the program as its leaf plus ``assumed.weight_scales.A_log_mean``,
the sum the reference computes too; every other leaf is the same array, no copy.

The program's model is imported at module top, on purpose: on a checkout
whose program lacks the family (the parent of the PR that added it),
``harness.Context`` fails on this import — a clean non-zero exit within
seconds, before any device work."""

from __future__ import annotations

import numpy as np

from accelerate_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM

LEAVES = {  # benchmark name -> path inside a program layer
    "attn_norm": ("input_layernorm", "weight"), "mlp_norm": ("post_attention_layernorm", "weight"),
    "qkvz": ("linear_attn", "in_proj_qkvz", "kernel"), "ba": ("linear_attn", "in_proj_ba", "kernel"),
    "conv": ("linear_attn", "conv1d"), "A_log": ("linear_attn", "A_log"),
    "dt_bias": ("linear_attn", "dt_bias"), "gdn_norm": ("linear_attn", "norm"),
    "gdn_out": ("linear_attn", "out_proj", "kernel"),
    "q": ("self_attn", "q_proj", "kernel"), "k": ("self_attn", "k_proj", "kernel"),
    "v": ("self_attn", "v_proj", "kernel"), "o": ("self_attn", "o_proj", "kernel"),
    "q_norm": ("self_attn", "q_norm", "weight"), "k_norm": ("self_attn", "k_norm", "weight"),
    "router": ("mlp", "gate", "kernel"),
    "gate": ("mlp", "experts_gate_proj"), "up": ("mlp", "experts_up_proj"),
    "down": ("mlp", "experts_down_proj"),
    "shared_gate": ("mlp", "shared_expert", "gate_proj", "kernel"),
    "shared_up": ("mlp", "shared_expert", "up_proj", "kernel"),
    "shared_down": ("mlp", "shared_expert", "down_proj", "kernel"),
    "shared_sigmoid": ("mlp", "shared_expert_gate", "kernel"),
}


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def _layer_shapes(cfg: dict, full: bool, scales: dict):
    """(name, shape, std) of one layer's leaves; std None marks a plain norm scale (ones)."""
    h, pub = cfg["hidden_size"], cfg.get("published", cfg)
    lecun = lambda fan_in: float(1.0 / np.sqrt(fan_in))
    yield from (("attn_norm", (h,), scales["norm"]), ("mlp_norm", (h,), scales["norm"]))
    if full:
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        yield from (("q", (h, 2 * q), lecun(h)), ("k", (h, kv), lecun(h)), ("v", (h, kv), lecun(h)),
                    # fan-in of the PUBLISHED head count: what the held heads add is a part of the sum
                    ("o", (q, h), lecun(pub["num_attention_heads"] * d)),
                    ("q_norm", (d,), scales["q_norm"]), ("k_norm", (d,), scales["norm"]))
    else:
        kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv, taps = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
        r = vh // kh
        yield from (("qkvz", (h, kh * (2 * dk + 2 * r * dv)), lecun(h)), ("ba", (h, kh * 2 * r), lecun(h)),
                    ("conv", (taps, 2 * kh * dk + vh * dv), scales["conv"] * lecun(taps)),
                    ("A_log", (vh,), scales["A_log"]), ("dt_bias", (vh,), scales["dt_bias"]),
                    ("gdn_norm", (dv,), None),
                    ("gdn_out", (vh * dv, h), lecun(pub["linear_num_value_heads"] * dv)))
    f, fs, e = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["num_experts"]
    yield from (("router", (h, pub["num_experts"]), scales["router"] * lecun(h)),
                ("gate", (e, h, f), lecun(h)), ("up", (e, h, f), lecun(h)),
                ("down", (e, f, h), lecun(f)), ("shared_gate", (h, fs), lecun(h)),
                ("shared_up", (h, fs), lecun(h)), ("shared_down", (fs, h), lecun(fs)),
                ("shared_sigmoid", (h, 1), lecun(h)))


def weight_shapes(cfg: dict, layers: int) -> dict:
    """name -> (shape, std); std None marks a plain norm scale (ones).  The
    held experts are stacked ``[E held, in, out]``; the router keeps the
    PUBLISHED expert count.  std 1/sqrt(fan_in), but for the leaves the
    configuration file's ``assumed.weight_scales`` names (and says why): the
    embedding's std, the router's and the conv taps' as multiples of
    1/sqrt(fan_in), the std of the zero-centred norms' ``w`` (``norm``; the
    per-head ``q_norm``'s own, which is attention's temperature), and the stds
    of ``A_log`` (around ``A_log_mean``, which ``to_program`` and the reference
    add) and ``dt_bias``, which set how fast a recurrent state forgets."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    scales = cfg["assumed"]["weight_scales"]
    shapes = {"embed": ((v, h), float(scales["embed"]))}
    for i in range(layers):
        for name, shape, std in _layer_shapes(cfg, is_full_attention(cfg, i), scales):
            shapes[f"layers.{i}.{name}"] = (shape, std)
    shapes["final_norm"] = ((h,), scales["norm"])
    shapes["head"] = ((h, v), float(1.0 / np.sqrt(h)))
    return shapes


def program_path(name: str) -> tuple:
    if name == "embed":
        return ("embed_tokens", "embedding")
    if name == "final_norm":
        return ("norm", "weight")
    if name == "head":
        return ("lm_head", "kernel")
    _, i, leaf = name.split(".")
    return (f"layers_{i}",) + LEAVES[leaf]


def to_program(weights: dict, cfg: dict) -> dict:
    """The benchmark's flat dict as the program's ``{"params": ...}`` tree:
    the same arrays, but ``A_log`` with its mean added (float32)."""
    mean = float(cfg["assumed"]["weight_scales"]["A_log_mean"])
    tree: dict = {}
    for name, arr in weights.items():
        node = tree
        path = program_path(name)
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = arr.astype("float32") + mean if name.endswith(".A_log") else arr
    return {"params": tree}


def build_model(cfg: dict, layers: int, dtype=None):
    import jax.numpy as jnp

    pub, share = cfg.get("published", cfg), cfg.get("share") or {}
    return Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=pub["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        num_hidden_layers=layers, num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        full_attention_interval=cfg["full_attention_interval"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_num_key_heads=pub["linear_num_key_heads"],
        linear_num_value_heads=pub["linear_num_value_heads"],
        num_experts=pub["num_experts"], num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], decoder_sparse_step=cfg["decoder_sparse_step"],
        mlp_only_layers=tuple(cfg["mlp_only_layers"]),
        max_position_embeddings=cfg["max_position_embeddings"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=cfg["rope_scaling"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(share.get("experts_held", range(cfg["num_experts"]))),
        attention_heads_held=cfg["num_attention_heads"],
        key_value_heads_held=cfg["num_key_value_heads"],
        linear_key_heads_held=cfg["linear_num_key_heads"],
        linear_value_heads_held=cfg["linear_num_value_heads"], vocab_held=cfg["vocab_size"],
        dtype=dtype or jnp.bfloat16))


def build_trainer(cfg: dict, layers: int, recipe: dict):
    raise NotImplementedError("the program has no training path for the qwen3_next family")


def build_engine(cfg: dict, layers: int, engine: dict, weights: dict, rehearse: bool):
    import jax.numpy as jnp

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils.dataclasses import ServingPlugin

    plugin = ServingPlugin(
        num_slots=engine["num_slots"], page_size=engine["page_size"],
        pages_per_slot=engine["pages_per_slot"], num_pages=engine["num_pages"],
        prefill_chunk=engine["prefill_chunk"], prefill_buckets=tuple(engine["prefill_buckets"]),
        decode_kernel="auto")
    gen = GenerationConfig(max_new_tokens=engine["max_new_tokens"], do_sample=False,
                           eos_token_id=None)
    # the rehearsal computes in float32 (as families/k_exaone.py: at its tiny widths one
    # routing choice moved by a bf16 rounding is a quarter of a layer); it proves the control flow
    model = build_model(cfg, layers, dtype=jnp.float32 if rehearse else jnp.bfloat16)
    return ServingEngine(model, to_program(weights, cfg), plugin, gen)
