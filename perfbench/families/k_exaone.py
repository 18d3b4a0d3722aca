"""Family adapter ``k_exaone``: how the benchmark reaches the system under
test for K-EXAONE's language model (``accelerate_tpu/models/k_exaone.py``:
window and full-attention layers in one cache, sigmoid-routed experts beside
a shared expert, a dense first layer), served as ONE chip's share of a layer
that eight chips divide.  Serving only.  The plain reference is
``perfbench/reference/k_exaone.py``.

The share is the configuration's: its top-level ``num_attention_heads``,
``num_key_value_heads``, ``num_experts`` and ``vocab_size`` are what this chip
HOLDS, ``published`` has the model's own counts (the router keeps its 128
outputs) and ``share.experts_held`` the global ids of the experts here.  The
benchmark makes exactly the held weights, and the program builds exactly those.

The program's model is imported at module top, on purpose: on a checkout
whose program lacks the family (the parent of the PR that added it),
``harness.Context`` fails on this import — a clean non-zero exit within
seconds, before any device work."""

from __future__ import annotations

import numpy as np

from accelerate_tpu.models.k_exaone import KExaoneConfig, KExaoneForCausalLM

ATTN = {"attn_norm": ("input_layernorm", "scale"), "q": ("self_attn", "q_proj", "kernel"),
        "k": ("self_attn", "k_proj", "kernel"), "v": ("self_attn", "v_proj", "kernel"),
        "o": ("self_attn", "o_proj", "kernel"), "q_norm": ("self_attn", "q_norm", "scale"),
        "k_norm": ("self_attn", "k_norm", "scale"),
        "mlp_norm": ("post_attention_layernorm", "scale")}
LEAVES = {  # benchmark name -> path inside a program layer
    **ATTN,
    "mlp_gate": ("mlp", "gate_proj", "kernel"), "mlp_up": ("mlp", "up_proj", "kernel"),
    "mlp_down": ("mlp", "down_proj", "kernel"),
    "router": ("mlp", "gate", "kernel"), "router_bias": ("mlp", "e_score_correction_bias"),
    "gate": ("mlp", "experts_gate_proj"), "up": ("mlp", "experts_up_proj"),
    "down": ("mlp", "experts_down_proj"),
    "shared_gate": ("mlp", "shared_experts", "gate_proj", "kernel"),
    "shared_up": ("mlp", "shared_experts", "up_proj", "kernel"),
    "shared_down": ("mlp", "shared_experts", "down_proj", "kernel"),
}
MTP = {"hnorm": ("hnorm", "scale"), "enorm": ("enorm", "scale"), "proj": ("eh_proj", "kernel")}


def _layer_shapes(cfg: dict, sparse: bool, scales: dict):
    """(name, shape, std) of one layer's leaves; std None marks a norm scale."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    lecun = lambda fan_in: float(1.0 / np.sqrt(fan_in))
    yield from (("attn_norm", (h,), None), ("q", (h, q), lecun(h)), ("k", (h, kv), lecun(h)),
                ("v", (h, kv), lecun(h)),
                # fan-in of the PUBLISHED head count: what the held heads add is a part of the sum
                ("o", (q, h), lecun(cfg.get("published", cfg)["num_attention_heads"] * d)),
                ("q_norm", (d,), scales.get("q_norm")), ("k_norm", (d,), None),
                ("mlp_norm", (h,), None))
    if not sparse:
        i = cfg["intermediate_size"]
        yield from (("mlp_gate", (h, i), lecun(h)), ("mlp_up", (h, i), lecun(h)),
                    ("mlp_down", (i, h), lecun(i)))
        return
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    routed = cfg.get("published", cfg)["num_experts"]
    yield from (("router", (h, routed), scales["router"] * lecun(h)),
                ("router_bias", (routed,), scales["router_bias"]),
                ("gate", (e, h, f), lecun(h)), ("up", (e, h, f), lecun(h)),
                ("down", (e, f, h), lecun(f)), ("shared_gate", (h, f), lecun(h)),
                ("shared_up", (h, f), lecun(h)), ("shared_down", (f, h), lecun(f)))


def weight_shapes(cfg: dict, layers: int, mtp: bool = False) -> dict:
    """name -> (shape, std); std None marks a norm scale (ones).  The held
    experts are stacked ``[E held, in, out]``; the router and its selection
    bias keep the PUBLISHED expert count.

    std 1/sqrt(fan_in), but for the leaves the configuration file's
    ``assumed.weight_scales`` names (and says why): the embedding's std, the
    router's as a multiple of 1/sqrt(H), the selection bias's std and,
    optionally, the per-head ``q_norm`` scale's std (ones without it): how
    peaked attention is, how much of the stream the layers are, and how many
    tokens the bias moves decide what ``correct`` can see of each mechanism
    (``limits/k-exaone.serve_reason.json``).
    ``mtp``: also the next-token-prediction module's leaves (CPU tests)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    scales = cfg["assumed"]["weight_scales"]
    shapes = {"embed": ((v, h), float(scales["embed"]))}
    for i in range(layers):
        for name, shape, std in _layer_shapes(cfg, cfg["mlp_layer_types"][i] == "sparse", scales):
            shapes[f"layers.{i}.{name}"] = (shape, std)
    shapes["final_norm"] = ((h,), None)
    shapes["head"] = ((h, v), float(1.0 / np.sqrt(h)))
    if mtp:
        shapes.update({"mtp.hnorm": ((h,), None), "mtp.enorm": ((h,), None),
                       "mtp.proj": ((2 * h, h), float(1.0 / np.sqrt(2 * h)))})
        for name, shape, std in _layer_shapes(cfg, True, scales):
            shapes[f"mtp.{name}"] = (shape, std)
    return shapes


def program_path(name: str) -> tuple:
    if name == "embed":
        return ("embed_tokens", "embedding")
    if name == "final_norm":
        return ("norm", "scale")
    if name == "head":
        return ("lm_head", "kernel")
    if name.startswith("mtp."):
        leaf = name.split(".", 1)[1]
        return ("mtp",) + (MTP[leaf] if leaf in MTP else ("block",) + LEAVES[leaf])
    _, i, leaf = name.split(".")
    return (f"layers_{i}",) + LEAVES[leaf]


def to_program(weights: dict) -> dict:
    """The benchmark's flat dict as the program's ``{"params": ...}`` tree
    (the same arrays, no copy)."""
    tree: dict = {}
    for name, arr in weights.items():
        node = tree
        path = program_path(name)
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = arr
    return {"params": tree}


def build_model(cfg: dict, layers: int, dtype=None):
    import jax.numpy as jnp

    pub, share = cfg.get("published", cfg), cfg.get("share") or {}
    return KExaoneForCausalLM(KExaoneConfig(
        vocab_size=pub["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]), mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        first_k_dense_replace=cfg["first_k_dense_replace"], sliding_window=cfg["sliding_window"],
        num_experts=pub["num_experts"], num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"], scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        max_position_embeddings=cfg["max_position_embeddings"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(share.get("experts_held", range(cfg["num_experts"]))),
        attention_heads_held=cfg["num_attention_heads"],
        key_value_heads_held=cfg["num_key_value_heads"], vocab_held=cfg["vocab_size"],
        dtype=dtype or jnp.bfloat16))


def build_trainer(cfg: dict, layers: int, recipe: dict):
    raise NotImplementedError("the program has no training path for the k_exaone family")


def build_engine(cfg: dict, layers: int, engine: dict, weights: dict, rehearse: bool):
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
    # the rehearsal computes in float32 (as families/keye_vl2.py: at its tiny widths one
    # routing choice moved by a bf16 rounding is a quarter of a layer); it proves the control flow
    import jax.numpy as jnp

    model = build_model(cfg, layers, dtype=jnp.float32 if rehearse else jnp.bfloat16)
    return ServingEngine(model, to_program(weights), plugin, gen)
