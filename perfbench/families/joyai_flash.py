"""Family adapter ``joyai_flash``: how the benchmark reaches the system under
test for JoyAI-LLM-Flash's language model
(``accelerate_tpu/models/joyai_flash.py``: latent attention over one pool of
latent rows a layer, a dense first layer, sigmoid-routed experts beside a
shared expert), served as ONE chip's share of a layer that eight chips divide.
Serving only.  The plain reference is ``perfbench/reference/joyai_flash.py``;
``families/joyai_flash.md`` says what this family had to solve.

The share is the configuration's, as in ``families/k_exaone.py``: the
top-level ``num_attention_heads``, ``n_routed_experts`` and ``vocab_size`` are
what this chip HOLDS, ``published`` has the model's own counts and
``share.experts_held`` the global ids.

The benchmark's weights are in the PUBLISHED column order (the rotary dims of
``q_b`` and ``kv_a`` interleaved, as the reference rotates them); the program
rotates halves, so ``to_program`` hands it those two leaves with their rotary
columns de-interleaved — the permutation ``load_hf_joyai_flash`` applies to a
checkpoint.  Every other leaf is the same array, no copy.

The program's model is imported at module top, on purpose: on a checkout
whose program lacks the family (the parent of the PR that added it),
``harness.Context`` fails on this import — a clean non-zero exit within
seconds, before any device work."""

from __future__ import annotations

import numpy as np

from accelerate_tpu.models.joyai_flash import (JoyAIFlashConfig, JoyAIFlashForCausalLM,
                                               deinterleave_rope_columns)

ATTN = {"attn_norm": ("input_layernorm", "scale"), "q_a": ("self_attn", "q_a_proj", "kernel"),
        "q_a_norm": ("self_attn", "q_a_layernorm", "scale"),
        "q_b": ("self_attn", "q_b_proj", "kernel"),
        "kv_a": ("self_attn", "kv_a_proj_with_mqa", "kernel"),
        "kv_a_norm": ("self_attn", "kv_a_layernorm", "scale"),
        "kv_b": ("self_attn", "kv_b_proj"), "o": ("self_attn", "o_proj", "kernel"),
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
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lecun = lambda fan_in: float(1.0 / np.sqrt(fan_in))
    yield from (("attn_norm", (h,), None), ("q_a", (h, rq), lecun(h)), ("q_a_norm", (rq,), None),
                ("q_b", (rq, heads * (dn + dr)), scales.get("q_b", 1.0) * lecun(rq)),
                ("kv_a", (h, r + dr), lecun(h)), ("kv_a_norm", (r,), scales.get("kv_a_norm")),
                ("kv_b", (r, heads * (dn + dv)), lecun(r)),
                # fan-in of the PUBLISHED head count: what the held heads add is a part of the sum
                ("o", (heads * dv, h), lecun(cfg.get("published", cfg)["num_attention_heads"] * dv)),
                ("mlp_norm", (h,), None))
    if not sparse:
        i = cfg["intermediate_size"]
        yield from (("mlp_gate", (h, i), lecun(h)), ("mlp_up", (h, i), lecun(h)),
                    ("mlp_down", (i, h), lecun(i)))
        return
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    routed = cfg.get("published", cfg)["n_routed_experts"]
    yield from (("router", (h, routed), scales["router"] * lecun(h)),
                ("router_bias", (routed,), scales["router_bias"]),
                ("gate", (e, h, f), lecun(h)), ("up", (e, h, f), lecun(h)),
                ("down", (e, f, h), lecun(f)), ("shared_gate", (h, f), lecun(h)),
                ("shared_up", (h, f), lecun(h)), ("shared_down", (f, h), lecun(f)))


def weight_shapes(cfg: dict, layers: int, mtp: bool = False) -> dict:
    """name -> (shape, std); std None marks a norm scale (ones).  The held
    experts are stacked ``[E held, in, out]``; the router and its selection
    bias keep the PUBLISHED expert count.  std 1/sqrt(fan_in), but for the
    leaves the configuration file's ``assumed.weight_scales`` names (and says
    why): the embedding's std, the router's and ``q_b``'s as multiples of
    1/sqrt(fan_in) (the second is attention's temperature: MLA has no per-head
    QK-norm to carry one), the selection bias's std, and the std of
    ``kv_a_layernorm``'s scale (a seeded normal where the other norms keep
    ones: over a latent that random weights leave at unit size already, a norm
    with a scale of ones is the identity and skipping it cannot be seen).
    ``mtp``: also the next-token-prediction module's leaves (CPU tests)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    scales = cfg["assumed"]["weight_scales"]
    sparse = lambda i: i >= cfg["first_k_dense_replace"]
    shapes = {"embed": ((v, h), float(scales["embed"]))}
    for i in range(layers):
        for name, shape, std in _layer_shapes(cfg, sparse(i), scales):
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


def _program_order(name: str, arr, cfg: dict):
    """``q_b`` and ``kv_a`` as the program reads them: rotary columns de-interleaved."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf not in ("q_b", "kv_a"):
        return arr
    dr = cfg["qk_rope_head_dim"]
    head_width = cfg["qk_nope_head_dim"] + dr if leaf == "q_b" else arr.shape[1]
    return deinterleave_rope_columns(arr, head_width, dr)


def to_program(weights: dict, cfg: dict) -> dict:
    """The benchmark's flat dict as the program's ``{"params": ...}`` tree:
    the same arrays, but ``q_b`` and ``kv_a`` in the program's column order."""
    tree: dict = {}
    for name, arr in weights.items():
        node = tree
        path = program_path(name)
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = _program_order(name, arr, cfg)
    return {"params": tree}


def build_model(cfg: dict, layers: int, dtype=None):
    import jax.numpy as jnp

    pub, share = cfg.get("published", cfg), cfg.get("share") or {}
    return JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=pub["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=pub["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"], moe_layer_freq=cfg["moe_layer_freq"],
        n_routed_experts=pub["n_routed_experts"], num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"], scoring_func=cfg["scoring_func"],
        topk_method=cfg["topk_method"], norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        max_position_embeddings=cfg["max_position_embeddings"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), rope_interleave=cfg["rope_interleave"],
        rope_scaling=cfg["rope_scaling"], tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(share.get("experts_held", range(cfg["n_routed_experts"]))),
        attention_heads_held=cfg["num_attention_heads"], vocab_held=cfg["vocab_size"],
        dtype=dtype or jnp.bfloat16))


def build_trainer(cfg: dict, layers: int, recipe: dict):
    raise NotImplementedError("the program has no training path for the joyai_flash family")


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
