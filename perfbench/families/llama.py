"""Family adapter ``llama``: how the benchmark reaches the system under test
for Llama-shaped decoders (``accelerate_tpu/models/llama.py``).  It names the
weights, hands the benchmark's arrays to the program in the program's own
tree, and builds the two timed objects (train step + state, serving engine)
through the program's normal entry points.  The plain reference for this
family is ``perfbench/reference/llama.py``."""

from __future__ import annotations

import numpy as np

LEAVES = {  # benchmark name -> path inside a program layer
    "attn_norm": ("input_layernorm", "scale"), "q": ("self_attn", "q_proj", "kernel"),
    "k": ("self_attn", "k_proj", "kernel"), "v": ("self_attn", "v_proj", "kernel"),
    "o": ("self_attn", "o_proj", "kernel"), "mlp_norm": ("post_attention_layernorm", "scale"),
    "gate": ("mlp", "gate_proj", "kernel"), "up": ("mlp", "up_proj", "kernel"),
    "down": ("mlp", "down_proj", "kernel"),
}


def weight_shapes(cfg: dict, layers: int) -> dict:
    """name -> (shape, std); std None marks a norm scale (ones)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    lecun = lambda fan_in: float(1.0 / np.sqrt(fan_in))
    shapes = {"embed": ((v, h), lecun(h))}
    for i in range(layers):
        for name, shape in (("attn_norm", (h,)), ("q", (h, q)), ("k", (h, kv)), ("v", (h, kv)),
                            ("o", (q, h)), ("mlp_norm", (h,)), ("gate", (h, f)), ("up", (h, f)),
                            ("down", (f, h))):
            shapes[f"layers.{i}.{name}"] = (shape, None if len(shape) == 1 else lecun(shape[0]))
    shapes["final_norm"] = ((h,), None)
    shapes["head"] = ((h, v), lecun(h))
    return shapes


def program_path(name: str) -> tuple:
    if name == "embed":
        return ("embed_tokens", "embedding")
    if name == "final_norm":
        return ("norm", "scale")
    if name == "head":
        return ("lm_head", "kernel")
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


def from_program(tree: dict) -> dict:
    """A program tree (params, momentum, ...) back to benchmark names."""
    inner = tree.get("params", tree)
    out = {}

    def leaf(name):
        node = inner
        for part in program_path(name):
            node = node[part]
        return node

    layers = sum(1 for k in inner if k.startswith("layers_"))
    names = ["embed"] + [f"layers.{i}.{k}" for i in range(layers) for k in LEAVES] + \
        ["final_norm", "head"]
    for name in names:
        out[name] = leaf(name)
    return out


def build_model(cfg: dict, layers: int, attn: str):
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / num_attention_heads")
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"], attn_implementation=attn,
        dtype=jnp.bfloat16))


def param_shardings(acc, cfg: dict, layers: int) -> dict:
    """name -> the Sharding the program's own plan gives that leaf."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(cfg, layers)
    abstract = to_program({n: jax.ShapeDtypeStruct(s, jnp.bfloat16) for n, (s, _) in shapes.items()})
    return from_program(acc._params_plan(abstract))


def build_trainer(cfg: dict, layers: int, recipe: dict):
    """Accelerator -> prepare_train_step over the flash model with the fused
    CE and the resident optimizer recipe.  Returns (accelerator, step,
    new_state); ``new_state(seed)`` makes the seeded weights sharded on the
    device and the optimizer state over them (``create_train_state``)."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import make_llama_loss_fn
    from accelerate_tpu.optimizer import make_optimizer
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    from perfbench.weights import make_weights

    par = recipe.get("parallelism") or {}
    pc = ParallelismConfig(**par) if par else None
    acc = Accelerator(mixed_precision="bf16", parallelism_config=pc,
                      kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")])
    model = build_model(cfg, layers, "flash")
    hy = recipe["optimizer_hyper"]
    if (hy["b1"], hy["b2"]) != (0.9, 0.99):
        raise ValueError("the program's lion recipes fix b1 = 0.9 and b2 = 0.99")
    shapes, shardings = weight_shapes(cfg, layers), param_shardings(acc, cfg, layers)

    def new_state(seed: int):
        tx = make_optimizer(recipe["optimizer"], learning_rate=hy["lr"],
                            weight_decay=hy["weight_decay"])
        weights = make_weights(shapes, seed, shardings)
        return acc.create_train_state(to_program(weights), tx, apply_fn=model.apply)

    step = acc.prepare_train_step(make_llama_loss_fn(model, fused_vocab_chunks=recipe["ce_chunks"]))
    return acc, step, new_state


def step_memory_bytes(step, state, batch) -> int:
    """What the compiled step program itself needs on each device: arguments +
    temporaries (+ outputs that alias nothing).  The allocator's
    ``peak_bytes_in_use`` leaves XLA's temporaries out on this backend."""
    m = step._jitted.lower(state, batch).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes)


def batch_sharding(acc, tokens):
    from jax.sharding import NamedSharding

    return NamedSharding(acc.mesh, acc._default_batch_spec()(tokens))


def momentum_of(state) -> dict:
    """The optimizer's first moment by benchmark name (lion-sr: ``mu``)."""
    import jax

    mus = [s.mu for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(mus) != 1:
        raise ValueError(f"expected one momentum tree in the optimizer state, found {len(mus)}")
    return from_program(mus[0])


def params_of(state) -> dict:
    return from_program(state.params)


def build_engine(cfg: dict, layers: int, engine: dict, weights: dict, rehearse: bool):
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils.dataclasses import ServingPlugin

    plugin = ServingPlugin(
        num_slots=engine["num_slots"], page_size=engine["page_size"],
        pages_per_slot=engine["pages_per_slot"], num_pages=engine["num_pages"],
        prefill_chunk=engine["prefill_chunk"], prefill_buckets=tuple(engine["prefill_buckets"]),
        decode_kernel="flash" if rehearse else "auto")
    gen = GenerationConfig(max_new_tokens=engine["max_new_tokens"], do_sample=False,
                           eos_token_id=None)
    eng = ServingEngine(build_model(cfg, layers, "flash"), to_program(weights), plugin, gen)
    if eng.model.config.attn_implementation != "flash":
        raise RuntimeError("the engine did not resolve to the paged Pallas kernels")
    return eng
