"""Family adapter ``olmo_hybrid``: how the benchmark reaches the system under
test for Olmo-Hybrid's decoder (``accelerate_tpu/models/olmo_hybrid.py``:
three Gated DeltaNet layers to every full-attention layer, post-norm blocks,
a dense SwiGLU MLP), TRAINED through the program's normal entry points -
``Accelerator`` -> ``create_train_state`` -> ``prepare_train_step`` over the
fused CE with the resident optimizer recipe, exactly as
``families/llama.py::build_trainer`` does for Mistral.  Training only.  The
plain reference is ``perfbench/reference/olmo_hybrid.py``;
``families/olmo_hybrid.md`` says what this family had to solve.

The benchmark's leaves are zero-mean seeded normals (or ones), so ``A_log`` is
handed to the program as its leaf plus ``assumed.weight_scales.A_log_mean``
(rounded to the leaf's bf16, the sum the reference starts from too) and read
back with the mean taken off again; every other leaf is the same array, no copy.

The program's model is imported at module top, on purpose: on a checkout
whose program lacks the family (the parent of the PR that added it),
``harness.Context`` fails on this import - a clean non-zero exit within
seconds, before any device work."""

from __future__ import annotations

import numpy as np

from accelerate_tpu.models.olmo_hybrid import (OlmoHybridConfig, OlmoHybridForCausalLM,
                                               make_olmo_hybrid_loss_fn)

from perfbench.families.llama import batch_sharding, step_memory_bytes  # noqa: F401  (family-general)

_MLP = {"mixer_norm": ("post_attention_layernorm", "scale"),
        "mlp_norm": ("post_feedforward_layernorm", "scale"),
        "gate": ("mlp", "gate_proj", "kernel"), "up": ("mlp", "up_proj", "kernel"),
        "down": ("mlp", "down_proj", "kernel")}
# kind -> benchmark name -> path inside a program layer; a LIST of paths is one benchmark leaf cut
# along its last axis (``ba`` = [b_proj; a_proj], ``conv`` = the q, k and v convs' taps side by side)
LEAVES = {
    "full_attention": {
        **{n: ("self_attn", f"{n}_proj", "kernel") for n in "qkvo"},
        "q_norm": ("self_attn", "q_norm", "scale"), "k_norm": ("self_attn", "k_norm", "scale"), **_MLP},
    "linear_attention": {
        **{f"l{n}": ("linear_attn", f"{p}_proj", "kernel") for n, p in zip("qkvzo", "qkvgo")},
        "ba": [("linear_attn", "b_proj", "kernel"), ("linear_attn", "a_proj", "kernel")],
        "conv": [("linear_attn", f"{n}_conv1d") for n in "qkv"],
        "A_log": ("linear_attn", "A_log"), "dt_bias": ("linear_attn", "dt_bias"),
        "o_norm": ("linear_attn", "o_norm", "scale"), **_MLP},
}


def cut_widths(cfg: dict, leaf: str) -> list:
    """The last-axis widths of the program leaves that one fused benchmark leaf holds."""
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return {"ba": [cfg["linear_num_value_heads"]] * 2, "conv": [keys, keys, values]}[leaf]


def _layer_shapes(cfg: dict, kind: str, scales: dict):
    """(name, shape, std) of one layer's leaves; std None marks a norm scale (ones)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    lecun = lambda fan_in: float(1.0 / np.sqrt(fan_in))
    if kind == "full_attention":
        d = h // cfg["num_attention_heads"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        yield from (("q", (h, q), lecun(h)), ("k", (h, kv), lecun(h)), ("v", (h, kv), lecun(h)),
                    ("o", (q, h), lecun(q)), ("q_norm", (q,), None), ("k_norm", (kv,), None))
    else:
        kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv, taps = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
        yield from (("lq", (h, kh * dk), lecun(h)), ("lk", (h, kh * dk), lecun(h)),
                    ("lv", (h, vh * dv), lecun(h)), ("lz", (h, vh * dv), lecun(h)),
                    ("ba", (h, 2 * vh), lecun(h)),
                    ("conv", (taps, 2 * kh * dk + vh * dv), scales["conv"] * lecun(taps)),
                    ("A_log", (vh,), scales["A_log"]), ("dt_bias", (vh,), scales["dt_bias"]),
                    ("o_norm", (dv,), None), ("lo", (vh * dv, h), lecun(vh * dv)))
    yield from (("mixer_norm", (h,), None), ("mlp_norm", (h,), None), ("gate", (h, f), lecun(h)),
                ("up", (h, f), lecun(h)), ("down", (f, h), lecun(f)))


def weight_shapes(cfg: dict, layers: int) -> dict:
    """name -> (shape, std); std None marks a norm scale (ones).  std
    1/sqrt(fan_in) for every matrix and the embedding, but for the leaves the
    configuration file's ``assumed.weight_scales`` names (and says why): the
    conv taps' as a multiple of 1/sqrt(taps), and the stds of ``A_log``
    (around ``A_log_mean``, which ``to_program`` and the reference add) and
    ``dt_bias``, which set how fast a recurrent state forgets."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    scales = cfg["assumed"]["weight_scales"]
    shapes = {"embed": ((v, h), float(1.0 / np.sqrt(h)))}
    for i, kind in enumerate(cfg["layer_types"][:layers]):
        for name, shape, std in _layer_shapes(cfg, kind, scales):
            shapes[f"layers.{i}.{name}"] = (shape, std)
    shapes["final_norm"] = ((h,), None)
    shapes["head"] = ((h, v), float(1.0 / np.sqrt(h)))
    return shapes


def layer_kinds(inner: dict) -> list:
    """Each layer's kind, read off a program tree."""
    kinds = []
    while f"layers_{len(kinds)}" in inner:
        kinds.append("full_attention" if "self_attn" in inner[f"layers_{len(kinds)}"] else "linear_attention")
    return kinds


def program_paths(name: str, kinds) -> list:
    """The program leaves behind one benchmark name (``kinds``: each layer's kind, in order)."""
    top = {"embed": ("embed_tokens", "embedding"), "final_norm": ("norm", "scale"), "head": ("lm_head", "kernel")}
    if name in top:
        return [top[name]]
    _, i, leaf = name.split(".")
    paths = LEAVES[kinds[int(i)]][leaf]
    return [(f"layers_{i}",) + p for p in (paths if isinstance(paths, list) else [paths])]


def to_program(weights: dict, cfg: dict, a_log_mean=None) -> dict:
    """The benchmark's flat dict as the program's ``{"params": ...}`` tree:
    the same arrays, but a fused leaf cut into the program's and ``A_log``
    with its mean added (in the leaf's dtype).  Shapes pass as shapes."""
    import jax
    import jax.numpy as jnp

    mean = float(cfg["assumed"]["weight_scales"]["A_log_mean"]) if a_log_mean is None else a_log_mean
    tree: dict = {}
    for name, arr in weights.items():
        paths = program_paths(name, cfg["layer_types"])
        if len(paths) == 1:
            parts = [(arr.astype("float32") + mean).astype(arr.dtype)
                     if mean and name.endswith(".A_log") else arr]
        elif isinstance(arr, jax.ShapeDtypeStruct):
            parts = [jax.ShapeDtypeStruct(arr.shape[:-1] + (w,), arr.dtype)
                     for w in cut_widths(cfg, name.split(".")[2])]
        else:
            parts = jnp.split(arr, np.cumsum(cut_widths(cfg, name.split(".")[2]))[:-1], axis=-1)
        for path, part in zip(paths, parts):
            node = tree
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = part
    return {"params": tree}


def from_program(tree: dict, a_log_mean: float = 0.0, join=None) -> dict:
    """A program tree (params, momentum, a plan) back to benchmark names;
    ``a_log_mean`` is taken off ``A_log`` (parameters only: a momentum or a
    plan has none); ``join`` makes one benchmark leaf of several program
    leaves (default: side by side along the last axis)."""
    import jax.numpy as jnp

    inner = tree.get("params", tree)
    kinds = layer_kinds(inner)
    join = join or (lambda parts: jnp.concatenate(parts, axis=-1))
    names = ["embed"] + [f"layers.{i}.{k}" for i, kind in enumerate(kinds) for k in LEAVES[kind]] + \
        ["final_norm", "head"]
    out = {}
    for name in names:
        parts = []
        for path in program_paths(name, kinds):
            node = inner
            for key in path:
                node = node[key]
            parts.append(node)
        leaf = parts[0] if len(parts) == 1 else join(parts)
        out[name] = leaf.astype("float32") - a_log_mean if a_log_mean and name.endswith(".A_log") else leaf
    return out


def build_model(cfg: dict, layers: int, remat: bool = False, dtype="bfloat16"):
    import jax.numpy as jnp

    return OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"][:layers]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        max_position_embeddings=cfg["max_position_embeddings"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"], remat=remat, dtype=jnp.dtype(dtype)))


def param_shardings(acc, cfg: dict, layers: int) -> dict:
    """name -> the Sharding the program's own plan gives that leaf (a fused
    leaf, a few thousand values, is made whole on every device and cut there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    shapes = weight_shapes(cfg, layers)
    abstract = to_program({n: jax.ShapeDtypeStruct(s, jnp.bfloat16) for n, (s, _) in shapes.items()},
                          cfg, a_log_mean=0.0)
    return from_program(acc._params_plan(abstract),
                        join=lambda parts: NamedSharding(acc.mesh, PartitionSpec()))


def build_trainer(cfg: dict, layers: int, recipe: dict):
    """Accelerator -> prepare_train_step over the model (flash attention in
    the full layers, the chunked rule in the others, every block recomputed
    in the backward pass: ``remat``, which is what fits - 10.41 GiB live with
    it, 17.7 without) with the fused CE and the resident optimizer recipe.
    Returns (accelerator, step, new_state); ``new_state(seed)`` makes the
    seeded weights sharded on the device and the optimizer state over them
    (``create_train_state``).  The model computes in bfloat16; the cell's
    ``rehearse`` block alone carries ``model_dtype: float32`` (it says why),
    so the rehearsal proves the float32 program and the chip the bf16 one.
    Parameters, momentum and gradients are bf16 leaves under lion-sr either way."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.optimizer import make_optimizer
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    from perfbench.weights import make_weights

    par = recipe.get("parallelism") or {}
    acc = Accelerator(mixed_precision="bf16", parallelism_config=ParallelismConfig(**par) if par else None,
                      kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")])
    model = build_model(cfg, layers, remat=True, dtype=recipe.get("model_dtype", "bfloat16"))
    hy = recipe["optimizer_hyper"]
    if (hy["b1"], hy["b2"]) != (0.9, 0.99):
        raise ValueError("the program's lion recipes fix b1 = 0.9 and b2 = 0.99")
    shapes, shardings = weight_shapes(cfg, layers), param_shardings(acc, cfg, layers)

    def new_state(seed: int):
        tx = make_optimizer(recipe["optimizer"], learning_rate=hy["lr"],
                            weight_decay=hy["weight_decay"])
        weights = make_weights(shapes, seed, shardings)
        return acc.create_train_state(to_program(weights, cfg), tx, apply_fn=model.apply)

    step = acc.prepare_train_step(make_olmo_hybrid_loss_fn(model, fused_vocab_chunks=recipe["ce_chunks"]))
    _TRAINED["a_log_mean"] = float(cfg["assumed"]["weight_scales"]["A_log_mean"])
    return acc, step, new_state


_TRAINED: dict = {}     # of the trainer this process built: what params_of takes off A_log again


def momentum_of(state) -> dict:
    """The optimizer's first moment by benchmark name (lion-sr: ``mu``)."""
    import jax

    mus = [s.mu for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(mus) != 1:
        raise ValueError(f"expected one momentum tree in the optimizer state, found {len(mus)}")
    return from_program(mus[0])


def params_of(state) -> dict:
    return from_program(state.params, _TRAINED["a_log_mean"])


def build_engine(cfg: dict, layers: int, engine: dict, weights: dict, rehearse: bool):
    raise NotImplementedError("the program has no serving path for the olmo_hybrid family")
