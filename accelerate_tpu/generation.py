"""Autoregressive generation: jitted prefill + KV-cache decode loop.

Reference capability parity: big-model *inference* (reference
big_modeling.py:513 ``load_checkpoint_and_dispatch`` + the reference's
``benchmarks/big_model_inference`` harness, which loads GPT-J/NeoX/OPT-class
models and generates).  The reference delegates the actual decode loop to
transformers ``model.generate``; here the loop is in-tree and TPU-native:

- **prefill**: one jitted forward over the whole (right-padded) prompt writes
  the KV cache — big matmuls, MXU-friendly, one compile for a given shape;
- **decode**: ``lax.scan`` over steps with a single-token forward per step —
  static shapes, one compile, no host round-trip per token;
- per-slot *positions* in the cache (models/llama.py ``init_cache``) mask
  padding and dead slots positionally, so variable-length prompts batch
  together without a separate attention-mask plumbing.

Sampling: greedy, temperature, top-k, top-p (nucleus) — the standard
transformers surface the reference's examples rely on.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .models.llama import init_cache


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Decode-loop knobs (transformers-compatible names)."""

    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0


@jax.named_scope("sample")
def sample_logits(logits, rng, config: GenerationConfig):
    """Next-token selection from [B, V] logits.

    Greedy when ``do_sample=False``; else temperature -> top-k -> top-p
    filtering, then categorical sampling.  Filtering masks logits to -inf
    (never renormalizes early — one softmax at the end, fused by XLA).
    """
    logits = logits.astype(jnp.float32)
    if not config.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if config.temperature != 1.0:
        logits = logits / max(config.temperature, 1e-6)
    neg = jnp.finfo(jnp.float32).min
    if config.top_k:  # transformers convention: top_k=0 disables the filter
        # clamp like transformers: top_k=50 on a 30-token vocab means "keep
        # everything", not a lax.top_k ValueError
        k = min(config.top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if config.top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose cumulative mass (inclusive of themselves) is the
        # first to cross top_p; the threshold logit is the smallest kept one.
        # The top token is always kept (cum - probs == 0 < top_p may be False
        # at top_p=0.0, which must mean greedy, not uniform-over-masked).
        keep = cum - probs < config.top_p
        keep = keep.at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, neg, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _generate_impl(model, gen_config, apply_fn, params, input_ids, prompt_lengths, rng, max_cache_len):
    apply = apply_fn or model.apply
    b, t_prompt = input_ids.shape
    cache = init_cache(model.config, b, max_cache_len)

    positions = jnp.broadcast_to(jnp.arange(t_prompt), (b, t_prompt))
    write_mask = positions < prompt_lengths[:, None]
    logits, cache = apply(
        params, input_ids, positions=positions, cache=cache, cache_write_mask=write_mask
    )
    # the last *real* prompt token's logits seed the loop
    last = jnp.take_along_axis(logits, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0]

    eos = gen_config.eos_token_id

    def step(carry, rng_step):
        cache, last_logits, cur_pos, done = carry
        token = sample_logits(last_logits, rng_step, gen_config)
        token = jnp.where(done, gen_config.pad_token_id, token)
        if eos is not None:
            done = done | (token == eos)
        logits, cache = apply(
            params, token[:, None], positions=cur_pos[:, None],
            cache=cache, cache_write_mask=~done[:, None],
        )
        return (cache, logits[:, 0], cur_pos + 1, done), token

    rngs = jax.random.split(rng, gen_config.max_new_tokens)
    init = (cache, last, prompt_lengths, jnp.zeros((b,), bool))
    _, tokens = jax.lax.scan(step, init, rngs)
    return tokens.T  # [B, max_new_tokens]


def generate(
    model,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    *,
    prompt_lengths=None,
    rng=None,
    apply_fn=None,
):
    """Generate ``max_new_tokens`` continuations for a batch of prompts.

    ``input_ids``: [B, T] right-padded prompts; ``prompt_lengths``: [B] real
    lengths (defaults to full width).  Returns [B, max_new_tokens] int32,
    padded with ``pad_token_id`` after EOS.  The whole prefill+decode program
    is one jit per (shape, config) pair.

    ``apply_fn`` overrides ``model.apply`` inside the loop — e.g.
    ``quantized_apply(model.apply)`` decodes from an int8/NF4-quantized
    param tree (dequant fuses into the step).  Pass a *stable* function:
    the compile cache keys on its identity.
    """
    generation_config = generation_config or GenerationConfig()
    if getattr(getattr(model, "config", None), "scan_layers", False):
        # cached decode needs the unrolled layout; convert transparently so
        # a scan_layers-trained state generates without manual steps
        model, params = _unrolled_view(model, params)
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, t_prompt = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), t_prompt, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    max_cache_len = t_prompt + generation_config.max_new_tokens
    # flax Modules and GenerationConfig are frozen/hashable — the jitted
    # program is cached per (model, config), so repeat calls at the same
    # shapes skip retracing entirely
    return _jitted_generate(model, generation_config, apply_fn)(
        params, input_ids, prompt_lengths, rng, max_cache_len
    )


# scan-layout -> unrolled-layout conversion, memoized so repeat generate()
# calls on the same state skip the host-side unstack.  The entry is validated
# leaf-by-leaf (weakrefs + `is` checks), so in-place updates to nested leaves
# miss and reconvert, and it holds NO strong refs to the stacked tree — when
# the caller drops the state the sentinel weakrefs die and the entry is
# evicted rather than pinning two full param trees.
_UNROLL_MEMO: dict = {}  # "entry" -> (leaf_weakrefs, converted_tree)


def _unrolled_view(model, params):
    """Return ``(model, params)`` rebuilt in the unrolled (per-layer) layout
    from a ``scan_layers`` state.  ``Module.clone`` keeps any extra attributes
    a model subclass may carry; only the config is swapped."""
    import weakref

    from .models.llama import unstack_layer_params

    cfg = dataclasses.replace(model.config, scan_layers=False, scan_block_size=1)
    new_model = model.clone(config=cfg) if hasattr(model, "clone") else type(model)(cfg)
    leaves = jax.tree_util.tree_leaves(params)
    entry = _UNROLL_MEMO.get("entry")
    if entry is not None:
        refs, converted = entry
        if len(refs) == len(leaves) and all(r() is l for r, l in zip(refs, leaves)):
            return new_model, converted
    converted = unstack_layer_params(params)

    def evict(_dead_ref, _memo=_UNROLL_MEMO):
        # the stacked state died: drop the converted copy immediately rather
        # than holding GBs until the next generate() call (or forever)
        _memo.pop("entry", None)

    try:
        _UNROLL_MEMO["entry"] = ([weakref.ref(l, evict) for l in leaves], converted)
    except TypeError:  # a leaf type without weakref support: skip memoization
        _UNROLL_MEMO.pop("entry", None)
    return new_model, converted


@lru_cache(maxsize=32)
def _jitted_generate(model, generation_config, apply_fn=None):
    return jax.jit(partial(_generate_impl, model, generation_config, apply_fn),
                   static_argnums=(4,))


def generate_paged(
    model,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    *,
    prompt_lengths=None,
    serving_plugin=None,
    rng=None,
    adapters=None,
    adapter_ids=None,
    speculate=None,
    speculate_k: Optional[int] = None,
    draft_model=None,
    draft_params=None,
    prefix_cache=None,
):
    """:func:`generate`-shaped decoding through the **paged serving path**
    (``accelerate_tpu/serving/``): the batch rows become requests, decode
    runs through the block-table paged KV cache and the continuous-batching
    engine, and the output comes back as the same right-padded
    ``[B, max_new_tokens]`` int32 array (``pad_token_id`` after EOS).

    Greedy paged serving emits tokens **identical** to :func:`generate` —
    the acceptance contract tests/test_serving.py pins.  This is also the
    offline entry point for batch inference over the serving stack (the
    per-request path is :class:`~accelerate_tpu.serving.ServingEngine`).

    Multi-tenant: pass an :class:`~accelerate_tpu.serving.AdapterStore` as
    ``adapters`` plus per-row tenant ``adapter_ids`` (0 = base model) to
    decode each row through its LoRA adapter — the per-request reference
    path the serve-with-adapters parity test pins the batched engine
    against.

    Speculative decode: ``speculate="ngram"`` (prompt-lookup self-drafting)
    or ``"draft"`` (pass ``draft_model``/``draft_params``) emits up to
    ``speculate_k + 1`` tokens per verify pass — greedy tokens stay BITWISE
    identical to :func:`generate` (the acceptance pin extends:
    tests/test_speculate.py pins it, including under eviction/recompute
    pressure and mixed LoRA tenant traffic).  ``speculate=True`` means
    ``"ngram"``.

    Prefix caching: ``prefix_cache=True`` (or ``"on"``) arms the
    content-addressed COW shared-page cache
    (``serving/prefix_cache.py``) — rows sharing a prompt prefix reuse
    each other's KV pages at page granularity, and greedy tokens stay
    BITWISE identical with it on or off (tests/test_prefix_cache.py).
    ``False`` is an explicit opt-out over a plugin/env-armed default.
    """
    import dataclasses as _dc

    from .serving import Request, ServingEngine
    from .utils.dataclasses import ServingPlugin

    generation_config = generation_config or GenerationConfig()
    input_ids = np.asarray(input_ids)
    b, t_prompt = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = [t_prompt] * b
    else:
        prompt_lengths = [int(x) for x in np.asarray(prompt_lengths)]
    if adapter_ids is None:
        adapter_ids = [0] * b
    else:
        adapter_ids = [int(x) for x in np.asarray(adapter_ids)]
    n_new = generation_config.max_new_tokens
    # None = "not provided" (plugin/env decide); False is an EXPLICIT
    # opt-out that must win over an env- or plugin-armed default, exactly
    # like ServingPlugin(speculate=False)
    if speculate is True:
        speculate = "ngram"
    elif speculate is False:
        speculate = "off"
    # same convention for content-addressed prefix reuse: True/"on" arms
    # the COW shared-page cache through the serving path (greedy tokens
    # stay BITWISE identical on/off — the acceptance pin
    # tests/test_prefix_cache.py extends)
    if prefix_cache is True:
        prefix_cache = "on"
    elif prefix_cache is False:
        prefix_cache = "off"
    if serving_plugin is None:
        # provision for the offline case: every row resident at once
        page_size = 16
        pages = max(1, -(-(t_prompt + n_new) // page_size))
        serving_plugin = ServingPlugin(
            num_slots=b, page_size=page_size, pages_per_slot=pages,
            num_pages=b * pages, prefill_chunk=max(16, t_prompt),
            **({"speculate": speculate} if speculate is not None else {}),
            **({"speculate_k": speculate_k} if speculate_k else {}),
            **({"prefix_cache": prefix_cache} if prefix_cache is not None else {}),
        )
    elif speculate is not None or speculate_k or prefix_cache is not None:
        serving_plugin = _dc.replace(
            serving_plugin,
            **({"speculate": speculate} if speculate is not None else {}),
            **({"speculate_k": speculate_k, "speculate_buckets": None}
               if speculate_k else {}),
            **({"prefix_cache": prefix_cache} if prefix_cache is not None else {}),
        )
    engine = ServingEngine(model, params, serving_plugin, generation_config,
                           rng=rng, adapters=adapters,
                           draft_model=draft_model, draft_params=draft_params)
    for i in range(b):
        engine.add_request(Request(
            uid=i, prompt=tuple(int(x) for x in input_ids[i, : prompt_lengths[i]]),
            max_new_tokens=n_new, adapter_id=adapter_ids[i],
        ))
    results = engine.run([])
    out = np.full((b, n_new), generation_config.pad_token_id, np.int32)
    for i in range(b):
        toks = results[i]
        out[i, : len(toks)] = toks
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Beam search (decoder-only)
# ---------------------------------------------------------------------------


def _beam_search_impl(model, gen_config, num_beams, length_penalty, apply_fn, params,
                      input_ids, prompt_lengths, max_cache_len):
    apply = apply_fn or model.apply
    b, t_prompt = input_ids.shape
    k = num_beams
    neg = jnp.float32(-1e9)
    eos = gen_config.eos_token_id
    pad = gen_config.pad_token_id

    cache = init_cache(model.config, b, max_cache_len)
    positions = jnp.broadcast_to(jnp.arange(t_prompt), (b, t_prompt))
    write_mask = positions < prompt_lengths[:, None]
    logits, cache = apply(
        params, input_ids, positions=positions, cache=cache, cache_write_mask=write_mask
    )
    last = jnp.take_along_axis(logits, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0]

    # tile prefill cache to B*K beams (beam-major within each batch row)
    def tile(x):
        return jnp.repeat(x, k, axis=0) if x.ndim > 0 else x

    cache = [{"k": tile(c["k"]), "v": tile(c["v"]), "pos": tile(c["pos"]),
              "index": c["index"]} for c in cache]
    last = jnp.repeat(last, k, axis=0)                      # [B*K, V]
    beam_lengths = jnp.repeat(prompt_lengths, k, axis=0)    # [B*K]
    # only beam 0 live at the start, else the K identical beams collapse
    beam_scores = jnp.tile(jnp.where(jnp.arange(k) == 0, 0.0, neg), (b,))
    done = jnp.zeros((b * k,), bool)

    v = last.shape[-1]

    def step(carry, step_i):
        cache, last_logits, beam_scores, done, cur_pos, tokens = carry
        logp = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)
        # finished beams expand only with pad at score 0 (they persist as-is)
        pad_row = jnp.full((v,), neg).at[pad].set(0.0)
        logp = jnp.where(done[:, None], pad_row[None, :], logp)
        cand = (beam_scores[:, None] + logp).reshape(b, k * v)
        top_scores, top_idx = jax.lax.top_k(cand, k)        # [B, K]
        src_beam = top_idx // v                             # beam within batch row
        token = (top_idx % v).astype(jnp.int32)
        flat_src = (jnp.arange(b)[:, None] * k + src_beam).reshape(-1)  # [B*K]

        beam_scores = top_scores.reshape(-1)
        token = token.reshape(-1)
        done = jnp.take(done, flat_src, axis=0)
        cur_pos = jnp.take(cur_pos, flat_src, axis=0)
        tokens = jnp.take(tokens, flat_src, axis=0)
        tokens = jax.lax.dynamic_update_slice(tokens, token[:, None], (0, step_i))
        was_done = done
        if eos is not None:
            done = done | (token == eos)
        done_now = done

        cache = [
            {"k": jnp.take(c["k"], flat_src, axis=0),
             "v": jnp.take(c["v"], flat_src, axis=0),
             "pos": jnp.take(c["pos"], flat_src, axis=0),
             "index": c["index"]}
            for c in cache
        ]
        logits, cache = apply(
            params, token[:, None], positions=cur_pos[:, None],
            cache=cache, cache_write_mask=~done_now[:, None],
        )
        # beams stop advancing the step *after* EOS: the EOS token itself
        # counts toward gen_len, matching transformers' GNMT normalization
        return (cache, logits[:, 0], beam_scores, done, cur_pos + (~was_done), tokens), None

    n = gen_config.max_new_tokens
    tokens0 = jnp.full((b * k, n), pad, jnp.int32)
    carry = (cache, last, beam_scores, done, beam_lengths, tokens0)
    (cache, _, beam_scores, done, cur_pos, tokens), _ = jax.lax.scan(
        step, carry, jnp.arange(n)
    )
    # pick the best beam per batch row, length-penalized (GNMT-style)
    gen_len = jnp.maximum((cur_pos - jnp.repeat(prompt_lengths, k)).astype(jnp.float32), 1.0)
    norm = beam_scores / (gen_len ** length_penalty)
    best = jnp.argmax(norm.reshape(b, k), axis=-1)          # [B]
    flat_best = jnp.arange(b) * k + best
    return jnp.take(tokens, flat_best, axis=0)


def beam_search(
    model,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    *,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    prompt_lengths=None,
    apply_fn=None,
):
    """Beam-search decoding with a per-beam KV cache.

    Beams live on the batch axis ([B*num_beams, ...]); each step re-gathers
    the cache by the surviving beams' source indices — a batched gather XLA
    fuses into the decode step, not a host-side reorder.  Finished beams
    persist by expanding only with ``pad_token_id`` at score 0.  The best
    hypothesis per batch row is chosen by GNMT length-penalized score.
    Returns [B, max_new_tokens] int32.
    """
    generation_config = generation_config or GenerationConfig()
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, t_prompt = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), t_prompt, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    max_cache_len = t_prompt + generation_config.max_new_tokens
    return _jitted_beam_search(model, generation_config, num_beams, length_penalty, apply_fn)(
        params, input_ids, prompt_lengths, max_cache_len
    )


@lru_cache(maxsize=32)
def _jitted_beam_search(model, generation_config, num_beams, length_penalty, apply_fn=None):
    return jax.jit(
        partial(_beam_search_impl, model, generation_config, num_beams, length_penalty, apply_fn),
        static_argnums=(3,),
    )


# ---------------------------------------------------------------------------
# Encoder-decoder (T5-family) generation
# ---------------------------------------------------------------------------


def _seq2seq_impl(model, gen_config, decoder_start_token_id, params, input_ids,
                  attention_mask, rng):
    b = input_ids.shape[0]
    n = gen_config.max_new_tokens
    # encode once; the decoder re-runs over a fixed [B, n] buffer each step
    # (static shapes -> one compile; relative-position bias and cross-
    # attention make true incremental caching a poor trade at T5 scale, and
    # rows past the current step are causally invisible to it)
    enc = model.apply(params, input_ids, None, attention_mask)
    buf = jnp.full((b, n + 1), decoder_start_token_id, jnp.int32)
    eos = gen_config.eos_token_id

    def step_i(carry, xs):
        buf, done = carry
        i, rng_step = xs
        logits = model.apply(params, None, buf, attention_mask, encoder_output=enc)
        step_logits = jnp.take_along_axis(
            logits, jnp.broadcast_to(i[None, None, None], (b, 1, 1)), axis=1
        )[:, 0]
        token = sample_logits(step_logits, rng_step, gen_config)
        token = jnp.where(done, gen_config.pad_token_id, token)
        if eos is not None:
            done = done | (token == eos)
        buf = jax.lax.dynamic_update_slice(buf, token[:, None], (0, i + 1))
        return (buf, done), token

    rngs = jax.random.split(rng, n)
    steps = jnp.arange(n)
    (_, _), tokens = jax.lax.scan(step_i, (buf, jnp.zeros((b,), bool)), (steps, rngs))
    return tokens.T


def generate_seq2seq(
    model,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    *,
    attention_mask=None,
    decoder_start_token_id: int = 0,
    rng=None,
):
    """Encoder-decoder generation (T5 family): encode once, autoregressively
    decode ``max_new_tokens``.  ``attention_mask`` [B, T] masks encoder
    padding.  Returns [B, max_new_tokens] int32 (pad after EOS)."""
    generation_config = generation_config or GenerationConfig()
    input_ids = jnp.asarray(input_ids, jnp.int32)
    if attention_mask is not None:
        attention_mask = jnp.asarray(attention_mask)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _jitted_seq2seq(model, generation_config, decoder_start_token_id)(
        params, input_ids, attention_mask, rng
    )


@lru_cache(maxsize=32)
def _jitted_seq2seq(model, generation_config, decoder_start_token_id):
    return jax.jit(partial(_seq2seq_impl, model, generation_config, decoder_start_token_id))


# ---------------------------------------------------------------------------
# Over-HBM inference: layer-streamed generation (reference AlignDevicesHook /
# disk-offload decode, hooks.py:227 + big_modeling.py:310 — the OPT-30B/70B
# "model larger than the accelerator" mode)
# ---------------------------------------------------------------------------


def place_params_host(params):
    """Move a param tree (including QuantizedTensor leaves) into pinned host
    memory — the staging tier :func:`generate_streamed` streams layers from.
    No-op where the backend lacks in-jit memory kinds (CPU tests)."""
    from .parallel.sharding import host_offload_supported, single_device_sharding

    if not host_offload_supported():
        return params
    host = single_device_sharding("pinned_host")
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, host), params)


@lru_cache(maxsize=8)
def _streamed_fns(model):
    """The jitted pieces of a streamed forward, shared across layers (every
    layer has identical shapes, so each fn compiles once)."""
    from .models.llama import LMHead, RMSNorm
    from .parallel.sharding import host_offload_supported, single_device_sharding

    cfg = model.config
    block = type(model).block_cls
    kinds_ok = host_offload_supported()

    def _fetch(tree):
        # host -> HBM copy of one layer's weights, inside the jit (single
        # dispatch per layer; the transfer runs on the TPU host's PCIe)
        if not kinds_ok:
            return tree
        dev = single_device_sharding()
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, dev), tree)

    @jax.jit
    def embed_fn(embedding, ids):
        return jnp.take(embedding, ids, axis=0).astype(cfg.dtype)

    @jax.jit
    def block_fn(layer_params, x, positions, cache_i, write_mask):
        return block(cfg).apply(
            {"params": _fetch(layer_params)}, x, positions, None, cache_i, write_mask
        )

    @jax.jit
    def head_fn(norm_scale, head_w, x):
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype).apply(
            {"params": {"scale": norm_scale}}, x
        )
        if cfg.tie_word_embeddings:
            # head_w is the [V, H] embedding table — contract hidden against
            # its dim 1, mirroring the model's tied path (models/llama.py)
            return jax.lax.dot_general(
                x, head_w.astype(cfg.dtype), (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        return LMHead(cfg.vocab_size, cfg.dtype).apply(
            {"params": {"kernel": head_w}}, x
        )

    return embed_fn, block_fn, head_fn


def generate_streamed(
    model,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    *,
    prompt_lengths=None,
    rng=None,
    prefetch: bool = True,
    prefetch_depth: int = 1,
    stream_stats=None,
    capture_logits: Optional[list] = None,
):
    """Generate from a model whose weights do NOT fit in HBM.

    ``params`` lives in (pinned) host memory — see :func:`place_params_host`
    — or carries numpy/memmap leaves straight out of an
    :class:`~accelerate_tpu.big_modeling.OffloadStore` (see
    :func:`~accelerate_tpu.big_modeling.offload_store_params`), and every
    forward streams one layer's
    weights to the device at a time: HBM holds ``prefetch_depth + 1`` layers
    + the KV cache, so the model-size ceiling is host RAM (or disk), not HBM
    (the reference's CPU/disk-offload inference mode, OPT-30B on a 24GB card
    at seconds/token — same trade here).  int8 ``QuantizedTensor`` leaves
    stream at one byte per weight and hit the Pallas in-tile-dequant matmul
    on device.

    With ``prefetch=True`` (default) the uploads are **double-buffered**
    (:class:`~accelerate_tpu.ops.streaming.LayerPrefetcher`): layer *k+1*'s
    H2D copy is dispatched before the loop blocks on layer *k*, so the next
    layer streams in under the current layer's matmuls, and layer 0's
    weights for the next token ride under the LM head + sampling.
    ``prefetch=False`` restores the serial fetch-inside-the-layer schedule
    (the A/B baseline — both produce identical logits, pinned by
    ``tests/test_generation.py``).  Pass a
    :class:`~accelerate_tpu.ops.streaming.StreamStats` as ``stream_stats``
    for overlap accounting (bytes, stall time, hits); ``capture_logits``
    (a list) collects each forward's logits for parity checks.

    The decode loop is host-driven (one dispatch per layer per token) —
    without prefetch, latency is dominated by the per-token PCIe sweep over
    the weights, exactly like the reference's offload decode.
    """
    import time as _time

    generation_config = generation_config or GenerationConfig()
    cfg = model.config
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, t_prompt = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), t_prompt, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    t_start = _time.perf_counter()

    p = params["params"] if "params" in params else params
    from .ops.streaming import LayerPrefetcher
    from .parallel.sharding import host_offload_supported, single_device_sharding

    embed = p["embed_tokens"]["embedding"]
    head = embed if cfg.tie_word_embeddings else p["lm_head"]["kernel"]
    norm_scale = p["norm"]["scale"]
    kinds_ok = host_offload_supported()
    dev = single_device_sharding() if kinds_ok else None
    if kinds_ok:
        # the embedding/norm/head tier stays HBM-resident (about one layer's
        # worth) — re-streaming the [V, H] table every token would waste
        # ~0.5 GiB of PCIe per step at 7B-class vocab sizes
        embed = jax.device_put(embed, dev)
        head = embed if cfg.tie_word_embeddings else jax.device_put(head, dev)
        norm_scale = jax.device_put(norm_scale, dev)
    max_len = t_prompt + generation_config.max_new_tokens
    cache = init_cache(cfg, b, max_len)
    embed_fn, block_fn, head_fn = _streamed_fns(model)

    fetcher = None
    if prefetch or stream_stats is not None:
        # stream_stats with prefetch=False still routes fetches through the
        # (disabled) prefetcher: the blocking out-of-jit fetches it does are
        # the measured serial-transfer baseline overlap_report() compares
        # against.  Without stats, prefetch=False keeps the original
        # fetch-inside-the-layer-jit schedule.
        def _fetch_layer(i):
            # H2D upload OUTSIDE the layer's jit: jax dispatch is async, so
            # the copy proceeds while the in-flight layer's matmuls run —
            # the serial path copied *inside* block_fn, taking turns with
            # compute.  memmap leaves (OffloadStore disk tier) upload the
            # same way; QuantizedTensor leaves stream their int8 codes.
            def _put(x):
                x = np.asarray(x) if isinstance(x, np.memmap) else x
                return jax.device_put(x, dev) if dev is not None else jax.device_put(x)

            return jax.tree_util.tree_map(_put, p[f"layers_{i}"])

        fetcher = LayerPrefetcher(
            _fetch_layer, cfg.num_hidden_layers, depth=prefetch_depth,
            wrap=True, enabled=prefetch, stats=stream_stats,
        )

    def forward(ids, positions, write_mask):
        x = embed_fn(embed, ids)
        for i in range(cfg.num_hidden_layers):
            layer = fetcher.get(i) if fetcher is not None else p[f"layers_{i}"]
            x, cache[i] = block_fn(layer, x, positions, cache[i], write_mask)
        logits = head_fn(norm_scale, head, x)
        if capture_logits is not None:
            capture_logits.append(logits)
        return logits

    positions = jnp.broadcast_to(jnp.arange(t_prompt), (b, t_prompt))
    logits = forward(positions=positions, ids=input_ids,
                     write_mask=positions < prompt_lengths[:, None])
    last = jnp.take_along_axis(logits, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0]

    eos = generation_config.eos_token_id
    cur_pos = prompt_lengths
    done = jnp.zeros((b,), bool)
    out = []
    for step in range(generation_config.max_new_tokens):
        rng, step_rng = jax.random.split(rng)
        token = sample_logits(last, step_rng, generation_config)
        token = jnp.where(done, generation_config.pad_token_id, token)
        if eos is not None:
            done = done | (token == eos)
        out.append(token)
        if step + 1 == generation_config.max_new_tokens:
            break
        logits = forward(ids=token[:, None], positions=cur_pos[:, None],
                         write_mask=~done[:, None])
        last = logits[:, 0]
        cur_pos = cur_pos + 1
    tokens = jnp.stack(out, axis=1)
    if stream_stats is not None:
        jax.block_until_ready(tokens)
        stream_stats.wall_s += _time.perf_counter() - t_start
    return tokens
