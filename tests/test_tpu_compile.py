"""AOT compiles of the main path's kernels for a DESCRIBED TPU v5e chip.

The TPU compiler is installed here and compiles for a chip that is described,
not attached (``topologies.get_topology_desc``): what it refuses here — a
block shape off the (8, 128) tiling, a shape cast Mosaic cannot lay out, too
much scoped VMEM — it would refuse on the chip, where the green
interpret-mode tests say nothing.  Llama-2-7B widths (32 MHA heads x 128) and
the 513M control cell's (16/8 GQA heads x 96), at the smoke's serving
geometry (16 slots, page 64, 256 pages), each kernel on one described chip;
the flash kernel also over the four of ``topo.devices`` as a mesh, where
GSPMD refuses a bare Mosaic call.  A compile that passes is a compile, never
a chip run.

All in this ONE file, the topology described inside a module-scoped fixture
(never at import, in a skipif or in parametrize arguments): only one process
may hold the TPU library, so only the xdist worker that is handed this file
loads it.  The compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from accelerate_tpu.ops import flash_attention as fa
from accelerate_tpu.ops.fused_xent import fused_causal_lm_loss
from accelerate_tpu.ops.lora import bgmv
from accelerate_tpu.ops.quantized_matmul import quantized_matmul
from accelerate_tpu.utils.quantization import QuantizedTensor

SLOTS, PAGE, PAGES, PAGES_PER_SLOT = 16, 64, 256, 16
RANK, ADAPTERS = 16, 4
BF16 = jnp.bfloat16
# (q heads, kv heads, head_dim, hidden)
LLAMA2_7B = (32, 32, 128, 4096)
CONTROL_513M = (16, 8, 96, 1536)
WIDTHS = pytest.mark.parametrize(
    "h,hkv,d,hidden", [LLAMA2_7B, CONTROL_513M], ids=["llama2_7b", "control_513m"])
KV_DTYPES = pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """``compile_for_chip(fn, *(shape, dtype))`` -> compiled HLO text, with
    the persistent compilation cache off for the module."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _pool_specs(hkv, d, kv_dtype):
    """(k_pages, v_pages[, k_scales, v_scales]) operand specs."""
    page = ((hkv, PAGES, PAGE, d), jnp.int8 if kv_dtype == "int8" else BF16)
    scale = ((hkv, PAGES), jnp.float32)
    return [page, page] + ([scale, scale] if kv_dtype == "int8" else [])


def _scales(rest):
    return dict(k_scales=rest[0], v_scales=rest[1]) if rest else {}


# (batch, seq, q heads, kv heads, head_dim): the smoke's widths at 2048, the
# two train cells' per-chip shapes (Mistral; Yi's tp shard), and the longest
# sequence whose K/V the backward keeps whole in VMEM (single-buffered)
FLASH_SHAPES = pytest.mark.parametrize("b,t,h,hkv,d", [
    (2, 2048) + LLAMA2_7B[:3], (2, 2048) + CONTROL_513M[:3],
    (2, 4096, 32, 8, 128), (2, 4096, 28, 4, 128), (1, 32768, 8, 2, 128),
], ids=["llama2_7b", "control_513m", "mistral_cell", "yi_cell_shard", "seq_32k"])


def _custom_calls(text, name):
    """Mosaic custom calls of the compiled HLO whose op_name carries the
    kernel's ``pallas_call(name=)`` as a whole word."""
    import re

    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and re.search(rf"\b{name}\b", line)]


@FLASH_SHAPES
def test_flash_forward_and_backward_compile(compile_for_chip, b, t, h, hkv, d):
    """The forward and the ONE backward kernel compile for the chip at the
    shapes the cells run, inside the VMEM the backward states from its plan
    (Mosaic refuses a kernel over its scoped limit at compile time)."""
    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = compile_for_chip(fwd_bwd, ((b, t, h, d), BF16),
                            ((b, t, hkv, d), BF16), ((b, t, hkv, d), BF16))
    assert len(_custom_calls(text, "flash_fwd")) == 1
    assert len(_custom_calls(text, "flash_bwd_dkv")) == 1
    assert not _custom_calls(text, "flash_bwd_dq")
    assert text.count("tpu_custom_call") == 2
    blocks, buffers, vmem = fa._bwd_vmem_plan(-(-t // 512), 512, 512, d, 2)
    assert (blocks, buffers) == (-(-t // 512), 1 if t > 16384 else 2)
    assert vmem <= fa._BWD_VMEM_BYTES


@pytest.mark.parametrize("case", ["segments", "positions_lse", "ragged", "kv_chunks_64k"])
def test_flash_backward_flags_compile(compile_for_chip, case):
    """The same kernel under the static flags no cell runs: packed segments,
    ring CP's positions with an lse cotangent, lengths off the block, and a
    sequence too long to stay whole in VMEM (equal kv chunks)."""
    t = {"ragged": 4000, "kv_chunks_64k": 65536}.get(case, 4096)
    b, h, hkv, d = (1, 4, 2, 128) if case == "kv_chunks_64k" else (2, 8, 2, 128)

    def grads(q, k, v, ids):
        kwargs = {"segments": dict(segment_ids=ids),
                  "positions_lse": dict(positions=ids, return_lse=True)}.get(case, {})

        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, interpret=False, **kwargs)
            if case == "positions_lse":
                return jnp.sum(out[0].astype(jnp.float32)) + jnp.sum(out[1])
            return jnp.sum(out.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = compile_for_chip(grads, ((b, t, h, d), BF16), ((b, t, hkv, d), BF16),
                            ((b, t, hkv, d), BF16), ((b, t), jnp.int32))
    assert len(_custom_calls(text, "flash_bwd_dkv")) == 1
    assert not _custom_calls(text, "flash_bwd_dq")


@pytest.mark.parametrize("region", ["gspmd", "pipeline_stage", "bare_kernel"])
def test_flash_under_a_four_chip_mesh(topo, compile_for_chip, region):
    """The sharded step's attention over ``topo.devices`` with NamedSharding
    operands, Llama-2-7B widths.  GSPMD cannot partition a Mosaic call (the
    bare kernel is refused), so ``mesh_flash_attention`` goes manual over the
    axes still Auto: all of them under plain GSPMD, the rest inside a GPipe
    stage that is already manual over ``pp``.  Invisible on the CPU mesh,
    where interpret-mode kernels are plain XLA."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, ParallelismConfig

    staged = region == "pipeline_stage"
    acc = Accelerator(parallelism_config=ParallelismConfig(
        tp_size=2, devices=list(topo.devices),
        **({"pp_size": 2} if staged else {"dp_shard_size": 2})))
    attn = fa.flash_attention if region == "bare_kernel" else fa.mesh_flash_attention

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: jnp.sum(attn(
            q, k, v, causal=True, interpret=False).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    if staged:
        fwd_bwd = jax.shard_map(fwd_bwd, mesh=acc.mesh, in_specs=P(), out_specs=P(),
                                axis_names={"pp"}, check_vma=False)
    sharding = NamedSharding(acc.mesh, P(None if staged else "dp_shard", None, "tp", None))
    h, hkv, d, _ = LLAMA2_7B
    lowered = lambda: jax.jit(fwd_bwd).lower(*(
        jax.ShapeDtypeStruct((4, 2048, heads, d), BF16, sharding=sharding)
        for heads in (h, hkv, hkv)))
    if region == "bare_kernel":
        with pytest.raises(Exception, match="Mosaic kernels cannot be automatically partitioned"):
            lowered().compile()
    else:
        text = lowered().compile().as_text()
        assert len(_custom_calls(text, "flash_fwd")) == 1
        assert len(_custom_calls(text, "flash_bwd_dkv")) == 1  # the one backward kernel


@WIDTHS
@KV_DTYPES
def test_paged_decode_attention_compiles(compile_for_chip, h, hkv, d, hidden, kv_dtype):
    def decode(q, bt, pos, k, v, *rest):
        return fa.paged_decode_attention(q, k, v, bt, pos, interpret=False, **_scales(rest))

    text = compile_for_chip(
        decode, ((SLOTS, h, d), BF16), ((SLOTS, PAGES_PER_SLOT), jnp.int32),
        ((SLOTS,), jnp.int32), *_pool_specs(hkv, d, kv_dtype))
    assert "tpu_custom_call" in text


@WIDTHS
@KV_DTYPES
@pytest.mark.parametrize("slots,width", [(SLOTS, 5), (1, 512)],
                         ids=["verify_k4", "prefill_512"])
def test_paged_multitoken_attention_compiles(compile_for_chip, h, hkv, d, hidden,
                                             kv_dtype, slots, width):
    def multi(q, bt, pos, k, v, *rest):
        return fa.paged_multitoken_attention(q, k, v, bt, pos, interpret=False,
                                             **_scales(rest))

    text = compile_for_chip(
        multi, ((slots, width, h, d), BF16), ((slots, PAGES_PER_SLOT), jnp.int32),
        ((slots, width), jnp.int32), *_pool_specs(hkv, d, kv_dtype))
    assert "tpu_custom_call" in text


@WIDTHS
def test_bgmv_compiles(compile_for_chip, h, hkv, d, hidden):
    text = compile_for_chip(
        lambda x, a, b, ids: bgmv(x, a, b, ids, interpret=False),
        ((SLOTS, hidden), BF16), ((ADAPTERS, hidden, RANK), BF16),
        ((ADAPTERS, RANK, h * d), BF16), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@WIDTHS
@KV_DTYPES
def test_fused_bgmv_paged_decode_compiles(compile_for_chip, h, hkv, d, hidden, kv_dtype):
    def fused(x, q, a, b, ids, cos, sin, bt, pos, k, v, *rest):
        return fa.fused_bgmv_paged_decode(x, q, a, b, ids, cos, sin, k, v, bt, pos,
                                          interpret=False, **_scales(rest))

    rope = ((4096, d // 2), jnp.float32)
    text = compile_for_chip(
        fused, ((SLOTS, hidden), BF16), ((SLOTS, h, d), BF16),
        ((ADAPTERS, hidden, RANK), BF16), ((ADAPTERS, RANK, h * d), BF16),
        ((SLOTS,), jnp.int32), rope, rope, ((SLOTS, PAGES_PER_SLOT), jnp.int32),
        ((SLOTS,), jnp.int32), *_pool_specs(hkv, d, kv_dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,rows,cols", [
    (512, 4096, 11008),   # tiled kernel, gate/up projection
    (512, 11008, 4096),   # tiled kernel, masked partial K tile (11008)
    (1, 4096, 11008),     # whole-F decode kernel
    (8, 11008, 4096),     # whole-F decode kernel, masked K
], ids=["tiled_up", "tiled_down", "wholef_up", "wholef_down"])
def test_int8_quantized_matmul_compiles(compile_for_chip, m, rows, cols):
    block = 128

    def qmm(x, codes, scales):
        qt = QuantizedTensor(codes, scales, (rows, cols), BF16, "int8", block, layout="k2d")
        return quantized_matmul(x, qt, interpret=False)

    text = compile_for_chip(qmm, ((m, rows), BF16), ((rows, cols), jnp.int8),
                            ((cols // block, rows), jnp.float32))
    assert "tpu_custom_call" in text  # the kernel, not the dequantize+matmul route


def test_fused_cross_entropy_compiles(compile_for_chip):
    """The fused linear+CE is XLA dots under a custom_vjp (no Pallas): the
    chip's compiler must take it at the smoke's widths, fwd and bwd."""
    def loss_and_grads(hidden, weight, labels):
        return jax.value_and_grad(
            lambda hid, w: fused_causal_lm_loss(hid, w, labels, vocab_major=False,
                                                num_chunks=4), argnums=(0, 1))(hidden, weight)

    text = compile_for_chip(loss_and_grads, ((1, 2048, 4096), BF16),
                            ((4096, 32000), BF16), ((1, 2048), jnp.int32))
    assert "fusion" in text


def test_fsdp_x_tp_block_moves_weights_not_activations(topo, compile_for_chip, monkeypatch):
    """Forward + backward of ONE ``LlamaBlock`` at Yi-1.5-34B widths (the
    four-chip benchmark cell: dp_shard 2 x tp 2, batch 4 x 4096, parameters on
    ``_params_plan``'s shardings, the flash kernel) over ``topo.devices``.
    With the rows pinned (``parallel/sharding.constrain_activation``) the
    chip's partitioner runs FSDP: it gathers ``gate``/``up`` whole over
    ``dp_shard`` and no collective's result carries the global batch — no
    ``[4, 4096, ...]`` partial-product all-reduce or gather, no all-to-all of
    the residual stream between a rows layout and a hidden-halved one."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models.llama import LlamaBlock, LlamaConfig

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)   # the kernel, not its interpreter
    batch, seq, hidden, mlp = 4, 4096, 7168, 20480
    acc = Accelerator(mixed_precision="bf16", parallelism_config=ParallelismConfig(
        dp_shard_size=2, tp_size=2, devices=list(topo.devices)))
    block = LlamaBlock(LlamaConfig(
        hidden_size=hidden, intermediate_size=mlp, num_attention_heads=56, num_key_value_heads=8,
        max_position_embeddings=seq, attn_implementation="flash", dtype=BF16))
    abstract = jax.eval_shape(lambda: block.init(
        jax.random.key(0), jnp.zeros((batch, 8, hidden), BF16), jnp.zeros((batch, 8), jnp.int32)))
    plan = acc._params_plan(abstract)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, BF16, sharding=s), abstract, plan)
    rows = lambda *rest: NamedSharding(acc.mesh, P("dp_shard", *rest))

    def fwd_bwd(params, x, positions):
        loss = lambda p, x: jnp.sum(block.apply(p, x, positions).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1))(params, x)

    out_shardings = (NamedSharding(acc.mesh, P()), (plan, rows(None, None)))
    text = jax.jit(fwd_bwd, out_shardings=out_shardings).lower(
        params, jax.ShapeDtypeStruct((batch, seq, hidden), BF16, sharding=rows(None, None)),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows(None))).compile().as_text()
    collectives = [(kind, tuple(int(d) for d in dims.split(",") if d)) for dims, kind in re.findall(
        r"= \(?\w+\[([0-9,]*)\]\S* (all-reduce|all-gather|all-to-all|reduce-scatter)(?:-start)?\(",
        text)]
    assert ("all-gather", (hidden, mlp // 2)) in collectives      # gate / up, whole, over dp_shard
    assert [c for c in collectives if c[1][:2] == (batch, seq)] == []   # nothing at the global batch
    assert [c for c in collectives
            if c[0] == "all-to-all" and seq in c[1] and hidden // 2 in c[1]] == []
    assert text.count("tpu_custom_call") >= 2      # flash forward, the one backward kernel


# -- Keye-VL-2.0's serving programs at the cell's shapes (keye-vl2.serve_long) -----------

KEYE_SLOTS, KEYE_PAGES, KEYE_PAGES_PER_SLOT = 8, 4160, 520


@pytest.mark.parametrize("program,width", [("decode", 1), ("prefill", 512), ("prefill", 2048)])
def test_keye_vl2_serving_programs_compile_at_the_cells_shapes(one_chip, compile_for_chip,
                                                               monkeypatch, program, width):
    """The engine's decode and prefill programs of ``models/keye_vl2.py`` at
    the published widths and the cell's geometry (8 slots, 4,160 pages of 64,
    520 a slot), one layer deep (every layer is alike): the grouped matmul
    must be the Mosaic kernel, the selection threshold the ``sparse_threshold``
    kernel with no sort beside it, and no op may copy or relay a whole page
    pool (the pools are written in the layout the reads use)."""
    import re

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import KeyeVL2Config, KeyeVL2ForCausalLM
    from accelerate_tpu.ops import sparse_attention as sa
    from accelerate_tpu.serving.engine import fresh_engine_jits

    monkeypatch.setattr(sa, "_on_tpu", lambda: True)   # the kernel, not its interpreter
    model = KeyeVL2ForCausalLM(KeyeVL2Config(num_hidden_layers=1))
    on_chip = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))), BF16)
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(KEYE_PAGES, PAGE, KEYE_SLOTS, KEYE_PAGES_PER_SLOT)))
    gen = GenerationConfig(max_new_tokens=512, do_sample=False, eos_token_id=None)
    decode, prefill, *_ = fresh_engine_jits(model, gen, PAGE)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode":
        lowered = decode.lower(params, cache, arg((KEYE_SLOTS,)), arg((KEYE_SLOTS,), jnp.bool_),
                               arg((2,), jnp.uint32))
    else:
        lowered = prefill.lower(params, cache, arg(()), arg((width,)), arg(()), arg(()))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3 and "tpu_custom_call" in text      # gate, up, down
    assert len(re.findall(r"%sparse_threshold\S* = .* custom-call\(", text)) == 1
    attention = [line for line in text.splitlines() if "/self_attn/" in line]
    assert attention and not [line for line in attention if re.search(r" (sort|topk)\(", line)]
    pools = re.findall(r"= bf16\[4160,64,(?:512|128)\]\S* (copy|transpose)\(", text)
    assert pools == []
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 3 * KEYE_PAGES * PAGE * 128 * 2      # the pools alias in place
    assert stats.temp_size_in_bytes < 2 * 2**30


@pytest.mark.parametrize("rows,width", [(8, 36864), (2048, 34816), (512, 34816)],
                         ids=["decode", "prefill_2048", "prefill_512"])
def test_sparse_threshold_kernel_compiles_at_the_cells_shapes(one_chip, compile_for_chip,
                                                              monkeypatch, rows, width):
    """``ops/sparse_attention.kth_largest_key`` at the rows and padded widths
    the cell's decode step and prefill buckets hand it (``topk`` 2048): Mosaic
    takes the kernel with its whole row tile, double-buffered, inside the
    default scoped VMEM (the call raises no limit, so a tile over it is
    refused here), nothing pads or copies the keys on the way in, and the
    program holds no sort."""
    import re

    from accelerate_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(sa, "_on_tpu", lambda: True)   # the kernel, not its interpreter
    text = compile_for_chip(lambda keys, kv_len: sa.kth_largest_key(keys, 2048, kv_len),
                            ((rows, width), jnp.int32), ((), jnp.int32))
    assert len(re.findall(r"%sparse_threshold\S* = .* custom-call\(", text)) == 1
    assert not re.search(rf"= s32\[{rows},{width}\]\S* (copy|pad|fusion)\(", text)
    assert not re.search(r" (sort|topk)\(", text)


# -- K-EXAONE's serving programs at the cell's shapes (k-exaone.serve_reason) -------------

EXAONE_SLOTS, EXAONE_PAGES, EXAONE_PAGES_PER_SLOT = 64, 18432, 288


@pytest.mark.parametrize("program,width", [("decode", 1), ("prefill", 512), ("prefill", 2048)])
def test_k_exaone_serving_programs_compile_at_the_cells_shapes(one_chip, monkeypatch, program,
                                                               width):
    """The engine's decode and prefill programs of ``models/k_exaone.py`` at
    the published widths, the cell's share (8 of 64 heads, 1 of 8 KV heads,
    16 of 128 experts, 19,200 vocabulary rows) and geometry (64 slots, 18,432
    pages of 64, 288 a slot), one period deep (``LLLG``: the dense layer,
    three sparse ones, window and full attention): the grouped matmuls are
    the Mosaic kernel over BLOCKS of held rows (never ``N x k``), no op
    copies or relays a whole page pool or a whole ring (both are written in
    the layout the reads use), a decode step walks the full-attention layer's
    K and V pools inside the ``paged_walk_decode`` Mosaic kernel, one call a
    layer, and gathers no block of pages; a prefill chunk gathers them."""
    import re

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import KExaoneConfig, KExaoneForCausalLM
    from accelerate_tpu.ops import page_walk as pw
    from accelerate_tpu.serving.engine import fresh_engine_jits

    monkeypatch.setattr(pw, "_on_tpu", lambda: True)   # the kernel, not its interpreter
    model = KExaoneForCausalLM(KExaoneConfig(
        num_hidden_layers=4, experts_held=tuple(range(16)), attention_heads_held=8,
        key_value_heads_held=1, vocab_held=19200))
    on_chip = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))), BF16)
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(EXAONE_PAGES, PAGE, EXAONE_SLOTS, EXAONE_PAGES_PER_SLOT)))
    gen = GenerationConfig(max_new_tokens=2048, do_sample=False, eos_token_id=None)
    decode, prefill, *_ = fresh_engine_jits(model, gen, PAGE)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode":
        lowered = decode.lower(params, cache, arg((EXAONE_SLOTS,)), arg((EXAONE_SLOTS,), jnp.bool_),
                               arg((2,), jnp.uint32))
    else:
        lowered = prefill.lower(params, cache, arg(()), arg((width,)), arg(()), arg(()))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 9 and "tpu_custom_call" in text   # gate, up, down x 3 sparse layers
    rows = {1: 128, 512: 640, 2048: 2560}[width]     # held_row_block: a quarter over an eighth of the pairs
    assert re.search(rf"%ragged-dot\S* = \S*\[{rows},", text)               # a block of held rows
    assert not re.search(rf"\[{max(width, EXAONE_SLOTS) * 8},(6144|2048)\]", text)   # never all N x k rows
    # no pool- or ring-shaped relayout: a copy, or a transpose that permutes anything
    moved = r"(copy\(|transpose\([^)]*\), dimensions=\{(?!0,1,2\}))"
    assert re.findall(rf"= bf16\[{EXAONE_PAGES},64,128\]\S* {moved}", text) == []
    assert re.findall(rf"= bf16\[{EXAONE_SLOTS},128,128\]\S* {moved}", text) == []
    gathered = re.findall(r"= bf16\[((?:\d+,)*\d+,64,128)\]\S* gather\(", text)   # blocks of pages of rows
    assert bool(gathered) == (program == "prefill"), gathered
    kernels = re.findall(r"%paged_walk_decode\S* = bf16\[64,8,128\]\S* custom-call\(", text)
    assert len(kernels) == (1 if program == "decode" else 0)       # one walk a full-attention layer
    assert ("global_attend/while" in text) == (program == "prefill")   # the XLA walk: the chunk's alone
    stats = compiled.memory_analysis()
    pool, rings = 2 * EXAONE_PAGES * PAGE * 128 * 2, 3 * 2 * EXAONE_SLOTS * 128 * 128 * 2
    assert stats.alias_size_in_bytes >= pool + rings                         # both kinds alias in place
    assert stats.temp_size_in_bytes < 2**30


# -- JoyAI-LLM-Flash's serving programs at the cell's shapes (joyai-flash.serve_docs) -------------

JOYAI_SLOTS, JOYAI_PAGES, JOYAI_PAGES_PER_SLOT = 48, 12672, 264


@pytest.mark.parametrize("program,width", [("decode", 1), ("prefill", 512), ("prefill", 2048)])
def test_joyai_flash_serving_programs_compile_at_the_cells_shapes(one_chip, monkeypatch, program,
                                                                  width):
    """The engine's decode and prefill programs of ``models/joyai_flash.py`` at
    the published widths, the cell's share (4 of 32 heads, 32 of 256 experts,
    16,160 vocabulary rows) and geometry (48 slots, 12,672 pages of 64, 264 a
    slot), the dense layer and one sparse one: the latent pool ``[P, 64,
    640]`` is written and read in one layout (no op copies or relays it, and
    it aliases in place), a decode step walks it inside the ``latent_decode``
    Mosaic kernel, one call a layer (the absorbed walk: no block of rows is
    gathered and no per-head key or value is built), a prefill chunk gathers
    blocks of whole latent rows and up-projects each (the expanded walk), and
    the grouped matmuls are the Mosaic kernel over BLOCKS of held rows."""
    import re

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import JoyAIFlashConfig, JoyAIFlashForCausalLM
    from accelerate_tpu.ops import page_walk as pw
    from accelerate_tpu.serving.engine import fresh_engine_jits

    monkeypatch.setattr(pw, "_on_tpu", lambda: True)   # the kernel, not its interpreter
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        num_hidden_layers=2, experts_held=tuple(range(32)), attention_heads_held=4,
        vocab_held=16160))
    assert model.config.latent_row == 640
    on_chip = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))), BF16)
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(JOYAI_PAGES, PAGE, JOYAI_SLOTS, JOYAI_PAGES_PER_SLOT)))
    gen = GenerationConfig(max_new_tokens=512, do_sample=False, eos_token_id=None)
    decode, prefill, *_ = fresh_engine_jits(model, gen, PAGE)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode":
        lowered = decode.lower(params, cache, arg((JOYAI_SLOTS,)), arg((JOYAI_SLOTS,), jnp.bool_),
                               arg((2,), jnp.uint32))
    else:
        lowered = prefill.lower(params, cache, arg(()), arg((width,)), arg(()), arg(()))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3 and "tpu_custom_call" in text   # gate, up, down of the sparse layer
    rows = {1: 128, 512: 640, 2048: 2560}[width]     # held_row_block: a quarter over an eighth of the pairs
    assert re.search(rf"%ragged-dot\S* = \S*\[{rows},", text)               # a block of held rows
    assert not re.search(rf"\[{max(width, JOYAI_SLOTS) * 8},2048\]", text)          # never all N x k rows
    # no pool-shaped relayout: a copy, or a transpose that permutes anything
    moved = r"(copy\(|transpose\([^)]*\), dimensions=\{(?!0,1,2\}))"
    assert re.findall(rf"= bf16\[{JOYAI_PAGES},64,640\]\S* {moved}", text) == []
    gathered = re.findall(r"= bf16\[((?:\d+,)?64,64,640)\]\S* gather\(", text)   # blocks of 64 pages of rows
    assert set(gathered) == (set() if program == "decode" else {"64,64,640"}), gathered
    kernels = re.findall(r"%latent_decode\S* = bf16\[48,4,512\]\S* custom-call\(", text)
    assert len(kernels) == (2 if program == "decode" else 0)       # one walk a layer, in the decode step alone
    expanded = re.findall(r"latent_prefill/while/body/bsr,rhd->bshd", text)
    assert bool(expanded) == (program == "prefill")          # W_kvb meets the rows in the chunk's walk only
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 2 * JOYAI_PAGES * PAGE * 640 * 2     # the pools alias in place
    assert stats.temp_size_in_bytes < 2**30


# -- Qwen3-Next's serving programs at the cell's shapes (qwen3-next.serve_assist) -------------

QWEN_SLOTS, QWEN_PAGES, QWEN_PAGES_PER_SLOT = 128, 36864, 288


@pytest.mark.parametrize("program,width", [("decode", 1), ("prefill", 512), ("prefill", 2048)])
def test_qwen3_next_serving_programs_compile_at_the_cells_shapes(one_chip, monkeypatch, program,
                                                                 width):
    """The engine's decode and prefill programs of ``models/qwen3_next.py`` at
    the published widths, the cell's share (4 of 16 heads, 1 of 2 KV heads, 4
    of 16 Gated DeltaNet key heads and 8 of 32 value heads, 128 of 512
    experts, 37,984 vocabulary rows) and geometry (128 slots, 36,864 pages of
    64, 288 a slot), one period deep (linear x 3, full): the recurrent state
    ``[128, 8, 128, 128]`` float32, the conv windows and the page pools all
    alias in place; a decode step updates each Gated DeltaNet layer's state
    inside ONE ``gated_delta_step`` Mosaic kernel (no ``while`` under
    ``linear_attend``, no copy or relayout of a state) and walks the
    full-attention layer's pools (head_dim 256) inside one
    ``paged_walk_decode`` kernel; a prefill chunk runs the chunked form (one
    scan over the blocks a layer) and gathers blocks of pages; the grouped
    matmuls are the Mosaic kernel over BLOCKS of held rows."""
    import re

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    from accelerate_tpu.ops import gated_delta as gd
    from accelerate_tpu.ops import page_walk as pw
    from accelerate_tpu.serving.engine import fresh_engine_jits

    monkeypatch.setattr(pw, "_on_tpu", lambda: True)   # the kernels, not their interpreter
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        num_hidden_layers=4, experts_held=tuple(range(128)), attention_heads_held=4,
        key_value_heads_held=1, linear_key_heads_held=4, linear_value_heads_held=8,
        vocab_held=37984))
    on_chip = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))), BF16)
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(QWEN_PAGES, PAGE, QWEN_SLOTS, QWEN_PAGES_PER_SLOT)))
    gen = GenerationConfig(max_new_tokens=2048, do_sample=False, eos_token_id=None)
    decode, prefill, *_ = fresh_engine_jits(model, gen, PAGE)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode":
        lowered = decode.lower(params, cache, arg((QWEN_SLOTS,)), arg((QWEN_SLOTS,), jnp.bool_),
                               arg((2,), jnp.uint32))
    else:
        lowered = prefill.lower(params, cache, arg(()), arg((width,)), arg(()), arg(()))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 12 and "tpu_custom_call" in text   # gate, up, down x 4 layers
    rows = {1: 512, 512: 1664, 2048: 6400}[width]    # held_row_block: a quarter over a quarter of the pairs
    assert re.search(rf"%ragged-dot\S* = \S*\[{rows},", text)               # a block of held rows
    assert not re.search(rf"\[{max(width, QWEN_SLOTS) * 10},2048\]", text)  # never all N x k rows
    steps = re.findall(r"%gated_delta_step\S* = ", text)
    walks = re.findall(r"%paged_walk_decode\S* = bf16\[128,4,256\]\S* custom-call\(", text)
    assert (len(steps), len(walks)) == ((3, 1) if program == "decode" else (0, 0))
    assert "linear_attend/while" not in text                     # the step: one kernel, no loop around it
    assert ("linear_chunk/while" in text) == (program == "prefill")    # the chunk: a scan over its blocks
    # ... in plain XLA: the rule's kernels are the differentiated path's alone (a start pays no Mosaic lowering for them)
    assert [line for line in text.splitlines() if "tpu_custom_call" in line and "linear_chunk" in line] == []
    assert ("global_attend/while" in text) == (program == "prefill")   # the XLA walk: the chunk's alone
    # no state- or pool-shaped relayout: a copy, or a transpose that permutes anything
    moved = r"(copy\(|transpose\([^)]*\), dimensions=\{(?!0,1,2(,3)?\}))"
    assert re.findall(rf"= f32\[{QWEN_SLOTS},8,128,128\]\S* {moved}", text) == []
    assert re.findall(rf"= bf16\[{QWEN_PAGES},64,256\]\S* {moved}", text) == []
    stats = compiled.memory_analysis()
    pools = 2 * QWEN_PAGES * PAGE * 256 * 2
    state, conv = 3 * QWEN_SLOTS * 8 * 128 * 128 * 4, 3 * QWEN_SLOTS * 3 * 2048 * 2
    assert stats.alias_size_in_bytes >= pools + state + conv     # all three kinds alias in place
    assert stats.temp_size_in_bytes < 2**30


def test_olmo_hybrids_linear_layer_trains_through_the_two_walks_kernels(one_chip, compile_for_chip, monkeypatch):
    """A Gated DeltaNet layer of ``models/olmo_hybrid.py`` at the published
    widths (30 value heads, ``Dk`` 96, ``Dv`` 192, hidden 3,840), forward and
    backward over the cell's 1 x 8,192 tokens under the model's ``remat``
    (all made again but ``T`` and ``A``, saved by name): the rule's forward
    rule runs TWICE (the first pass and the recompute: jax runs a
    ``custom_vjp``'s forward rule, not its primal, wherever the call is being
    differentiated) and its backward once, each ONE Mosaic kernel a segment
    inside ``_chunk``'s loop over the four segments, all under the
    ``linear_chunk`` scope that the cell's two metrics read; no other loop is
    left there (the 32-step scans over the blocks are gone), and both kernels
    fit the scoped VMEM (Mosaic refuses at compile time).

    Around the rule (PR 46, ``ops/delta_mixer.py``) the conv + silu + L2 norm
    and the gated norm are ONE kernel each way each: ``linear_conv_fwd`` and
    ``linear_out_fwd`` twice (the first pass, the recompute), ``linear_conv_bwd``
    and ``linear_out_bwd`` once, every one under the scope word that
    ``linear_project_device_ms.train`` reads, the gradient's too; beside them
    the compiler leaves under those two words no fusion over the rows, only
    the relayouts of ``o`` and ``d(o)`` between the rule and the gate's kernel.  What every start pays for them is
    guarded without a clock: the program's StableHLO is at most 1.5 x the
    196,725 bytes it had before the passes were kernels, and TWO such layers
    lower each pass's body as often as one does (a ``jit`` a pass: one
    function, called from each layer)."""
    import re

    from accelerate_tpu.models import OlmoHybridConfig
    from accelerate_tpu.models.olmo_hybrid import OlmoHybridGatedDeltaNet
    from accelerate_tpu.ops import delta_mixer
    from accelerate_tpu.ops import gated_delta as gd

    del compile_for_chip                                # the compile cache off, as for every compile here
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    monkeypatch.setattr(delta_mixer, "_on_tpu", lambda: True)
    cfg = OlmoHybridConfig.olmo_hybrid_7b(dtype=BF16)
    assert (cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim) == (30, 96, 192)
    layer = OlmoHybridGatedDeltaNet(cfg)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.float32, sharding=one_chip)
    params = on_chip(jax.eval_shape(layer.init, jax.random.key(0), x))
    mixed = jax.checkpoint(layer.apply, policy=jax.checkpoint_policies.save_only_these_names(gd.KEPT_ACROSS_REMAT))
    loss = lambda p, x: jnp.sum(jnp.square(mixed(p, x)))                 # the layers behind it read its output
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
    bodies = lambda text: {name: text.count(f'kernel_name = "{name}"') for name in (
        "linear_conv_fwd", "linear_conv_bwd", "linear_out_fwd", "linear_out_bwd")}
    stablehlo = lowered.as_text()
    assert len(stablehlo) <= 1.5 * 196_725, len(stablehlo)
    assert bodies(stablehlo) == {"linear_conv_fwd": 2, "linear_conv_bwd": 1, "linear_out_fwd": 2, "linear_out_bwd": 1}
    two = lambda p, q, x: jnp.sum(jnp.square(mixed(q, x + mixed(p, x))))
    assert bodies(jax.jit(jax.grad(two, argnums=(0, 1))).lower(params, params, x).as_text()) == bodies(stablehlo)

    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(_custom_calls(text, "linear_chunk_fwd")) == 2 and len(_custom_calls(text, "linear_chunk_bwd")) == 1
    for scope, counts in (("linear_conv", (2, 1)), ("linear_out", (2, 1))):
        calls = [_custom_calls(text, f"{scope}_{way}") for way in ("fwd", "bwd")]
        assert tuple(len(c) for c in calls) == counts, (scope, calls)
        assert all(re.search(rf"/{scope}/", line) for c in calls for line in c)
    assert len(kernels) == 9
    assert sum(bool(re.search(r"\blinear_chunk\b", line)) for line in kernels) == 3
    # beside the kernels, no fusion over the rows carries the two words (the passes' arithmetic is all inside);
    # what does is the relayout of ``o`` into the array the gate's kernel reads, twice, and of ``d(o)`` out of it
    named = re.findall(r'^\s+%?\S+ = \S+\[1,8192,\S+ (fusion|copy)\(.*op_name="[^"]*/(?:linear_conv|linear_out)/',
                       text, re.M)
    assert named.count("fusion") == 0 and named.count("copy") <= 3, named
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert len(loops) == 3 and all("linear_chunk" in name and name.count("while") == 1 for name in loops), loops
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


def test_the_walks_kernels_under_a_four_chip_mesh(topo, compile_for_chip, monkeypatch):
    """The rule's forward + backward over ``topo.devices`` as ``dp_shard`` 4,
    four rows of the cell's heads: GSPMD cannot partition a Mosaic call, so
    each walk goes manual over the batch axes (``per_shard``; the rows were
    folded into the heads rows first) and every device walks its own row's
    30 heads.  Invisible on the CPU mesh, where interpreted kernels are plain XLA."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.ops import gated_delta as gd

    del compile_for_chip
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, devices=list(topo.devices)))
    rows = NamedSharding(acc.mesh, P("dp_shard"))
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rows)
    b, t, hv, dk, dv = 4, 256, 30, 96, 192
    loss = lambda *a: jnp.sum(jnp.square(gd.gated_delta_chunk(*a)[0]))
    text = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        arg(b, t, hv, dk), arg(b, t, hv, dk), arg(b, t, hv, dv), arg(b, t, hv), arg(b, t, hv),
        arg(b, hv, dk, dv)).compile().as_text()
    assert len(_custom_calls(text, "linear_chunk_fwd")) == 1 and len(_custom_calls(text, "linear_chunk_bwd")) == 1
    assert re.search(r"%linear_chunk_fwd\S* = \(f32\[30,4,96,192\]", text)      # one row's heads a device
    assert "all-gather" not in text and "all-to-all" not in text           # no row leaves its device


def test_the_mixers_passes_under_a_four_chip_mesh(topo, compile_for_chip, monkeypatch):
    """The two passes around the rule (``ops/delta_mixer.py``), forward +
    backward over ``topo.devices`` as ``dp_shard`` 4, four rows at the cell's
    widths: each launch goes manual over the batch axes (``per_shard``), a
    device runs the kernels on its own row, no row leaves its device, and the
    sums over the rows (the taps' gradients, the norm's scale's) are added up
    outside the kernels: one all-reduce each, of ``[4, C]`` and ``[1, C]``."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.ops import delta_mixer

    del compile_for_chip
    monkeypatch.setattr(delta_mixer, "_on_tpu", lambda: True)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, devices=list(topo.devices)))
    rows, whole = NamedSharding(acc.mesh, P("dp_shard")), NamedSharding(acc.mesh, P())
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rows if len(shape) == 3 else whole)
    b, t, heads, dk, dv = 4, 256, 30, 96, 192

    def loss(q, k, v, q_taps, k_taps, v_taps, z, weight):
        q, k, v = delta_mixer.conv_silu_l2norm(q, k, v, q_taps, k_taps, v_taps, heads)
        gated = delta_mixer.gated_rmsnorm(v, z, weight, 1e-6, BF16)
        return jnp.sum(jnp.square(q)) + jnp.sum(jnp.square(k)) + jnp.sum(jnp.square(gated.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=range(8))).lower(
        arg(b, t, heads * dk), arg(b, t, heads * dk), arg(b, t, heads * dv), arg(4, heads * dk), arg(4, heads * dk),
        arg(4, heads * dv), arg(b, t, heads * dv), arg(dv)).compile().as_text()
    for name in ("linear_conv_fwd", "linear_conv_bwd", "linear_out_fwd", "linear_out_bwd"):
        assert len(_custom_calls(text, name)) == 1, name
    assert re.search(r"%linear_conv_bwd\S* = \(f32\[1,256,2880\]", text)        # one row a device
    assert "all-gather" not in text and "all-to-all" not in text                # no row leaves its device
