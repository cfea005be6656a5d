"""Compile the main path's jitted steps and every Pallas kernel for a
DESCRIBED TPU v5e, at the widths the shipped configurations give them.

The chip's compiler is installed here and compiles for a chip that is not
attached (``/opt/skills/guides/on-chip-measurement`` section 2): what it
refuses — a block the TPU lowering cannot tile, a program that does not fit
the chip's memory — fails here, at no chip time. Nothing runs, so nothing
here is a result or a time. Kernels are called with ``interpret=False``
because ``jax.default_backend()`` still says ``cpu`` in this process.

This is the only file of its kind: one process at a time may load the TPU
library, and the worker that gets this file keeps it. The topology is
described inside a fixture — never at import, in a ``skipif`` or in a
``parametrize`` — so every xdist worker collects the same tests.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32

# GPT2_MEDIUM as TPUDecoderChat(continuous=True, n_slots=16) sizes it:
# prompt bucket 512 + 64 new + (pipeline_depth 4 + 1) x 16 chunk steps
N_SLOTS, CACHE_LEN, CHUNK_STEPS, PREFILL_CHUNK = 16, 656, 16, 64
HEADS, HEAD_DIM = 16, 64
PAGED_BLOCK, PAGED_CACHE_LEN = 64, 704  # cache_len rounded up to blocks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an argument placed on one described chip,
    with the persistent compile cache off around the whole module (a
    compile for a described chip is written but can never be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _is_mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _placed(shape, tree):
    return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)


@pytest.fixture(scope="module")
def medium(shape):
    """(cfg, params, dense pool) of the GPT2_MEDIUM continuous server."""
    from pathway_tpu.models import decoder as D

    cfg = D.GPT2_MEDIUM
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(
        lambda: D.pool_init(None, cfg, N_SLOTS, CACHE_LEN))
    return cfg, _placed(shape, params), _placed(shape, pool)


def test_gpt2_medium_decode_chunk(shape, medium):
    from pathway_tpu.models import decoder as D

    cfg, params, pool = medium
    c = _compile(
        lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                CHUNK_STEPS),
        params, pool, shape((N_SLOTS,), jnp.bool_), shape((2,), jnp.uint32),
        donate_argnums=(1,),
    )
    m = c.memory_analysis()
    # one program's footprint, well inside one v5e chip's 16 GB
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 8 << 30


def test_gpt2_medium_spec_decode(shape, medium):
    """The step a default (greedy) server actually dispatches: spec decode
    is on by default, 4 cycles of 3 drafts from the first 6 layers."""
    from pathway_tpu.models import decoder as D

    cfg, params, pool = medium
    _compile(
        lambda p, pl, a: D.pool_decode_spec(
            p, pl, a, cfg, CHUNK_STEPS // 4, draft_layers=cfg.layers // 4,
            n_spec=3),
        params, pool, shape((N_SLOTS,), jnp.bool_), donate_argnums=(1,),
    )


def test_gpt2_medium_prefill_chunk(shape, medium):
    from pathway_tpu.models import decoder as D

    cfg, params, pool = medium
    piece = shape((1, PREFILL_CHUNK), I32)
    _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=True),
        params, piece, piece, piece, pool, shape((), I32), shape((), I32),
        shape((1,), I32), donate_argnums=(4,),
    )


def test_minilm_embed_fn(shape):
    from pathway_tpu.models import MINILM_L6
    from pathway_tpu.models import embedder as E
    from pathway_tpu.models import transformer as T

    params = jax.eval_shape(lambda: E.cast_params_for_inference(
        T.init_params(jax.random.PRNGKey(0), MINILM_L6), MINILM_L6))
    ids = shape((256, 128), I32)
    _compile(lambda p, i, m: E.embed_fn(p, i, m, MINILM_L6),
             _placed(shape, params), ids, ids)


def test_minilm_rerank_batch_of_the_long_cell(shape):
    """384 pairs of 423 tokens, the long-context cell's rerank batch, in
    their bucket of 512 tokens: the reranker bounds its own dispatch
    (``cross_encoder._MAX_SCORE_BYTES``) and sends three of 128 pairs,
    each dense: one layer's float32 scores are 1.6 GB, as the accepted
    cells' 512 pairs of 256 tokens are, where 512 x 512 would be 6.4 GB
    beside 9 GB of decoder."""
    import dataclasses

    from pathway_tpu.models import MINILM_L6
    from pathway_tpu.models import transformer as T
    from pathway_tpu.models.cross_encoder import CrossEncoderModel

    cfg = dataclasses.replace(MINILM_L6, dtype=BF16)
    m = CrossEncoderModel.__new__(CrossEncoderModel)
    m.cfg, m.flash_prefill = cfg, False
    assert [len(r) for r in m._dispatch_rows(384, 423)] == [128] * 3
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    ids = shape((128, 512), I32)
    c = _compile(lambda p, i, m, t: T.encode(p, i, m, cfg, t),
                 _placed(shape, params), ids, ids, ids)
    assert not _is_mosaic(c)
    assert c.memory_analysis().temp_size_in_bytes < 4 << 30


def test_knn_search_1m_rows(shape):
    from pathway_tpu.ops import knn as K

    n = 1 << 20
    _compile(
        lambda c, v, q: K._search_kernel(c, v, q, 10, "cos",
                                         normalize=True),
        shape((n, 384), BF16), shape((n,), jnp.bool_), shape((16, 384), F32),
    )


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_paged_attn_decode_kernel(shape, kv_int8):
    from pathway_tpu.models.paged_attention import paged_attn_decode

    m = PAGED_CACHE_LEN // PAGED_BLOCK
    nb = N_SLOTS * m + 1
    plane = shape((nb, HEADS, PAGED_BLOCK, HEAD_DIM), I8 if kv_int8 else BF16)
    scale = shape((nb, HEADS, PAGED_BLOCK, 1), F32) if kv_int8 else None
    c = _compile(
        lambda q, kb, vb, ks, vs, t, msk: paged_attn_decode(
            q, kb, vb, ks, vs, t, msk, interpret=False),
        shape((N_SLOTS, HEADS, HEAD_DIM), BF16), plane, plane, scale, scale,
        shape((N_SLOTS, m), I32), shape((N_SLOTS, PAGED_CACHE_LEN), I32),
    )
    assert _is_mosaic(c)


@pytest.mark.parametrize(
    "dims,causal",
    [((8, HEADS, 512, HEAD_DIM), True),   # decoder whole-prompt prefill
     ((256, 12, 128, 32), False)],        # MiniLM encoder, head_dim 32
    ids=["decoder-hd64", "encoder-hd32"],
)
def test_flash_attn_kernel(shape, dims, causal):
    from pathway_tpu.models.flash_attention import flash_attn

    qkv = shape(dims, BF16)
    c = _compile(
        lambda q, k, v, m: flash_attn(q, k, v, m, causal=causal,
                                      interpret=False),
        qkv, qkv, qkv, shape((dims[0], dims[2]), I32),
    )
    assert _is_mosaic(c)


def test_flash_chunk_attn_kernel(shape):
    """Chunk-vs-cache over a dense int8 row of the default cache length
    (656 has no 8-aligned divisor near 128: the tile must be derived)."""
    from pathway_tpu.models.flash_attention import flash_chunk_attn

    row = shape((HEADS, CACHE_LEN, HEAD_DIM), I8)
    scale = shape((HEADS, CACHE_LEN, 1), F32)
    c = _compile(
        lambda q, k, v, kc, s, ks, vs: flash_chunk_attn(
            q, k, v, kc, s, k_scale=ks, v_scale=vs, interpret=False),
        shape((HEADS, PREFILL_CHUNK, HEAD_DIM), BF16), row, row,
        shape((CACHE_LEN,), I32), shape((), I32), scale, scale,
    )
    assert _is_mosaic(c)


@pytest.mark.parametrize(
    "rows,window", [(8304, 0), (4352 + 512, 4096)],
    ids=["full-row-8304", "window-ring-4352+512"],
)
def test_flash_chunk_attn_kernel_at_the_answer_cells_widths(
        shape, rows, window):
    """Trinity's attention as ``trinity_rag_answer_closed16`` runs it: 48
    query heads over 8 key-value heads of 128, a piece of 512, against a
    full layer's row (8,304: no MXU-sized divisor, so its last tile is
    ragged) and a window layer's ``[ring | own]``, bfloat16."""
    from pathway_tpu.models.flash_attention import flash_chunk_attn

    row = shape((8, rows, 128), BF16)
    c = _compile(
        lambda q, k, v, kc, s: flash_chunk_attn(
            q, k, v, kc, s, window=window, interpret=False),
        shape((48, 512, 128), BF16), row, row, shape((rows,), I32),
        shape((), I32),
    )
    assert _is_mosaic(c)


def test_trinity_width_prefill_piece(shape, monkeypatch):
    """The whole prefill piece of the answer cell (the configuration the
    benchmark runs, 16 slots of 8,304 columns, a piece of 512) with the
    blockwise read its shapes choose: the kernel is in the program, and the
    piece's temporaries are under the dense read's 1.84 GB (``PERF.md``
    section 4 has both)."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest as M

    from pathway_tpu.models import decoder as D

    # this process's default backend is the CPU: steer the kernel's own
    # choice of the interpreter here, in the test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(bench, "configs",
                           "trinity-large-ep8-rag.json")) as f:
        model = json.load(f)["models"]["decoder"]
    cfg = M.resolve(M.load_manifest(), "layouts", "afmoe").program_config(
        model)
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, 16, 8304))
    assert D.pool_ring(pool) == 4352
    piece = shape((1, 512), I32)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=False),
        _placed(shape, params), piece, piece, piece, _placed(shape, pool),
        shape((), I32), shape((), I32), shape((1,), I32),
        donate_argnums=(4,),
    )
    assert _is_mosaic(c)
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1_400_000_000
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16 << 30


LATENT_COLUMNS = 16384 + 64 + 5 * 16     # the long-context cell's slot row


@pytest.mark.parametrize("piece", [512, 1024, 2048])
def test_flash_chunk_attn_latent_kernel_at_the_long_cells_widths(
        shape, piece):
    """Latent attention as ``deepseek_v2_rag_long_closed8`` runs it: 128
    query heads of 128 + 64 over ONE row of 16,528 x 576 latent values, a
    head's 512 x 256 of ``W_UKV`` beside it: a key of 192 (two dots) and a
    value of 128 made inside the kernel's walk from blocks of the row
    TRANSPOSED (576 x 512 columns), the last tile ragged."""
    from pathway_tpu.models.flash_attention import flash_chunk_attn_latent

    c = _compile(
        lambda q, row, w, kc, s: flash_chunk_attn_latent(
            q, row, w, kc, s, nope=128, sm_scale=0.1147, interpret=False),
        shape((128, piece, 192), BF16), shape((576, LATENT_COLUMNS), BF16),
        shape((512, 128, 256), BF16), shape((LATENT_COLUMNS,), I32),
        shape((), I32),
    )
    assert _is_mosaic(c)


@pytest.fixture(scope="module")
def deepseek(shape):
    """(cfg, params, pool) of the long-context cell: the configuration the
    benchmark runs, 8 slots of 16,528 columns, the prefix arena of the
    server's default 64 MB."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest as M

    from pathway_tpu.models import decoder as D

    with open(os.path.join(bench, "configs",
                           "deepseek-v2-ep8-rag.json")) as f:
        model = json.load(f)["models"]["decoder"]
    cfg = M.resolve(M.load_manifest(), "layouts",
                    "deepseek_v2").program_config(model)
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(lambda: D.pool_init(
        None, cfg, 8, LATENT_COLUMNS, arena_blocks=18, arena_block=512))
    return cfg, _placed(shape, params), _placed(shape, pool)


@pytest.mark.parametrize("piece", [512, 2048])
def test_deepseek_width_prefill_piece(shape, deepseek, monkeypatch, piece):
    """The whole prefill piece of the long-context cell with the blockwise
    read its shapes choose: the kernel is in the program, no per-head key
    or value of the slot's row among its temporaries (expanded, 16,528 x
    128 x 320 x 2 B would be 1.35 GB a layer), and weights, pool and
    temporaries inside the chip (``PERF.md`` section 4 has the numbers)."""
    from pathway_tpu.models import decoder as D

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, pool = deepseek
    assert D.blockwise_chunk_read(cfg.heads, piece, LATENT_COLUMNS)
    ids = shape((1, piece), I32)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=False),
        params, ids, ids, ids, pool, shape((), I32), shape((), I32),
        shape((1,), I32), donate_argnums=(4,),
    )
    assert _is_mosaic(c)
    m = c.memory_analysis()
    # 1.18 GB and 2.04 GB here: 0.76 GB of it one copy of the expert
    # layers' latent stack at the loop's exit (PERF.md section 7)
    assert m.temp_size_in_bytes < (1_400_000_000 if piece == 512
                                   else 2_400_000_000)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 11 << 30


def test_deepseek_width_decode_chunk(shape, deepseek):
    """The decode chunk of the long-context cell, 16 steps over 8 slots:
    every slot's latent rows read ABSORBED (scores of 8 x 128 x 16,528 in
    float32 are 68 MB); no per-head key or value of the pool among its
    temporaries (one layer's would be 10.8 GB)."""
    from pathway_tpu.models import decoder as D

    cfg, params, pool = deepseek
    c = _compile(
        lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                CHUNK_STEPS),
        params, pool, shape((8,), jnp.bool_), shape((2,), jnp.uint32),
        donate_argnums=(1,),
    )
    m = c.memory_analysis()
    # 3.34 GB here: the pool's two stacks once in the layout the loop
    # prefers and once back, and one layer's rows cut out and put back
    assert m.temp_size_in_bytes < 3_700_000_000
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 12 << 30
    # ... and the step a default (greedy) server dispatches first
    c = _compile(
        lambda p, pl, a: D.pool_decode_spec(
            p, pl, a, cfg, CHUNK_STEPS // 4, draft_layers=1, n_spec=3),
        params, pool, shape((8,), jnp.bool_), donate_argnums=(1,),
    )
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13 << 30


def test_flash_chunk_attn_paged_kernel(shape):
    from pathway_tpu.models.flash_attention import flash_chunk_attn_paged

    m = PAGED_CACHE_LEN // PAGED_BLOCK
    plane = shape((N_SLOTS * m + 1, HEADS, PAGED_BLOCK, HEAD_DIM), BF16)
    c = _compile(
        lambda q, kb, vb, t, kc, s: flash_chunk_attn_paged(
            q, kb, vb, None, None, t, kc, s, interpret=False),
        shape((HEADS, PREFILL_CHUNK, HEAD_DIM), BF16), plane, plane,
        shape((m,), I32), shape((PAGED_CACHE_LEN,), I32), shape((), I32),
    )
    assert _is_mosaic(c)


def test_wq_matmul_kernel(shape):
    """GPT2_MEDIUM's widest decode matmul: 16 rows x (1024 -> 4096)."""
    from pathway_tpu.models.wq_matmul import wq_matmul

    c = _compile(
        lambda x, w, s: wq_matmul(x, w, s, interpret=False),
        shape((N_SLOTS, 1024), BF16), shape((1024, 4096), I8),
        shape((1, 4096), F32),
    )
    assert _is_mosaic(c)


def test_sharded_ivf_exhaustive_search_four_chips(topo, shape):
    """The index the KNN factories build under the mesh flag, at 1M rows a
    shard on four chips: exhaustive probing must not gather a per-query
    copy of the shard (24 GiB; the compiler refused it before PR 22).
    ``shape`` is asked for only to keep the compile cache off."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pathway_tpu.parallel import sharded_ivf as SI
    from pathway_tpu.parallel.mesh import DATA_AXIS, TENSOR_AXIS, MeshRef

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), (DATA_AXIS, TENSOR_AXIS))
    rows, rep = NamedSharding(mesh, P(DATA_AXIS)), NamedSharding(mesh, P())
    cells, cap = 4 * 16, 131072  # 16 cells a shard, ~65k rows a cell
    c = SI._sharded_ivf_search.lower(
        jax.ShapeDtypeStruct((cells, cap, 384), BF16, sharding=rows),
        jax.ShapeDtypeStruct((cells, cap), jnp.bool_, sharding=rows),
        jax.ShapeDtypeStruct((cells, 384), F32, sharding=rows),
        jax.ShapeDtypeStruct((16, 384), F32, sharding=rep),
        k=10, nprobe=16, metric="cos", mesh_ref=MeshRef(mesh),
    ).compile()
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 4 << 30
