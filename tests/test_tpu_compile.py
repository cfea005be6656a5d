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


# ---- the slot pool is written where it lies (PR 33) ------------------------
#
# Read off the compiled text: every instruction OUTSIDE fused computations
# (the entry, a loop's body) whose result is a whole run array of the pool or
# one layer's rows of all slots. Such a result is 142 MB to 761 MB at the
# cells' widths, so each is a pass over HBM; the only ones a dispatch may
# hold are the writes of the carried buffer where it lies.

_SKIP = ("parameter", "get-tuple-element", "tuple", "bitcast", "while",
         "constant", "conditional", "call", "opt-barrier")


def _computations(text: str) -> dict:
    """name -> (is the entry, its instruction lines)."""
    import re

    out, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if m:
            cur = m.group(2)
            out[cur] = (bool(m.group(1)), [])
        elif line.startswith("}"):
            cur = None
        elif cur:
            out[cur][1].append(line)
    return out


def _dims(ty: str) -> list:
    import re

    return [tuple(int(x) for x in d.split(",") if x)
            for d in re.findall(r"\w+\[([\d,]*)\]", ty)]


def _whole_array_ops(text: str, stacks: list) -> list:
    """``(where, name, kind)`` of every instruction outside fused
    computations with a result as large as one of ``stacks`` (the pool's run
    arrays' shapes) or as one layer of one. ``where`` is ``"entry"`` or
    ``"loop"``; ``kind`` is ``"in place"`` for a ``dynamic-update-slice`` or
    ``scatter`` (bare, or the root of a fusion) whose operand is the buffer
    itself, else the opcode (a fusion's root's)."""
    import re

    whole = set()
    for d in stacks:
        for full in (tuple(d), tuple(d[1:])):
            whole |= {full, (1,) + full, tuple(x for x in full if x != 1)}
    comps = _computations(text)
    fused = {c.lstrip("%") for c in re.findall(r"calls=(%?[\w.\-]+)", text)}
    inst = re.compile(
        r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")

    def root_kind(comp: str) -> str:
        """What a fused computation's root (each element, if a tuple) is."""
        lines = comps[comp][1]
        by_name = {}
        for line in lines:
            m = inst.match(line)
            if m:
                by_name[m.group(1)] = (m.group(3), line)
        root = next(l for l in lines if l.lstrip().startswith("ROOT"))
        m = inst.match(root)
        roots = [m.group(1)]
        if m.group(3) == "tuple":
            roots = re.findall(r"%([\w.\-]+)", root.split("tuple(", 1)[1])
        kinds = set()
        for r in roots:
            op, line = by_name[r]
            if not any(d in whole for d in _dims(inst.match(line).group(2))):
                continue
            first = re.search(op + r"\(%([\w.\-]+)", line).group(1)
            while by_name.get(first, ("",))[0] == "bitcast":
                first = re.search(r"bitcast\(%([\w.\-]+)",
                                  by_name[first][1]).group(1)
            in_place = (op in ("dynamic-update-slice", "scatter")
                        and by_name.get(first, ("",))[0] == "parameter")
            kinds.add("in place" if in_place else op)
        return kinds.pop() if len(kinds) == 1 else "/".join(sorted(kinds))

    found = []
    for name, (entry, lines) in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = inst.match(line)
            if not m or m.group(3) in _SKIP:
                continue
            if not any(d in whole for d in _dims(m.group(2))):
                continue
            kind = m.group(3)
            if kind in ("dynamic-update-slice", "scatter"):
                kind = "in place"
            elif kind == "fusion":
                kind = root_kind(
                    re.search(r"calls=%?([\w.\-]+)", line).group(1))
            found.append(("entry" if entry else "loop", m.group(1), kind))
    return found


def _pool_stacks(pool) -> list:
    from pathway_tpu.models import decoder as D

    return [a.shape for a in D._kv_stacks(pool).values()]


def test_whole_array_ops_reads_the_compiled_text():
    """The guard's own reading, on a text with one of each: a copy and a
    cut-out layer are found, the writes in place are told apart, a small
    result and anything inside a fused computation are not listed."""
    text = """HloModule m
%fused_put (p0: bf16[2,4,1,64,8], p1: bf16[1,1,1,8,8]) -> bf16[2,4,1,64,8] {
  %p0 = bf16[2,4,1,64,8]{4,3,2,1,0} parameter(0)
  %p1 = bf16[1,1,1,8,8]{4,3,2,1,0} parameter(1)
  %c = s32[] constant(0)
  ROOT %dus = bf16[2,4,1,64,8]{4,3,2,1,0} dynamic-update-slice(%p0, %p1, %c, %c, %c, %c, %c)
}
%fused_cut (p0: bf16[2,4,1,64,8], p1: s32[]) -> bf16[4,64,8] {
  %p0 = bf16[2,4,1,64,8]{4,3,2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %c = s32[] constant(0)
  %ds = bf16[1,4,1,64,8]{4,3,2,1,0} dynamic-slice(%p0, %p1, %c, %c, %c, %c), dynamic_slice_sizes={1,4,1,64,8}
  ROOT %b = bf16[4,64,8]{2,1,0} bitcast(%ds)
}
%body (t: (bf16[2,4,1,64,8], s32[])) -> (bf16[2,4,1,64,8], s32[]) {
  %t = (bf16[2,4,1,64,8]{4,3,2,1,0}, s32[]) parameter(0)
  %kv = bf16[2,4,1,64,8]{4,3,2,1,0} get-tuple-element(%t), index=0
  %i = s32[] get-tuple-element(%t), index=1
  %new = bf16[1,1,1,8,8]{4,3,2,1,0} constant({...})
  %put = bf16[2,4,1,64,8]{4,3,2,1,0} fusion(%kv, %new), kind=kLoop, calls=%fused_put
  %cut = bf16[4,64,8]{2,1,0} fusion(%put, %i), kind=kLoop, calls=%fused_cut
  %small = bf16[1,64,8]{2,1,0} copy(%new)
  ROOT %out = (bf16[2,4,1,64,8]{4,3,2,1,0}, s32[]) tuple(%put, %i)
}
ENTRY %main (a: bf16[2,4,1,64,8]) -> bf16[2,4,1,64,8] {
  %a = bf16[2,4,1,64,8]{4,3,2,1,0} parameter(0)
  %there = bf16[2,4,1,64,8]{3,4,2,1,0} copy(%a)
  ROOT %back = bf16[2,4,1,64,8]{4,3,2,1,0} copy(%there)
}
"""
    assert _whole_array_ops(text, [(2, 4, 1, 64, 8)]) == [
        ("loop", "put", "in place"), ("loop", "cut", "bitcast"),
        ("entry", "there", "copy"), ("entry", "back", "copy")]


def _answer_cell(shape, config: str, layout: str, slots: int, columns: int,
                 **pool_kw):
    """(cfg, params, pool) of an answer cell, as shapes on the described
    chip: the configuration file the benchmark runs, through its layout."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest as M

    from pathway_tpu.models import decoder as D

    with open(os.path.join(bench, "configs", config)) as f:
        model = json.load(f)["models"]["decoder"]
    cfg = M.resolve(M.load_manifest(), "layouts", layout).program_config(
        model)
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(
        lambda: D.pool_init(None, cfg, slots, columns, **pool_kw))
    return cfg, _placed(shape, params), _placed(shape, pool)


@pytest.fixture(scope="module")
def trinity(shape):
    """The answer cell: 16 slots of 8,304 columns, window rings of 4,352."""
    from pathway_tpu.models import decoder as D

    cell = _answer_cell(shape, "trinity-large-ep8-rag.json", "afmoe", 16,
                        8304)
    assert D.pool_ring(cell[2]) == 4352
    return cell


def test_trinity_width_prefill_piece(shape, trinity, monkeypatch):
    """The whole prefill piece of the answer cell (the configuration the
    benchmark runs, 16 slots of 8,304 columns, a piece of 512) with the
    blockwise read its shapes choose: the kernel is in the program, and the
    piece holds NO whole key-value array among its temporaries (``PERF.md``
    section 4: 1.16 GB of them before PR 33, when every window layer's ring
    of all 16 slots was copied into the layout a scatter asks for and back,
    and a run of two layers cut out of its scanned stack and put back): all
    it does to a run array is write the piece's rows where they lie."""
    from pathway_tpu.models import decoder as D

    # this process's default backend is the CPU: steer the kernel's own
    # choice of the interpreter here, in the test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, pool = trinity
    piece = shape((1, 512), I32)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=False),
        params, piece, piece, piece, pool,
        shape((), I32), shape((), I32), shape((1,), I32),
        donate_argnums=(4,),
    )
    assert _is_mosaic(c)
    ops = _whole_array_ops(c.as_text(), _pool_stacks(pool))
    # the full layer's rows, each window layer's two stretches of its ring
    assert ops and {kind for _w, _n, kind in ops} == {"in place"}, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 44_000_000      # 39,547,392 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16 << 30


def test_trinity_width_decode_chunk(shape, trinity):
    """The decode chunk of the answer cell, 16 steps over 16 slots. The
    pool changes layout ONCE a chunk, at entry and at exit (a one-column
    write wants the rows outside the heads, a piece the heads outside the
    rows: 16 copies, pinned); inside a step every write is in place and the
    only other whole-array operation is ONE read of a layer's rows of all
    slots an array, in the run of two window layers (the loop's body holds
    it once for keys, once for values; a run of one is indexed statically
    and reads in place)."""
    from pathway_tpu.models import decoder as D

    cfg, params, pool = trinity
    c = _compile(
        lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                CHUNK_STEPS),
        params, pool, shape((16,), jnp.bool_), shape((2,), jnp.uint32),
        donate_argnums=(1,),
    )
    ops = _whole_array_ops(c.as_text(), _pool_stacks(pool))
    entry = [kind for where, _n, kind in ops if where == "entry"]
    step = [kind for where, _n, kind in ops
            if where == "loop" and kind != "in place"]
    assert entry == ["copy"] * 16, ops
    assert step == ["dynamic-slice"] * 2, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1_862_000_000   # 1,692,039,168 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16 << 30


LATENT_COLUMNS = 16384 + 64 + 5 * 16     # the long-context cell's slot row
# a piece's temporaries there: 97,279,488 and 486,489,088 bytes, + a tenth
PIECE_TEMP = {512: 107_000_000, 2048: 535_000_000}


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
    """The long-context cell: 8 slots of 16,528 columns, the prefix arena of
    the server's default 64 MB."""
    return _answer_cell(shape, "deepseek-v2-ep8-rag.json", "deepseek_v2", 8,
                        LATENT_COLUMNS, arena_blocks=18, arena_block=512)


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
    # the latent stacks ride the layer loop's carry: the piece's rows go in
    # where they lie and nothing else touches a whole stack (before PR 33:
    # one layer's rows of all 8 slots cut out and put back a layer, and the
    # 761 MB stack copied at the loop's exit)
    ops = _whole_array_ops(c.as_text(), _pool_stacks(pool))
    assert ops and {kind for _w, _n, kind in ops} == {"in place"}, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < PIECE_TEMP[piece]
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
    # the pool's two stacks once in the layout the loop prefers and once
    # back (4 copies, pinned); inside a step nothing but the write in place
    ops = _whole_array_ops(c.as_text(), _pool_stacks(pool))
    assert [kind for where, _n, kind in ops if where == "entry"] \
        == ["copy"] * 4, ops
    assert {kind for where, _n, kind in ops if where == "loop"} \
        == {"in place"}, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1_905_000_000   # 1,731,513,344 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 12 << 30
    # ... and the step a default (greedy) server dispatches first
    c = _compile(
        lambda p, pl, a: D.pool_decode_spec(
            p, pl, a, cfg, CHUNK_STEPS // 4, draft_layers=1, n_spec=3),
        params, pool, shape((8,), jnp.bool_), donate_argnums=(1,),
    )
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13 << 30


def test_ouro_width_piece_and_chunk(shape):
    """The looped cell (``ouro_rag_reason_closed4``: 4 slots of 656 columns,
    48 layers run four times, 192 cache layers of 2.06 GB an array): a piece
    of 256 and a chunk of 16 steps hold ONE pass body (two loops a piece,
    pass and layer; three a chunk), a piece writes every pass's rows in
    place and keeps 6.6 MB of temporaries, a chunk keeps the pool once more
    in the layout its loop prefers (4 copies at entry and exit, nothing
    whole inside a step: ``PERF.md`` section 4, ROADMAP S10), and either
    fits the chip beside 9.46 GB of weights and pool."""
    import re

    from pathway_tpu.models import decoder as D

    cfg, params, pool = _answer_cell(shape, "ouro-2.6b-rag.json", "ouro", 4,
                                     656)
    assert _pool_stacks(pool) == [(192, 4, 16, 656, 128)] * 2
    piece = shape((1, 256), I32)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=True),
        params, piece, piece, piece, pool,
        shape((), I32), shape((), I32), shape((1,), I32),
        donate_argnums=(4,),
    )
    text = c.as_text()
    ops = _whole_array_ops(text, _pool_stacks(pool))
    assert len(ops) == 2 and {kind for _w, _n, kind in ops} == {"in place"}, \
        ops
    assert len(re.findall(r"\bwhile\(", text)) == 2
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 7_300_000        # 6,564,864 + a tenth
    assert m.argument_size_in_bytes == pytest.approx(9_464_744_960, rel=1e-3)
    c = _compile(
        lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                CHUNK_STEPS),
        params, pool, shape((4,), jnp.bool_), shape((2,), jnp.uint32),
        donate_argnums=(1,),
    )
    text = c.as_text()
    ops = _whole_array_ops(text, _pool_stacks(pool))
    assert [kind for where, _n, kind in ops if where == "entry"] \
        == ["copy"] * 4, ops
    assert {kind for where, _n, kind in ops if where == "loop"} \
        == {"in place"}, ops
    assert len(re.findall(r"\bwhile\(", text)) == 3
    for scope in ("decoder.pass", "decoder.exit_gate", "decoder.attn.full"):
        assert scope in text
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 4_541_000_000    # 4,127,930,880 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14 << 30


def _instructions(text: str) -> int:
    import re

    return sum(1 for line in text.splitlines()
               if re.match(r"\s*(ROOT\s+)?%?[\w.\-]+\s*=", line))


@pytest.mark.parametrize("cell,lanes,temp", [
    ("trinity", 4, 124_000_000), ("deepseek", 2, 282_000_000)])
def test_the_grouped_piece_at_the_answer_cells_widths(
        shape, trinity, deepseek, monkeypatch, cell, lanes, temp):
    """The grouped piece the rule gives each cell (``decoder.
    prefill_group_width``: 4 lanes of 512 for Trinity's stage, 2 for
    DeepSeek-V2's) compiles with the kernel in it, writes every lane's rows
    in place and holds no whole key-value array, fits the chip beside its
    arguments (``PERF.md`` section 4: 112,550,912 and 256,144,384 bytes of
    temporaries), and is NOT unrolled by lane: it holds at most a quarter
    more instructions than the 1-wide last piece (the lanes' reads and
    writes are one loop a run of layers, whatever the width)."""
    from pathway_tpu.models import decoder as D

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, pool = trinity if cell == "trinity" else deepseek
    assert D.prefill_group_width(cfg, params, 512, pool) == lanes
    one = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=True),
        params, shape((1, 512), I32), shape((1, 512), I32),
        shape((1, 512), I32), pool, shape((), I32), shape((), I32),
        shape((1,), I32), donate_argnums=(4,))
    ids, vec = shape((lanes, 512), I32), shape((lanes,), I32)
    flag = shape((lanes,), jnp.bool_)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n, f, la, col: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=f, last=la, last_col=col),
        params, ids, ids, ids, pool, vec, vec, vec, flag, flag, vec,
        donate_argnums=(4,))
    assert _is_mosaic(c)
    text = c.as_text()
    ops = _whole_array_ops(text, _pool_stacks(pool))
    assert ops and {kind for _w, _n, kind in ops} == {"in place"}, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < temp
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16 << 30
    assert _instructions(text) <= 1.25 * _instructions(one.as_text())


# the lowered text of the looped cell's piece (a prompt's last) and chunk at
# PR 35's parent (df6e00a): where the rule gives one lane nothing changed
OURO_LOWERED = {"piece": "713a35529a604512", "chunk": "38bc835e9f91ddaf"}


def test_the_looped_cells_executables_are_the_parents(shape):
    """Ouro's pieces reach the ridge alone (256 FLOP a byte of its layers),
    so its server builds no grouped piece, and its 1-wide piece and chunk
    lower to the text they lowered to before the grouped piece existed."""
    import hashlib

    from pathway_tpu.models import decoder as D

    cfg, params, pool = _answer_cell(shape, "ouro-2.6b-rag.json", "ouro", 4,
                                     656)
    assert D.prefill_group_width(cfg, params, 256, pool) == 1
    ids = shape((1, 256), I32)
    one = shape((), I32)
    texts = {
        "piece": jax.jit(lambda p, i, pl, s: D.pool_prefill_chunk(
            p, i, i, i, pl, s, s, s[None], cfg, first=False, last=True),
            donate_argnums=(2,)).lower(params, ids, pool, one).as_text(),
        "chunk": jax.jit(lambda p, pl, a, k: D.pool_decode_chunk(
            p, pl, a, k, cfg, CHUNK_STEPS)).lower(
                params, pool, shape((4,), jnp.bool_),
                shape((2,), jnp.uint32)).as_text()}
    assert {k: hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in texts.items()} == OURO_LOWERED


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


@pytest.fixture(scope="module")
def kimi(shape):
    """The decode-heavy cell: 64 slots of 1,024 + 2,048 + 5 x 16 columns,
    six gated delta-rule layers' states beside two latent layers' rows."""
    cell = _answer_cell(shape, "kimi-linear-48b-ep8-rag.json", "kimi_linear",
                        64, 3152)
    from pathway_tpu.models import decoder as D

    assert D.pool_bytes(cell[2]) == 1_298_399_232
    return cell


def test_kimi_width_piece_and_decode_chunk(shape, kimi, monkeypatch):
    """A prefill piece of 512 (the chunked form of the recurrence in chunks
    of 64, the latent layers' blockwise read) and a decode chunk of 16 steps
    (the recurrence over every slot's float32 state) at the cell's widths:
    both compile for the chip, carry the states and rows in place (the
    chunk copies no run array whole inside its loop), and fit beside 5.5 GB
    of weights and pool (``PERF.md`` section 4: 101,329,920 and 605,176,320
    bytes of temporaries)."""
    from pathway_tpu.models import decoder as D

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, pool = kimi
    piece = shape((1, 512), I32)
    c = _compile(
        lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=False, last=True),
        params, piece, piece, piece, pool, shape((), I32), shape((), I32),
        shape((1,), I32), donate_argnums=(4,))
    assert _is_mosaic(c)
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 112_000_000        # 101,329,920 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14 << 30
    c = _compile(
        lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                CHUNK_STEPS),
        params, pool, shape((64,), jnp.bool_), shape((2,), jnp.uint32),
        donate_argnums=(1,))
    # the states and rows (the convolutions' tails, 4.7 MB a layer, are
    # small enough to move): inside the loop every slot's new states are
    # written where they lie (a run of one layer's into a buffer that takes
    # the old one's place), an idle lane's as an identity step, and no run
    # array is copied; the latent runs' rows are laid out the loop's way at
    # entry and back at exit, as every latent cell's are (ROADMAP S10)
    stacks = [a.shape for n, a in D._kv_stacks(pool).items()
              if not n.startswith("sc")]
    ops = _whole_array_ops(c.as_text(), stacks)
    assert {kind for where, _n, kind in ops if where == "loop"} \
        == {"in place", "add"}, ops
    assert [kind for where, _n, kind in ops if where == "entry"] \
        == ["copy"] * 4, ops
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 666_000_000        # 605,176,320 + a tenth
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14 << 30


# the lowered text (debug info left out) of the accepted answer cells'
# executables at their widths, at commit 1b7ca61: a first, middle
# and last piece, the grouped piece, the decode chunks of 16, 8 and 4 steps,
# one-shot admissions alone and in pairs at two buckets, DeepSeek-V2's
# speculative cycle: what a layout without a recurrent state runs did not
# change a byte
OTHER_LOWERED = {
    "deepseek.admit256": "07d3db582ba352a7",
    "deepseek.admit8": "cd54addd89f06ee7",
    "deepseek.admit_batch256": "b2a5244dcb948a47",
    "deepseek.admit_batch8": "08973270b15322cc",
    "deepseek.chunk16": "dd412713ad1b1881",
    "deepseek.chunk4": "3fdb2e99ebe493c4",
    "deepseek.chunk8": "b2279964e5718106",
    "deepseek.group": "5853816cf9f998ad",
    "deepseek.piece00": "573105af4768467e",
    "deepseek.piece01": "b9d19678979020e5",
    "deepseek.piece10": "2a7e65b3163b9299",
    "deepseek.spec": "64c003dce663833d",
    "ouro.admit256": "88c59dd6bd66aaf1",
    "ouro.admit8": "b669e8f43973a8d0",
    "ouro.admit_batch256": "228cb8b796a88ad3",
    "ouro.admit_batch8": "6023dca7c22da0e7",
    "ouro.chunk16": "823118d091fbe84f",
    "ouro.chunk4": "d4105f34bea9fdb9",
    "ouro.chunk8": "fe26dea30ca81a19",
    "ouro.piece00": "09cbade67f5e001c",
    "ouro.piece01": "2937b00bd0af34bf",
    "ouro.piece10": "9b64d2d8b3006f1f",
    "trinity.admit256": "aed38c4007d2215e",
    "trinity.admit8": "1f9bd5a503ab9c45",
    "trinity.admit_batch256": "2b6ff37f6e307392",
    "trinity.admit_batch8": "d2819816c4f95482",
    "trinity.chunk16": "44c4758c60a62844",
    "trinity.chunk4": "b589d4e954694306",
    "trinity.chunk8": "3abb9d5eed8bf13c",
    "trinity.group": "d5154a779a771449",
    "trinity.piece00": "8544ab5c6339a1fa",
    "trinity.piece01": "2c702fd6c1218a71",
    "trinity.piece10": "c70171acec754940",
}


def _lowered_texts(name, config, layout, slots, columns, T, G, **pool_kw):
    """The lowered text of each executable the answer cell's server builds,
    from shapes alone (no device, no compile)."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest as M

    from pathway_tpu.models import decoder as D

    S_ = jax.ShapeDtypeStruct
    with open(os.path.join(bench, "configs", config)) as f:
        model = json.load(f)["models"]["decoder"]
    cfg = M.resolve(M.load_manifest(), "layouts", layout).program_config(
        model)
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(
        lambda: D.pool_init(None, cfg, slots, columns, **pool_kw))
    ids, one = S_((1, T), I32), S_((), I32)
    out = {}
    for first, last in ((True, False), (False, False), (False, True)):
        out[f"piece{int(first)}{int(last)}"] = jax.jit(
            lambda p, i, pl, s, first=first, last=last: D.pool_prefill_chunk(
                p, i, i, i, pl, s, s, s[None], cfg, first=first, last=last),
            donate_argnums=(2,)).lower(params, ids, pool, one)
    if G > 1:
        vec, flag = S_((G,), I32), S_((G,), jnp.bool_)
        out["group"] = jax.jit(
            lambda p, i, pl, s, f, la: D.pool_prefill_chunk(
                p, i, i, i, pl, s, s, s, cfg, first=f, last=la,
                last_col=s), donate_argnums=(2,)).lower(
                    params, S_((G, T), I32), pool, vec, flag, flag)
    for steps in (16, 8, 4):
        out[f"chunk{steps}"] = jax.jit(
            lambda p, pl, a, k, steps=steps: D.pool_decode_chunk(
                p, pl, a, k, cfg, steps), donate_argnums=(1,)).lower(
                    params, pool, S_((slots,), jnp.bool_),
                    S_((2,), jnp.uint32))
    for s in (8, 256):
        out[f"admit{s}"] = jax.jit(
            lambda p, i, pl, sl: D.pool_admit(p, i, i, pl, sl, cfg),
            donate_argnums=(2,)).lower(params, S_((1, s), I32), pool, one)
        out[f"admit_batch{s}"] = jax.jit(
            lambda p, i, pl, sl: D.pool_admit_batch(p, i, i, pl, sl, cfg),
            donate_argnums=(2,)).lower(params, S_((2, s), I32), pool,
                                       S_((2,), I32))
    if name == "deepseek":
        out["spec"] = jax.jit(
            lambda p, pl, a: D.pool_decode_spec(
                p, pl, a, cfg, 4, draft_layers=1, n_spec=3),
            donate_argnums=(1,)).lower(params, pool, S_((slots,), jnp.bool_))
    return {f"{name}.{k}": v.as_text() for k, v in out.items()}


def test_the_other_cells_executables_are_the_parents():
    """What the recurrent state added to the decoder lowers to nothing in a
    configuration without it: every executable the three other answer
    cells' servers build lowers to the text it lowered to before."""
    import hashlib

    texts = {}
    texts.update(_lowered_texts("trinity", "trinity-large-ep8-rag.json",
                                "afmoe", 16, 8304, 512, 4))
    texts.update(_lowered_texts("deepseek", "deepseek-v2-ep8-rag.json",
                                "deepseek_v2", 8, 16528, 512, 2,
                                arena_blocks=18, arena_block=512))
    texts.update(_lowered_texts("ouro", "ouro-2.6b-rag.json", "ouro", 4,
                                656, 256, 1))
    assert {k: hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in texts.items()} == OTHER_LOWERED
