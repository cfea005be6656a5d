"""Tiled online-softmax Pallas flash attention for the prefill/encode paths.

``paged_attention.py`` covers the decode read; this module covers every
place that still materialized full O(Sq x Sk) f32 score/prob/mask-bias
tensors through the dense ``_attn_ctx`` funnel:

* ``flash_attn`` — causal self-attention over a whole prompt
  (``forward`` / ``prefill`` / ``pool_admit`` / ``pool_admit_batch``)
  and, with ``causal=False``, the encoder's ``core(q, k, v)`` seam
  (``models/transformer.py``) so the MiniLM embedder and cross-encoder
  rerank cascade get the same O(S) memory profile.
* ``flash_chunk_attn`` — chunk-vs-cache cross attention for
  ``pool_prefill_chunk``: a T-token query piece at offset ``start``
  attends the keys of one slot's row. The kernel is told the absolute
  cache COLUMN each key row holds (-1: dead), so one predicate serves a
  full layer's row, a window layer's ``[ring | own]`` and the paged
  planes; key-value heads are a grid axis with the query heads that share
  one folded into the query rows (grouped query; multi-head is a group of
  one); the blocks that hold no key any query of the piece can see are
  found on the device beforehand and neither computed nor DMA'd; the
  row's last tile may be ragged. int8 dequantization of the cached KV is
  FUSED into the tile read (the per-token f32 scales multiply the int8
  payload inside the kernel), so cached KV never round-trips through HBM
  at f32. ``pool_prefill_chunk`` takes this read by a rule on its shapes
  (``decoder.blockwise_chunk_read``), not by the kill switch below.
* ``flash_chunk_attn_paged`` — the same chunk read over the paged pool's
  physical block planes, walking one slot's block-table row via
  ``PrefetchScalarGridSpec`` exactly like the decode kernel.

The mask is computed from lengths INSIDE the kernel (a per-column live
mask tile plus iota row/column comparisons), so no ``(B, 1, S, S)`` bias
tensor is ever materialized.

Numerics: online softmax is mathematically identical to the dense
softmax but associates the reductions differently, so flash output is
allclose-not-bitwise vs the dense path — which is why the whole-prompt
and encoder paths ride the ``PATHWAY_TPU_FLASH_PREFILL`` kill switch (off
= the dense path, byte-identical, pinned by
``tests/test_flash_prefill.py``), and why the chunk read engages only for
rows far longer than any test's. One visible
divergence is DEFINED behavior: a query row with no attendable column
(left-padding before the first real token) is exact zeros here, where
dense softmax yields a uniform average over masked columns. Those rows'
hidden states never reach real positions (their columns stay masked
downstream and logits read the last real position), so flash-on
equivalence is judged on logits/tokens, at kernel level on live rows.

``interpret`` defaults to True off-TPU so tier-1 (JAX_PLATFORMS=cpu)
runs the same kernel bodies through the Pallas interpreter; on a TPU the
same calls compile natively, and nothing falls back to the interpreter
there. Tile row counts are kept 8-aligned (or the whole axis) and every
block's last dim is the array's, which the TPU lowering accepts at
head_dim 128, 64 and 32 alike (``tests/test_tpu_compile.py`` compiles all
three kernels for a v5e, the chunk kernel at the answer cell's widths
too). Tile sizes tune via
``PATHWAY_TPU_FLASH_BLOCK_Q`` / ``PATHWAY_TPU_FLASH_BLOCK_K``
(``configure_blocks`` installs them at construction time).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Large-negative finite sentinel rather than -inf: exp(-inf - -inf) is
# NaN where exp(_NEG - _NEG) is 1.0, and the post-mask zeroing of p
# keeps the phantom weight out of l and acc.
_NEG = -1e30

# Construction-time tile-size overrides (0 = auto). Installed by
# ``configure_blocks`` from the PATHWAY_TPU_FLASH_BLOCK_Q/_K flags when
# a server/model is built; deliberately immutable ints rebound wholesale
# so jit-reachable readers never capture a mutable object.
_BLOCK_Q = 0
_BLOCK_K = 0

# Auto tile caps: one MXU-friendly tile per axis, shrunk to the (8-
# rounded) sequence when the prompt is shorter than a full tile.
_AUTO_BLOCK = 128


def configure_blocks(block_q=0, block_k=0):
    """Install default tile sizes (0 = auto) for subsequent traces.

    Called host-side at server/model construction after reading the
    ``flash_block_q``/``flash_block_k`` flags — the construction-reload
    idiom: a jit cache built afterwards bakes these in statically.
    """
    global _BLOCK_Q, _BLOCK_K
    _BLOCK_Q = int(block_q or 0)
    _BLOCK_K = int(block_k or 0)


def _round8(n):
    return -(-int(n) // 8) * 8


# --------------------------------------------------------------------------
# (a)/(c): whole-sequence self attention, causal (prefill) or not (encoder)
# --------------------------------------------------------------------------

# Index maps are named top-level functions on purpose: graft-lint roots
# them as jit-purity trace roots alongside the kernel bodies.
def _q_tile_map(b, qt, kt):
    return (b, 0, qt, 0)


def _kv_tile_map(b, qt, kt):
    return (b, 0, kt, 0)


def _mask_tile_map(b, qt, kt):
    return (b, kt, 0, 0)


def _self_attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref,
                      m_ref, l_ref, acc_ref, *,
                      sm_scale, causal, block_q, block_k, n_kt):
    """Grid (batch, q_tiles, k_tiles); the k axis is innermost, so the
    VMEM scratch carries one q tile's running (max, denom, acc) across
    its k tiles and is re-initialized when the k index wraps to 0."""
    qt = pl.program_id(1)
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile():
        q = q_ref[0].astype(jnp.float32)            # (nh, Bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (nh, Bk, hd)
        v = v_ref[0].astype(jnp.float32)
        # s[n, r, c] = q[n, r] . k[n, c] — batched over heads on the MXU
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                # (nh, Bq, Bk)
        live = jnp.broadcast_to(mask_ref[0, 0] > 0, (block_q, block_k))
        if causal:
            rows = qt * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kt * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            live = live & (cols <= rows)
        s = jnp.where(live[None, :, :], s, _NEG)

        m_prev = m_ref[...]                         # (nh, Bq)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live[None, :, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                           # (nh, Bq, hd)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = m_new

    if causal:
        # tiles strictly above the diagonal contribute nothing
        pl.when(kt * block_k <= qt * block_q + (block_q - 1))(_tile)
    else:
        _tile()

    @pl.when(kt == n_kt - 1)
    def _finish():
        l = l_ref[...]
        # a row with no attendable column divides by 1 instead of 0 and
        # emits exact zeros; see the module docstring
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)[..., None]
                    ).astype(o_ref.dtype)


def flash_attn(q, k, v, mask, *, causal=True, sm_scale=None,
               block_q=None, block_k=None, interpret=None):
    """Tiled flash attention over whole sequences.

    Args:
      q/k/v: (B, heads, S, head_dim) in compute dtype.
      mask: (B, S) attendable-column mask (>0 = live).
      causal: also mask columns after each query's own position (prefill
        self-attention); False gives the encoder's pad-only masking.
      sm_scale: score scale; defaults to 1/sqrt(head_dim).
      block_q/block_k: tile sizes; default to the construction-time
        ``configure_blocks`` values, else one 128 tile (shrunk to the
        8-rounded sequence when shorter). Sequences are zero-padded to
        tile multiples and the padding sliced back off.
      interpret: run the Pallas interpreter; defaults to True off-TPU.

    Returns (B, heads, S, head_dim) float32 context.
    """
    B, nh, Sq, hd = q.shape
    Sk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq = int(block_q or _BLOCK_Q or min(_AUTO_BLOCK, _round8(Sq)))
    bk = int(block_k or _BLOCK_K or min(_AUTO_BLOCK, _round8(Sk)))
    pq = -Sq % bq
    pk = -Sk % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    mask = mask.astype(jnp.int32)
    if pk:
        mask = jnp.pad(mask, ((0, 0), (0, pk)))
    n_qt = (Sq + pq) // bq
    n_kt = (Sk + pk) // bk
    out = pl.pallas_call(
        functools.partial(
            _self_attn_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, n_kt=n_kt,
        ),
        grid=(B, n_qt, n_kt),
        in_specs=[
            pl.BlockSpec((1, nh, bq, hd), _q_tile_map),
            pl.BlockSpec((1, nh, bk, hd), _kv_tile_map),
            pl.BlockSpec((1, nh, bk, hd), _kv_tile_map),
            # (B, k_tiles, 1, bk): a block whose last two dims equal the
            # array's — the TPU lowering refuses a (1, bk) block of the
            # flat (B, S) mask (rows neither 8-aligned nor the whole axis)
            pl.BlockSpec((1, 1, 1, bk), _mask_tile_map),
        ],
        out_specs=pl.BlockSpec((1, nh, bq, hd), _q_tile_map),
        out_shape=jax.ShapeDtypeStruct((B, nh, Sq + pq, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nh, bq), jnp.float32),      # running max
            pltpu.VMEM((nh, bq), jnp.float32),      # running denom
            pltpu.VMEM((nh, bq, hd), jnp.float32),  # unnormalized context
        ],
        interpret=interpret,
    )(q, k, v, mask.reshape(B, n_kt, 1, bk))
    return out[:, :, :Sq, :] if pq else out


# --------------------------------------------------------------------------
# (b): chunk-vs-cache cross attention for pool_prefill_chunk
# --------------------------------------------------------------------------

# Scalar-prefetched, all int32: ``meta`` = [start, n_live]; ``blk`` (k_tiles,)
# the key blocks that hold a key some query of the piece may see, in order,
# the last of them repeated to the end (a repeated index is not DMA'd again);
# ``src`` (k_tiles,) where each of those blocks' K/V lie: ``blk`` itself for a
# dense row, the slot's physical blocks for the paged planes.
def _chunk_q_map(h, j, meta, blk, src):
    return (h, 0, 0)


def _chunk_kv_map(h, j, meta, blk, src):
    return (0, h, src[j], 0)


def _paged_chunk_kv_map(h, j, meta, blk, src):
    return (src[j], h, 0, 0)


def _chunk_latent_map(h, j, meta, blk, src):
    return (0, 0, 0, src[j])


def _chunk_w_map(h, j, meta, blk, src):
    return (h, 0, 0)


def _chunk_kcol_map(h, j, meta, blk, src):
    return (blk[j], 0, 0)


def _chunk_kernel(meta_ref, blk_ref, src_ref, *refs, sm_scale, group, block_t,
                  block_k, n_kt, n_rows, window, quant, latent=None):
    """Grid (kv_heads, k_tiles), the key axis innermost. One grid step is
    ONE key-value head against one block of its keys: the ``group`` query
    heads that share it lie folded into the query rows (``group * T``,
    resident in VMEM across the head's blocks) and walk the block one after
    another against one predicate tile, so K and V are read once a group.

    ``kcol`` is the absolute cache column each key row holds (-1: dead), so
    one predicate serves a dense row, a paged row and a window layer's
    ``[ring | own]``: query row t, at column ``start + t``, reads a key when
    ``0 <= kcol <= start + t`` and, with a window, ``start + t - kcol <
    window``. Only the first ``meta[1]`` steps of a head hold a block some
    query can see; the rest neither compute nor move anything. Operands go
    into both dots as they come (bfloat16 on the chip), scores, statistics
    and the accumulator (the resident output block) are float32, and the
    probabilities are cast to the operands' type before the value dot: the
    precision of ``decoder._attn_ctx``.

    ``latent = (rank, nope)``: the head axis of the grid is a QUERY head and
    what it walks is the one latent row all heads share, TRANSPOSED: ``(rank
    + rope, Bk)`` a block, beside the head's own ``W_UKV`` transposed
    ``(nope + v, rank)``. The block becomes the head's keys and values HERE,
    for this step only (``kv^T = W_UKV^T @ latent^T``: keys^T its first
    ``nope`` rows, values^T the rest), and the score is two dots, the
    queries' first ``nope`` values against those keys and their last
    ``rope`` against the block's shared rotary key: a key of 192 = 128 + 64
    beside a value of 128, no per-head key or value ever in HBM."""
    if latent:
        q_ref, k_ref, v_ref, kcol_ref, o_ref = refs[:5]     # q, rows, W_UKV
        ks_ref = vs_ref = None
    elif quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, kcol_ref, o_ref = refs[:7]
    else:
        q_ref, k_ref, v_ref, kcol_ref, o_ref = refs[:5]
        ks_ref = vs_ref = None
    m_ref, l_ref = refs[-2:]
    j = pl.program_id(1)
    start = meta_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < meta_ref[1])
    def _tile():
        k = k_ref[0, 0]                             # (Bk, hd)
        v = None if latent else v_ref[0, 0]         # latent: k (width, Bk)
        if quant:
            # fused int8 dequant: (Bk, 1) f32 scales broadcast over hd
            k = (k.astype(jnp.float32) * ks_ref[0, 0]).astype(q_ref.dtype)
            v = (v.astype(jnp.float32) * vs_ref[0, 0]).astype(q_ref.dtype)
        if n_rows % block_k:
            # the row's last tile reaches past its end: what was read there
            # is not data, and 0 x NaN is NaN, so it is zeroed before it
            # meets its zero probability (its scores are masked by kcol -1)
            if latent:
                cols = blk_ref[j] * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1)
                k = jnp.where(cols < n_rows, k, jnp.zeros_like(k))
            else:
                rows = blk_ref[j] * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0)
                v = jnp.where(rows < n_rows, v, jnp.zeros_like(v))
        # the key in parts, each against its columns of the queries; a part
        # contracts its ``k_dim`` (1: keys by row; 0: keys transposed)
        keys, k_dim = ((k, slice(None)),), 1
        if latent:
            rank, nope = latent
            kv = jax.lax.dot_general(
                v_ref[0], k[:rank, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(k.dtype)
            keys, k_dim = ((kv[:nope, :], slice(0, nope)),
                           (k[rank:, :], slice(nope, None))), 0
            v = kv[nope:, :]                        # (v, Bk): transposed too
        kcol = kcol_ref[0]                          # (1, Bk)
        qcol = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_k), 0)
        live = (kcol >= 0) & (kcol <= qcol)
        if window:
            live = live & (qcol - kcol < window)
        for g in range(group):
            rows = pl.ds(g * block_t, block_t)
            s = None
            for part, cols in keys:
                dot = jax.lax.dot_general(
                    q_ref[0, rows, cols], part, (((1,), (k_dim,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = dot if s is None else s + dot
            s = s * sm_scale                        # (T, Bk)
            s = jnp.where(live, s, _NEG)
            m_prev = m_ref[rows, :]                 # (T, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1 - k_dim,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                       # (T, hd)
            o_ref[0, rows, :] = o_ref[0, rows, :] * alpha + pv
            m_ref[rows, :] = m_new

    @pl.when(j == n_kt - 1)
    def _finish():
        l = l_ref[...]
        # a row with no attendable column divides by 1 instead of 0 and
        # emits exact zeros; see the module docstring
        o_ref[0] = o_ref[0] / jnp.where(l == 0.0, 1.0, l)


# What one kernel instance should need in VMEM by :func:`_chunk_vmem_bytes`
# (a rough count: the compiler is given twice it as its limit, 32 MiB here,
# well inside the smallest core this runs on: 64 MiB; a v5e's has 128).
_CHUNK_VMEM_BUDGET = 16 << 20


def _chunk_vmem_bytes(T, group, hd, bk, itemsize):
    """VMEM one grid step of :func:`_chunk_kernel` needs: the group's
    queries and float32 output (double-buffered), its two statistics
    (a lane-padded column each), K and V tiles, and one head's scores,
    probabilities and predicate."""
    rows = group * T
    resident = 2 * rows * hd * (itemsize + 4) + 2 * rows * 128 * 4
    tiles = 4 * bk * (hd * itemsize + 128 * 4) + 2 * 8 * bk * 4
    return resident + tiles + T * bk * (4 + 4 + 4 + itemsize)


def chunk_block(columns, T, group, hd, itemsize):
    """Key rows a block of the chunk read, from the shapes: the largest of
    512, 256 and 128 that fits the VMEM budget (a larger block amortises a
    grid step's fixed cost; a smaller one skips dead columns more finely),
    or the whole 8-rounded row when that is shorter. The row's length need
    not be a multiple: its last tile is ragged. ``flash_block_k`` overrides
    (``configure_blocks``)."""
    want = _BLOCK_K
    if not want:
        want = 512
        while want > 128 and _chunk_vmem_bytes(
                T, group, hd, want, itemsize) > _CHUNK_VMEM_BUDGET:
            want //= 2
    return min(_round8(want), _round8(columns))


def blocks_seen(xp, kcol, start, T, window, bk):
    """``kcol`` (rows,) padded with -1 to whole blocks of ``bk`` as
    ``(n, bk)``, and for each block whether it holds a key that SOME query
    of the piece ``[start, start + T)`` may see. ``xp`` is ``jnp`` (the
    kernel's prefetch) or ``numpy`` (the host's counter): one predicate."""
    n = -(-kcol.shape[0] // bk)
    kcol = xp.pad(kcol, (0, n * bk - kcol.shape[0]),
                  constant_values=-1).reshape(n, bk)
    seen = (kcol >= 0) & (kcol < start + T)
    if window:
        seen = seen & (kcol > start - window)
    return kcol, seen.any(axis=1)


def chunk_live_blocks(kcol, start, T, window, bk):
    """What the kernel prefetches: ``(kcol as (n, 1, bk), n_live, blk)``,
    ``blk`` (n,) the blocks :func:`blocks_seen` marks, in order, the last
    one repeated to the end. On the device, before the kernel: a reduction
    over a few thousand ints."""
    kcol, seen = blocks_seen(jnp, kcol.astype(jnp.int32), start, T, window,
                             bk)
    n = seen.shape[0]
    n_live = seen.sum().astype(jnp.int32)
    order = jnp.argsort(~seen, stable=True).astype(jnp.int32)
    blk = order[jnp.minimum(jnp.arange(n), jnp.maximum(n_live - 1, 0))]
    return kcol.reshape(n, 1, bk), n_live, blk


def _chunk_call(q, kv_operands, kv_specs, kcol, start, tbl, *, window,
                sm_scale, block_k, n_rows, quant, interpret, latent=None,
                out_dim=None, vmem=None):
    """``q`` (heads, T, hd) against K/V given as 4-D operands: the keys of
    logical block ``b`` lie in block ``b`` of a dense row (``tbl`` None),
    in block ``tbl[b]`` of the paged planes. ``latent``: the operands are
    the one latent row and ``W_UKV`` by head, every query head a grid row
    of its own, the context ``out_dim`` wide (:func:`_chunk_kernel`)."""
    nq, T, hd = q.shape
    nkv = nq if latent else kv_operands[0].shape[1]
    group = nq // nkv
    out_dim = out_dim or hd
    if vmem is None:
        vmem = _chunk_vmem_bytes(T, group, hd, block_k, q.dtype.itemsize)
    kcol, n_live, blk = chunk_live_blocks(kcol, start, T, window, block_k)
    n_kt = blk.shape[0]
    meta = jnp.stack([jnp.asarray(start, jnp.int32), n_live])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nkv, n_kt),
        in_specs=[pl.BlockSpec((1, group * T, hd), _chunk_q_map)] + kv_specs
        # key columns as (k_tiles, 1, block_k), see flash_attn's mask spec
        + [pl.BlockSpec((1, 1, block_k), _chunk_kcol_map)],
        out_specs=pl.BlockSpec((1, group * T, out_dim), _chunk_q_map),
        scratch_shapes=[
            pltpu.VMEM((group * T, 1), jnp.float32),    # running max
            pltpu.VMEM((group * T, 1), jnp.float32),    # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, sm_scale=sm_scale, group=group, block_t=T,
            block_k=block_k, n_kt=n_kt, n_rows=n_rows, window=int(window),
            quant=quant, latent=latent,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nkv, group * T, out_dim),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * max(_CHUNK_VMEM_BUDGET, vmem),
        ),
        interpret=interpret,
    )(meta, blk, blk if tbl is None else tbl[blk],
      q.reshape(nkv, group * T, hd), *kv_operands, kcol)
    return out.reshape(nq, T, out_dim)


def flash_chunk_attn(q, k_row, v_row, kcol, start, *, window=0,
                     k_scale=None, v_scale=None, sm_scale=None,
                     block_k=None, interpret=None):
    """Chunk-vs-cache attention over one slot's DENSE cache row.

    Args:
      q: (heads, T, head_dim) query piece in compute dtype.
      k_row/v_row: (kv_heads, rows, head_dim) the slot's keys and values
        (int8 when quantized, else compute dtype); ``heads // kv_heads``
        query heads share each (grouped query; 1: multi-head).
      kcol: (rows,) the absolute cache column each key row holds, -1 where
        it holds none that may be read: ``arange`` under the row's mask for
        a full layer, the ring's columns then the piece's own for a window
        layer.
      start: absolute column of the piece's first query (scalar, may be
        traced); query row t reads ``0 <= kcol <= start + t``.
      window: > 0 also asks ``start + t - kcol < window`` (static).
      k_scale/v_scale: (kv_heads, rows, 1) f32 per-token scales, or None
        when the cache is unquantized.
      block_k: key rows a tile; defaults to :func:`chunk_block`.
      interpret: run the Pallas interpreter; defaults to True off-TPU.

    Returns (heads, T, head_dim) float32 context.
    """
    nq, T, hd = q.shape
    nkv, C, _ = k_row.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bk = int(block_k or chunk_block(C, T, nq // nkv, hd, q.dtype.itemsize))
    quant = k_scale is not None

    kv_operands = [k_row[None], v_row[None]]
    kv_specs = [pl.BlockSpec((1, 1, bk, hd), _chunk_kv_map)] * 2
    if quant:
        kv_operands += [k_scale[None], v_scale[None]]
        kv_specs += [pl.BlockSpec((1, 1, bk, 1), _chunk_kv_map)] * 2
    return _chunk_call(
        q, kv_operands, kv_specs, kcol, start, None,
        window=window, sm_scale=sm_scale, block_k=bk, n_rows=C,
        quant=quant, interpret=interpret,
    )


def _latent_vmem_bytes(T, hd, width, rank, d, bk, itemsize):
    """:func:`_chunk_vmem_bytes` for the latent read: one head's queries,
    float32 output and statistics and its ``W_UKV`` (double-buffered), the
    latent tile, its expansion in float32 and cast, and the scores."""
    out = d - hd + (width - rank)       # the value's size: d - nope
    resident = 2 * T * (hd * itemsize + out * 4) + 2 * T * 128 * 4 \
        + 2 * rank * d * itemsize
    tiles = 2 * bk * width * itemsize + 2 * 8 * bk * 4 \
        + bk * d * (4 + itemsize)
    return resident + tiles + T * bk * (4 + 4 + 4 + itemsize)


def latent_chunk_block(columns, T, hd, width, rank, d, itemsize):
    """:func:`chunk_block` for the latent read (``hd`` a query's size,
    ``width`` a latent row's, ``d`` a head's columns of ``W_UKV``)."""
    want = _BLOCK_K
    if not want:
        want = 512
        while want > 128 and _latent_vmem_bytes(
                T, hd, width, rank, d, want, itemsize) > _CHUNK_VMEM_BUDGET:
            want //= 2
    return min(_round8(want), _round8(columns))


def flash_chunk_attn_latent(q, c_row, w_ukv, kcol, start, *, nope, sm_scale,
                            block_k=None, interpret=None):
    """Chunk-vs-cache attention over one slot's LATENT row: every block of
    rows some query can see is expanded into the head's keys and values
    inside the kernel's walk, read, and dropped.

    Args:
      q: (heads, T, nope + rope) query piece, the rotary part rotated.
      c_row: (rank + rope, rows) the slot's cached rows TRANSPOSED: of each
        column the normed latent, then the rotated key all heads share.
      w_ukv: (rank, heads, nope + v) the up-projection by head: a head's
        keys' ``nope`` columns, then its values'.
      kcol, start: as :func:`flash_chunk_attn`.
      nope: a key's size without positions (static); sm_scale: the scores'
        multiplier (static: YaRN's is not ``size^-1/2``).

    Returns (heads, T, v) float32 context.
    """
    nq, T, hd = q.shape
    width, C = c_row.shape
    rank, _nq, d = w_ukv.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    it = q.dtype.itemsize
    bk = int(block_k or latent_chunk_block(C, T, hd, width, rank, d, it))
    return _chunk_call(
        q, [c_row[None, None], w_ukv.transpose(1, 2, 0)],
        [pl.BlockSpec((1, 1, width, bk), _chunk_latent_map),
         pl.BlockSpec((1, d, rank), _chunk_w_map)],
        kcol, start, None, window=0, sm_scale=sm_scale, block_k=bk,
        n_rows=C, quant=False, interpret=interpret, latent=(rank, nope),
        out_dim=d - nope,
        vmem=_latent_vmem_bytes(T, hd, width, rank, d, bk, it),
    )


def flash_chunk_attn_paged(q, kb, vb, kb_scale, vb_scale, tbl_row,
                           kcol, start, *, window=0, sm_scale=None,
                           interpret=None):
    """Chunk-vs-cache attention straight over the PAGED pool's physical
    block planes — no gather of the slot's row. Each live grid step DMAs
    exactly the physical block the slot's table references (scalar-
    prefetched), mirroring ``paged_attention.paged_attn_decode``.

    Args:
      q: (heads, T, head_dim) query piece.
      kb/vb: (n_blocks, kv_heads, block, head_dim) physical KV block planes
        (int8 when quantized).
      kb_scale/vb_scale: (n_blocks, kv_heads, block, 1) f32 scales or None.
      tbl_row: (cache_len // block,) int32 — ONE slot's block-table row.
      kcol: (cache_len,) the column each key row holds in logical order,
        -1 where dead (:func:`flash_chunk_attn`).
      start: absolute offset of the piece (scalar, may be traced).

    Returns (heads, T, head_dim) float32 context.
    """
    hd = q.shape[2]
    Bk = kb.shape[2]
    M = tbl_row.shape[0]
    if kcol.shape[0] != M * Bk:
        raise ValueError(
            f"kcol width {kcol.shape[0]} != table blocks "
            f"{M} x block {Bk}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quant = kb_scale is not None

    kv_operands = [kb, vb]
    kv_specs = [pl.BlockSpec((1, 1, Bk, hd), _paged_chunk_kv_map)] * 2
    if quant:
        kv_operands += [kb_scale, vb_scale]
        kv_specs += [pl.BlockSpec((1, 1, Bk, 1), _paged_chunk_kv_map)] * 2
    return _chunk_call(
        q, kv_operands, kv_specs, kcol, start, tbl_row.astype(jnp.int32),
        window=window, sm_scale=sm_scale, block_k=Bk, n_rows=M * Bk,
        quant=quant, interpret=interpret,
    )
