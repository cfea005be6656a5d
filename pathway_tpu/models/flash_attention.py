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
  attends cache columns ``[0, start + t]``. int8 dequantization of the
  cached KV is FUSED into the tile read (the per-token f32 scales
  multiply the int8 payload inside the kernel), so cached KV never
  round-trips through HBM at f32.
* ``flash_chunk_attn_paged`` — the same chunk read over the paged pool's
  physical block planes, walking one slot's block-table row via
  ``PrefetchScalarGridSpec`` exactly like the decode kernel.

The mask is computed from lengths INSIDE the kernel (a per-column live
mask tile plus iota row/column comparisons), so no ``(B, 1, S, S)`` bias
tensor is ever materialized.

Numerics: online softmax is mathematically identical to the dense
softmax but associates the reductions differently, so flash output is
allclose-not-bitwise vs the dense path — which is why everything rides
the ``PATHWAY_TPU_FLASH_PREFILL`` kill switch (off = today's dense path,
byte-identical, pinned by ``tests/test_flash_prefill.py``). One visible
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
head_dim 64 and 32 alike (``tests/test_tpu_compile.py`` compiles all
three kernels for a v5e). Tile sizes tune via
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


def _pick_block(n, want):
    """Largest divisor of ``n`` that is <= ``want`` and a multiple of 8
    (cache rows cannot be padded without copying the whole row, so the
    tile must divide C, and the TPU lowering wants the tile's row count
    8-aligned); a row with no such divisor rides as ONE tile, which is
    legal at any length because it spans the whole axis."""
    for b in range(min(int(want), int(n)) // 8 * 8, 0, -8):
        if n % b == 0:
            return b
    return int(n)


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

# Chunk index maps take (k_tile, meta) — meta is the scalar-prefetched
# int32 vector [start] (dense rows) or [start, *block_table_row] (paged).
def _chunk_q_map(i, meta):
    return (0, 0, 0)


def _chunk_kv_map(i, meta):
    return (0, 0, i, 0)


def _chunk_mask_map(i, meta):
    return (i, 0, 0)


def _paged_chunk_kv_map(i, meta):
    return (meta[i + 1], 0, 0, 0)


def _chunk_kernel(meta_ref, *refs, sm_scale, block_t, block_k, n_kt, quant):
    """Grid (k_tiles,): the whole T-token query piece stays resident in
    VMEM while cache column tiles stream past; ``meta_ref[0]`` is the
    piece's absolute ``start`` offset, so query row t attends logical
    columns ``live & (col <= start + t)``. Shared by the dense-row and
    block-table variants — only the index maps differ."""
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, o_ref = refs[:7]
    else:
        q_ref, k_ref, v_ref, mask_ref, o_ref = refs[:5]
        ks_ref = vs_ref = None
    m_ref, l_ref, acc_ref = refs[-3:]
    i = pl.program_id(0)
    start = meta_ref[0]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile():
        q = q_ref[...].astype(jnp.float32)          # (nh, T, hd)
        k = k_ref[0].astype(jnp.float32)            # (nh, Bk, hd)
        v = v_ref[0].astype(jnp.float32)
        if quant:
            # fused int8 dequant: (nh, Bk, 1) f32 scales broadcast over hd
            k = k * ks_ref[0].astype(jnp.float32)
            v = v * vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                # (nh, T, Bk)
        rows = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_k), 0)
        cols = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_k), 1)
        live = jnp.broadcast_to(mask_ref[0] > 0,
                                (block_t, block_k)) & (cols <= rows)
        s = jnp.where(live[None, :, :], s, _NEG)

        m_prev = m_ref[...]                         # (nh, T)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live[None, :, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = m_new

    # tiles entirely past the piece's last written column are dead (the
    # tile is still DMA'd by the BlockSpec schedule; only compute skips)
    pl.when(i * block_k <= start + (block_t - 1))(_tile)

    @pl.when(i == n_kt - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] /
                      jnp.where(l == 0.0, 1.0, l)[..., None]
                      ).astype(o_ref.dtype)


def _chunk_call(meta, q, kv_operands, kv_specs, row_mask, *,
                sm_scale, block_t, block_k, n_kt, quant, interpret, nh, hd):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kt,),
        in_specs=[pl.BlockSpec((nh, block_t, hd), _chunk_q_map)] + kv_specs
        # row mask as (k_tiles, 1, block_k), see flash_attn's mask spec
        + [pl.BlockSpec((1, 1, block_k), _chunk_mask_map)],
        out_specs=pl.BlockSpec((nh, block_t, hd), _chunk_q_map),
        scratch_shapes=[
            pltpu.VMEM((nh, block_t), jnp.float32),
            pltpu.VMEM((nh, block_t), jnp.float32),
            pltpu.VMEM((nh, block_t, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_kernel, sm_scale=sm_scale, block_t=block_t,
            block_k=block_k, n_kt=n_kt, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nh, block_t, hd), jnp.float32),
        interpret=interpret,
    )(meta, q, *kv_operands, row_mask.reshape(n_kt, 1, block_k))


def flash_chunk_attn(q, k_row, v_row, row_mask, start, *,
                     k_scale=None, v_scale=None, sm_scale=None,
                     block_k=None, interpret=None):
    """Chunk-vs-cache attention over one slot's DENSE cache row.

    Args:
      q: (heads, T, head_dim) query piece in compute dtype.
      k_row/v_row: (heads, cache_len, head_dim) full cache row (int8
        when quantized, else compute dtype).
      row_mask: (cache_len,) attendable-column mask (>0 = live).
      start: absolute offset of the piece (scalar, may be traced); query
        row t attends columns ``live & (col <= start + t)``.
      k_scale/v_scale: (heads, cache_len, 1) f32 per-token scales, or
        None when the cache is unquantized.
      block_k: cache tile size; defaults to the construction-time value,
        else the largest divisor of cache_len that is <= 128.
      interpret: run the Pallas interpreter; defaults to True off-TPU.

    Returns (heads, T, head_dim) float32 context.
    """
    nh, T, hd = q.shape
    C = k_row.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bk = _pick_block(C, block_k or _BLOCK_K or _AUTO_BLOCK)
    n_kt = C // bk
    quant = k_scale is not None
    meta = jnp.full((1,), start, jnp.int32)

    kv_operands = [k_row[None], v_row[None]]
    kv_specs = [pl.BlockSpec((1, nh, bk, hd), _chunk_kv_map)] * 2
    if quant:
        kv_operands += [k_scale[None], v_scale[None]]
        kv_specs += [pl.BlockSpec((1, nh, bk, 1), _chunk_kv_map)] * 2
    return _chunk_call(
        meta, q, kv_operands, kv_specs, row_mask.astype(jnp.int32),
        sm_scale=sm_scale, block_t=T, block_k=bk, n_kt=n_kt,
        quant=quant, interpret=interpret, nh=nh, hd=hd,
    )


def flash_chunk_attn_paged(q, kb, vb, kb_scale, vb_scale, tbl_row,
                           row_mask, start, *, sm_scale=None,
                           interpret=None):
    """Chunk-vs-cache attention straight over the PAGED pool's physical
    block planes — no gather of the slot's row. The scalar-prefetched
    vector packs ``[start, *tbl_row]`` so each grid step DMAs exactly
    the physical block the slot's table references, mirroring
    ``paged_attention.paged_attn_decode``.

    Args:
      q: (heads, T, head_dim) query piece.
      kb/vb: (n_blocks, heads, block, head_dim) physical KV block planes
        (int8 when quantized).
      kb_scale/vb_scale: (n_blocks, heads, block, 1) f32 scales or None.
      tbl_row: (cache_len // block,) int32 — ONE slot's block-table row.
      row_mask: (cache_len,) attendable-column mask in logical order.
      start: absolute offset of the piece (scalar, may be traced).

    Returns (heads, T, head_dim) float32 context.
    """
    nh, T, hd = q.shape
    Bk = kb.shape[2]
    M = tbl_row.shape[0]
    if row_mask.shape[0] != M * Bk:
        raise ValueError(
            f"row_mask width {row_mask.shape[0]} != table blocks "
            f"{M} x block {Bk}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quant = kb_scale is not None
    meta = jnp.concatenate([
        jnp.full((1,), start, jnp.int32), tbl_row.astype(jnp.int32),
    ])

    kv_operands = [kb, vb]
    kv_specs = [pl.BlockSpec((1, nh, Bk, hd), _paged_chunk_kv_map)] * 2
    if quant:
        kv_operands += [kb_scale, vb_scale]
        kv_specs += [pl.BlockSpec((1, nh, Bk, 1), _paged_chunk_kv_map)] * 2
    return _chunk_call(
        meta, q, kv_operands, kv_specs, row_mask.astype(jnp.int32),
        sm_scale=sm_scale, block_t=T, block_k=Bk, n_kt=M,
        quant=quant, interpret=interpret, nh=nh, hd=hd,
    )
