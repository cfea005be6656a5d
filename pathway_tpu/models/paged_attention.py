"""Pallas paged-attention decode kernel over the block-table KV store.

One decode step of attention for a batch of serving slots whose KV lives
in the global paged pool (``decoder.paged_pool_init``): each slot owns a
row of the block table mapping logical cache block m to a physical block
id in the shared ``(n_blocks, heads, block, head_dim)`` planes. The
kernel walks that row with a scalar-prefetched block table —
``PrefetchScalarGridSpec`` makes the table available to the index maps,
so each grid step DMAs exactly the physical block the slot references —
and runs an online-softmax (flash-decode) accumulation across blocks in
VMEM scratch. int8 KV dequantization is FUSED into the attention read:
the per-token f32 scales multiply the int8 payload inside the kernel,
so neither the dequantized KV nor the scales ever round-trip through
HBM at f32.

Numerics: online softmax is mathematically identical to the dense
``_attn_ctx`` softmax but associates the reductions differently, so the
result is allclose-not-bitwise vs the gather-run-scatter reference path.
That is why the kernel rides its own flag (``PATHWAY_TPU_PAGED_KERNEL``)
on top of ``PATHWAY_TPU_PAGED_KV``: the byte-equality grid pins the
reference path, and the kernel is pinned to it at tolerance by
``tests/test_paged_kv.py``.

``interpret`` defaults to True off-TPU, so tier-1 (JAX_PLATFORMS=cpu)
exercises the same kernel body through the Pallas interpreter; on a TPU
the same call compiles natively, and nothing falls back to the
interpreter there. Every block's last two dims equal the array's (the
KV planes' ``(block, head_dim)``, the scales' ``(block, 1)``, the mask
reshaped to ``(1, block)`` rows), which is what the TPU lowering accepts
at any ``head_dim`` and block size — ``tests/test_tpu_compile.py``
compiles it for a v5e at head_dim 64 with bf16 and int8 KV.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Masked scores use a large-negative finite sentinel rather than -inf so
# the running max stays NaN-free when a whole block is masked (exp(-inf
# - -inf) is NaN; exp(_NEG - _NEG) is 1.0 and the post-mask zeroing of p
# keeps the phantom weight out of l and acc).
_NEG = -1e30


def _decode_kernel(tbl_ref, *refs, sm_scale, n_blk, quant):
    """Grid (n_slots, blocks_per_slot); the block axis is innermost, so
    the VMEM scratch carries one slot's running (max, denom, acc) across
    its blocks and is re-initialized when the block index wraps to 0."""
    if quant:
        q_ref, kb_ref, vb_ref, ks_ref, vs_ref, mask_ref, o_ref = refs[:7]
    else:
        q_ref, kb_ref, vb_ref, mask_ref, o_ref = refs[:5]
        ks_ref = vs_ref = None
    m_ref, l_ref, acc_ref = refs[-3:]
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (nh, 1, hd)
    k = kb_ref[0].astype(jnp.float32)           # (nh, Bk, hd)
    v = vb_ref[0].astype(jnp.float32)
    if quant:
        k = k * ks_ref[0].astype(jnp.float32)   # (nh, Bk, 1) broadcasts
        v = v * vs_ref[0].astype(jnp.float32)
    # s[n, 0, t] = q[n, 0] . k[n, t] — batched over heads on the MXU. The
    # query keeps a unit row axis: the TPU compiler refuses a batched
    # dot whose left operand has no free dimension.
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * sm_scale                                # (nh, 1, Bk)
    live = mask_ref[0] > 0                      # (1, 1, Bk) broadcasts over heads
    s = jnp.where(live, s, _NEG)

    m_prev = m_ref[...]                         # (nh, 1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(live, p, 0.0)                 # fully-masked block -> 0
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                           # (nh, 1, hd)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(m == n_blk - 1)
    def _finish():
        l = l_ref[...]
        # a slot with an all-empty mask (never admitted) divides by 1
        # instead of 0; its lane's output is discarded by the caller
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def paged_attn_decode(q, kb, vb, kb_scale, vb_scale, tbl, slot_mask, *,
                      sm_scale=None, interpret=None):
    """Single-position paged attention for every slot in one dispatch.

    Args:
      q: (n_slots, heads, head_dim) query at each slot's write position.
      kb/vb: (n_blocks, heads, block, head_dim) ONE layer's physical KV
        block planes (int8 when quantized, else compute dtype).
      kb_scale/vb_scale: (n_blocks, heads, block, 1) f32 per-token
        scales, or None when the pool is unquantized.
      tbl: (n_slots, cache_len // block) int32 block table; entry 0 is
        the sentinel block (all zeros, always masked).
      slot_mask: (n_slots, cache_len) int32 attendable-column mask in
        LOGICAL column order.
      sm_scale: score scale; defaults to 1/sqrt(head_dim).
      interpret: run the Pallas interpreter; defaults to True off-TPU so
        CPU tests exercise the same kernel body.

    Returns (n_slots, heads, head_dim) context in ``q.dtype``.
    """
    B, nh, hd = q.shape
    Bk = kb.shape[2]
    M = tbl.shape[1]
    if slot_mask.shape[1] != M * Bk:
        raise ValueError(
            f"slot_mask width {slot_mask.shape[1]} != table blocks "
            f"{M} x block {Bk}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quant = kb_scale is not None

    # index maps take (slot, block, table) — the scalar-prefetched table
    # turns the logical block step into a physical block-plane index
    blk = lambda shp: pl.BlockSpec(shp, lambda b, m, t: (t[b, m],) + (0,) * (len(shp) - 1))
    in_specs = [
        pl.BlockSpec((1, nh, 1, hd), lambda b, m, t: (b, 0, 0, 0)),  # q
        blk((1, nh, Bk, hd)),                                   # kb
        blk((1, nh, Bk, hd)),                                   # vb
    ]
    operands = [q[:, :, None, :], kb, vb]
    if quant:
        in_specs += [blk((1, nh, Bk, 1)), blk((1, nh, Bk, 1))]
        operands += [kb_scale, vb_scale]
    # the mask rides as (n_slots, M, 1, Bk) so its block's last two dims
    # equal the array's: the TPU lowering refuses a (1, Bk) block of the
    # flat (n_slots, C) mask (rows neither 8-aligned nor the whole axis)
    in_specs.append(
        pl.BlockSpec((1, 1, 1, Bk), lambda b, m, t: (b, m, 0, 0)))
    operands.append(slot_mask.reshape(B, M, 1, Bk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, 1, hd), lambda b, m, t: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1, 1), jnp.float32),   # running max
            pltpu.VMEM((nh, 1, 1), jnp.float32),   # running denom
            pltpu.VMEM((nh, 1, hd), jnp.float32),  # unnormalized context
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, sm_scale=sm_scale, n_blk=M, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, 1, hd), q.dtype),
        interpret=interpret,
    )(tbl, *operands)
    return out[:, :, 0, :]
