"""Pallas fused int8-weight matmul for the decode hot loop.

``wq_matmul(x, w_int8, scale)`` computes ``(x @ dequant(w))`` with the
dequantization fused into the tile read: each grid step streams one
``(K, block_n)`` int8 weight tile out of HBM — a quarter of the f32
bytes the unquantized einsum moves, which is the whole point on a
memory-bound decode — widens it to the activation dtype in VMEM (int8
values <= 127 are exact in bf16), runs the MXU with guaranteed f32
accumulation, and multiplies the per-output-channel f32 scale into the
accumulator before it ever leaves the kernel. A full-precision copy of
the weight never exists, in HBM or VMEM.

This is the optional ``PATHWAY_TPU_WQ_KERNEL`` arm of the weight-quant
seam (``decoder._wq_matmul``); the XLA fused-dequant einsum is the
default and the numerical reference. The kernel's contraction is
mathematically identical (same widen-then-multiply-accumulate in f32)
but may associate tile reductions differently, so parity is
allclose-not-bitwise — which is why the kernel rides its own kill
switch on top of ``PATHWAY_TPU_WEIGHT_QUANT``'s.

``interpret`` defaults to True off-TPU so tier-1 (JAX_PLATFORMS=cpu)
runs the same kernel body through the Pallas interpreter, exactly like
flash/paged attention; on a TPU the same call compiles natively
(``tests/test_tpu_compile.py``). Native compilation wants lane-aligned tiles:
int8 operands want (32, 128) minimum register shapes, so the auto tile
sizes below stay in multiples of 128 on the output-channel axis and the
full (unpadded) K on the contracted axis — decoder K is the hidden or
ffn width, already MXU-friendly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Auto tile caps (rows of x per step, output channels per step). M is
# the flattened token axis — a decode chunk's B*1 rows round up to 8.
_AUTO_BLOCK_M = 128
_AUTO_BLOCK_N = 128


def _round8(n):
    return -(-int(n) // 8) * 8


# Index maps are named top-level functions on purpose: graft-lint roots
# them as jit-purity trace roots alongside the kernel body.
def _x_tile_map(mt, nt):
    return (mt, 0)


def _w_tile_map(mt, nt):
    return (0, nt)


def _s_tile_map(mt, nt):
    return (0, nt)


def _o_tile_map(mt, nt):
    return (mt, nt)


def _wq_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    """One (block_m, block_n) output tile: widen the int8 weight tile to
    the activation dtype, contract over the full K with f32 accumulation,
    scale per output channel. Grid (m_tiles, n_tiles) — K is not tiled,
    so no cross-step accumulator scratch is needed."""
    x = x_ref[...]                                   # (Bm, K) activation dtype
    w = w_ref[...].astype(x.dtype)                   # (K, Bn) int8 -> exact
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (Bm, Bn) f32
    o_ref[...] = acc * s_ref[...]


def wq_matmul(x, w, scale, *, block_m=None, block_n=None, interpret=None):
    """Fused-dequant matmul: ``x (M, K) @ int8 w (K, N)`` scaled per
    output channel by ``scale (1, N) f32``. Returns (M, N) float32.

    M and N are zero-padded up to tile multiples (zero scale columns
    yield zero outputs) and the padding sliced back off; K rides whole.
    ``interpret`` defaults to True off-TPU.
    """
    M, K = x.shape
    N = w.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bm = int(block_m or min(_AUTO_BLOCK_M, _round8(M)))
    bn = int(block_n or min(_AUTO_BLOCK_N, _round8(N)))
    pm = -M % bm
    pn = -N % bn
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    if pn:
        w = jnp.pad(w, ((0, 0), (0, pn)))
        scale = jnp.pad(scale, ((0, 0), (0, pn)))
    out = pl.pallas_call(
        _wq_matmul_kernel,
        grid=((M + pm) // bm, (N + pn) // bn),
        in_specs=[
            pl.BlockSpec((bm, K), _x_tile_map),
            pl.BlockSpec((K, bn), _w_tile_map),
            pl.BlockSpec((1, bn), _s_tile_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), _o_tile_map),
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), jnp.float32),
        interpret=interpret,
    )(x, w, scale.astype(jnp.float32))
    return out[:M, :N] if (pm or pn) else out
