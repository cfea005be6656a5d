"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context scaling, TPU-first: the sequence axis is sharded over mesh axis
``sp`` and K/V shards rotate around the ring with ``lax.ppermute`` (one hop
per step — the transfer rides ICI and overlaps with the local block matmul)
while each device keeps a flash-style running (max, denominator, weighted-sum)
accumulator for its resident Q shard. Memory per device is O(S/n * S/n) per
block instead of O(S^2); the result is *exact* attention, not an approximation.

The reference framework has no model-parallel code at all (its models are
opaque external libraries called via UDF — SURVEY.md §2.11); this module is
the TPU-native capability that replaces "send long inputs to an external
GPU model": embedder/reranker forwards over sequences far longer than one
chip's HBM would allow.

Design follows the public ring-attention recipe (blockwise softmax
accumulation + ppermute rotation) re-derived for this codebase; see
jax-ml scaling-book's collective-matmul pattern.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

_MASK_BIAS = -1e9


def ring_attention_core(q, k, v, kv_mask, axis_name: str, n_shards: int,
                        scale: float | None = None):
    """Exact attention for one Q shard against the full (ring-rotated) K/V.

    q, k, v: (B, nh, S_loc, hd) — this device's sequence shard.
    kv_mask: (B, S_loc) int/bool — padding mask for this device's K/V shard
        (rotates together with K/V).
    Returns (B, nh, S_loc, hd) float32 context for the resident queries.

    Fully-masked blocks are harmless: their exp(0)=1 contributions are wiped
    by the exp(m - new_m) rescale as soon as any real block raises the
    running max (and every encoder input has >= 1 unmasked token).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    B, nh, S, hd = q.shape
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def accumulate(acc, k_, v_, msk):
        o, m, l = acc
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k_,
                            preferred_element_type=jnp.float32) * scale
        scores = scores + jnp.where(msk[:, None, None, :] > 0, 0.0, _MASK_BIAS)
        blk_max = jnp.max(scores, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - new_m)                      # exp(-inf - x) == 0
        p = jnp.exp(scores - new_m)
        o = o * alpha + jnp.einsum("bnqk,bnkd->bnqd", p.astype(v_.dtype), v_,
                                   preferred_element_type=jnp.float32)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return o, new_m, l

    # local block first, then n-1 rotate-and-accumulate steps — the final
    # rotation would only bring K/V back home, so it is skipped entirely
    acc0 = accumulate(
        (jnp.zeros((B, nh, S, hd), jnp.float32),
         jnp.full((B, nh, S, 1), -jnp.inf, jnp.float32),
         jnp.zeros((B, nh, S, 1), jnp.float32)),
        k, v, kv_mask,
    )

    def step(_, carry):
        acc, k_, v_, msk = carry
        k_ = jax.lax.ppermute(k_, axis_name, perm)
        v_ = jax.lax.ppermute(v_, axis_name, perm)
        msk = jax.lax.ppermute(msk, axis_name, perm)
        return accumulate(acc, k_, v_, msk), k_, v_, msk

    (o, _, l), _, _, _ = jax.lax.fori_loop(
        0, n_shards - 1, step, (acc0, k, v, kv_mask)
    )
    return o / jnp.maximum(l, 1e-30)


def encode_sequence_parallel(params, input_ids, attention_mask, cfg, mesh,
                             sp_axis: str = "sp"):
    """Transformer encoder forward with the sequence axis sharded over
    ``mesh.shape[sp_axis]`` devices and ring attention between shards.

    Everything except attention is per-token, so it runs on the local shard
    with zero communication; attention is the only ring exchange. Output is
    (B, S, H) float32 with the same values as ``transformer.encode`` (up to
    accumulation-order rounding).

    input_ids / attention_mask: (B, S) with S divisible by the sp axis size.
    """
    from pathway_tpu.models import transformer as T

    n = mesh.shape[sp_axis]
    S = input_ids.shape[1]
    if S % n != 0:
        raise ValueError(f"sequence length {S} not divisible by sp={n}")
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def local_fn(params, ids, msk):
        S_loc = ids.shape[1]
        shard = jax.lax.axis_index(sp_axis)
        emb = params["embeddings"]
        pos = shard * S_loc + jnp.arange(S_loc)
        x = emb["word"][ids] + emb["position"][pos][None, :, :]
        x = x + emb["type"][jnp.zeros_like(ids)]
        x = T._layer_norm(x, emb["ln_scale"], emb["ln_bias"],
                          cfg.layer_norm_eps).astype(cfg.dtype)

        def core(q, k, v):
            return ring_attention_core(q, k, v, msk, sp_axis, n, scale)

        def body(carry, lp):
            return T._layer(carry, lp, None, cfg, core=core), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        return x.astype(jnp.float32)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(None, sp_axis), P(None, sp_axis)),
        out_specs=P(None, sp_axis),
        check_vma=False,
    )(params, input_ids, attention_mask)
