"""BERT-family transformer encoder, TPU-first.

Functional JAX (params are plain pytrees) rather than a torch port: every
matmul is laid out for the MXU (compute-dtype inputs AND outputs — the MXU
accumulates f32 internally, and keeping gemm outputs/bias/gelu in bf16
halves the elementwise HBM traffic; layernorm statistics stay f32), shapes
are static under ``jit``, and each weight carries a tensor-parallel
``PartitionSpec`` so the same forward runs 1-chip or sharded over a mesh
``("dp", "tp")`` with XLA inserting the collectives.

Architecture parity targets (reference consumes these as opaque torch models):
- all-MiniLM-L6-v2  — 6L/384H/12A  (embedders.py:270 SentenceTransformerEmbedder)
- ms-marco-MiniLM-L-6-v2 cross-encoder (rerankers.py:186 CrossEncoderReranker)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 6
    heads: int = 12
    intermediate: int = 1536
    max_position: int = 512
    type_vocab: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16  # activation/compute dtype (MXU-native)
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


MINILM_L6 = TransformerConfig(layers=6, hidden=384, heads=12, intermediate=1536)
MINILM_L12 = TransformerConfig(layers=12, hidden=384, heads=12, intermediate=1536)
BGE_SMALL = TransformerConfig(layers=12, hidden=384, heads=12, intermediate=1536)


def _dense_init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Initialise a parameter pytree. Layers are stacked along a leading axis
    so the whole encoder runs as one ``lax.scan`` — one compiled layer body
    instead of ``cfg.layers`` unrolled copies (faster compiles, same speed)."""
    pd = cfg.param_dtype
    n, h, i = cfg.layers, cfg.hidden, cfg.intermediate
    ks = jax.random.split(rng, 16)

    def stack(key, shape, scale=0.02):
        return _dense_init(key, (n, *shape), pd, scale)

    params = {
        "embeddings": {
            "word": _dense_init(ks[0], (cfg.vocab_size, h), pd),
            "position": _dense_init(ks[1], (cfg.max_position, h), pd),
            "type": _dense_init(ks[2], (cfg.type_vocab, h), pd),
            "ln_scale": jnp.ones((h,), pd),
            "ln_bias": jnp.zeros((h,), pd),
        },
        "layers": {
            # fused QKV: one (h, 3h) matmul keeps the MXU busy vs 3 small ones
            "qkv_w": stack(ks[3], (h, 3 * h)),
            "qkv_b": jnp.zeros((n, 3 * h), pd),
            "attn_out_w": stack(ks[4], (h, h)),
            "attn_out_b": jnp.zeros((n, h), pd),
            "ln1_scale": jnp.ones((n, h), pd),
            "ln1_bias": jnp.zeros((n, h), pd),
            "mlp_in_w": stack(ks[5], (h, i)),
            "mlp_in_b": jnp.zeros((n, i), pd),
            "mlp_out_w": stack(ks[6], (i, h)),
            "mlp_out_b": jnp.zeros((n, h), pd),
            "ln2_scale": jnp.ones((n, h), pd),
            "ln2_bias": jnp.zeros((n, h), pd),
        },
        "pooler": {
            "w": _dense_init(ks[7], (h, h), pd),
            "b": jnp.zeros((h,), pd),
        },
    }
    return params


def param_partition_specs(cfg: TransformerConfig, tp_axis: str = "tp") -> dict:
    """Tensor-parallel layout (Megatron-style): QKV and MLP-in shard their
    output feature dim; attn-out and MLP-out shard their input dim, so each
    layer needs exactly one psum (inserted by XLA from these specs) on the
    residual add. Embeddings shard the vocab dim."""
    t = tp_axis
    return {
        "embeddings": {
            "word": P(t, None),
            "position": P(None, None),
            "type": P(None, None),
            "ln_scale": P(None),
            "ln_bias": P(None),
        },
        "layers": {
            "qkv_w": P(None, None, t),
            "qkv_b": P(None, t),
            "attn_out_w": P(None, t, None),
            "attn_out_b": P(None, None),
            "ln1_scale": P(None, None),
            "ln1_bias": P(None, None),
            "mlp_in_w": P(None, None, t),
            "mlp_in_b": P(None, t),
            "mlp_out_w": P(None, t, None),
            "mlp_out_b": P(None, None),
            "ln2_scale": P(None, None),
            "ln2_bias": P(None, None),
        },
        "pooler": {"w": P(None, t), "b": P(t)},
    }


# ---- weight-only int8 quantization (PATHWAY_TPU_WEIGHT_QUANT=int8) --------
#
# Encoder counterpart of the decoder's quantize_params seam: the four
# stacked layer matmul weights and the word-embedding table store as
# symmetric per-output-channel int8 (scale = max|w| / 127 over the
# CONTRACTED axis) with dequant fused into the einsum read — int8 payload
# in the compute dtype (int8 values <= 127 are exact in bf16), f32
# accumulation, per-output-channel scale on the OUTPUT. Presence of a
# ``word_scale`` key under ``embeddings`` is the static format marker;
# without it every expression below is byte-identical to the historical
# encoder. Position/type embeddings, layernorms and the pooler stay
# full-precision (tiny, and the pooler feeds a tanh in f32).

_WQ_QMAX = 127.0
_WQ_SCALE_FLOOR = 1e-8
_WQ_ENC_LAYER_WEIGHTS = ("qkv_w", "attn_out_w", "mlp_in_w", "mlp_out_w")


def _wq_quant(w, axis: int):
    """Symmetric int8 over the contracted ``axis``; scale keeps a size-1
    dim there (one f32 scale per output channel). Never clips."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / _WQ_QMAX, _WQ_SCALE_FLOOR)
    return jnp.round(wf / scale).astype(jnp.int8), scale


def encoder_params_quantized(params: dict) -> bool:
    """True when ``params`` store int8 weights
    (:func:`quantize_encoder_params`)."""
    return "word_scale" in params["embeddings"]


def quantize_encoder_params(params: dict, out: dict | None = None) -> dict:
    """int8-quantize the large encoder weights for serving: the word
    table per vocab row, each stacked layer weight per output channel.
    Quantize from the ORIGINAL full-precision ``params`` — an already-
    cast copy would bake the cast's mantissa loss into the scales.
    ``out`` optionally supplies the base tree the unquantized leaves are
    taken from (the embedder passes its compute-dtype cast), so quant
    payloads/scales stay int8/f32 while everything else keeps the
    caller's storage treatment."""
    out = dict(out if out is not None else params)
    emb = dict(out["embeddings"])
    emb["word"], emb["word_scale"] = _wq_quant(params["embeddings"]["word"],
                                               axis=-1)
    out["embeddings"] = emb
    layers = dict(out["layers"])
    for name in _WQ_ENC_LAYER_WEIGHTS:
        q, s = _wq_quant(params["layers"][name], axis=-2)
        layers[name], layers[name + "_scale"] = q, s
    out["layers"] = layers
    return out


def _wq_einsum(eq: str, x, lp: dict, name: str, cfg: TransformerConfig):
    """The encoder's weight-matmul seam: historical unquantized ops when
    ``lp`` has no ``{name}_scale`` key (byte-identical), fused-dequant
    int8 read when it does."""
    w = lp[name]
    scale = lp.get(name + "_scale")
    if scale is None:
        return jnp.einsum(eq, x, w.astype(cfg.dtype),
                          preferred_element_type=cfg.dtype)
    out = jnp.einsum(eq, x, w.astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    return (out * scale).astype(cfg.dtype)


def validate_encoder_mesh(cfg: TransformerConfig, mesh) -> None:
    """Typed ``MeshShapeError`` when ``cfg`` cannot shard over the
    serving mesh's tp axis (heads, ffn features, vocab must divide)."""
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS, MeshShapeError

    tp = int(mesh.shape.get(SERVE_TP_AXIS, 1))
    bad = []
    if cfg.heads % tp != 0:
        bad.append(f"heads={cfg.heads}")
    if cfg.intermediate % tp != 0:
        bad.append(f"intermediate={cfg.intermediate}")
    if cfg.vocab_size % tp != 0:
        bad.append(f"vocab_size={cfg.vocab_size}")
    if bad:
        raise MeshShapeError(
            f"encoder config does not divide the tp axis: {', '.join(bad)} "
            f"% tp={tp} != 0",
            data=int(mesh.shape.get("data", 1)),
            fsdp=int(mesh.shape.get("fsdp", 1)),
            tp=tp, n_devices=int(mesh.devices.size),
        )


def shard_encoder_params(params: dict, cfg: TransformerConfig,
                         mesh) -> dict:
    """Commit encoder params onto the ``(data, fsdp, tp)`` serving mesh
    (PATHWAY_TPU_MESH): the Megatron layout above over ``tp`` with the
    ``fsdp`` axis overlaid on each param's first unsharded divisible
    dim. Placement is LENIENT — the encoder has no ``shard_map`` seam,
    so a dim the tp axis does not divide (e.g. heads=12 on tp=8, or the
    30522-row vocab) degrades to replicated rather than refusing the
    mesh; ``validate_encoder_mesh`` stays available for callers that
    want the strict check. No-op when ``mesh`` is None; a 1x1x1 mesh
    degenerates to plain single-chip placement (the kill-switch
    byte-identity regime)."""
    from pathway_tpu.parallel.mesh import (
        SERVE_FSDP_AXIS, SERVE_TP_AXIS, place_pytree,
        spec_dropping_nondividing, spec_with_fsdp,
    )

    if mesh is None:
        return params
    fsdp = int(mesh.shape.get(SERVE_FSDP_AXIS, 1))
    specs = param_partition_specs(cfg, tp_axis=SERVE_TP_AXIS)

    def leaf_spec(path, leaf):
        node = specs
        for key in path[:-1]:
            node = node[key.key]
        name = path[-1].key
        if name in node:
            s = node[name]
        elif name.endswith("_scale") and name[: -len("_scale")] in node:
            # int8 weight-quant scale plane: inherit the payload's spec
            # (non-dividing axes drop below, so the keepdims size-1
            # contracted dim replicates and the output-channel dim keeps
            # its shard, co-locating scale rows with their int8 columns)
            s = node[name[: -len("_scale")]]
        else:
            raise KeyError(f"no partition spec for encoder param {name!r}")
        return spec_with_fsdp(
            spec_dropping_nondividing(s, leaf.shape, mesh), leaf.shape, fsdp
        )

    return place_pytree(
        params, mesh, jax.tree_util.tree_map_with_path(leaf_spec, params)
    )


# Odd minimax-style fit of erf over |t|<=3.2 (erf(t) ~ t*P(t^2), P below;
# |t|>3.2 clamps to sign(t) where 1-erf < 7e-6). Max |gelu error| 1.9e-5
# absolute — two orders of magnitude below bf16 resolution (~2e-3 for O(1)
# activations), so under bf16 compute the result is indistinguishable from
# exact erf while replacing ~60 VPU transcendental ops per element with 9
# fused multiply-adds: measured 13.8 -> 11.1 ms per 256x128 encoder batch
# (v5e), pooled-embedding drift 1.7e-4 max abs.
_ERF_POLY = (
    1.1283258790481554, -0.375708425265248, 0.11186609008719957,
    -0.025815739455015935, 0.0045846851469556376, -0.000611430760234131,
    5.848816009248211e-05, -3.741659781969581e-06, 1.4200819258585872e-07,
    -2.4020404766197523e-09,
)
_INV_SQRT2 = 0.7071067811865476


def _poly_gelu(x):
    """Exact-erf gelu via polynomial erf, for bf16 compute: evaluated in
    f32 (Horner in bf16 would accumulate rounding), cast back to x.dtype.
    XLA fuses the whole chain into the surrounding gemm epilogue, so HBM
    traffic is unchanged — only VPU work drops."""
    xf = x.astype(jnp.float32)
    t = jnp.clip(xf * jnp.float32(_INV_SQRT2), -3.2, 3.2)
    u = t * t
    p = jnp.float32(_ERF_POLY[-1])
    for c in reversed(_ERF_POLY[:-1]):
        p = p * u + jnp.float32(c)
    erf = jnp.where(
        jnp.abs(xf) >= jnp.float32(3.2 / _INV_SQRT2), jnp.sign(xf), t * p
    )
    return (0.5 * xf * (1.0 + erf)).astype(x.dtype)


def _gelu(x, cfg: TransformerConfig):
    """BERT-family exact (erf) gelu — checkpoints are trained with it, and
    the tanh approximation drifts ~1e-3/layer vs HF. Under bf16 compute the
    polynomial form is exact-to-resolution and ~5x cheaper; f32 configs
    (the HF-parity tests) keep the true erf bit-for-bit."""
    if cfg.dtype == jnp.bfloat16:
        return _poly_gelu(x)
    return jax.nn.gelu(x, approximate=False)


def _layer_norm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)


def _attention(x, lp, mask_bias, cfg: TransformerConfig, core=None):
    """x: (B, S, H) in compute dtype; lp: one layer's param slice.

    ``core(q, k, v) -> (B, nh, S, hd) f32`` swaps the dense softmax-attention
    inner for an alternative (the sequence-parallel ring core in
    ``parallel/ring_attention.py``); it owns scaling and masking.

    Matmul OUTPUTS are cfg.dtype (the MXU still accumulates f32
    internally): with bf16 compute this halves the gemm-output and
    bias/gelu HBM traffic that dominated the profile — measured 12.4 ->
    10.6 ms per 256x128 batch (30 -> 35% MFU) at 7e-4 max pooled-embedding
    drift vs the all-f32-intermediate path. f32 configs are bit-unchanged."""
    B, S, H = x.shape
    nh, hd = cfg.heads, cfg.head_dim
    qkv = _wq_einsum("bsh,hk->bsk", x, lp, "qkv_w", cfg)
    qkv = qkv + lp["qkv_b"].astype(cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
    if core is not None:
        ctx = core(q, k, v).astype(cfg.dtype)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H)
    else:
        # XLA's fused attention: the (B, nh, S, S) scores/probs tensors
        # never round-trip HBM
        ctx = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias=mask_bias.astype(cfg.dtype),
        )
        ctx = ctx.reshape(B, S, H)
    out = _wq_einsum("bsh,hk->bsk", ctx, lp, "attn_out_w", cfg)
    return out + lp["attn_out_b"].astype(cfg.dtype)


def _layer(x, lp, mask_bias, cfg: TransformerConfig, core=None):
    attn = _attention(x, lp, mask_bias, cfg, core=core)
    x = _layer_norm(x + attn, lp["ln1_scale"],
                    lp["ln1_bias"], cfg.layer_norm_eps).astype(cfg.dtype)
    h = _wq_einsum("bsh,hi->bsi", x, lp, "mlp_in_w", cfg)
    h = _gelu(h + lp["mlp_in_b"].astype(cfg.dtype), cfg)
    h = _wq_einsum("bsi,ih->bsh", h, lp, "mlp_out_w", cfg)
    h = h + lp["mlp_out_b"].astype(cfg.dtype)
    x = _layer_norm(x + h, lp["ln2_scale"],
                    lp["ln2_bias"], cfg.layer_norm_eps).astype(cfg.dtype)
    return x


def embed_inputs(params: dict, input_ids: jax.Array,
                 attention_mask: jax.Array, cfg: TransformerConfig,
                 token_type_ids: jax.Array | None = None):
    """Shared embedding preamble: (embedded activations in compute dtype,
    additive attention mask bias). Used by the sequential, pipelined, and
    sequence-parallel encoders so the paths cannot diverge.

    ``token_type_ids`` defaults to all-zeros (single-segment); cross-encoder
    pair inputs pass segment ids so pretrained type embeddings apply."""
    B, S = input_ids.shape
    emb = params["embeddings"]
    rows = emb["word"][input_ids]
    ws = emb.get("word_scale")
    if ws is not None:
        # dequant fused into the row gather — O(rows), never the table
        rows = rows.astype(jnp.float32) * ws[input_ids]
    x = rows + emb["position"][jnp.arange(S)][None, :, :]
    if token_type_ids is None:
        x = x + emb["type"][jnp.zeros((B, S), jnp.int32)]
    else:
        x = x + emb["type"][token_type_ids]
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], cfg.layer_norm_eps)
    x = x.astype(cfg.dtype)
    mask_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e9
                          ).astype(jnp.float32)
    return x, mask_bias


def encode(params: dict, input_ids: jax.Array, attention_mask: jax.Array,
           cfg: TransformerConfig,
           token_type_ids: jax.Array | None = None,
           *, n_layers: int | None = None,
           flash: bool = False) -> jax.Array:
    """Full encoder forward. Returns final hidden states (B, S, H) float32.

    Static shapes only; the S dimension is the caller's padded bucket size
    (the UDF microbatcher pads to pow2 buckets so executables are reused).

    ``n_layers`` truncates the depth: the scan runs over only the first
    ``n_layers`` stacked layer slices (a static Python int — each depth is
    its own executable). Used by the cascade rerank's cheap first pass;
    ``None`` (default) runs the full stack and is byte-identical to the
    pre-truncation path.

    ``flash`` (static) plugs the non-causal tiled flash kernel
    (``models/flash_attention.py``) into the ``core(q, k, v)`` seam: the
    pad mask is applied from lengths inside the kernel and the
    (B, nh, S, S) score/prob tensors never materialize — O(S) attention
    memory for the embedder and the cross-encoder rerank cascade.
    Online softmax is allclose-not-bitwise vs the dense path; ``False``
    (default, the ``PATHWAY_TPU_FLASH_PREFILL`` kill-switch position)
    is byte-identical to before the flag existed."""
    x, mask_bias = embed_inputs(params, input_ids, attention_mask, cfg,
                                token_type_ids)
    core = None
    if flash:
        from pathway_tpu.models import flash_attention as _fa

        def core(q, k, v):
            return _fa.flash_attn(q, k, v, attention_mask, causal=False)

    def body(carry, lp):
        return _layer(carry, lp, mask_bias, cfg, core=core), None

    layers = params["layers"]
    if n_layers is not None and n_layers < cfg.layers:
        layers = jax.tree.map(lambda a: a[:n_layers], layers)
    x, _ = jax.lax.scan(body, x, layers)
    return x.astype(jnp.float32)


def count_params(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
