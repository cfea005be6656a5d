"""Causal decoder-only transformer (GPT-2 family) with KV-cache decode,
TPU-first.

The reference's local-LLM chat (``HFPipelineChat``,
``/root/reference/python/pathway/xpacks/llm/llms.py:441-542``) runs a torch
``text-generation`` pipeline host-side. Here generation is TPU-native: the
prefill, every decode step, and the sampling all live inside ONE jitted
function (``generate``), so a whole completion costs a single dispatch
instead of one per token.

Design mirrors ``models/transformer.py`` (the encoder): functional param
pytrees, layers stacked on a leading axis and driven by ``lax.scan``,
compute-dtype matmul outputs/bias/gelu/residuals (attention scores, the
probs@v accumulation, layernorm statistics, and logits stay f32), and
Megatron-style tensor-parallel ``PartitionSpec``s so the same forward runs
1-chip or sharded. The layout is HF-GPT-2-compatible (pre-LN blocks, learned
positions, tanh-approximate gelu, weight-tied LM head); weights load via
``checkpoint.params_from_hf_gpt2`` and logits-parity against transformers
is pinned by ``tests/test_decoder.py``.

Batched generation uses LEFT-padded prompts (the HF convention for batched
decode): every row writes its KV at the same slot each step, so the cache
update is a single ``dynamic_update_slice`` with static shapes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """One decoder block, driven by its configuration. The defaults are
    GPT-2's (pre-LN LayerNorm, learned positions, full multi-head
    attention, tanh gelu, biases, the head tied to ``wte``); every other
    architecture is a configuration of the same block."""

    vocab_size: int = 50257
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    max_position: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # routes the fused int8-weight matmuls through the Pallas kernel
    # (models/wq_matmul.py) — a CONFIG field, not a module global, so the
    # jit caches key on it and a rebuilt server cannot serve stale traces
    wq_kernel: bool = False
    # -- what other architectures set --------------------------------------
    kv_heads: int | None = None     # grouped-query heads (None: = heads)
    head_size: int | None = None    # None: hidden // heads
    norm: str = "layernorm"         # layernorm | rmsnorm
    sandwich_norm: bool = False     # a norm AFTER attention and after MLP
    qk_norm: bool = False           # per-head RMSNorm of q and k
    attn_gate: bool = False         # ctx * sigmoid(x Wg) before Wo
    # learned | rotary | none; a tuple gives one per layer
    positions: Any = "learned"
    rope_theta: float = 10000.0
    mlp: str = "gelu"               # gelu | swiglu
    bias: bool = True
    tied_head: bool = True
    embed_scale: float = 1.0
    layer_types: tuple | None = None  # window | full per layer (None: full)
    sliding_window: int = 0
    dense_layers: int = 0           # leading dense-MLP layers before `moe`
    moe: Any = None                 # models.moe.MoEConfig: routed experts
    # latent attention (kv_rank > 0): queries and key-values through low-rank
    # projections with an inner RMSNorm each; a head's query and key are
    # `nope_size` values without positions beside `rope_size` rotary ones
    # (the rotary key ONE a token, shared by all heads), its value
    # `v_size`. What is cached is the normed latent and the rotated key:
    # kv_rank + rope_size values a token a layer, nothing per head
    q_rank: int = 0                 # 0: queries straight from the hidden
    kv_rank: int = 0
    nope_size: int = 0
    rope_size: int = 0
    v_size: int = 0
    # YaRN (rope_factor > 1): frequencies blended between theta's and the
    # same over `rope_factor` by a ramp between the dimensions at which
    # `rope_original` positions make `rope_beta_fast` and `rope_beta_slow`
    # rotations; `rope_mscale_all_dim` scales the softmax (as its square)
    rope_factor: float = 1.0
    rope_original: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # a LOOPED stack (loops > 1): the layers run `loops` times over the same
    # weights, the final norm closing every pass and feeding the next; pass u
    # of layer l keeps a cache of its own (cache layer u * layers + l: pass u
    # of a later token attends what pass u of the earlier tokens wrote). With
    # `exit_gate` a learned gate reads every pass's normed state and the
    # logits come from the first pass whose cumulative exit probability
    # reaches `exit_threshold`, else the last (1 or more: always the last).
    # EVERY pass runs for every token whatever the gate says
    loops: int = 1
    exit_gate: bool = False
    exit_threshold: float = 1.0

    @property
    def latent(self) -> bool:
        return self.kv_rank > 0

    @property
    def head_dim(self) -> int:
        """A head's query and key size."""
        if self.latent:
            return self.nope_size + self.rope_size
        return self.head_size or self.hidden // self.heads

    @property
    def v_dim(self) -> int:
        """A head's value size (latent attention's differs from its key's)."""
        return self.v_size if self.latent else self.head_dim

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a token: latent and rotary key."""
        return self.kv_rank + self.rope_size

    @property
    def n_kv(self) -> int:
        return self.kv_heads or self.heads

    def layer_kind(self, i: int) -> tuple:
        """``(window | full | latent, learned | rotary | none, dense |
        moe)`` of layer ``i``."""
        attn = "latent" if self.latent else (
            self.layer_types[i] if self.layer_types else "full")
        pos = self.positions if isinstance(self.positions, str) \
            else self.positions[i]
        mlp = "moe" if self.moe is not None and i >= self.dense_layers \
            else "dense"
        return attn, pos, mlp

    def runs(self, n_layers: int | None = None) -> tuple:
        """Stacks of consecutive like layers, ``(kind, first, count)``
        each: ``lax.scan`` runs over one stack at a time. GPT-2 is one."""
        out: list = []
        for i in range(self.layers if n_layers is None else n_layers):
            kind = self.layer_kind(i)
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, i, 1])
        return tuple((k, f, n) for k, f, n in out)

    @property
    def uniform(self) -> bool:
        """One stack of like layers: ``params["layers"]`` is one dict of
        stacked leaves (GPT-2's layout), else ``{"run0": ..., "run1": ...}``
        with one such dict per run."""
        return len(self.runs()) == 1

    def n_layers_of(self, attn: str, n_layers: int | None = None) -> int:
        return sum(n for (a, _p, _m), _f, n in self.runs(n_layers)
                   if a == attn)

    @property
    def learned_positions(self) -> bool:
        return any(k[1] == "learned" for k, _f, _n in self.runs())


GPT2_SMALL = DecoderConfig()
GPT2_MEDIUM = DecoderConfig(hidden=1024, layers=24, heads=16, intermediate=4096)


class UnsupportedForLayout(TypeError):
    """A serving mechanism that this configuration's layers cannot ride
    yet. Raised at construction, naming the mechanism: never a silent
    fallback to another path."""

    def __init__(self, mechanism: str, why: str):
        super().__init__(
            f"{mechanism} is not supported for this decoder layout: {why}")
        self.mechanism = mechanism


def gpt2_block(cfg: DecoderConfig) -> bool:
    """The block is GPT-2's own (the only one the paged pool, the Pallas
    kernels, int8 KV and weights, the lane migration and the serving mesh
    have been written for)."""
    return (cfg.n_kv == cfg.heads and cfg.norm == "layernorm"
            and not cfg.latent and not cfg.sandwich_norm and not cfg.qk_norm
            and not cfg.attn_gate and cfg.positions == "learned"
            and cfg.mlp == "gelu" and cfg.bias and cfg.tied_head
            and cfg.embed_scale == 1.0 and cfg.moe is None
            and not cfg.sliding_window and cfg.head_size is None
            and cfg.loops == 1
            and (cfg.layer_types is None
                 or all(t == "full" for t in cfg.layer_types)))


def require_gpt2_block(cfg: DecoderConfig, mechanism: str) -> None:
    if not gpt2_block(cfg):
        raise UnsupportedForLayout(
            mechanism, "it is written for full multi-head layers with "
            "learned positions, LayerNorm, gelu and a tied head; this "
            "configuration's layers differ"
            + (" (latent attention: one compressed row a token a layer, "
               "no per-head keys and values)" if cfg.latent else "")
            + (f" (a looped stack: {cfg.loops} passes over the same weights, "
               "a cache layer for every pass of every layer)"
               if cfg.loops > 1 else ""))


def require_single_pass(cfg: DecoderConfig, mechanism: str) -> None:
    """A depth prefix of the layers is a prefix of the computation only where
    the stack runs once: the self-speculative draft refuses a looped one."""
    if cfg.loops > 1:
        raise UnsupportedForLayout(
            mechanism, "its draft is a depth prefix of the layers, and a "
            f"stack run {cfg.loops} times has none (a looped stack: the first "
            "layers of the first pass are not a shallower model)")


def _init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _layer_leaves(cfg: DecoderConfig, kind: tuple) -> dict:
    """One layer's leaves for a run of ``kind``: name -> (shape, how, tp
    axis). ``how``: ``one``/``zero`` constants, or the index of the key a
    random matrix draws from (GPT-2's four keep their historical keys, so
    its initial weights are what they always were)."""
    _attn, _pos, mlp = kind
    h, hd, nq, nkv = cfg.hidden, cfg.head_dim, cfg.heads, cfg.n_kv
    ln = cfg.norm == "layernorm"
    out: dict = {}

    def norm(name, width=h):
        out[name + "_scale"] = ((width,), "one", None)
        if ln:
            out[name + "_bias"] = ((width,), "zero", None)

    norm("ln1")
    if _attn == "latent":
        # W_DQ, W_UQ (a head's columns: nope | rope), W_DKV (latent | the
        # shared rotary key), W_UKV (a head's columns: key nope | value)
        if cfg.q_rank:
            out["q_a_w"] = ((h, cfg.q_rank), 16, None)
            out["q_a_norm_scale"] = ((cfg.q_rank,), "one", None)
        out["q_b_w"] = ((cfg.q_rank or h, nq * hd), 17, 1)
        out["kv_a_w"] = ((h, cfg.latent_width), 18, None)
        out["kv_a_norm_scale"] = ((cfg.kv_rank,), "one", None)
        out["kv_b_w"] = ((cfg.kv_rank, nq * (cfg.nope_size + cfg.v_dim)),
                         19, 1)
    else:
        out["qkv_w"] = ((h, (nq + 2 * nkv) * hd), 2, 1)
        if cfg.bias:
            out["qkv_b"] = (((nq + 2 * nkv) * hd,), "zero", 0)
        if cfg.qk_norm:
            out["q_norm_scale"] = ((hd,), "one", None)
            out["k_norm_scale"] = ((hd,), "one", None)
    if cfg.attn_gate:
        out["gate_w"] = ((h, nq * cfg.v_dim), 6, 1)
    out["attn_out_w"] = ((nq * cfg.v_dim, h), 3, 0)
    if cfg.bias:
        out["attn_out_b"] = ((h,), "zero", None)
    if cfg.sandwich_norm:
        norm("ln1p")
    norm("ln2")
    if mlp == "dense":
        i = cfg.intermediate
        out["mlp_in_w"] = ((h, i), 4, 1)
        if cfg.bias:
            out["mlp_in_b"] = ((i,), "zero", 0)
        if cfg.mlp == "swiglu":
            out["mlp_up_w"] = ((h, i), 7, 1)
        out["mlp_out_w"] = ((i, h), 5, 0)
        if cfg.bias:
            out["mlp_out_b"] = ((h,), "zero", None)
    else:
        moe = cfg.moe
        count, w = moe.held_range[1], moe.width
        out["router_w"] = ((h, moe.experts), 8, None)
        if moe.bias:
            out["router_bias"] = ((moe.experts,), "zero", None)
        out["moe_in_w"] = ((count, h, w), 10, None)
        out["moe_up_w"] = ((count, h, w), 11, None)
        out["moe_out_w"] = ((count, w, h), 12, None)
        if moe.shared:
            out["shared_in_w"] = ((h, moe.shared * w), 13, 1)
            out["shared_up_w"] = ((h, moe.shared * w), 14, 1)
            out["shared_out_w"] = ((moe.shared * w, h), 15, 0)
    if cfg.sandwich_norm:
        norm("ln2p")
    return out


# leaves the forward consumes in float32 whatever the compute type: norm
# gains and biases, the router (float32 as published)
_F32_LEAVES = frozenset(
    [f"{ln}_{leaf}" for ln in ("ln1", "ln2", "ln_f", "ln1p", "ln2p",
                               "q_norm", "k_norm", "q_a_norm", "kv_a_norm")
     for leaf in ("scale", "bias")]
    + ["router_w", "router_bias", "exit_w", "exit_b"])


def init_params(rng: jax.Array, cfg: DecoderConfig) -> dict:
    pd = cfg.param_dtype
    h = cfg.hidden
    ks = jax.random.split(rng, 8)

    def key_of(run: int, idx: int):
        key = ks[idx] if idx < 8 else jax.random.fold_in(rng, idx)
        return key if run == 0 else jax.random.fold_in(key, 1000 + run)

    def run_params(r: int, kind: tuple, n: int) -> dict:
        out = {}
        for name, (shape, how, _tp) in _layer_leaves(cfg, kind).items():
            dt = jnp.float32 if name in _F32_LEAVES else pd
            if how == "one":
                out[name] = jnp.ones((n, *shape), dt)
            elif how == "zero":
                out[name] = jnp.zeros((n, *shape), dt)
            else:
                out[name] = _init(key_of(r, how), (n, *shape), dt)
        return out

    runs = [run_params(r, kind, n)
            for r, (kind, _first, n) in enumerate(cfg.runs())]
    params = {"wte": _init(ks[0], (cfg.vocab_size, h), pd)}
    if cfg.learned_positions:
        params["wpe"] = _init(ks[1], (cfg.max_position, h), pd, 0.01)
    params["layers"] = runs[0] if cfg.uniform else {
        f"run{r}": run for r, run in enumerate(runs)}
    params["ln_f_scale"] = jnp.ones((h,), pd)
    if cfg.norm == "layernorm":
        params["ln_f_bias"] = jnp.zeros((h,), pd)
    if not cfg.tied_head:
        # GPT-2's head is weight-tied to wte; an untied one is its own leaf
        params["lm_head"] = _init(jax.random.fold_in(rng, 9),
                                  (cfg.vocab_size, h), pd)
    if cfg.loops > 1 and cfg.exit_gate:
        # the exit gate of a looped stack: Linear(hidden -> 1) with a bias
        params["exit_w"] = _init(jax.random.fold_in(rng, 20), (h, 1),
                                 jnp.float32)
        params["exit_b"] = jnp.zeros((1,), jnp.float32)
    return params


def param_partition_specs(cfg: DecoderConfig, tp_axis: str = "tp") -> dict:
    """Megatron TP: QKV/MLP-in shard output features, attn-out/MLP-out shard
    input features (one psum per block, inserted by XLA); embeddings shard
    the vocab dim, which also shards the LM-head logits. Experts stay whole
    (their own axis is ``ep``: ``models/moe.py``)."""
    t = tp_axis

    def run_specs(kind: tuple) -> dict:
        out = {}
        for name, (shape, _how, tp) in _layer_leaves(cfg, kind).items():
            spec = [None] * (1 + len(shape))
            if tp is not None:
                spec[1 + tp] = t
            out[name] = P(*spec)
        return out

    runs = [run_specs(kind) for kind, _first, _n in cfg.runs()]
    specs = {"wte": P(t, None)}
    if cfg.learned_positions:
        specs["wpe"] = P(None, None)
    specs["layers"] = runs[0] if cfg.uniform else {
        f"run{r}": run for r, run in enumerate(runs)}
    specs["ln_f_scale"] = P(None)
    if cfg.norm == "layernorm":
        specs["ln_f_bias"] = P(None)
    if not cfg.tied_head:
        specs["lm_head"] = P(t, None)
    if cfg.loops > 1 and cfg.exit_gate:
        specs["exit_w"] = P(None, None)
        specs["exit_b"] = P(None)
    return specs


# ---- serving-mesh placement (PATHWAY_TPU_MESH) ----------------------------
#
# The specs above describe WHAT shards over tp; the helpers below bind
# them to a concrete ``(data, fsdp, tp)`` serving mesh
# (``parallel/mesh.py:make_serving_mesh``): params get the Megatron
# layout plus an fsdp overlay on whatever tp left replicated, and the
# KV pool (dense or paged, arena included) shards its HEAD axis over tp
# — attention is per-head, so every pool op partitions with zero
# cross-shard traffic except the one psum per block the param specs
# already imply. Divisibility is validated host-side
# (:class:`parallel.mesh.MeshShapeError`), never left to XLA.


def validate_decoder_mesh(cfg: DecoderConfig, mesh) -> None:
    """Raise a typed ``MeshShapeError`` when ``cfg`` cannot shard over
    ``mesh``'s tp axis: heads, ffn features and vocab must all divide."""
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS, MeshShapeError

    require_gpt2_block(cfg, "mesh")
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
            f"decoder config does not divide the tp axis: {', '.join(bad)} "
            f"% tp={tp} != 0",
            data=int(mesh.shape.get("data", 1)),
            fsdp=int(mesh.shape.get("fsdp", 1)),
            tp=tp, n_devices=int(mesh.devices.size),
        )


def param_mesh_specs(params: dict, cfg: DecoderConfig, mesh) -> dict:
    """Per-param ``PartitionSpec`` pytree for the serving mesh: the
    Megatron tp layout of :func:`param_partition_specs` with the fsdp
    axis overlaid on each param's first unsharded divisible dim."""
    from pathway_tpu.parallel.mesh import (
        SERVE_FSDP_AXIS, SERVE_TP_AXIS, spec_with_fsdp,
    )

    from pathway_tpu.parallel.mesh import spec_dropping_nondividing

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
            # int8 weight-quant scale plane (quantize_params): inherit
            # the payload's tp spec with non-dividing axes dropped — the
            # keepdims size-1 contracted dim degrades to replicated, the
            # output-channel dim keeps its shard so scale rows co-locate
            # with their int8 columns.
            s = spec_dropping_nondividing(
                node[name[: -len("_scale")]], leaf.shape, mesh)
        else:
            raise KeyError(f"no partition spec for decoder param {name!r}")
        return spec_with_fsdp(s, leaf.shape, fsdp)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def pool_partition_specs(pool: dict, mesh) -> dict:
    """Per-plane ``PartitionSpec``s for a serving pool (dense or paged):
    KV planes and their int8 scales shard the HEAD axis over tp, logits
    shard the vocab (matching the vocab-sharded tied LM head, so the
    decode-step write needs no resharding), and the block table /
    masks / cursors replicate."""
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS

    t = SERVE_TP_AXIS
    tp = int(mesh.shape.get(t, 1))
    head3 = P(None, None, t, None, None)  # (L, S|NB, nh, T|Bk, d)
    arena = P(None, None, t, None, None)  # (A, L, nh, Bk, d)
    specs: dict = {}
    for key in pool:
        if key in ("k", "v", "k_scale", "v_scale", "kb", "vb",
                   "kb_scale", "vb_scale"):
            specs[key] = head3
        elif key in ("arena_k", "arena_v", "arena_k_scale",
                     "arena_v_scale"):
            specs[key] = arena
        elif key == "logits" and pool[key].shape[1] % tp == 0:
            specs[key] = P(None, t)
        else:
            specs[key] = P()
    return specs


def shard_decoder_params(params: dict, cfg: DecoderConfig, mesh) -> dict:
    """Commit ``params`` onto the serving mesh with the Megatron + fsdp
    layout (validated first). No-op when ``mesh`` is None."""
    from pathway_tpu.parallel.mesh import place_pytree

    if mesh is None:
        return params
    validate_decoder_mesh(cfg, mesh)
    return place_pytree(params, mesh, param_mesh_specs(params, cfg, mesh))


def shard_pool(pool: dict, cfg: DecoderConfig, mesh) -> dict:
    """Commit a freshly built serving pool onto the mesh (head axis over
    tp). Jitted pool ops then inherit the layout through GSPMD sharding
    propagation, and donation keeps it across dispatches. No-op when
    ``mesh`` is None."""
    from pathway_tpu.parallel.mesh import place_pytree

    if mesh is None:
        return pool
    validate_decoder_mesh(cfg, mesh)
    return place_pytree(pool, mesh, pool_partition_specs(pool, mesh))


def _ln(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)


def _split_heads(x, nh, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)  # (B, nh, S, hd)


# ---- int8 KV quantization (PATHWAY_TPU_KV_QUANT=int8) ---------------------
#
# Decode streams the whole KV cache from HBM every step, so halving its
# bytes is a direct decode-throughput lever (not measured on the
# chip). Storage is symmetric per-(layer, slot, head, token)
# int8: one f32 scale per head-token (max|x| / 127 over the head dim)
# rides next to the payload, so a head-token costs hd + 4 bytes instead
# of 2*hd bf16 bytes — 1.88x the slots per HBM byte at hd=64. Writes
# quantize (`_kv_quant`), reads dequantize inside `_block` just before
# the attention matmuls; presence of a ``k_scale`` key in the pool dict
# is the static format marker every pool function branches on.

_KV_QMAX = 127.0
_KV_SCALE_FLOOR = 1e-8  # all-zero rows (padding) quantize to exact zeros


def _kv_quant(x):
    """Symmetric int8 quantization over the last (head) dim: returns
    ``(payload int8, scale f32 (..., 1))`` with ``x ~= payload * scale``.
    By construction ``|x| / scale <= 127`` so the round never clips."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / _KV_QMAX, _KV_SCALE_FLOOR)
    return jnp.round(xf / scale).astype(jnp.int8), scale


def kv_token_bytes(cfg: DecoderConfig, itemsize: int,
                   quant: bool = False) -> int:
    """Cache bytes one token costs over all cache layers (a looped stack
    keeps one for every pass of every layer): K and V of every key-value head
    (int8 with a float32 scale a head-token where ``quant``), or a latent
    layer's one row."""
    cache_layers = cfg.loops * cfg.layers
    if cfg.latent:
        return cache_layers * cfg.latent_width * itemsize
    per_head = cfg.head_dim + 4 if quant else cfg.head_dim * itemsize
    return 2 * cache_layers * cfg.n_kv * per_head


def pool_quantized(pool: dict) -> bool:
    """True when the pool stores int8 KV (``pool_init(kv_quant=True)`` /
    ``paged_pool_init(kv_quant=True)``)."""
    return "k_scale" in pool or "kb_scale" in pool


# ---- weight-only int8 quantization (PATHWAY_TPU_WEIGHT_QUANT=int8) --------
#
# Decode streams the WHOLE parameter set from HBM every step (spec decode
# amortizes it over k+1 tokens, but the stream itself is full-precision).
# Weight-only quantization stores every large matmul weight — qkv_w,
# attn_out_w, the MLP pair, and wte (embedding table AND tied LM head) —
# as symmetric per-output-channel int8 with one f32 scale per output
# channel (max|w| / 127 over the CONTRACTED axis), the standard roofline
# move for a memory-bound decode. Dequant is fused into the matmul read:
# the int8 payload feeds the einsum directly (int8 values <= 127 are
# exact in bf16) with f32 accumulation, and the per-output-channel scale
# multiplies the OUTPUT — algebraically identical to dequantizing the
# weight first, without ever materializing a full-precision copy.
# Presence of a ``wte_scale`` key is the static format marker every
# forward path branches on (mirroring the pool's ``k_scale``), so
# prefill, chunked prefill, decode chunks, spec draft/verify and the
# paged kernel path all pick the quantized read up from ONE seam
# (:func:`_wq_matmul` / :func:`_tok_embed` / :func:`_logits`) without
# forking numerics. With no scale keys present every branch reproduces
# the historical ops byte-for-byte (tests/test_weight_quant.py pins it).

_WQ_QMAX = 127.0
_WQ_SCALE_FLOOR = 1e-8  # all-zero channels quantize to exact zeros
# the decoder leaves that quantize, with their contracted axis
_WQ_LAYER_WEIGHTS = ("qkv_w", "attn_out_w", "mlp_in_w", "mlp_out_w")


def _wq_quant(w, axis: int):
    """Symmetric int8 quantization of one weight over its contracted
    ``axis``: returns ``(payload int8, scale f32)`` with the scale
    keeping a size-1 dim at ``axis`` (one scale per OUTPUT channel).
    ``|w| / scale <= 127`` by construction, so the round never clips."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / _WQ_QMAX, _WQ_SCALE_FLOOR)
    return jnp.round(wf / scale).astype(jnp.int8), scale


def params_quantized(params: dict) -> bool:
    """True when ``params`` store int8 weights (:func:`quantize_params`)."""
    return "wte_scale" in params


def quantize_params(params: dict, cfg: DecoderConfig) -> dict:
    """int8-quantize the large decoder weights for serving: wte (and the
    tied LM head with it) per vocab row, each stacked layer weight per
    output channel. Everything else (wpe, biases, layernorms) keeps the
    :func:`cast_params_for_inference` treatment. Scales are computed from
    the ORIGINAL full-precision leaves — quantizing after a bf16 cast
    would bake the cast's mantissa loss into the scales."""
    require_gpt2_block(cfg, "weight_quant")
    out = dict(cast_params_for_inference(params, cfg))
    out["wte"], out["wte_scale"] = _wq_quant(params["wte"], axis=-1)
    layers = dict(out["layers"])
    for name in _WQ_LAYER_WEIGHTS:
        q, s = _wq_quant(params["layers"][name], axis=-2)
        layers[name], layers[name + "_scale"] = q, s
    out["layers"] = layers
    return out


def _wq_matmul(eq: str, x, lp: dict, name: str, cfg: DecoderConfig):
    """The ONE weight-matmul seam: ``einsum(eq, x, lp[name])`` with the
    historical unquantized ops when ``lp`` has no ``{name}_scale`` key
    (byte-identical — same cast, same accumulation preference), or the
    fused-dequant int8 read when it does: int8 payload in the compute
    dtype, f32 accumulation, per-output-channel scale applied to the
    output. ``cfg.wq_kernel`` routes the quantized branch through the
    Pallas fused kernel (models/wq_matmul.py) when the operand layout
    fits; the XLA expression is the fallback and the reference."""
    w = lp[name]
    scale = lp.get(name + "_scale")
    if scale is None:
        return jnp.einsum(eq, x, w.astype(cfg.dtype),
                          preferred_element_type=cfg.dtype)
    if cfg.wq_kernel and x.ndim == 3 and w.ndim == 2:
        from pathway_tpu.models import wq_matmul as _wqk

        B, S, K = x.shape
        out = _wqk.wq_matmul(
            x.reshape(B * S, K), w, scale.reshape(1, -1)
        ).reshape(B, S, w.shape[-1])
        return out.astype(cfg.dtype)
    out = jnp.einsum(eq, x, w.astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    return (out * scale).astype(cfg.dtype)


def _tok_embed(params: dict, ids: jax.Array) -> jax.Array:
    """Token-embedding gather with dequant fused into the row read:
    unquantized tables pass the gathered rows through untouched (the
    historical expression, byte-identical); int8 tables dequantize the
    gathered rows with their per-row scales — O(rows) work, never the
    full table."""
    rows = params["wte"][ids]
    s = params.get("wte_scale")
    if s is None:
        return rows
    return rows.astype(jnp.float32) * s[ids]


def params_device_bytes(params: dict) -> dict[str, int]:
    """Physical param bytes per device id (scales included), from each
    leaf's addressable shards — the ``weights.*`` HBM ledger's source,
    mirroring :func:`pool_component_device_bytes` for the KV pool."""
    out: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for dev, n in _device_bytes(leaf).items():
            out[dev] = out.get(dev, 0) + n
    return out


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def _norm(x, lp, name: str, cfg: DecoderConfig):
    """The configuration's norm (float32 out), by the leaves' prefix."""
    if cfg.norm == "layernorm":
        return _ln(x, lp[name + "_scale"], lp[name + "_bias"],
                   cfg.layer_norm_eps)
    return _rms(x, lp[name + "_scale"], cfg.layer_norm_eps)


def yarn_inv_freq(cfg: DecoderConfig, dim: int):
    """YaRN's frequencies for a rotary head of ``dim`` values (numpy, at
    trace time): ``theta``'s own where ``rope_original`` positions make more
    than ``rope_beta_fast`` rotations, the same over ``rope_factor`` where
    they make fewer than ``rope_beta_slow``, a linear ramp between."""
    import numpy as np

    half = dim // 2
    extra = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(cfg.rope_original / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    return (extra / cfg.rope_factor * ramp + extra * (1 - ramp)
            ).astype(np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attn_scale(cfg: DecoderConfig) -> float:
    """What a latent layer's scores are multiplied by: the head's
    ``size^-1/2`` times YaRN's ``m(mscale_all_dim)`` squared."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return m * m / math.sqrt(cfg.head_dim)


def _rope(t, pos, theta: float, cfg: DecoderConfig | None = None):
    """Rotary positions on ``t`` (B, n, S, hd) at ``pos`` (B, S), halves
    rotated against each other (the released code's ``rotate_half``). With
    ``cfg`` scaled by YaRN, the frequencies are :func:`yarn_inv_freq`'s and
    cos and sin carry ``m(mscale) / m(mscale_all_dim)``."""
    half = t.shape[-1] // 2
    if cfg is not None and cfg.rope_factor > 1:
        inv = jnp.asarray(yarn_inv_freq(cfg, t.shape[-1]))
        amp = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
            / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    else:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        amp = 1.0
    ang = pos.astype(jnp.float32)[:, None, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    t = t.astype(jnp.float32)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


_GPT2_KIND = ("full", "learned", "dense")


def _project(x, lp, cfg: DecoderConfig, kind: tuple, pos, want_q: bool):
    """The block's first norm and fused QKV projection, head-split, with
    what the configuration puts on q and k (per-head RMSNorm, rotary
    positions on a rotary layer): ``(q | None, k, v)``, q (B, nq, S, hd),
    k and v (B, nkv, S, hd). A latent layer gives ``(q, c, None)``
    (:func:`_project_latent`)."""
    nq, nkv, hd = cfg.heads, cfg.n_kv, cfg.head_dim
    h1 = _norm(x, lp, "ln1", cfg)
    if kind[0] == "latent":
        return _project_latent(h1.astype(cfg.dtype), lp, cfg, pos, want_q)
    qkv = _wq_matmul("bsh,hk->bsk", h1.astype(cfg.dtype), lp, "qkv_w", cfg)
    if cfg.bias:
        qkv = qkv + lp["qkv_b"].astype(cfg.dtype)
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    q = _split_heads(q, nq, hd) if want_q else None
    k, v = _split_heads(k, nkv, hd), _split_heads(v, nkv, hd)
    if cfg.qk_norm:
        if want_q:
            q = _rms(q, lp["q_norm_scale"], cfg.layer_norm_eps)
        k = _rms(k, lp["k_norm_scale"], cfg.layer_norm_eps)
    if kind[1] == "rotary":
        if want_q:
            q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
    if want_q:
        q = q.astype(cfg.dtype)
    return q, k.astype(cfg.dtype), v.astype(cfg.dtype)


def _project_latent(h1, lp, cfg: DecoderConfig, pos, want_q: bool):
    """A latent layer's projections of the normed ``h1`` (B, S, H):
    ``(q | None, c, None)``. ``q`` (B, nq, S, nope + rope), its last
    ``rope`` values rotated; ``c`` (B, 1, S, kv_rank + rope) what the layer
    CACHES of a token: the latent after its norm, then the one rotary key
    all heads share, after its rotation."""
    B, S, _H = h1.shape
    nq, rank, rd = cfg.heads, cfg.kv_rank, cfg.rope_size
    eps = cfg.layer_norm_eps
    q = None
    if want_q:
        cq = h1
        if cfg.q_rank:
            cq = _wq_matmul("bsh,hk->bsk", h1, lp, "q_a_w", cfg)
            cq = _rms(cq, lp["q_a_norm_scale"], eps).astype(cfg.dtype)
        q = _split_heads(_wq_matmul("bsh,hk->bsk", cq, lp, "q_b_w", cfg),
                         nq, cfg.head_dim)
        q = jnp.concatenate(
            [q[..., :cfg.nope_size],
             _rope(q[..., cfg.nope_size:], pos, cfg.rope_theta, cfg
                   ).astype(cfg.dtype)], axis=-1)
    ckv = _wq_matmul("bsh,hk->bsk", h1, lp, "kv_a_w", cfg)
    c = jnp.concatenate(
        [_rms(ckv[..., :rank], lp["kv_a_norm_scale"], eps),
         _rope(ckv[:, None, :, rank:], pos, cfg.rope_theta, cfg)[:, 0]],
        axis=-1).astype(cfg.dtype)
    return q, c.reshape(B, 1, S, rank + rd), None


def latent_absorbed(cfg: DecoderConfig, n_queries: int) -> bool:
    """THE rule that chooses a latent layer's read, from the shapes in hand:
    ABSORBED (``W_UK`` folded into the queries, ``W_UV`` applied to the
    context; every head reads the latent row as it lies) where that is fewer
    operations a key row than EXPANDING the row into per-head keys and
    values first. A decode step's handful of queries read absorbed; a
    prefill piece's hundreds read expanded."""
    nq, rank = cfg.heads, cfg.kv_rank
    expanded = 2 * rank * nq * (cfg.nope_size + cfg.v_dim) \
        + 2 * n_queries * nq * (cfg.head_dim + cfg.v_dim)
    absorbed = 2 * n_queries * nq * (2 * rank + cfg.rope_size)
    return absorbed <= expanded


def _w_ukv(lp, cfg: DecoderConfig):
    """``W_UKV`` by head, (kv_rank, nq, nope + v): ``[..., :nope]`` is
    ``W_UK`` and ``[..., nope:]`` ``W_UV``, views of the one leaf."""
    return lp["kv_b_w"].astype(cfg.dtype).reshape(
        cfg.kv_rank, cfg.heads, cfg.nope_size + cfg.v_dim)


def latent_expand(c, lp, cfg: DecoderConfig):
    """Latent rows ``c`` (B, 1, C, kv_rank + rope) as per-head keys
    (B, nq, C, nope + rope) and values (B, nq, C, v): for the time of one
    read, never kept."""
    with jax.named_scope("mla.expand"):
        rank, nope = cfg.kv_rank, cfg.nope_size
        kv = jnp.einsum("bcr,rnd->bncd", c[:, 0, :, :rank], _w_ukv(lp, cfg),
                        preferred_element_type=cfg.dtype)
        k_pe = jnp.broadcast_to(c[:, :, :, rank:],
                                (*kv.shape[:3], cfg.rope_size))
        return (jnp.concatenate([kv[..., :nope], k_pe], axis=-1),
                kv[..., nope:])


def _latent_ctx(q, c, lp, mask_bias, cfg: DecoderConfig,
                absorbed: bool | None = None):
    """A latent layer's attention read of rows ``c`` (B, 1, C, kv_rank +
    rope) by queries ``q`` (B, nq, Sq, nope + rope): (B, nq, Sq, v). Both
    forms are the same mathematics (:func:`latent_absorbed` chooses)."""
    if absorbed is None:
        absorbed = latent_absorbed(cfg, q.shape[2])
    scale = attn_scale(cfg)
    if not absorbed:
        k, v = latent_expand(c, lp, cfg)
        return _attn_ctx(q, k, v, mask_bias, cfg, scale=scale)
    with jax.named_scope("mla.absorb"):
        rank, nope = cfg.kv_rank, cfg.nope_size
        w = _w_ukv(lp, cfg)
        # q' = q_nope W_UK^T: a head's query against the latent itself
        q_lat = jnp.einsum("bnsd,rnd->bnsr", q[..., :nope], w[..., :nope],
                           preferred_element_type=cfg.dtype)
        q_abs = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
        # 128 query heads over ONE key row, whose first kv_rank values are
        # the value row too: the grouped-query read with one key-value head
        ctx = _attn_ctx(q_abs, c, c, mask_bias, cfg, scale=scale)
        return jnp.einsum("bnsr,rnd->bnsd", ctx[..., :rank], w[..., nope:],
                          preferred_element_type=cfg.dtype)


def _block_qkv(x, lp, cfg: DecoderConfig, kind: tuple = _GPT2_KIND,
               pos=None):
    """First norm + fused QKV projection, head-split: ``(q, k_new,
    v_new)``. Shared by :func:`_block` and the paged-kernel decode path,
    so both read identical projections."""
    return _project(x, lp, cfg, kind, pos, True)


def _attn_ctx(q, k, v, mask_bias, cfg: DecoderConfig, k_scale=None,
              v_scale=None, scale: float | None = None):
    """Attention read over ALREADY-PROJECTED k/v: scores in f32, softmax,
    f32-accumulated probs@v. With ``k_scale``/``v_scale`` given, k/v
    arrive as int8 payloads and dequantize here, on read — the one place
    every dense decode/prefill variant funnels through, so quantized
    serving cannot fork the numerics. The Pallas paged kernel
    (``models/paged_attention.py``) is the block-table counterpart of
    exactly this function. With fewer key-value heads than query heads
    each is shared by ``heads // kv_heads`` query heads (grouped query).
    ``scale`` multiplies the scores in place of ``head_dim^-1/2``; the
    context has the values' size."""
    if k_scale is not None:
        k = (k.astype(jnp.float32) * k_scale).astype(cfg.dtype)
        v = (v.astype(jnp.float32) * v_scale).astype(cfg.dtype)
    if k.shape[1] != q.shape[1]:
        B, nq, Sq, hd = q.shape
        nkv = k.shape[1]
        qg = q.reshape(B, nkv, nq // nkv, Sq, hd)
        scores = jnp.einsum("bngqd,bnkd->bngqk", qg, k.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        scores = (scores / math.sqrt(cfg.head_dim) if scale is None
                  else scores * scale) + mask_bias[:, :, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v.astype(cfg.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(cfg.dtype).reshape(B, nq, Sq, v.shape[-1])
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    scores = (scores / math.sqrt(cfg.head_dim) if scale is None
              else scores * scale) + mask_bias
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    # the weighted-sum over up to cache_len values keeps GUARANTEED f32
    # accumulation (same as the encoder's explicit-softmax path) — with a
    # bf16 preference some backends may use bf16 partial sums
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v.astype(cfg.dtype),
                      preferred_element_type=jnp.float32).astype(cfg.dtype)


def _block_finish(x, lp, ctx, cfg: DecoderConfig,
                  kind: tuple = _GPT2_KIND):
    """Post-attention half of the block: output gate, output projection,
    residual, MLP (dense, or routed experts: ``models/moe.py``). ``ctx``
    is the attention read (B, nh, S, hd). Returns ``(x, counts)``:
    ``counts`` the expert layer's (held, all) assignments, else None."""
    B, S, _H = x.shape
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, cfg.heads * cfg.v_dim)
    if cfg.attn_gate:
        h1 = _norm(x, lp, "ln1", cfg).astype(cfg.dtype)
        gate = _wq_matmul("bsh,hk->bsk", h1, lp, "gate_w", cfg)
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)
                                   ).astype(cfg.dtype)
    attn = _wq_matmul("bsh,hk->bsk", ctx, lp, "attn_out_w", cfg)
    if cfg.sandwich_norm:
        attn = _norm(attn, lp, "ln1p", cfg).astype(cfg.dtype)
    if cfg.bias:
        x = x + attn + lp["attn_out_b"].astype(cfg.dtype)
    else:
        x = x + attn
    h2 = _norm(x, lp, "ln2", cfg)
    counts = None
    if kind[2] == "moe":
        from pathway_tpu.models import moe as _moe

        m, counts = _moe.moe_mlp(h2, lp, cfg.moe, cfg.dtype)
    else:
        m = _wq_matmul("bsh,hi->bsi", h2.astype(cfg.dtype), lp, "mlp_in_w",
                       cfg)
        if cfg.bias:
            m = m + lp["mlp_in_b"].astype(cfg.dtype)
        if cfg.mlp == "swiglu":
            up = _wq_matmul("bsh,hi->bsi", h2.astype(cfg.dtype), lp,
                            "mlp_up_w", cfg)
            m = jax.nn.silu(m) * up
        else:
            # gelu_new (tanh approximation) — what GPT-2 checkpoints are
            # trained with
            m = jax.nn.gelu(m, approximate=True)
        m = _wq_matmul("bsi,ih->bsh", m, lp, "mlp_out_w", cfg)
    if cfg.sandwich_norm:
        m = _norm(m, lp, "ln2p", cfg).astype(cfg.dtype)
    if cfg.bias:
        x = x + m + lp["mlp_out_b"].astype(cfg.dtype)
    else:
        x = x + m
    return x.astype(cfg.dtype), counts


def _block(x, lp, k, v, mask_bias, cfg: DecoderConfig, k_scale=None,
           v_scale=None, ctx_fn=None, kind: tuple = _GPT2_KIND, pos=None):
    """One decoder block over ALREADY-PROJECTED k/v (B, nkv, Skv, hd); a
    latent layer's ``k`` is its cached rows (B, 1, Skv, kv_rank + rope) and
    its ``v`` None (:func:`_latent_ctx`).

    The caller owns the KV source — the in-sequence keys for prefill, the
    cache for decode — so prefill and decode share one block body and
    cannot diverge numerically. Composed of :func:`_block_qkv` →
    :func:`_attn_ctx` → :func:`_block_finish`; matmul outputs / bias /
    activation / residuals stay in cfg.dtype (the MXU accumulates f32
    internally; attention SCORES and norm statistics stay f32) — same
    HBM-traffic optimization as the encoder's _layer, bit-unchanged for
    f32 configs. ``kind`` is the layer's (:meth:`DecoderConfig.layer_kind`);
    ``pos`` (B, S) the queries' positions, read by rotary layers.

    ``ctx_fn(q, k, v, k_scale, v_scale) -> (B, nh, Sq, hd)`` swaps the
    dense :func:`_attn_ctx` read for an alternative (the flash-prefill
    Pallas kernels); it owns scaling and masking, mirroring the
    encoder's ``core`` seam. ``None`` (default) keeps the dense path
    byte-identical. Returns ``(x, counts)`` (:func:`_block_finish`)."""
    q, _k_new, _v_new = _block_qkv(x, lp, cfg, kind, pos)
    with jax.named_scope("decoder.attn." + kind[0]):
        if ctx_fn is not None:
            ctx = ctx_fn(q, k, v, k_scale, v_scale).astype(cfg.dtype)
        elif kind[0] == "latent":
            ctx = _latent_ctx(q, k, lp, mask_bias, cfg)
        else:
            ctx = _attn_ctx(q, k, v, mask_bias, cfg, k_scale, v_scale)
    return _block_finish(x, lp, ctx, cfg, kind)


def _flash_self_attn_fn(mesh):
    """The whole-sequence flash-attention entry the prefill paths call
    as a ``_block`` ``ctx_fn`` factory: the plain Pallas kernel on a
    single chip, or a ``shard_map``-wrapped version on a serving mesh
    with tp > 1 (q/k/v all carry the head axis, attention never mixes
    heads, so the UNCHANGED kernel runs per shard with no collective —
    the same treatment as :func:`_paged_attn_fn`)."""
    from pathway_tpu.models import flash_attention as _fa

    def plain(q, k, v, mask):
        return _fa.flash_attn(q, k, v, mask, causal=True)

    if mesh is None:
        return plain
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS

    if int(mesh.shape.get(SERVE_TP_AXIS, 1)) == 1:
        return plain
    t = SERVE_TP_AXIS
    head = P(None, t, None, None)  # q / k / v / ctx: (B, nh, S, hd)
    rep = P(None, None)            # attention mask: (B, S)
    return jax.shard_map(
        plain, mesh=mesh, in_specs=(head, head, head, rep),
        out_specs=head, check_vma=False,
    )


# The dense read of a prefill piece writes ``heads x T x columns`` float32
# scores to HBM, and reads and writes them again for each pass of the softmax
# and for the probabilities; the blockwise read keeps them in VMEM and skips
# the blocks no query can see. Past this many bytes of scores the dense read's
# trips through HBM cost more than the blockwise read's fixed costs (a kernel
# call a layer, a grid step a block): the serving defaults of a GPT-2-sized
# model stay under it by a factor of ten (16 heads x 64 x 656: 2.7 MB), a
# long-context row is over it by as much (48 x 512 x 8,304: 816 MB).
_DENSE_SCORE_BYTES = 32 << 20


def blockwise_chunk_read(heads: int, T: int, columns: int) -> bool:
    """THE rule that chooses a prefill piece's attention read, from the
    shapes in hand: ``T`` queries of ``heads`` heads against a row of
    ``columns`` keys (:func:`pool_prefill_chunk`)."""
    return heads * T * columns * 4 > _DENSE_SCORE_BYTES


def _flash_chunk_attn_fn(mesh, quant, window=0):
    """Chunk-vs-cache flash entry for :func:`pool_prefill_chunk`,
    adapting ``_block``'s (1, nh, ...) operands to the batchless kernel
    layout. Quantized pools get a separate wrapper because ``shard_map``
    in_specs cannot describe the ``None`` scale operands of the
    full-precision layout (same split as :func:`_paged_attn_fn`)."""
    from pathway_tpu.models import flash_attention as _fa

    def plain(q, k_row, v_row, ks_row, vs_row, kcol, start):
        return _fa.flash_chunk_attn(
            q[0], k_row[0], v_row[0], kcol[0], start, window=window,
            k_scale=None if ks_row is None else ks_row[0],
            v_scale=None if vs_row is None else vs_row[0],
        )[None]

    if mesh is None:
        return plain
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS

    if int(mesh.shape.get(SERVE_TP_AXIS, 1)) == 1:
        return plain
    t = SERVE_TP_AXIS
    head = P(None, t, None, None)  # q / rows / scales: (1, nh, ., .)
    rep = P(None, None)            # key columns: (1, C)
    if quant:
        return jax.shard_map(
            plain, mesh=mesh,
            in_specs=(head, head, head, head, head, rep, P()),
            out_specs=head, check_vma=False,
        )

    def unquant(q, k_row, v_row, kcol, start):
        return plain(q, k_row, v_row, None, None, kcol, start)

    mapped = jax.shard_map(
        unquant, mesh=mesh, in_specs=(head, head, head, rep, P()),
        out_specs=head, check_vma=False,
    )
    return lambda q, k_row, v_row, _ks, _vs, kcol, start: \
        mapped(q, k_row, v_row, kcol, start)


def _final_norm(params, x, cfg):
    if cfg.norm == "layernorm":
        return _ln(x, params["ln_f_scale"], params["ln_f_bias"],
                   cfg.layer_norm_eps)
    return _rms(x, params["ln_f_scale"], cfg.layer_norm_eps)


def _logits(params, x, cfg):
    # a looped stack's state is normed already: the final norm closes every
    # pass (:func:`_scan_layers`), the last like the others
    h = x if cfg.loops > 1 else _final_norm(params, x, cfg)
    head = params["wte"] if cfg.tied_head else params["lm_head"]
    out = jnp.einsum("bsh,vh->bsv", h.astype(cfg.dtype),
                     head.astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    s = params.get("wte_scale")
    if s is not None:
        # tied LM head over the int8 table: wte_scale is (V, 1) — one
        # scale per vocab row == per output channel of this einsum
        out = out * s[:, 0]
    return out


def _embed(params, ids, pos, cfg: DecoderConfig):
    """Token rows (scaled where the configuration says so) plus learned
    positions where it has them: ``ids``/``pos`` (B, S) -> (B, S, H)."""
    x = _tok_embed(params, ids)
    if cfg.embed_scale != 1.0:
        x = x.astype(jnp.float32) * cfg.embed_scale
    if cfg.learned_positions:
        x = x + params["wpe"][pos]
    return x.astype(cfg.dtype)


# ---- stacks of like layers -------------------------------------------------
#
# Every pass over the layers goes through :func:`_scan_layers`, which runs
# one ``lax.scan`` per run of like layers (:meth:`DecoderConfig.runs`). GPT-2
# is one run: its KV is ``k``/``v`` (and the int8 pool's ``k_scale``/
# ``v_scale``), one scan over the whole stack. A model of several runs keeps
# ONE pair of arrays per run, named for the run's kind and number
# (``kf2``/``vf2``: run 2, full attention, rows of ``cache_len``;
# ``kw3``/``vw3``: run 3, window layers, rings; ``cl1``: run 1, latent rows).
#
# A run's arrays ride the scan's CARRY, whole, beside ``x``; what is scanned
# is the layer's own small leaves and the layer INDEX. A scan cannot alias a
# scanned input with a scanned output, so a stack handed over as one is cut
# out of, a layer's rows of every slot at a time, and put back; a carried
# buffer is written where it lies. So a layer's body writes its new rows at
# ``(layer, slot, 0, column, 0)`` of the stack (:func:`_kv_put`,
# :func:`_kv_put_lanes`, :func:`_ring_put`) and reads what it needs of it
# (:func:`_kv_rows`): the pool is donated to every dispatch, and no dispatch
# copies one of its arrays.

def _kv_names(cfg: DecoderConfig, r: int, kind: tuple) -> tuple:
    """Names of run ``r``'s (k, v, k_scale, v_scale) arrays in a pool or a
    prefill cache."""
    if kind[0] == "latent":
        # ONE array a run, (layers, slots, 1, columns, kv_rank + rope): the
        # key row of the absorbed read, whose unit axis is the one key-value
        # head every query head shares; there is no second array
        return (f"cl{r}", None, None, None)
    if cfg.uniform:
        return ("k", "v", "k_scale", "v_scale")
    t = "w" if kind[0] == "window" else "f"
    return (f"k{t}{r}", f"v{t}{r}", None, None)


def _is_kv(name: str, window: bool | None = None) -> bool:
    """``name`` is a run's KV array (of a window run / of a run whose rows
    are whole, full or latent, where ``window`` says which)."""
    if name in ("k", "v"):
        return not window
    ok = (len(name) > 2 and name[:2] in ("kf", "vf", "kw", "vw", "cl")
          and name[2:].isdigit())
    return ok and (window is None or (name[1] == "w") == window)


def _kv_stacks(pool: dict) -> dict:
    """The pool's KV arrays (and int8 scale planes), by name."""
    return {n: a for n, a in pool.items()
            if _is_kv(n) or n in ("k_scale", "v_scale")}


def _run_stacks(params: dict, cfg: DecoderConfig) -> list:
    """The stacked leaves of each run of like layers, in layer order:
    ``params["layers"]`` itself where the model is one run (GPT-2's
    layout), else its entries ``run0``, ``run1``, ..."""
    if cfg.uniform:
        return [params["layers"]]
    return [params["layers"][f"run{r}"] for r in range(len(cfg.runs()))]


_EXPERT_LEAVES = ("moe_in_w", "moe_up_w", "moe_out_w")


_KV_SHORT = ("k", "v", "k_scale", "v_scale")


def _kv_put(stack, new, layer, slot, col):
    """``new`` (b, heads, t, d) written at ``(layer, slot, 0, col, 0)`` of a
    run's ``stack`` (layers, slots, heads, columns, d), where it lies."""
    return jax.lax.dynamic_update_slice(
        stack, new[None].astype(stack.dtype), (layer, slot, 0, col, 0))


def _kv_rows(stack, layer, slot=None, col=0, n: int | None = None):
    """What a layer reads of its run's ``stack``: ``n`` rows from ``col``
    (all by default) of one ``slot``, (1, heads, n, d); without a slot the
    layer's rows of EVERY slot, (slots, heads, columns, d)."""
    if slot is None:
        return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    _L, _S, nh, C, d = stack.shape
    return jax.lax.dynamic_slice(
        stack, (layer, slot, 0, col, 0),
        (1, 1, nh, C if n is None else n, d))[0]


def _kv_put_lanes(stack, new, layer, cols, active):
    """Every lane at its own columns: ``new`` (B, heads, t, d) written at
    columns ``cols`` (B, t) of lane b's row of ``layer``; a lane that is not
    ``active`` (B,) keeps its bytes."""
    b = jnp.arange(new.shape[0])[:, None]
    old = stack[layer, b, :, cols, :]                   # (B, t, heads, d)
    return stack.at[layer, b, :, cols, :].set(jnp.where(
        active[:, None, None, None],
        new.transpose(0, 2, 1, 3).astype(stack.dtype), old))


def _put_lanes(st: dict, k_new, v_new, layer, cols, active, quant: bool):
    """A step's new keys and values (B, heads, t, d) — a latent layer's one
    row and no V — into the run's stacks ``st``, every lane at its own
    ``cols`` (B, t): payloads and scales where the pool is int8."""
    ks, vs, kss, vss = (st[n] for n in _KV_SHORT)
    if quant:
        k_new, sk = _kv_quant(k_new)
        v_new, sv = _kv_quant(v_new)
        kss = _kv_put_lanes(kss, sk, layer, cols, active)
        vss = _kv_put_lanes(vss, sv, layer, cols, active)
    ks = _kv_put_lanes(ks, k_new, layer, cols, active)
    if v_new is not None:
        vs = _kv_put_lanes(vs, v_new, layer, cols, active)
    return {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss}


def _block_lanes(x, lp, st: dict, layer, mask_bias, cfg, kind, pos):
    """:func:`_block` over ``layer``'s rows of every slot, read out of the
    run's stacks ``st`` as they lie."""
    kl, vl, ksl, vsl = (None if st[n] is None else _kv_rows(st[n], layer)
                        for n in _KV_SHORT)
    return _block(x, lp, kl, vl, mask_bias, cfg, k_scale=ksl, v_scale=vsl,
                  kind=kind, pos=pos)


def _ring_put(stack, new, layer, slot, start, real):
    """A piece's rows into a window layer's ring: ``new`` (1, heads, T, d)
    holds cache columns ``start .. start + T - 1``, column c goes to ring
    row ``c mod R``, and only ``real`` (T,) columns go in. That is at most
    TWO stretches of contiguous rows — up to the ring's end, then from its
    start — and each is written as ONE fixed window of T rows on the row
    axis: the window's old rows read, kept where no real column of the
    stretch lands, and written back where they lie. (A gather and a scatter
    over slot and row ask for the array rows-outside-heads, and every
    dispatch then copies it into that layout and back, all slots of it.)"""
    R, T = stack.shape[3], new.shape[2]
    s0 = jnp.mod(start, R)
    n0 = jnp.minimum(T, R - s0)         # columns before the ring's end
    at = jnp.minimum(s0, R - T)         # the first window ends inside it
    new = new.astype(stack.dtype)
    # (the window's first row, how far the piece is rolled under it): row i
    # of a window takes piece column i - shift, if the piece has one
    for row0, shift in ((at, s0 - at), (0, -n0)):
        j = jnp.arange(T) - shift
        take = (j >= 0) & (j < T) & jnp.roll(real, shift)
        old = _kv_rows(stack, layer, slot, row0, T)
        stack = _kv_put(
            stack, jnp.where(take[:, None], jnp.roll(new, shift, axis=2), old),
            layer, slot, row0)
    return stack


def _scan_run(body, x, lp: dict, stacks: dict, n: int, kind: tuple,
              base=None):
    """``lax.scan`` of ``body(x, lp_l, stacks, layer, kind) -> (x, stacks |
    kvl, counts)`` over the first ``n`` layers of one run; with ``base`` (a
    looped stack's pass times the run's layers) ``body`` is handed ``base +
    layer``, the index of this PASS of the layer in the run's KV stacks,
    while the weights are the layer's own. SCANNED: the
    run's stacked leaves but its experts, and the layer index. CARRIED, whole:
    ``x`` and the run's KV ``stacks`` — ``body`` writes a layer's rows into
    them in place and hands them on. The experts are neither: they ride the
    closure whole and are read in place by ``moe_layer``
    (``models/moe.py:_expert``): as scanned leaves, one layer's experts —
    nine tenths of its bytes — would be copied out at every step, as a
    scanned stack's rows of every slot would be. Where ``stacks`` holds
    nothing (a pass over whole sequences) what ``body`` returns beside ``x``
    is the layer's own keys and values, stacked as scanned outputs."""
    experts = {k: lp[k] for k in _EXPERT_LEAVES if k in lp}
    scanned = {k: a for k, a in lp.items() if k not in experts}
    if jax.tree_util.tree_leaves(scanned)[0].shape[0] != n:
        # a depth prefix ends inside this run
        scanned = jax.tree.map(lambda a: a[:n], scanned)
    carried = stacks["k"] is not None

    def step(carry, inp):
        x, st = carry
        lp_l, layer = inp
        if experts:
            lp_l = {**lp_l, **experts, "moe_layer": layer}
        x, new, cnt = body(x, lp_l, st,
                           layer if base is None else base + layer, kind)
        return ((x, new), (None, cnt)) if carried else ((x, st), (new, cnt))

    (x, st), (ys, cnt) = jax.lax.scan(
        step, (x, stacks), (scanned, jnp.arange(n, dtype=jnp.int32)))
    return x, (st if carried else ys), cnt


def _one_pass(cfg: DecoderConfig, params: dict, x, kv: dict, body,
              n_layers: int | None = None, u=None):
    """ONE pass over the runs of like layers (pass ``u`` of a looped stack:
    its layers' rows lie ``u`` times a run's layers further down the run's
    stacks): ``(x, kv_out, counts)`` as :func:`_scan_layers` says."""
    layers = _run_stacks(params, cfg)
    out = dict(kv)
    counts = None
    for r, (kind, _first, n) in enumerate(cfg.runs(n_layers)):
        names = _kv_names(cfg, r, kind)
        stacks = {short: (kv.get(name) if name else None)
                  for short, name in zip(_KV_SHORT, names)}
        x, kv_run, cnt = _scan_run(body, x, layers[r], stacks, n, kind,
                                   None if u is None else u * n)
        for short, name in zip(_KV_SHORT, names):
            if name is not None and kv_run[short] is not None:
                out[name] = kv_run[short]
        if cnt is not None:
            cnt = cnt.sum(axis=0)
            counts = cnt if counts is None else counts + cnt
    return x, out, counts


def loop_exit(z, threshold: float):
    """The exit rule of a looped stack from its gate's logits ``z`` (loops,
    ...), one a pass: ``lambda_u = sigmoid(z_u)``; the exit distribution
    ``p_u = lambda_u prod_{j<u} (1 - lambda_j)`` for every pass but the
    last, which takes the remainder; the exit step the first pass whose
    cumulative ``p`` reaches ``threshold``, else the last. The cumulative is
    read off what is LEFT (``prod_{j<=u} (1 - lambda_j) <= 1 - threshold``,
    each factor ``sigmoid(-z_j)``), so a threshold of 1 is reached by no
    pass before the last unless a factor underflows, and one above 1 by
    none. Returns ``(p (loops, ...) float32, step (...) int32)``."""
    z = z.astype(jnp.float32)
    left = jnp.cumprod(jax.nn.sigmoid(-z), axis=0)      # after pass u
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]], axis=0)
    p = jnp.concatenate([jax.nn.sigmoid(z[:-1]) * before[:-1], before[-1:]],
                        axis=0)
    reached = left[:-1] <= 1.0 - threshold
    last = z.shape[0] - 1
    step = jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), last)
    return p, step.astype(jnp.int32)


def _scan_layers(cfg: DecoderConfig, params: dict, x, kv: dict, body,
                 n_layers: int | None = None):
    """Run ``body(x, lp, stacks, layer, kind) -> (x, stacks, counts | None)``
    over the first ``n_layers`` layers (all by default). ``stacks`` holds the
    layer's RUN's ``k``, ``v``, ``k_scale``, ``v_scale`` arrays as ``kv`` has
    them, whole, and ``layer`` is the layer's index in them: they ride the
    scan's carry (:func:`_scan_run`), ``body`` writes its rows in place and
    returns them. A depth prefix that ends inside a run visits the run's
    first layers and leaves the others' rows alone. A pass over whole
    sequences starts from ``{}``: ``stacks`` holds None, and ``body`` returns
    the layer's keys and values in their place. Returns ``(x, kv_out,
    counts, exits)``: ``kv_out`` is ``kv`` with what ``body`` returned for
    the visited runs, ``counts`` the expert layers' summed (held, all) or
    None, ``exits`` None.

    A LOOPED stack (``cfg.loops > 1``) runs the layers ``loops`` times in
    ONE ``lax.scan`` whose body is one pass (the executable holds one pass
    body, not ``loops`` copies): ``x`` and the KV stacks ride its carry
    whole; ``body`` is handed the index of THIS pass of the layer, ``u *
    layers + l`` within its run's stacks, which have ``loops * layers`` on
    their leading axis; the final norm closes every pass and its output is
    what the next starts from. Every pass's normed state is kept, the exit
    gate reads each, and :func:`loop_exit` picks among them: ``x`` is then
    the state of each position's exit pass, NORMED (:func:`_logits` does not
    norm it again), and ``exits`` is ``(step (B, S) int32, p (loops, B, S))``.
    No pass is skipped whatever the gate says."""
    if cfg.loops == 1:
        return (*_one_pass(cfg, params, x, kv, body, n_layers), None)
    if n_layers is not None:
        require_single_pass(cfg, "a depth prefix of the layers (n_layers)")
    names = [name for r, (kind, _f, _n) in enumerate(cfg.runs())
             for name in _kv_names(cfg, r, kind) if name and name in kv]
    gate = cfg.exit_gate

    def one(carry, u):
        x, st = carry
        with jax.named_scope("decoder.pass"):
            x, new, cnt = _one_pass(cfg, params, x, st, body, None, u)
        h = _final_norm(params, x, cfg)
        z = None
        if gate:
            with jax.named_scope("decoder.exit_gate"):
                z = jnp.einsum(
                    "bsh,ho->bso", h, params["exit_w"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)[..., 0] \
                    + params["exit_b"].astype(jnp.float32)[0]
        x = h.astype(cfg.dtype)
        if names:       # carried stacks, written in place
            return (x, {n: new[n] for n in names}), (x, z, cnt, None)
        return (x, st), (x, z, cnt, new)

    (x, st), (states, z, cnt, made) = jax.lax.scan(
        one, (x, {n: kv[n] for n in names}),
        jnp.arange(cfg.loops, dtype=jnp.int32))
    if names:
        out = {**kv, **st}
    else:
        # a pass over whole sequences: each pass's keys and values were its
        # outputs, (loops, layers, ...) -> the stacks' (loops * layers, ...)
        out = {**kv, **{n: a.reshape(-1, *a.shape[2:])
                        for n, a in made.items()}}
    counts = None if cnt is None else cnt.sum(axis=0)
    B, S = x.shape[:2]
    if gate:
        p, step = loop_exit(z, cfg.exit_threshold)
        x = jnp.take_along_axis(states, step[None, :, :, None], axis=0)[0]
    else:
        step = jnp.full((B, S), cfg.loops - 1, jnp.int32)
        p = jnp.zeros((cfg.loops, B, S), jnp.float32).at[-1].set(1.0)
    return x, out, counts, (step, p)


def _ring_cols(hi, R: int):
    """A window layer's slot row is a RING of ``R`` rows indexed by cache
    column mod ``R``. With ``hi`` (...,) the newest column written, ring
    index ``r`` holds the largest column <= ``hi`` congruent to ``r``:
    returns those columns (..., R); negative where none was written."""
    r = jnp.arange(R, dtype=jnp.int32)
    return hi[..., None] - jnp.mod(hi[..., None] - r, R)


def _ring_bias(cols, live, qcol, window: int):
    """Mask bias of a ring read: ``cols`` (B, R) the column each ring
    index holds, ``live`` (B, [Q,] R) whether that column is attendable,
    ``qcol`` (B, [Q]) the queries' columns. A key is read when live, not
    ahead of the query and inside its window."""
    if live.ndim == 3:
        cols, q = cols[:, None, :], qcol[:, :, None]
    else:
        q = qcol[:, None]
    ok = live & (cols >= 0) & (cols <= q) & (cols > q - window)
    bias = jnp.where(ok, 0.0, -1e9).astype(jnp.float32)
    return bias[:, None, None, :] if live.ndim == 2 else bias[:, None, :, :]


def _live_at(slot_mask, cols):
    """``slot_mask`` (B, C) read at the ring's columns (B, R)."""
    return jnp.take_along_axis(
        slot_mask, jnp.clip(cols, 0, slot_mask.shape[1] - 1), axis=1) > 0


def _causal_bias(attention_mask, S: int, window: int = 0):
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if window:
        causal = causal & ~jnp.tril(jnp.ones((S, S), jnp.bool_), -window)
    allowed = (causal[None, None, :, :]
               & (attention_mask[:, None, None, :] > 0))
    return jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)


def _self_attend(params, input_ids, attention_mask, cfg: DecoderConfig,
                 flash: bool, mesh, keep_kv: bool):
    """The causal forward over whole sequences that :func:`forward` and
    :func:`prefill` share: ``(x, kv, counts, exits)``, ``kv`` every layer's
    in-sequence keys and values by stack (``keep_kv``; every pass's, where
    the stack is looped), unpadded."""
    B, S = input_ids.shape
    pos = jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0)
    x = _embed(params, input_ids, pos, cfg)
    ctx_fn = None
    bias = {"full": None, "window": None, "latent": None}
    if flash:
        require_gpt2_block(cfg, "flash_prefill")
        attn = _flash_self_attn_fn(mesh)
        ctx_fn = lambda q, k, v, ks, vs: attn(q, k, v, attention_mask)
    else:
        bias["full"] = bias["latent"] = _causal_bias(attention_mask, S)
        if cfg.n_layers_of("window"):
            bias["window"] = _causal_bias(attention_mask, S,
                                          cfg.sliding_window)

    def body(x, lp, none, _layer, kind):
        # nothing is carried: the layer's keys and values are its outputs
        k, v = _prefill_kv(x, lp, cfg, kind, pos)
        x, cnt = _block(x, lp, k, v, bias[kind[0]], cfg, ctx_fn=ctx_fn,
                        kind=kind, pos=pos)
        return x, ({**none, "k": k, "v": v} if keep_kv else none), cnt

    return _scan_layers(cfg, params, x, {}, body)


def forward(params: dict, input_ids: jax.Array, attention_mask: jax.Array,
            cfg: DecoderConfig, *, flash: bool = False,
            mesh=None) -> jax.Array:
    """Full causal forward. Returns logits (B, S, V) float32.

    ``attention_mask`` is 1 for real tokens (left- or right-padded); masked
    positions neither attend nor are attended to. Position ids follow the HF
    convention ``cumsum(mask) - 1`` (clipped), so left-padded rows see the
    same positions as their unpadded equivalents.

    ``flash`` (static) runs attention through the tiled flash kernel
    (``models/flash_attention.py``): no ``(B, 1, S, S)`` bias is
    materialized, the column mask is computed from lengths inside the
    kernel. Logits at LIVE positions match dense at online-softmax
    tolerance; fully-masked query rows (left-padding) produce different
    hidden states (flash: zeros) that never reach live positions.
    ``mesh`` shard-maps the kernel over tp shards (heads split)."""
    x, _kv, _counts, _exits = _self_attend(
        params, input_ids, attention_mask, cfg, flash, mesh, False)
    return _logits(params, x, cfg)


def exit_profile(params: dict, input_ids: jax.Array,
                 attention_mask: jax.Array, cfg: DecoderConfig):
    """What a looped stack's exit rule says of every position of whole
    sequences: ``(step (B, S) int32, p (loops, B, S))`` (:func:`loop_exit`)."""
    return _self_attend(params, input_ids, attention_mask, cfg, False, None,
                        False)[3]


def _prefill_kv(x, lp, cfg, kind: tuple = _GPT2_KIND, pos=None):
    """Project this layer's k/v from the in-sequence activations (the
    block's first norm applied inside, mirroring _block's own
    projection)."""
    _q, k, v = _project(x, lp, cfg, kind, pos, False)
    return k, v


def prefill(params: dict, input_ids: jax.Array, attention_mask: jax.Array,
            cfg: DecoderConfig, cache_len: int, *, flash: bool = False,
            mesh=None):
    """Causal forward over the (left-padded) prompt, returning
    ``(last_logits (B, V), cache)`` with per-layer K/V written into a cache
    padded to ``cache_len`` slots (``k``/``v``; one pair per run of like
    layers where the model has several, :func:`_kv_names` — window layers
    here at full length too: only the slot pool keeps them as rings).

    ``flash``/``mesh`` as in :func:`forward` — the flash arm's cached KV
    at fully-masked (padding) columns differs from dense, but those
    columns stay masked by every downstream ``slot_mask``/``row_mask``
    read, so decode streams see identical attention inputs."""
    B, S = input_ids.shape
    assert cache_len >= S
    x, kv, _counts, _exits = _self_attend(
        params, input_ids, attention_mask, cfg, flash, mesh, True)
    pad = [(0, 0), (0, 0), (0, 0), (0, cache_len - S), (0, 0)]
    # (L, B, nkv, cache_len, hd)
    cache = {name: jnp.pad(a, pad) for name, a in kv.items()}
    return _logits(params, x[:, -1:, :], cfg)[:, 0, :], cache


def decode_step(params: dict, token: jax.Array, step_pos: jax.Array,
                slot: jax.Array, slot_mask: jax.Array, cache: dict,
                cfg: DecoderConfig, n_layers: int | None = None):
    """One decode step. ``token`` (B,), ``step_pos`` (B,) position ids,
    ``slot`` scalar cache slot to write, ``slot_mask`` (B, cache_len) 1 for
    live cache slots INCLUDING the one being written. Returns
    ``(logits (B, V), cache)``.

    ``n_layers`` runs only the first N blocks (plus the final norm + the
    head) — the cascade-rerank trick (``transformer.encode(n_layers=)``)
    applied to decode: the shallow stack is the self-speculative DRAFT
    model, its KV a depth-prefix of the same cache (layers >= N pass
    through untouched), no second parameter set anywhere."""
    pos = step_pos[:, None]
    x = _embed(params, token[:, None], pos, cfg)
    live = slot_mask[:, None, None, :] > 0
    bias = {"full": jnp.where(live, 0.0, -1e9).astype(jnp.float32)}
    bias["latent"] = bias["full"]
    if cfg.n_layers_of("window"):
        idxs = jnp.arange(slot_mask.shape[1])[None, None, None, :]
        bias["window"] = jnp.where(
            live & (idxs > slot - cfg.sliding_window), 0.0, -1e9
        ).astype(jnp.float32)

    def body(x, lp, st, layer, kind):
        k_new, v_new = _prefill_kv(x, lp, cfg, kind, pos)  # (B, nkv, 1, hd)
        ks = _kv_put(st["k"], k_new, layer, 0, slot)
        vs = None if v_new is None else _kv_put(st["v"], v_new, layer, 0,
                                                slot)
        x, cnt = _block(x, lp, _kv_rows(ks, layer),
                        None if vs is None else _kv_rows(vs, layer),
                        bias[kind[0]], cfg, kind=kind, pos=pos)
        return x, {**st, "k": ks, "v": vs}, cnt

    x, out, _counts, _exits = _scan_layers(cfg, params, x, cache, body,
                                           n_layers)
    return _logits(params, x, cfg)[:, 0, :], out


def _filter_logits(logits, top_k: int | None, top_p: float | None):
    """Standard nucleus/top-k logit filtering, fully on device (static
    shapes: both filters mask to -inf rather than shrinking the vocab).
    With both set, top-k applies first, then top-p within the survivors —
    the HF ``text-generation`` composition."""
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob > top_p; the token
        # that CROSSES the threshold stays (shift the mask by one)
        cut = cum - probs > top_p
        cutoff = jnp.where(  # smallest KEPT logit (excluded -> +inf)
            cut, jnp.inf, sorted_logits
        ).min(axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _sample_fn(temperature: float, top_k: int | None, top_p: float | None):
    """The ONE greedy-vs-nucleus sampling closure, shared by
    :func:`generate`, :func:`pool_decode_chunk` and the paged-kernel
    decode chunk (they carried three identical copies). Returns
    ``sample(logits, key) -> (B,) int32``; ``temperature == 0`` is
    greedy argmax and ignores the key, otherwise temperature FIRST, then
    the nucleus (HF warper order): the top-p set must be chosen from the
    TEMPERED distribution — filtering untempered logits would nullify
    high temperatures. Bitwise-pinned against the historical inline
    closures by ``tests/test_flash_prefill.py``."""
    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)

    return sample


def generate(params: dict, prompt_ids: jax.Array, attention_mask: jax.Array,
             cfg: DecoderConfig, max_new: int, temperature: float = 0.0,
             key: jax.Array | None = None,
             eos_id: int | None = None,
             top_k: int | None = None,
             top_p: float | None = None) -> jax.Array:
    """Generate ``max_new`` tokens after a LEFT-padded prompt batch, fully
    on device (prefill + all steps + sampling in one traced computation —
    jit this whole function). Returns (B, max_new) int32; positions after a
    row's EOS are filled with ``eos_id`` when given.

    ``temperature == 0`` is greedy argmax; otherwise softmax sampling at
    the given temperature using ``key``, optionally restricted to the
    ``top_k`` highest logits and/or the ``top_p`` nucleus."""
    B, S = prompt_ids.shape
    cache_len = S + max_new
    if S + max_new > cfg.max_position:
        # positions run up to n_prompt + max_new - 1; past max_position the
        # wpe gather would silently CLAMP (JAX gather semantics) and degrade
        # generation, where torch would raise — fail loudly instead
        raise ValueError(
            f"prompt ({S}) + max_new ({max_new}) exceeds max_position "
            f"({cfg.max_position})"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    last_logits, cache = prefill(params, prompt_ids, attention_mask, cfg,
                                 cache_len)
    n_prompt = jnp.sum(attention_mask, axis=1)  # (B,)
    slot_mask0 = jnp.concatenate(
        [attention_mask, jnp.zeros((B, max_new), attention_mask.dtype)], axis=1
    )

    sample = _sample_fn(temperature, top_k, top_p)

    done0 = jnp.zeros((B,), jnp.bool_)

    if eos_id is None:
        # no stop signal: every row decodes max_new tokens — scan
        def body(carry, t):
            logits, cache, slot_mask, done, key = carry
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)
            slot = S + t
            slot_mask = slot_mask.at[:, slot].set(1)
            step_pos = n_prompt + t  # position id of the sampled token
            logits, cache = decode_step(
                params, tok, step_pos, slot, slot_mask, cache, cfg
            )
            return (logits, cache, slot_mask, done, key), tok

        (_, _, _, _, _), toks = jax.lax.scan(
            body, (last_logits, cache, slot_mask0, done0, key),
            jnp.arange(max_new),
        )
        return toks.T  # (B, max_new)

    # per-row early exit: a while_loop that stops as soon as EVERY row has
    # emitted EOS — a batch of short answers pays for its longest answer,
    # not for max_new (the serving win: mixed-length request batches).
    # Token draws and outputs are bit-identical to the scan path: finished
    # rows keep emitting eos_id, and the untouched tail of the buffer is
    # eos_id-filled.
    toks0 = jnp.full((B, max_new), eos_id, jnp.int32)

    def cond(carry):
        t, _logits, _cache, _mask, done, _key, _toks = carry
        return jnp.logical_and(t < max_new, ~jnp.all(done))

    def wbody(carry):
        t, logits, cache, slot_mask, done, key, toks = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        tok = jnp.where(done, eos_id, tok)
        done = done | (tok == eos_id)
        toks = toks.at[:, t].set(tok)
        slot = S + t
        slot_mask = slot_mask.at[:, slot].set(1)
        step_pos = n_prompt + t
        logits, cache = decode_step(
            params, tok, step_pos, slot, slot_mask, cache, cfg
        )
        return (t + 1, logits, cache, slot_mask, done, key, toks)

    (_, _, _, _, _, _, toks) = jax.lax.while_loop(
        cond,
        wbody,
        (jnp.int32(0), last_logits, cache, slot_mask0, done0, key, toks0),
    )
    return toks  # (B, max_new)


# ---- continuous-batching slot pool ----------------------------------------
#
# Serving state for admitting requests into an IN-FLIGHT decode loop
# (reference bar: HFPipelineChat runs one torch pipeline call per batch —
# a new request waits for the whole batch; here it waits at most one
# decode chunk). The host owns slot lifecycle: it admits a request into a
# free slot (pool_admit), advances every active slot T steps per dispatch
# (pool_decode_chunk), reads the (T, n_slots) token block, and frees a
# slot on EOS or when the request's own max_new budget is spent —
# per-row prompt lengths and budgets need no device bookkeeping. Lanes
# not in ``active`` still flow through the chunk's compute (static
# shapes) but their state does not advance.


def pool_init(params: dict, cfg: DecoderConfig, n_slots: int,
              cache_len: int, arena_blocks: int = 0,
              arena_block: int = 0, kv_quant: bool = False,
              window_slack: int = 256) -> dict:
    """Empty serving pool: per-slot KV caches, last logits, attention
    slot masks and cursors. ``cache_len`` must cover the largest
    admitted prompt + its budget + one chunk of overrun slack per
    pipelined chunk in flight INCLUDING the one being dispatched (a
    lane may overrun its budget until its tokens are drained —
    ``_ContinuousServer`` runs ``pipeline_depth`` chunks ahead and
    sizes prompt + budget + (pipeline_depth + 1) * chunk_steps; writes
    clamp to the last slot).

    With ``arena_blocks > 0`` the pool also carries a prefix-cache KV
    arena: ``arena_blocks`` blocks of ``arena_block`` tokens each,
    shaped ``(A, L, nh, block, hd)`` (block-major so :func:`kv_extract`
    / :func:`kv_insert` gather and scatter whole blocks with one
    indexed op). Which arena block holds which token prefix is host
    state (``engine/prefix_cache.PrefixCache``); the pool functions
    below pass unknown keys through untouched, so the arena rides
    every donated dispatch and device-side data dependencies order
    extract/insert against prefill and decode for free.

    ``kv_quant=True`` stores the caches (and the arena) as symmetric
    per-head-token int8 with f32 scales (``k_scale``/``v_scale``,
    trailing dim 1) — ~1.88x the tokens per HBM byte at hd=64. Every
    pool function quantizes on write and ``_block`` dequantizes on
    read; the ``k_scale`` key doubles as the format marker."""
    nh, hd = cfg.n_kv, cfg.head_dim
    del params
    if kv_quant:
        require_gpt2_block(cfg, "kv_quant")
    kv_dtype = jnp.int8 if kv_quant else cfg.dtype
    # a window layer's slot row is a ring of window + slack rows, indexed by
    # cache column mod its length (_ring_cols); the slack covers what one
    # dispatch writes ahead of the committed cursor (a speculative cycle's
    # rejected tail)
    ring = min(cache_len, cfg.sliding_window + window_slack)
    pool = {}
    for r, (kind, _first, n) in enumerate(cfg.runs()):
        kn, vn, ksn, vsn = _kv_names(cfg, r, kind)
        # a looped stack keeps a cache layer for every PASS of every layer:
        # pass u of the run's layer l at u * n + l (the arena's blocks too: a
        # prefix hit restores every pass's rows)
        n = cfg.loops * n
        if kind[0] == "latent":
            # the normed latent and the rotated shared key of every column:
            # kv_rank + rope values a token a layer, nothing per head
            pool[kn] = jnp.zeros(
                (n, n_slots, 1, cache_len, cfg.latent_width), kv_dtype)
            if arena_blocks > 0:
                pool["arena_" + kn] = jnp.zeros(
                    (arena_blocks, n, 1, arena_block, cfg.latent_width),
                    kv_dtype)
            continue
        rows = ring if kind[0] == "window" else cache_len
        pool[kn] = jnp.zeros((n, n_slots, nh, rows, hd), kv_dtype)
        pool[vn] = jnp.zeros((n, n_slots, nh, rows, hd), kv_dtype)
        if kv_quant:
            pool[ksn] = jnp.zeros((n, n_slots, nh, rows, 1), jnp.float32)
            pool[vsn] = jnp.zeros((n, n_slots, nh, rows, 1), jnp.float32)
        if arena_blocks > 0:
            shape = (arena_blocks, n, nh, arena_block, hd)
            pool["arena_" + kn] = jnp.zeros(shape, kv_dtype)
            pool["arena_" + vn] = jnp.zeros(shape, kv_dtype)
            if kv_quant:
                ashape = (arena_blocks, n, nh, arena_block, 1)
                pool["arena_" + ksn] = jnp.zeros(ashape, jnp.float32)
                pool["arena_" + vsn] = jnp.zeros(ashape, jnp.float32)
    pool.update({
        "logits": jnp.zeros((n_slots, cfg.vocab_size), jnp.float32),
        "slot_mask": jnp.zeros((n_slots, cache_len), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),    # next position id
        "write": jnp.zeros((n_slots,), jnp.int32),  # next cache slot
    })
    if cfg.moe is not None:
        # (held, all) expert assignments since the pool was built, by
        # phase (row 0: prefill, row 1: decode), summed on the device by
        # every prefill and decode op; wraps mod 2**32
        pool["moe_counts"] = jnp.zeros((2, 2), jnp.uint32)
    if cfg.loops > 1:
        # the pass the exit rule took each slot's staged logits from, and
        # the tokens sampled since the pool was built by that pass: summed
        # on the device as each is sampled, wraps mod 2**32
        pool["exit_step"] = jnp.zeros((n_slots,), jnp.int32)
        pool["loop_exits"] = jnp.zeros((cfg.loops,), jnp.uint32)
    return pool


def pool_ring(pool: dict) -> int:
    """Rows of a window layer's ring (0: the pool has no window layer)."""
    for name, a in pool.items():
        if _is_kv(name, window=True):
            return a.shape[3]
    return 0


def _add_counts(pool: dict, out: dict, counts, decode: bool = False) -> dict:
    if counts is not None and "moe_counts" in pool:
        out["moe_counts"] = pool["moe_counts"].at[int(decode)].add(
            counts.astype(jnp.uint32))
    return out


def _stage_exits(pool: dict, out: dict, steps, slots) -> dict:
    """Beside the logits a prefill stages for ``slots`` (a scalar, or (n,)
    distinct ones): the pass the exit rule took each from, ``steps`` (n,)
    (a looped stack's; None otherwise). The decode chunk counts it when the
    token is sampled (``loop_exits``)."""
    if steps is not None and "exit_step" in pool:
        if jnp.ndim(slots) == 0:
            out["exit_step"] = jax.lax.dynamic_update_slice(
                pool["exit_step"], steps, (slots,))
        else:
            out["exit_step"] = pool["exit_step"].at[slots].set(steps)
    return out


def pool_component_bytes(pool: dict) -> dict[str, int]:
    """HBM bytes of the pool's KV storage split by ledger component:
    ``slot_pool`` (per-slot caches), ``kv_scales`` (int8 dequant scales),
    ``prefix_arena`` (+ ``arena_scales``); a PAGED pool reports
    ``kv_blocks`` (the global block pool — which also absorbs the
    prefix arena's role), ``kv_scales``, and ``block_table``. The HBM
    ledger (``probes.record_hbm``) records these per component at pool
    build; :func:`pool_bytes` sums them for the historical total."""
    out: dict[str, int] = {}
    for component, keys in _component_keys(pool).items():
        n = sum(int(pool[c].size) * pool[c].dtype.itemsize for c in keys)
        if n:
            out[component] = n
    return out


# ledger component -> pool keys it accounts (both layouts; absent keys skip)
_HBM_COMPONENT_KEYS = {
    "slot_pool": ("k", "v"),
    "kv_blocks": ("kb", "vb"),
    "kv_scales": ("k_scale", "v_scale", "kb_scale", "vb_scale"),
    "block_table": ("block_tbl",),
    "prefix_arena": ("arena_k", "arena_v"),
    "arena_scales": ("arena_k_scale", "arena_v_scale"),
}


def _component_keys(pool: dict) -> dict:
    """ledger component -> the pool's keys it accounts: the fixed names
    above, and a several-run model's per-run arrays (``slot_pool``: the
    full-attention runs' rows of ``cache_len``; ``slot_pool_window``: the
    window runs' rings; ``slot_pool_latent``: the latent runs' one array
    each; their arena blocks under ``prefix_arena``)."""
    out = {c: [k for k in keys if k in pool]
           for c, keys in _HBM_COMPONENT_KEYS.items()}
    out["slot_pool_window"] = []
    out["slot_pool_latent"] = []
    for name in pool:
        if name in ("k", "v"):
            continue
        if _is_kv(name):
            out[{"w": "slot_pool_window", "l": "slot_pool_latent"}.get(
                name[1], "slot_pool")].append(name)
        elif name.startswith("arena_") and _is_kv(name[6:]) \
                and name[6:] not in ("k", "v"):
            out["prefix_arena"].append(name)
    return out


def _device_bytes(arr) -> dict[str, int]:
    """Physical bytes of one array per device id, from its addressable
    shards. Replicated arrays correctly charge the full size to EVERY
    device; arrays without shard info (numpy, tracers) charge device
    "0", matching the single-chip ledger label."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards:
        return {"0": int(arr.size) * arr.dtype.itemsize}
    out: dict[str, int] = {}
    for s in shards:
        dev = str(s.device.id)
        out[dev] = out.get(dev, 0) + int(s.data.size) * arr.dtype.itemsize
    return out


def pool_component_device_bytes(pool: dict) -> dict[str, dict[str, int]]:
    """:func:`pool_component_bytes` split per DEVICE: ``{component:
    {device_id: bytes}}``. On a single chip every component lands on
    device "0" and the per-device view degenerates to the component
    view; on a serving mesh the tp-sharded planes report 1/tp bytes per
    device while the replicated block table charges every device in
    full — exactly what capacity planning needs to size the block
    allocator against the TIGHTEST device."""
    out: dict[str, dict[str, int]] = {}
    for component, keys in _component_keys(pool).items():
        per_dev: dict[str, int] = {}
        for c in keys:
            for dev, n in _device_bytes(pool[c]).items():
                per_dev[dev] = per_dev.get(dev, 0) + n
        if any(per_dev.values()):
            out[component] = per_dev
    return out


def pool_bytes(pool: dict) -> int:
    """HBM bytes of the pool's KV storage (caches + arena + scales, or
    the block pool + table when paged) — the denominator of the kv_quant
    capacity claim and the number the HBM ledger records. Derived from
    :func:`pool_component_bytes`, which knows both layouts, so
    ``hbm_bytes{component=}`` and ``cli stats`` stay honest under
    ``PATHWAY_TPU_PAGED_KV=1``."""
    return sum(pool_component_bytes(pool).values())


# ---- paged block-table KV store (PATHWAY_TPU_PAGED_KV) ---------------------
#
# The dense pool above strands HBM: every slot owns a full
# ``cache_len`` row sized for the worst-case request, so a short
# request wastes most of its row, and ``pool_admit_cached`` COPIES
# arena blocks into the row instead of referencing them. The paged
# store replaces per-slot rows with ONE global pool of fixed-size KV
# blocks plus a per-slot block table: slot ``s``'s logical cache
# column ``c`` lives at block ``block_tbl[s, c // block]``, block-local
# column ``c % block``. The host allocates only the blocks a request
# actually needs (``ceil((prompt + budget + slack) / block)``), frees
# them the moment the slot drains, and shares prompt-prefix blocks
# BETWEEN slots copy-on-write: a cached prefix is pinned into a new
# slot's table (refcount++) with zero data movement, and is never
# written again because suffix writes start past it.
#
# Reference semantics (this file) are gather-run-scatter: each jitted
# pool op gathers the table rows into the dense per-slot layout, runs
# the UNCHANGED dense computation, and scatters written rows back into
# their blocks. Gathered bytes at live columns are exactly what the
# dense pool would hold, and dead columns contribute exactly 0.0 to
# attention (the -1e9 mask bias underflows softmax in f32), so paged
# greedy tokens are byte-identical to the dense pool — the grid
# ``tests/test_paged_kv.py`` pins. The scatter's duplicate indices
# (COW-shared blocks, the sentinel) always carry identical values, so
# write order cannot matter. The TPU fast path skips the gather
# entirely: ``models/paged_attention.py`` walks the table per slot
# inside a Pallas kernel (``PATHWAY_TPU_PAGED_KERNEL``).
#
# Block 0 is a SENTINEL: never allocated, every unallocated table entry
# points at it, so gathers of unallocated tails read zeros and scatters
# write the zeros straight back. The allocator below is pure host
# state — frees touch no device memory (a stale table row gathers
# masked garbage, which is harmless by the argument above).


class PagedPoolOOM(RuntimeError):
    """Typed allocation failure of the paged KV block pool. Raised on
    the HOST before any device mutation: a failed allocation leaves the
    allocator, the block table, and every refcount exactly as they
    were — no torn state for the serving loop to unwind."""

    def __init__(self, want: int, free: int):
        super().__init__(
            f"paged KV pool exhausted: need {want} blocks, {free} free"
        )
        self.want = want
        self.free = free


class BlockAllocator:
    """Host-side free list + refcounts over the paged pool's blocks.

    Block ids are global pool indices in ``[1, n_blocks)`` — block 0 is
    the sentinel and never handed out. ``alloc`` is atomic (all-or-
    nothing, raising :class:`PagedPoolOOM` otherwise); ``pin`` adds a
    reference to an already-live block (copy-on-write prefix sharing);
    ``release`` drops one reference per id and returns a block to the
    free list only when its count hits zero. Everything here is plain
    Python — the serving loop owns it from one thread, and frees need
    no device work at all."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("paged pool needs >= 2 blocks (one sentinel)")
        self.n_blocks = int(n_blocks)
        # pop() takes from the tail: reversed so low ids allocate first
        # (deterministic layouts keep the tests' table assertions exact)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._refs: dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise PagedPoolOOM(n, len(self._free))
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._refs[i] = 1
        return ids

    def pin(self, ids) -> None:
        for i in ids:
            if i not in self._refs:
                raise ValueError(f"pin of unallocated block {i}")
            self._refs[i] += 1

    def release(self, ids) -> int:
        """Drop one reference per id; returns how many blocks were
        actually freed (refcount reached zero)."""
        freed = 0
        for i in ids:
            r = self._refs.get(i, 0) - 1
            if r > 0:
                self._refs[i] = r
            elif r == 0:
                del self._refs[i]
                self._free.append(i)
                freed += 1
            else:
                raise ValueError(f"release of unallocated block {i}")
        return freed

    def stats(self) -> dict:
        return {
            "n_blocks": self.n_blocks,
            "free": self.n_free,
            "allocated": self.n_allocated,
            "shared": sum(1 for r in self._refs.values() if r > 1),
        }


# pool keys private to the paged layout (everything else — logits,
# slot_mask, cursors — is shared with the dense layout verbatim)
_PAGED_KEYS = ("kb", "vb", "kb_scale", "vb_scale", "block_tbl")


def pool_paged(pool: dict) -> bool:
    """True when the pool stores KV as a global block pool + per-slot
    block table (``paged_pool_init``)."""
    return "block_tbl" in pool


def paged_block(pool: dict) -> int:
    """Tokens per KV block of a paged pool."""
    return pool["kb"].shape[3]


def paged_pool_init(params: dict, cfg: DecoderConfig, n_slots: int,
                    cache_len: int, n_blocks: int, block: int,
                    kv_quant: bool = False) -> dict:
    """Empty PAGED serving pool: ``n_blocks`` KV blocks of ``block``
    tokens each (block 0 reserved as the sentinel) plus an
    ``(n_slots, cache_len // block)`` block table, alongside the same
    logits / slot_mask / cursor planes as :func:`pool_init`.
    ``cache_len`` must be a multiple of ``block`` so a gathered table
    row is layout-identical to a dense slot row. The table rides the
    donated pool pytree; WHICH blocks a slot owns is host state
    (:class:`BlockAllocator`)."""
    if cache_len % block != 0:
        raise ValueError(
            f"cache_len ({cache_len}) must be a multiple of the paged "
            f"block size ({block})"
        )
    if n_blocks < 2:
        raise ValueError("paged pool needs >= 2 blocks (one sentinel)")
    require_gpt2_block(cfg, "paged_kv")
    L, nh, hd = cfg.layers, cfg.heads, cfg.head_dim
    del params
    kv_dtype = jnp.int8 if kv_quant else cfg.dtype
    pool = {
        "kb": jnp.zeros((L, n_blocks, nh, block, hd), kv_dtype),
        "vb": jnp.zeros((L, n_blocks, nh, block, hd), kv_dtype),
        "block_tbl": jnp.zeros((n_slots, cache_len // block), jnp.int32),
        "logits": jnp.zeros((n_slots, cfg.vocab_size), jnp.float32),
        "slot_mask": jnp.zeros((n_slots, cache_len), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "write": jnp.zeros((n_slots,), jnp.int32),
    }
    if kv_quant:
        sshape = (L, n_blocks, nh, block, 1)
        pool["kb_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["vb_scale"] = jnp.zeros(sshape, jnp.float32)
    return pool


def _paged_gather(pool: dict) -> dict:
    """Dense VIEW of a paged pool: gather every slot's table row into the
    per-slot layout the dense pool functions consume. At live columns the
    view is byte-identical to what the dense pool would hold; unallocated
    tails read the sentinel block (zeros). The non-KV planes pass through
    by reference."""
    tbl = pool["block_tbl"]  # (n_slots, max_blocks)
    L = pool["kb"].shape[0]
    nh = pool["kb"].shape[2]
    Bk = pool["kb"].shape[3]
    S, M = tbl.shape

    def g(plane):
        d = plane.shape[-1]
        x = plane[:, tbl]  # (L, S, M, nh, Bk, d)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(L, S, nh, M * Bk, d)

    view = {k: v for k, v in pool.items() if k not in _PAGED_KEYS}
    view["k"] = g(pool["kb"])
    view["v"] = g(pool["vb"])
    if "kb_scale" in pool:
        view["k_scale"] = g(pool["kb_scale"])
        view["v_scale"] = g(pool["vb_scale"])
    return view


def _paged_scatter(pool: dict, view: dict) -> dict:
    """Write a dense view produced by :func:`_paged_gather` (and advanced
    by a dense pool op) back into the block pool. Duplicate table entries
    (COW-shared blocks, sentinel tails) always scatter identical bytes —
    shared columns are never written by the op — so write order cannot
    matter."""
    tbl = pool["block_tbl"]
    Bk = pool["kb"].shape[3]

    def s(plane, row):
        L, S, nh, C, d = row.shape
        x = row.reshape(L, S, nh, C // Bk, Bk, d).transpose(0, 1, 3, 2, 4, 5)
        return plane.at[:, tbl].set(x)

    out = dict(pool)
    out["kb"] = s(pool["kb"], view["k"])
    out["vb"] = s(pool["vb"], view["v"])
    if "kb_scale" in pool:
        out["kb_scale"] = s(pool["kb_scale"], view["k_scale"])
        out["vb_scale"] = s(pool["vb_scale"], view["v_scale"])
    for key, val in view.items():
        if key not in ("k", "v", "k_scale", "v_scale"):
            out[key] = val
    return out


def paged_table_set(pool: dict, slot: jax.Array, row: jax.Array) -> dict:
    """Install ``slot``'s block-table row (``row`` (max_blocks,) int32,
    unallocated tail = sentinel 0). The one device-side edit an admission
    needs beyond the prefill itself; jit with the pool donated, like
    every other pool op. ``slot`` and ``row`` are traced."""
    return {**pool, "block_tbl": pool["block_tbl"].at[slot].set(row)}


def paged_admit_cached(pool: dict, slot: jax.Array, row: jax.Array,
                       n_cached: int) -> dict:
    """Copy-on-write counterpart of :func:`pool_admit_cached`: install
    ``slot``'s table row (whose first ``n_cached // block`` entries are
    PINNED shared blocks holding the cached prompt prefix) and mark the
    first ``n_cached`` mask columns live. No KV bytes move — that is the
    whole point. The host drives the uncached suffix through ordinary
    right-padded prefill pieces (``first=False``), whose writes start at
    column ``n_cached`` and therefore never touch a shared block. jit per
    n_cached; ``slot``/``row`` are traced."""
    C = pool["slot_mask"].shape[1]
    out = paged_table_set(pool, slot, row)
    row_mask = (jnp.arange(C)[None, :] < n_cached).astype(jnp.int32)
    out["slot_mask"] = jax.lax.dynamic_update_slice(
        pool["slot_mask"], row_mask, (slot, 0)
    )
    return out


def pool_admit(params: dict, ids: jax.Array, mask: jax.Array, pool: dict,
               slot: jax.Array, cfg: DecoderConfig, *,
               flash: bool = False, mesh=None) -> dict:
    """Prefill ONE left-padded prompt (``ids``/``mask`` shaped (1, S))
    and install it in ``slot``: KV written, cursors set, first-token
    logits staged. jit per prompt-length bucket; ``slot`` is traced.

    PAGED pools run the identical computation over a gathered dense
    view and scatter the written row back into the slot's table blocks
    — the dict-key branch is static under jit. ``flash``/``mesh``
    (static) as in :func:`prefill`."""
    if pool_paged(pool):
        return _paged_scatter(
            pool, pool_admit(params, ids, mask, _paged_gather(pool),
                             slot, cfg, flash=flash, mesh=mesh)
        )
    C = pool["slot_mask"].shape[1]
    S = ids.shape[1]
    last_logits, cache, counts, exits = _prefill_for_pool(
        params, ids, mask, pool, cfg, flash, mesh)
    upd = {}
    if pool_quantized(pool):
        cache["k"], sk = _kv_quant(cache["k"])
        cache["v"], sv = _kv_quant(cache["v"])
        upd["k_scale"] = jax.lax.dynamic_update_slice(
            pool["k_scale"], sk, (0, slot, 0, 0, 0)
        )
        upd["v_scale"] = jax.lax.dynamic_update_slice(
            pool["v_scale"], sv, (0, slot, 0, 0, 0)
        )
    for name, new in cache.items():
        upd[name] = jax.lax.dynamic_update_slice(
            pool[name], new.astype(pool[name].dtype), (0, slot, 0, 0, 0)
        )
    row_mask = jnp.concatenate(
        [mask.astype(jnp.int32), jnp.zeros((1, C - S), jnp.int32)], axis=1
    )
    slot_mask = jax.lax.dynamic_update_slice(
        pool["slot_mask"], row_mask, (slot, 0)
    )
    logits = jax.lax.dynamic_update_slice(
        pool["logits"], last_logits, (slot, 0)
    )
    n_prompt = jnp.sum(mask, axis=1).astype(jnp.int32)  # (1,)
    pos = jax.lax.dynamic_update_slice(pool["pos"], n_prompt, (slot,))
    write = jax.lax.dynamic_update_slice(
        pool["write"], jnp.full((1,), S, jnp.int32), (slot,)
    )
    return _stage_exits(pool, _add_counts(pool, {
        **pool, **upd, "logits": logits,
        "slot_mask": slot_mask, "pos": pos, "write": write}, counts),
        exits, slot)


def _prefill_for_pool(params, ids, mask, pool, cfg, flash, mesh):
    """One-shot prefill in the pool's own layout: ``(last_logits, cache,
    counts, exits)``; the full layers' keys and values padded to the slot
    row, the window layers' LAST ring-length columns laid out as the ring
    holds them (column ``c`` at index ``c`` mod its length); ``exits`` the
    exit pass of each prompt's first token (a looped stack's, else None)."""
    C, S = pool["slot_mask"].shape[1], ids.shape[1]
    x, kv, counts, exits = _self_attend(params, ids, mask, cfg, flash, mesh,
                                        True)
    R = pool_ring(pool)
    cache = {}
    for name, new in kv.items():
        if not _is_kv(name, window=True):
            cache[name] = jnp.pad(
                new, [(0, 0), (0, 0), (0, 0), (0, C - S), (0, 0)])
        elif S <= R:
            cache[name] = jnp.pad(
                new, [(0, 0), (0, 0), (0, 0), (0, R - S), (0, 0)])
        else:
            cache[name] = jnp.roll(new[:, :, :, S - R:, :], (S - R) % R,
                                   axis=3)
    return (_logits(params, x[:, -1:, :], cfg)[:, 0, :], cache, counts,
            None if exits is None else exits[0][:, -1])


def pool_admit_batch(params: dict, ids: jax.Array, mask: jax.Array,
                     pool: dict, slots: jax.Array,
                     cfg: DecoderConfig, *,
                     flash: bool = False, mesh=None) -> dict:
    """Prefill M left-padded prompts (``ids``/``mask`` shaped (M, S)) and
    install them in ``slots`` (M distinct slot indices) in ONE dispatch.

    Row-wise identical to M calls of :func:`pool_admit` — prompts are
    independent through the causal forward, and the per-row cache/mask/
    cursor scatters touch disjoint slots — but the M prefill matmuls batch
    into one kernel and the M dispatches collapse into one, so a burst of
    same-bucket arrivals costs one admission RTT instead of M
    (``PATHWAY_TPU_BATCH_ADMIT``). jit per (M, prompt-bucket);
    ``slots`` is traced. Paged pools gather-run-scatter (see
    :func:`pool_admit`)."""
    if pool_paged(pool):
        return _paged_scatter(
            pool, pool_admit_batch(params, ids, mask, _paged_gather(pool),
                                   slots, cfg, flash=flash, mesh=mesh)
        )
    C = pool["slot_mask"].shape[1]
    M, S = ids.shape
    last_logits, cache, counts, exits = _prefill_for_pool(
        params, ids, mask, pool, cfg, flash, mesh)
    upd = {}
    if pool_quantized(pool):
        cache["k"], sk = _kv_quant(cache["k"])
        cache["v"], sv = _kv_quant(cache["v"])
        upd["k_scale"] = pool["k_scale"].at[:, slots].set(sk)
        upd["v_scale"] = pool["v_scale"].at[:, slots].set(sv)
    for name, new in cache.items():
        upd[name] = pool[name].at[:, slots].set(new.astype(pool[name].dtype))
    row_mask = jnp.concatenate(
        [mask.astype(jnp.int32), jnp.zeros((M, C - S), jnp.int32)], axis=1
    )
    slot_mask = pool["slot_mask"].at[slots].set(row_mask)
    logits = pool["logits"].at[slots].set(last_logits)
    n_prompt = jnp.sum(mask, axis=1).astype(jnp.int32)  # (M,)
    pos = pool["pos"].at[slots].set(n_prompt)
    write = pool["write"].at[slots].set(jnp.full((M,), S, jnp.int32))
    return _stage_exits(pool, _add_counts(pool, {
        **pool, **upd, "logits": logits,
        "slot_mask": slot_mask, "pos": pos, "write": write}, counts),
        exits, slots)


def pool_prefill_chunk(params: dict, ids: jax.Array, mask: jax.Array,
                       pos: jax.Array, pool: dict, slot: jax.Array,
                       start: jax.Array, n_prompt: jax.Array,
                       cfg: DecoderConfig, *, first: bool,
                       last: bool,
                       last_col: jax.Array | None = None,
                       flash: bool = False, mesh=None) -> dict:
    """CHUNKED prefill: write ONE piece of a left-padded prompt
    (``ids``/``mask``/``pos`` shaped (1, T)) into ``slot``'s cache at
    offsets ``[start, start + T)``, sharing ``_block`` with decode and
    full prefill so the chunked path cannot diverge numerically.

    The host splits a bucket-padded prompt into fixed-size pieces and
    dispatches one per server-loop tick, interleaved with decode chunks
    (``_ContinuousServer``) — a long prompt no longer stalls every active
    lane for a whole-prompt prefill. ``pos`` carries the host-computed
    position ids (``cumsum(mask) - 1`` clipped, the same convention as
    :func:`prefill`); ``first`` clears the slot's stale mask row (a
    re-admitted slot would otherwise attend the PREVIOUS occupant's cache
    tail beyond this prompt); ``last`` installs the next-token logits and
    the pos/write cursors (``n_prompt`` (1,) is the real token count).
    Because attention is causal, piece i's queries only see cache entries
    written by pieces <= i, so the union of pieces is elementwise
    identical to :func:`pool_admit`'s one-shot prefill. jit per (piece
    length, first, last); ``slot``/``start``/``n_prompt`` are traced.

    ``last_col`` (traced scalar, only meaningful with ``last``) names
    the piece column holding the prompt's REAL last token. The default
    ``None`` keeps the historical static read of the piece's final
    column — correct for left-padded prompts, whose last piece always
    ends on the last real token. The prefix-cache path admits prompts
    RIGHT-padded (token i must sit at cache column i for arena blocks
    to be layout-exact), so its final piece may end on pad columns and
    the next-token logits live mid-piece. Paged pools gather-run-
    scatter (see :func:`pool_admit`).

    The pool is donated and every write of it is IN PLACE: each run's
    stacks ride the layer loop's carry whole (:func:`_scan_layers`; what is
    scanned is the layer's leaves and its index), a full or latent layer's
    rows go in by one ``dynamic_update_slice`` at ``(layer, slot, 0, start,
    0)``, a window layer's by two on the row axis of the slot's ring
    (:func:`_ring_put`), and what the piece reads back is ONE slot's row.
    No instruction of the compiled piece produces a whole run array or a
    layer's rows of all slots (``tests/test_tpu_compile.py`` holds it to
    that at both answer cells' widths).

    Each kind of layer reads its row DENSE (:func:`_attn_ctx` under a mask
    bias) or BLOCKWISE (``flash_attention.flash_chunk_attn``: online
    softmax over the key blocks some query of the piece can see, scores
    never in HBM) by :func:`blockwise_chunk_read` on the shapes in hand;
    ``flash`` forces the kernel. A query row with no visible key (left
    padding) is zeros blockwise and a uniform average dense: no real
    position ever reads it."""
    if pool_paged(pool):
        return _paged_scatter(
            pool, pool_prefill_chunk(
                params, ids, mask, pos, _paged_gather(pool), slot, start,
                n_prompt, cfg, first=first, last=last, last_col=last_col,
                flash=flash, mesh=mesh,
            )
        )
    C = pool["slot_mask"].shape[1]
    T = ids.shape[1]
    R, W = pool_ring(pool), cfg.sliding_window
    p = jnp.clip(pos, 0, cfg.max_position - 1)
    x = _embed(params, ids, p, cfg)
    if first:
        row_mask = jnp.zeros((1, C), jnp.int32)
    else:
        row_mask = jax.lax.dynamic_slice(pool["slot_mask"], (slot, 0), (1, C))
    row_mask = jax.lax.dynamic_update_slice(
        row_mask, mask.astype(jnp.int32), (0, start)
    )
    slot_mask = jax.lax.dynamic_update_slice(
        pool["slot_mask"], row_mask, (slot, 0)
    )
    quant = pool_quantized(pool)
    # each kind of layer reads blockwise or dense by ONE rule on its shapes
    # (``flash`` forces the kernel, as it always has): the dense read stays
    # the small rows' and the decode step's, and the tests' reference
    full_fn = latent_fn = window_fn = mask_bias = ring_bias = None
    qcol = (start + jnp.arange(T))[None, :]             # (1, T)
    if flash or blockwise_chunk_read(cfg.heads, T, C):
        # the kernel builds the live-&-causal predicate from the column
        # each key row holds, with int8 dequant fused into the tile read:
        # no (1, 1, T, C) bias, no f32 KV row, no scores in HBM
        kcol = jnp.where(row_mask > 0, jnp.arange(C, dtype=jnp.int32), -1)
        if not cfg.latent:
            attn_c = _flash_chunk_attn_fn(mesh, quant)
            full_fn = lambda q, kr, vr, ksr, vsr: \
                attn_c(q, kr, vr, ksr, vsr, kcol, start)
        else:
            from pathway_tpu.models import flash_attention as _fa

            # the same walk over the slot's LATENT row: a block becomes a
            # head's keys and values inside the kernel, for that step only.
            # The kernel takes the row with its COLUMNS minor, (width,
            # columns): keys transposed are what a score multiplies, and it
            # is how the chip's compiler keeps an array whose rows are 4.5
            # lane tiles wide, so the row goes in as it lies
            latent_fn = lambda q, c_row, w_ukv: \
                _fa.flash_chunk_attn_latent(
                    q[0], c_row[0, 0].T, w_ukv, kcol[0], start,
                    nope=cfg.nope_size, sm_scale=attn_scale(cfg))[None]
    else:
        # a piece query at cache index start+j attends every LIVE index
        # of this row <= start+j (earlier pieces + its own causal
        # prefix) — elementwise the same predicate as prefill()'s
        # causal & pad mask
        idxs = jnp.arange(C)[None, None, None, :]
        allowed = (row_mask[:, None, None, :] > 0) \
            & (idxs <= qcol[:, None, :, None])
        mask_bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)
    if R:
        # a window layer reads its ring as the EARLIER pieces left it
        # (columns < start) beside this piece's own keys, then writes the
        # piece in: the ring needs no room for the piece itself
        if T > R:
            raise ValueError(f"a prefill piece of {T} columns does not "
                             f"fit a window layer's ring of {R}")
        cols = _ring_cols(jnp.reshape(start - 1, (1,)), R)      # (1, R)
        ring_live = _live_at(row_mask, cols)                    # (1, R)
        if flash or blockwise_chunk_read(cfg.heads, T, R + T):
            attn_w = _flash_chunk_attn_fn(mesh, quant, W)
            kcol_w = jnp.concatenate([
                jnp.where(ring_live, cols, -1),
                jnp.where(mask > 0, qcol, -1)], axis=1).astype(jnp.int32)
            window_fn = lambda q, kr, vr, ksr, vsr: \
                attn_w(q, kr, vr, ksr, vsr, kcol_w, start)
        else:
            live = jnp.broadcast_to(ring_live[:, None, :], (1, T, R))
            old = _ring_bias(cols, live, qcol, W)           # (1, 1, T, R)
            j = jnp.arange(T)
            own = (mask[:, None, :] > 0) \
                & (j[None, None, :] <= j[None, :, None]) \
                & (j[None, :, None] - j[None, None, :] < W)
            ring_bias = jnp.concatenate(
                [old, jnp.where(own, 0.0, -1e9).astype(jnp.float32)[:, None]],
                axis=-1)

    def layer(x, lp, st, li, kind):
        # ``st``: the run's stacks, carried whole; this layer's rows of
        # ``slot`` are written where they lie and read back as ONE row
        ks, vs, kss, vss = (st[n] for n in _KV_SHORT)
        k_new, v_new = _prefill_kv(x, lp, cfg, kind, p)  # (1, nh, T, hd)
        if kind[0] == "latent":
            # the piece's latent rows go in, then its queries read the row:
            # per-head keys and values exist only inside that read
            ks = _kv_put(ks, k_new, li, slot, start)
            read = latent_fn and (lambda q, c, _v, _ks, _vs:
                                  latent_fn(q, c, _w_ukv(lp, cfg)))
            x, cnt = _block(x, lp, _kv_rows(ks, li, slot), None, mask_bias,
                            cfg, ctx_fn=read, kind=kind, pos=p)
            return x, {**st, "k": ks}, cnt
        if kind[0] == "window":
            x, cnt = _block(
                x, lp,
                jnp.concatenate([_kv_rows(ks, li, slot),
                                 k_new.astype(ks.dtype)], 2),
                jnp.concatenate([_kv_rows(vs, li, slot),
                                 v_new.astype(vs.dtype)], 2),
                ring_bias, cfg, ctx_fn=window_fn, kind=kind, pos=p)
            # only REAL tokens enter the ring: a pad column's index still
            # holds an earlier column that a later query may read
            real = mask[0] > 0
            return x, {**st, "k": _ring_put(ks, k_new, li, slot, start, real),
                       "v": _ring_put(vs, v_new, li, slot, start, real)}, cnt
        ks_row = vs_row = None
        if quant:
            k_new, sk = _kv_quant(k_new)
            v_new, sv = _kv_quant(v_new)
            kss = _kv_put(kss, sk, li, slot, start)
            vss = _kv_put(vss, sv, li, slot, start)
            ks_row, vs_row = _kv_rows(kss, li, slot), _kv_rows(vss, li, slot)
        ks = _kv_put(ks, k_new, li, slot, start)
        vs = _kv_put(vs, v_new, li, slot, start)
        x, cnt = _block(x, lp, _kv_rows(ks, li, slot), _kv_rows(vs, li, slot),
                        mask_bias, cfg, k_scale=ks_row, v_scale=vs_row,
                        ctx_fn=full_fn, kind=kind, pos=p)
        return x, {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss}, cnt

    x, kv, counts, exits = _scan_layers(cfg, params, x, pool, layer)
    out = _add_counts(pool, {**kv, "slot_mask": slot_mask}, counts)
    if last:
        if last_col is None:
            x_last = x[:, -1:, :]
        else:
            H = x.shape[2]
            x_last = jax.lax.dynamic_slice(x, (0, last_col, 0), (1, 1, H))
        if exits is not None:
            # the exit pass of the prompt's first token
            out = _stage_exits(pool, out, jax.lax.dynamic_slice_in_dim(
                exits[0][0], T - 1 if last_col is None else last_col, 1),
                slot)
        last_logits = _logits(params, x_last, cfg)[:, 0, :]
        out["logits"] = jax.lax.dynamic_update_slice(
            pool["logits"], last_logits, (slot, 0)
        )
        out["pos"] = jax.lax.dynamic_update_slice(
            pool["pos"], n_prompt.astype(jnp.int32), (slot,)
        )
        # the next cache column: right after the last REAL token, so that
        # decode leaves no dead columns behind it (a window counts columns)
        write_end = start + jnp.full(
            (1,), T if last_col is None else last_col + 1, jnp.int32)
        out["write"] = jax.lax.dynamic_update_slice(
            pool["write"], write_end, (slot,)
        )
    return out


def chunk_rows(cfg: DecoderConfig, T: int, rows: int,
               itemsize: int) -> int:
    """Key rows a block of the blockwise read of a piece of ``T`` queries
    over ``rows`` key rows: the kernel's own choice, from the shapes."""
    from pathway_tpu.models import flash_attention as _fa

    if cfg.latent:
        return _fa.latent_chunk_block(
            rows, T, cfg.head_dim, cfg.latent_width, cfg.kv_rank,
            cfg.nope_size + cfg.v_dim, itemsize)
    return _fa.chunk_block(rows, T, cfg.heads // cfg.n_kv, cfg.head_dim,
                           itemsize)


def prefill_blocks_visited(cfg: DecoderConfig, T: int, C: int, R: int,
                           start: int, lo: int, hi: int,
                           flash: bool = False) -> dict:
    """What one prefill piece at ``start`` adds to the counter
    ``prefill_attn_blocks{layer, visited}`` (a latent layer expands exactly
    the blocks it visits, so ``latent_rows_expanded`` is their rows): for
    each kind of layer whose read is blockwise (:func:`blockwise_chunk_read`), the key blocks its
    kernel visits (``visited=1``) and skips, times the layers of that kind.
    On the HOST, in numpy, from the piece's offset and the row's live
    columns ``[lo, hi]`` (the prompt's first live column; this piece's last)
    — ``flash_attention.blocks_seen``, the kernel's own predicate, without
    the device: no sync. ``{}`` where every layer reads dense."""
    kinds = [(kind, rows, window, cfg.n_layers_of(kind))
             for kind, rows, window in (("full", C, 0), ("latent", C, 0),
                                        ("window", R + T, cfg.sliding_window))
             if cfg.n_layers_of(kind)
             and (flash or blockwise_chunk_read(cfg.heads, T, rows))]
    if not kinds:
        return {}       # before the kernel's module is ever imported
    import numpy as np

    from pathway_tpu.models.flash_attention import blocks_seen

    it = jnp.dtype(cfg.dtype).itemsize

    def live(cols):
        return np.where((cols >= lo) & (cols <= hi), cols, -1)

    out = {}
    for kind, rows, window, layers in kinds:
        if kind != "window":
            kcol = live(np.arange(C))
        else:
            ring = (start - 1) - np.mod(start - 1 - np.arange(R), R)
            kcol = np.concatenate([live(ring), live(start + np.arange(T))])
        _kcol, seen = blocks_seen(np, kcol, start, T, window,
                                  chunk_rows(cfg, T, rows, it))
        out[(kind, 1)] = int(seen.sum()) * layers
        out[(kind, 0)] = int((~seen).sum()) * layers
    return out


def _kv_channels(pool: dict) -> list[tuple[str, str]]:
    """(cache key, arena key) pairs the block copies move — the int8
    scale planes ride along whenever the pool is quantized, so extract/
    insert/admit_cached stay format-agnostic."""
    # every run's arrays; a window run's blocks are layout-exact only while
    # its ring has not wrapped (ring index == cache column), which the HOST
    # guarantees before it publishes or seeds a prefix (`_ContinuousServer`)
    ch = [(n, "arena_" + n) for n in pool if _is_kv(n)]
    if pool_quantized(pool):
        ch += [("k_scale", "arena_k_scale"), ("v_scale", "arena_v_scale")]
    return ch


def kv_extract(pool: dict, slot: jax.Array, start: jax.Array,
               idxs: jax.Array, cfg: DecoderConfig) -> dict:
    """Copy the block-aligned KV span ``[start, start + n*block)`` of
    ``slot``'s cache into arena blocks ``idxs`` ((n,) int32). Called
    after a prompt's prefill lands, to publish its freshly-computed
    blocks into the prefix-cache arena. Pure data movement — no
    compute — so the cached bytes are bit-identical to what the slot
    holds. jit per n; ``slot``/``start``/``idxs`` are traced. Paged
    pools never extract — they pin their own blocks into the prefix
    cache (zero copy)."""
    if pool_paged(pool):
        raise ValueError(
            "kv_extract is dense-arena machinery; a paged pool publishes "
            "prefixes by pinning its own blocks (paged_admit_cached)"
        )
    del cfg
    n = idxs.shape[0]
    out = dict(pool)
    for c, a in _kv_channels(pool):
        L, _, nh, _, d = pool[c].shape  # d: hd for payloads, 1 for scales
        Bk = pool[a].shape[3]
        span = jax.lax.dynamic_slice(
            pool[c], (0, slot, 0, start, 0), (L, 1, nh, n * Bk, d)
        )
        span = span[:, 0].reshape(L, nh, n, Bk, d).transpose(2, 0, 1, 3, 4)
        out[a] = pool[a].at[idxs].set(span)
    return out


def kv_insert(pool: dict, slot: jax.Array, start: jax.Array,
              idxs: jax.Array, cfg: DecoderConfig) -> dict:
    """Scatter arena blocks ``idxs`` into ``slot``'s cache at
    ``[start, start + n*block)`` — the inverse of :func:`kv_extract`.
    The arena stores KV for token i of a prefix at block-local column
    i % block, so the copy is layout-exact only when the receiving
    prompt ALSO places token i at cache column i (right-padded
    admission, ``start = 0``). jit per n; traced like extract."""
    if pool_paged(pool):
        raise ValueError(
            "kv_insert is dense-arena machinery; a paged pool admits "
            "cached prefixes by table edit (paged_admit_cached)"
        )
    del cfg
    n = idxs.shape[0]
    out = dict(pool)
    for c, a in _kv_channels(pool):
        L, _, nh, _, d = pool[c].shape
        Bk = pool[a].shape[3]
        span = pool[a][idxs]  # (n, L, nh, Bk, d)
        span = span.transpose(1, 2, 0, 3, 4).reshape(L, nh, n * Bk, d)
        out[c] = jax.lax.dynamic_update_slice(
            pool[c], span[:, None], (0, slot, 0, start, 0)
        )
    return out


def _block_store_channels(pool: dict) -> list[tuple[str, str]]:
    """(blob key, pool key) pairs for the pool's block store — the dense
    pool's prefix arena or the paged pool's global block planes. Blob
    keys are layout-neutral so an exported payload round-trips across
    pool kinds of the same model shape."""
    if pool_paged(pool):
        ch = [("k", "kb"), ("v", "vb")]
        if pool_quantized(pool):
            ch += [("k_scale", "kb_scale"), ("v_scale", "vb_scale")]
        return ch
    ch = [(n, "arena_" + n) for n in pool if _is_kv(n)]
    if pool_quantized(pool):
        ch += [("k_scale", "arena_k_scale"), ("v_scale", "arena_v_scale")]
    return ch


def kv_block_export(pool: dict, idxs: jax.Array) -> dict:
    """Gather KV blocks ``idxs`` ((n,) int32) out of the pool's block
    store into per-channel ``(n, L, nh, block, d)`` arrays. This is the
    tier-2 prefix cache's host-blob format (demotion device_gets the
    result) and the cross-device lane-migration payload — pure data
    movement, so the bytes are bit-identical to what the blocks hold.
    Works on both layouts: the dense pool exports prefix-arena blocks,
    the paged pool exports global-pool blocks. jit per n; ``idxs`` is
    traced."""
    paged = pool_paged(pool)
    out = {}
    for b, a in _block_store_channels(pool):
        if paged:  # (L, n_blocks, nh, Bk, d) -> (n, L, nh, Bk, d)
            out[b] = pool[a][:, idxs].transpose(1, 0, 2, 3, 4)
        else:  # arena already leads with the block axis
            out[b] = pool[a][idxs]
    return out


def kv_block_import(pool: dict, idxs: jax.Array, blobs: dict) -> dict:
    """Scatter exported block payloads back into block-store blocks
    ``idxs`` — the inverse of :func:`kv_block_export`, used by tier-2
    promotion (h2d) and by the receiving side of a cross-device lane
    migration. The blob's channel set must match the pool's (an int8
    pool needs the scale planes). jit per n with the pool donated;
    ``idxs`` and the blobs are traced."""
    paged = pool_paged(pool)
    out = dict(pool)
    for b, a in _block_store_channels(pool):
        if b not in blobs:
            raise ValueError(f"kv_block_import: blob missing channel {b!r}")
        blob = blobs[b].astype(pool[a].dtype)
        if paged:
            out[a] = pool[a].at[:, idxs].set(blob.transpose(1, 0, 2, 3, 4))
        else:
            out[a] = pool[a].at[idxs].set(blob)
    return out


def pool_admit_cached(pool: dict, slot: jax.Array, idxs: jax.Array,
                      cfg: DecoderConfig) -> dict:
    """Seed ``slot`` with a cached prompt prefix: arena blocks ``idxs``
    ((n,) int32) land at cache columns ``[0, n*block)`` and the slot's
    mask row becomes 1 there, 0 beyond — exactly the state
    :func:`pool_prefill_chunk` would have left after prefilling those
    tokens right-padded (its ``first`` piece clears the stale row the
    same way). The host then drives the UNCACHED suffix through the
    ordinary chunked-prefill pieces (``first=False``, ``pos`` starting
    at ``n*block``), so a cache hit skips compute without forking the
    numerics: the suffix attends to seeded KV that is bit-identical to
    what it would have computed itself. No logits/cursor writes — the
    suffix's ``last`` piece owns those. jit per n; ``slot``/``idxs``
    are traced. Paged pools use :func:`paged_admit_cached` — pinning
    shared blocks instead of copying them."""
    if pool_paged(pool):
        raise ValueError(
            "pool_admit_cached copies arena blocks; paged pools pin "
            "shared blocks copy-on-write (paged_admit_cached)"
        )
    out = kv_insert(pool, slot, jnp.int32(0), idxs, cfg)
    C = pool["slot_mask"].shape[1]
    Bk = next(a.shape[3] for n, a in pool.items() if n.startswith("arena_"))
    n_cached = idxs.shape[0] * Bk
    row_mask = (jnp.arange(C)[None, :] < n_cached).astype(jnp.int32)
    out["slot_mask"] = jax.lax.dynamic_update_slice(
        pool["slot_mask"], row_mask, (slot, 0)
    )
    return out


def pool_decode_chunk(params: dict, pool: dict, active: jax.Array,
                      key: jax.Array, cfg: DecoderConfig, n_steps: int,
                      temperature: float = 0.0,
                      top_k: int | None = None,
                      top_p: float | None = None,
                      paged_kernel: bool = False,
                      mesh=None) -> tuple[dict, jax.Array]:
    """Advance every ``active`` slot ``n_steps`` decode steps in ONE
    dispatch. Returns ``(pool, tokens (n_steps, n_slots))`` — the host
    truncates each slot's stream at EOS / its budget (a lane keeps
    decoding garbage past its own EOS until the chunk ends; discarded).
    Inactive lanes compute but their state does not advance.

    Paged pools gather-run-scatter (see :func:`pool_admit`) unless
    ``paged_kernel`` is set, in which case the chunk runs directly on
    the block planes with the Pallas paged-attention kernel — no dense
    materialization, int8 dequant fused into the attention read.

    ``mesh`` (a serving mesh, static) makes the Pallas kernel run
    per-tp-shard via ``shard_map`` — the block planes are head-sharded,
    attention is per-head, so each shard walks its own heads with zero
    cross-shard traffic. ``None`` (or a trivial mesh) is the single-chip
    path, byte-identical to before the flag existed."""
    if pool_paged(pool):
        if paged_kernel:
            return _paged_decode_chunk_kernel(
                params, pool, active, key, cfg, n_steps,
                temperature, top_k, top_p, mesh=mesh,
            )
        view, toks = pool_decode_chunk(
            params, _paged_gather(pool), active, key, cfg, n_steps,
            temperature, top_k, top_p,
        )
        return _paged_scatter(pool, view), toks
    C = pool["slot_mask"].shape[1]
    R, W = pool_ring(pool), cfg.sliding_window
    act_i = active.astype(jnp.int32)
    quant = pool_quantized(pool)
    sample = _sample_fn(temperature, top_k, top_p)
    stacks = _kv_stacks(pool)

    def body(carry, _):
        kv, logits, slot_mask, pos, write, counts, key, looped = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        if looped is not None:
            # a token is sampled: count it under the pass its logits are of
            exit_step, exited = looped
            hit = (exit_step[:, None] == jnp.arange(cfg.loops)) \
                & active[:, None]
            exited = exited + hit.sum(axis=0).astype(jnp.uint32)
        w = jnp.minimum(write, C - 1)
        # the sampled token's own cache slot attends to itself
        slot_mask = jnp.where(
            active[:, None] & (jnp.arange(C)[None, :] == w[:, None]),
            1, slot_mask,
        )
        p = jnp.minimum(pos, cfg.max_position - 1)[:, None]
        x = _embed(params, tok[:, None], p, cfg)
        bias = {"full": jnp.where(
            slot_mask[:, None, None, :] > 0, 0.0, -1e9
        ).astype(jnp.float32)}
        bias["latent"] = bias["full"]
        col = {"full": w[:, None], "latent": w[:, None]}
        if R:
            cols = _ring_cols(w, R)
            bias["window"] = _ring_bias(cols, _live_at(slot_mask, cols), w, W)
            col["window"] = jnp.mod(w, R)[:, None]

        def layer(x, lp, st, li, kind):
            k_new, v_new = _prefill_kv(x, lp, cfg, kind, p)  # (B, nh, 1, hd)
            # per-ROW write position (each lane is at its own slot); a
            # latent layer has one row a lane to write and no V: the step
            # reads every slot's rows ABSORBED, as they lie (_latent_ctx)
            st = _put_lanes(st, k_new, v_new, li, col[kind[0]], active, quant)
            x, cnt = _block_lanes(x, lp, st, li, bias[kind[0]], cfg, kind, p)
            return x, st, cnt

        x, kv, cnt, exits = _scan_layers(cfg, params, x, kv, layer)
        if cnt is not None:
            counts = counts + cnt
        if looped is not None:
            looped = (jnp.where(active, exits[0][:, 0], exit_step), exited)
        new_logits = _logits(params, x, cfg)[:, 0, :]
        logits = jnp.where(active[:, None], new_logits, logits)
        return (kv, logits, slot_mask, pos + act_i, write + act_i, counts,
                key, looped), tok

    (kv, logits, slot_mask, pos, write, counts, _, looped), toks = \
        jax.lax.scan(
            body,
            (stacks, pool["logits"], pool["slot_mask"], pool["pos"],
             pool["write"], jnp.zeros((2,), jnp.uint32), key,
             (pool["exit_step"], pool["loop_exits"]) if cfg.loops > 1
             else None),
            None,
            length=n_steps,
        )
    out = {**pool, **kv, "logits": logits,
           "slot_mask": slot_mask, "pos": pos, "write": write}
    if looped is not None:
        out["exit_step"], out["loop_exits"] = looped
    return _add_counts(pool, out, counts, decode=True), toks


def _paged_attn_fn(mesh, quant):
    """The paged-attention entry the decode chunk should call: the
    plain Pallas kernel on a single chip, or a ``shard_map``-wrapped
    version on a serving mesh with tp > 1. The wrapper splits the HEAD
    axis (q / block planes / scales all carry it) over ``tp`` and runs
    the UNCHANGED kernel per shard — attention never mixes heads, so
    ``check_vma=False`` is the only concession and no collective is
    inserted. Quantized pools get a separate wrapper because
    ``shard_map`` in_specs cannot describe the ``None`` scale operands
    of the bf16 layout."""
    from pathway_tpu.models import paged_attention as _pa

    if mesh is None:
        return _pa.paged_attn_decode
    from pathway_tpu.parallel.mesh import SERVE_TP_AXIS

    if int(mesh.shape.get(SERVE_TP_AXIS, 1)) == 1:
        return _pa.paged_attn_decode
    t = SERVE_TP_AXIS
    head = P(None, t, None)           # q / ctx: (B, nh, hd)
    blocks = P(None, t, None, None)   # kb / vb / scales: (NB, nh, Bk, d)
    rep = P(None, None)               # block table / slot mask
    if quant:
        return jax.shard_map(
            _pa.paged_attn_decode, mesh=mesh,
            in_specs=(head, blocks, blocks, blocks, blocks, rep, rep),
            out_specs=head, check_vma=False,
        )

    def unquant(q, kb, vb, tbl, slot_mask):
        return _pa.paged_attn_decode(q, kb, vb, None, None, tbl, slot_mask)

    mapped = jax.shard_map(
        unquant, mesh=mesh,
        in_specs=(head, blocks, blocks, rep, rep),
        out_specs=head, check_vma=False,
    )
    return lambda q, kb, vb, _ks, _vs, tbl, slot_mask: \
        mapped(q, kb, vb, tbl, slot_mask)


def _paged_decode_chunk_kernel(params, pool, active, key, cfg, n_steps,
                               temperature, top_k, top_p, mesh=None):
    """:func:`pool_decode_chunk` running DIRECTLY on the paged block
    planes — no dense gather/scatter. Each step writes the new token's
    KV into its slot's current physical block (one advanced-index
    scatter per layer instead of a full-pool materialization) and reads
    attention through the Pallas paged kernel
    (:mod:`pathway_tpu.models.paged_attention`), which walks the block
    table and fuses int8 dequant into the read. Same op sequence as the
    dense chunk otherwise (embedding, QKV, MLP, logits), so tokens
    match the reference path at online-softmax tolerance. On a serving
    mesh the kernel runs per-tp-shard (:func:`_paged_attn_fn`)."""
    B, C = pool["slot_mask"].shape
    Bk = paged_block(pool)
    tbl = pool["block_tbl"]
    b_idx = jnp.arange(B)
    act_i = active.astype(jnp.int32)
    act_b = active[:, None, None]
    quant = pool_quantized(pool)
    attn = _paged_attn_fn(mesh, quant)
    sample = _sample_fn(temperature, top_k, top_p)

    def body(carry, _):
        kb_c, vb_c, kbs_c, vbs_c, logits, slot_mask, pos, write, key = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        w = jnp.minimum(write, C - 1)
        slot_mask = jnp.where(
            active[:, None] & (jnp.arange(C)[None, :] == w[:, None]),
            1, slot_mask,
        )
        p = jnp.minimum(pos, cfg.max_position - 1)
        x = (_tok_embed(params, tok)[:, None, :]
             + params["wpe"][p][:, None, :]).astype(cfg.dtype)
        # each lane's write column in PHYSICAL coordinates: the block
        # table maps its logical block, the remainder is the in-block
        # column. Active lanes own disjoint blocks; inactive lanes
        # write their old bytes back (possibly into the sentinel), so
        # duplicate indices always carry identical values.
        dst_b = tbl[b_idx, w // Bk]
        dst_c = w % Bk

        def layer(x, inp):
            lp, kbl, vbl, kbsl, vbsl = inp
            q, k_new, v_new = _block_qkv(x, lp, cfg)  # (B, nh, 1, hd)
            if quant:
                k_new, sk = _kv_quant(k_new)
                v_new, sv = _kv_quant(v_new)
                kbsl = kbsl.at[dst_b, :, dst_c, :].set(
                    jnp.where(act_b, sk[:, :, 0, :],
                              kbsl[dst_b, :, dst_c, :])
                )
                vbsl = vbsl.at[dst_b, :, dst_c, :].set(
                    jnp.where(act_b, sv[:, :, 0, :],
                              vbsl[dst_b, :, dst_c, :])
                )
            kbl = kbl.at[dst_b, :, dst_c, :].set(
                jnp.where(act_b, k_new[:, :, 0, :], kbl[dst_b, :, dst_c, :])
            )
            vbl = vbl.at[dst_b, :, dst_c, :].set(
                jnp.where(act_b, v_new[:, :, 0, :], vbl[dst_b, :, dst_c, :])
            )
            ctx = attn(
                q[:, :, 0, :], kbl, vbl, kbsl, vbsl, tbl, slot_mask,
            )
            x, _cnt = _block_finish(x, lp, ctx[:, :, None, :], cfg)
            return x, (kbl, vbl, kbsl, vbsl)

        x, (kb_c, vb_c, kbs_c, vbs_c) = jax.lax.scan(
            layer, x, (params["layers"], kb_c, vb_c, kbs_c, vbs_c)
        )
        new_logits = _logits(params, x, cfg)[:, 0, :]
        logits = jnp.where(active[:, None], new_logits, logits)
        return (kb_c, vb_c, kbs_c, vbs_c, logits, slot_mask, pos + act_i,
                write + act_i, key), tok

    (kb_c, vb_c, kbs_c, vbs_c, logits, slot_mask, pos, write, _), toks = \
        jax.lax.scan(
            body,
            (pool["kb"], pool["vb"],
             pool.get("kb_scale"), pool.get("vb_scale"),
             pool["logits"], pool["slot_mask"], pool["pos"], pool["write"],
             key),
            None,
            length=n_steps,
        )
    out = {**pool, "kb": kb_c, "vb": vb_c, "logits": logits,
           "slot_mask": slot_mask, "pos": pos, "write": write}
    if quant:
        out["kb_scale"], out["vb_scale"] = kbs_c, vbs_c
    return out, toks


# ---- self-speculative decoding --------------------------------------------
#
# Decode is memory-bound: every step streams the full parameter set +
# the live KV from HBM to emit ONE token per lane. Self-speculative
# decode amortizes that stream: the first D layers of the SAME model
# (the cascade's first-N-layers trick, transformer.encode(n_layers=))
# draft k cheap continuation tokens, then ONE full-model pass scores
# all k+1 positions at once — a multi-token verify streams the weights
# once, exactly like one plain step. The longest draft prefix matching
# the full model's argmaxes is accepted, so with acceptance rate a the
# pool advances 1+a*k tokens per weight-stream instead of 1, and with
# a = 0 it still advances 1 (the cycle's first token needs no draft to
# be correct). Greedy-only: acceptance compares argmaxes, which makes
# spec-on output BYTE-IDENTICAL to plain greedy decode by construction.
# No second model, no extra params: the draft's KV is a depth-prefix of
# the same slot pool.


def _draft_scan(params, cfg: DecoderConfig, kv: dict, slot_mask,
                pos, w, t0, active, n_draft: int, n_layers: int):
    """``n_draft`` greedy draft steps with the first ``n_layers`` layers.

    ``kv`` carries the pool's KV stacks (``k``/``v``, scale planes,
    ``kw``/``vw``), whole: the draft writes the first ``n_layers`` layers'
    rows of them where they lie and never touches the rest. Starting from
    certain token ``t0`` at cache column ``w`` / position ``pos``, each step
    writes the fed token's shallow KV at its column and predicts the next
    via the final norm + the head over the truncated stack. Returns
    ``(drafts (B, n_draft), kv)``: the drafted continuation d_1..d_k, and
    the stacks with the shallow rows in them — columns ``w .. w + n_draft -
    1`` of active lanes, outside ``slot_mask``, which the cycle's verify
    rewrites for ALL layers (a caller that only wants the drafts drops
    them, and its pool is as it was)."""
    C = slot_mask.shape[1]
    R, W = pool_ring(kv), cfg.sliding_window
    idxs = jnp.arange(C)[None, :]
    quant = kv.get("k_scale") is not None

    def step(carry, j):
        kv, tok = carry
        col = jnp.minimum(w + j, C - 1)
        p = jnp.clip(pos + j, 0, cfg.max_position - 1)[:, None]
        x = _embed(params, tok[:, None], p, cfg)
        # attend the live cache plus every column this cycle already
        # wrote (w..col) — the draft's own freshly-drafted context
        allowed = (slot_mask > 0) | ((idxs >= w[:, None])
                                     & (idxs <= col[:, None]))
        bias = {"full": jnp.where(allowed, 0.0, -1e9
                                  ).astype(jnp.float32)[:, None, None, :]}
        bias["latent"] = bias["full"]
        at = {"full": col[:, None], "latent": col[:, None]}
        if R:
            cols = _ring_cols(col, R)
            live = _live_at(slot_mask, cols) | (cols >= w[:, None])
            bias["window"] = _ring_bias(cols, live, col, W)
            at["window"] = jnp.mod(col, R)[:, None]

        def layer(x, lp, st, li, kind):
            k_new, v_new = _prefill_kv(x, lp, cfg, kind, p)  # (B, nh, 1, hd)
            st = _put_lanes(st, k_new, v_new, li, at[kind[0]], active, quant)
            x, cnt = _block_lanes(x, lp, st, li, bias[kind[0]], cfg, kind, p)
            return x, st, cnt

        x, kv, _cnt, _exits = _scan_layers(cfg, params, x, kv, layer,
                                           n_layers)
        nxt = jnp.argmax(_logits(params, x, cfg)[:, 0, :], axis=-1
                         ).astype(jnp.int32)
        return (kv, nxt), nxt

    (kv, _), drafts = jax.lax.scan(step, (kv, t0), jnp.arange(n_draft))
    return drafts.T, kv  # (B, n_draft)


def pool_decode_draft(params: dict, pool: dict, active: jax.Array,
                      cfg: DecoderConfig, *, draft_layers: int,
                      n_draft: int) -> jax.Array:
    """Draft ``n_draft`` greedy tokens per active lane with the first
    ``draft_layers`` layers of the stack. Pure with respect to the pool:
    the stacks with the shallow KV rows in them are dropped here —
    :func:`pool_decode_spec`'s verify pass owns every write that a reader
    sees. Exposed standalone for tests and
    draft-quality probing; the serving path uses the fused cycle.
    Paged pools gather-run-scatter (see :func:`pool_admit`); drafting
    never writes, so only the gather side is needed."""
    require_single_pass(cfg, "spec_decode")
    if pool_paged(pool):
        return pool_decode_draft(
            params, _paged_gather(pool), active, cfg,
            draft_layers=draft_layers, n_draft=n_draft,
        )
    C = pool["slot_mask"].shape[1]
    t0 = jnp.argmax(pool["logits"], axis=-1).astype(jnp.int32)
    w = jnp.minimum(pool["write"], C - n_draft)
    drafts, _kv = _draft_scan(
        params, cfg, _kv_stacks(pool), pool["slot_mask"], pool["pos"], w,
        t0, active, n_draft, draft_layers,
    )
    return drafts


def pool_decode_spec(params: dict, pool: dict, active: jax.Array,
                     cfg: DecoderConfig, n_cycles: int, *,
                     draft_layers: int, n_spec: int):
    """``n_cycles`` draft/verify/accept cycles over every active lane in
    ONE dispatch — the speculative counterpart of
    :func:`pool_decode_chunk` (greedy only).

    Per cycle: (1) the staged logits' argmax is the cycle's first token
    t0 — plain greedy decode would emit exactly it, so it is CERTAIN;
    (2) the first ``draft_layers`` layers draft ``n_spec`` continuation
    tokens one step at a time (:func:`_draft_scan`); (3) one full-model
    pass scores all ``n_spec + 1`` positions at once, writing their KV
    at columns ``w..w+n_spec`` — its per-position logits are elementwise
    what sequential decode would produce, because layer i at position t
    reads only layers < i at positions <= t (the same invariant the
    chunked-prefill byte-equality tests pin); (4) the longest draft
    prefix matching the full model's argmaxes is accepted: the lane
    emits ``1 + accepted`` tokens, the staged logits become the verify
    logits at the last accepted position (their argmax IS the
    correction token — it becomes the next cycle's certain t0), and the
    rejected tail's columns simply stay masked out of ``slot_mask`` —
    the rewind is a mask, not a copy; the next cycle's verify overwrites
    them. A window layer's ring takes the rejected tail too: it lands on
    columns that have left every later query's window (the ring's slack
    is at least ``n_spec``). Inactive lanes compute but do not advance.

    Returns ``(pool, toks (n_cycles, n_slots, n_spec + 1), n_emit
    (n_cycles, n_slots))``: the host consumes each cycle's first
    ``n_emit`` tokens per lane and ignores the rest.

    Paged pools gather-run-scatter (see :func:`pool_admit`); the paged
    kernel does not apply to the spec path — verify scores ``n_spec+1``
    query positions, while the kernel is single-query decode."""
    require_single_pass(cfg, "spec_decode")
    if pool_paged(pool):
        view, toks, n_emit = pool_decode_spec(
            params, _paged_gather(pool), active, cfg, n_cycles,
            draft_layers=draft_layers, n_spec=n_spec,
        )
        return _paged_scatter(pool, view), toks, n_emit
    B = pool["logits"].shape[0]
    C = pool["slot_mask"].shape[1]
    R, W = pool_ring(pool), cfg.sliding_window
    if R and R < C and R < W + n_spec:
        raise ValueError(
            f"a window layer's ring of {R} rows has no room for "
            f"{n_spec} speculated columns past the window of {W}")
    D, k = draft_layers, n_spec
    quant = pool_quantized(pool)
    idxs = jnp.arange(C)
    offs = jnp.arange(k + 1)

    def cycle(carry, _):
        kv, logits, slot_mask, pos, write, counts = carry
        # verify writes k+1 columns; clamp like pool_decode_chunk's w so
        # an over-budget lane (tokens still draining) never writes past
        # the cache — the host sizes slack so live lanes never clamp
        w = jnp.minimum(write, C - 1 - k)
        t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # the draft's shallow rows land on columns the verify rewrites
        drafts, kv = _draft_scan(
            params, cfg, kv, slot_mask, pos, w, t0, active, k, D,
        )
        u = jnp.concatenate([t0[:, None], drafts], axis=1)  # (B, k+1)
        p = jnp.clip(pos[:, None] + offs[None, :], 0, cfg.max_position - 1)
        x = _embed(params, u, p, cfg)
        qcol = w[:, None] + offs[None, :]  # (B, k+1) per-query column
        # query i attends the live cache plus this cycle's columns up to
        # its own (w..w+i) — causal within the speculated window, the
        # union of what i sequential decode steps would each have seen
        allowed = (slot_mask[:, None, :] > 0) | (
            (idxs[None, None, :] >= w[:, None, None])
            & (idxs[None, None, :] <= qcol[:, :, None])
        )
        bias = {"full": jnp.where(allowed, 0.0, -1e9
                                  ).astype(jnp.float32)[:, None, :, :]}
        bias["latent"] = bias["full"]
        at = {"full": qcol, "latent": qcol}
        if R:
            cols = _ring_cols(w + k, R)                     # (B, R)
            live = (_live_at(slot_mask, cols) | (cols >= w[:, None])
                    )[:, None, :]
            bias["window"] = _ring_bias(
                cols, jnp.broadcast_to(live, (B, k + 1, R)), qcol, W)
            at["window"] = jnp.mod(qcol, R)

        def vlayer(x, lp, st, li, kind):
            k_new, v_new = _prefill_kv(x, lp, cfg, kind, p)  # (B,nh,k+1,hd)
            # each lane's k+1 new entries land at ITS columns; inactive
            # lanes keep their bytes
            st = _put_lanes(st, k_new, v_new, li, at[kind[0]], active, quant)
            x, cnt = _block_lanes(x, lp, st, li, bias[kind[0]], cfg, kind, p)
            return x, st, cnt

        x, kv, cnt, _exits = _scan_layers(cfg, params, x, kv, vlayer)
        if cnt is not None:
            counts = counts + cnt
        out_logits = _logits(params, x, cfg)  # (B, k+1, V) f32
        g = jnp.argmax(out_logits, axis=-1).astype(jnp.int32)  # (B, k+1)
        # g[:, i] is the TRUE next token after u_0..u_i; accept drafts
        # while they match it — the longest greedy-agreeing prefix
        match = (drafts == g[:, :k]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)  # (B,) in [0, k]
        n_emit = jnp.where(active, acc + 1, 0).astype(jnp.int32)
        # the logits AT the last accepted position: their argmax is the
        # correction token g_acc — the next cycle's certain t0, so a
        # rejected draft costs nothing beyond its wasted column
        new_logits = jnp.take_along_axis(
            out_logits, acc[:, None, None], axis=1
        )[:, 0, :]
        logits = jnp.where(active[:, None], new_logits, logits)
        # accept = mask in columns w..w+acc; the rejected tail's KV
        # stays masked (and is overwritten by the next cycle's verify)
        live = ((idxs[None, :] >= w[:, None])
                & (idxs[None, :] <= (w + acc)[:, None])
                & active[:, None])
        slot_mask = jnp.where(live, 1, slot_mask)
        return (kv, logits, slot_mask, pos + n_emit, write + n_emit,
                counts), (u, n_emit)

    carry0 = (_kv_stacks(pool), pool["logits"], pool["slot_mask"],
              pool["pos"], pool["write"], jnp.zeros((2,), jnp.uint32))
    (kv, logits, slot_mask, pos, write, counts), (toks, n_emit) = \
        jax.lax.scan(cycle, carry0, None, length=n_cycles)
    out = {**pool, **kv, "logits": logits,
           "slot_mask": slot_mask, "pos": pos, "write": write}
    return _add_counts(pool, out, counts, decode=True), toks, n_emit


def cast_params_for_inference(params: dict, cfg: DecoderConfig) -> dict:
    """Store matmul weights in the compute dtype for generation: every
    decode step reads the whole parameter set from HBM, so f32-stored
    weights double the bandwidth bill of the phase that IS
    bandwidth-bound. Layernorm scale/bias leaves stay f32 — the forward
    consumes them in f32 (``_ln``), so bf16 storage would silently drop
    mantissa on trained checkpoints; they are a negligible byte fraction.
    (Deliberately NOT shared with ``embedder.cast_params_for_inference``,
    which casts everything — the encoder path's measured/pinned behavior.)
    f32 configs (HF-parity tests) pass through unchanged; training keeps
    f32 masters (models/train.py)."""
    if cfg.dtype == jnp.float32:
        return params

    _LN_LEAVES = _F32_LEAVES

    def cast(path, p):
        # exact leaf names, not an "ln" substring test — a future matmul
        # weight that happens to contain "ln" in its path must still cast
        leaf = str(getattr(path[-1], "key", path[-1])) if path else ""
        if leaf in _LN_LEAVES or p.dtype != jnp.float32:
            return p
        return p.astype(cfg.dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def count_params(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
