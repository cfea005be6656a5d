"""The plain references: each model's forward pass in straightforward
``jax.numpy`` at float32 and ``highest`` matmul precision, with no kernels,
no cache and no batching tricks, written from the published descriptions
(BERT, Devlin et al. 2018; GPT-2, Radford et al. 2019) and importing NOTHING
of the program. They read the benchmark's own weights
(:mod:`harness.weights`) and tokenize with their own copy of the rule the
configuration states (``tokenizer`` in the config's file).

``precision="fp8"`` is the CONTROL: the same forward computed in 8-bit
floating point (e4m3) — the nearest precision below the bfloat16 the
configurations state: every weight matrix and table is rounded to fp8 with
one scale per output channel (per row for a table), the input of every
weight matmul is rounded to fp8 with one scale per token, and the rest of
the arithmetic is bfloat16 at default precision. A comparison that this
control passes is too loose.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

# ---- the tokenizer rules the configurations state -------------------------

PAD_ID, CLS_ID, SEP_ID = 0, 101, 102
_WORD = re.compile(r"[a-z0-9]+")


def _fnv1a(word: str) -> int:
    h = 0xCBF29CE484222325
    for b in word.encode("utf-8"):
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def hash_word_ids(text: str, vocab_size: int) -> list[int]:
    """``hash-words-v1``: lower-case, split on runs of ``[a-z0-9]``, id =
    999 + FNV-1a(word) mod (vocab - 999) (BERT's id layout: specials below
    999, [CLS] 101, [SEP] 102, [PAD] 0)."""
    span = vocab_size - 999
    return [999 + _fnv1a(w) % span for w in _WORD.findall(text.lower())]


def encode_single(texts: list[str], vocab_size: int, max_length: int):
    """``[CLS] words [SEP]``, truncated to ``max_length``; ids and mask."""
    seqs = []
    for t in texts:
        words = hash_word_ids(t, vocab_size)[: max_length - 2]
        seqs.append([CLS_ID] + words + [SEP_ID])
    return _pad(seqs)


def encode_pairs(pairs: list[tuple[str, str]], vocab_size: int,
                 max_length: int):
    """``[CLS] a [SEP] b [SEP]`` with segment ids 0 / 1: ``a`` gets at most
    half of the room, ``b`` the rest."""
    half = (max_length - 3) // 2
    seqs, types = [], []
    for a, b in pairs:
        wa = hash_word_ids(a, vocab_size)[:half]
        wb = hash_word_ids(b, vocab_size)[: max_length - 3 - len(wa)]
        seqs.append([CLS_ID] + wa + [SEP_ID] + wb + [SEP_ID])
        types.append([0] * (len(wa) + 2) + [1] * (len(wb) + 1))
    ids, mask = _pad(seqs)
    ty, _ = _pad(types)
    return ids, mask, ty


def _pad(seqs: list[list[int]]):
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int32)
    mask = np.zeros((len(seqs), width), np.int32)
    for r, s in enumerate(seqs):
        ids[r, : len(s)] = s
        mask[r, : len(s)] = 1
    return ids, mask


# ---- precision ---------------------------------------------------------------


FP8_MAX = 448.0  # the largest e4m3 value


def fp8_round(x, axis: int):
    """``x`` as e4m3 stands for it: one scale per slice along ``axis`` (the
    contracted one), so that the slice's largest value lands on 448."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / FP8_MAX, 1e-12)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _prepare(params: dict, precision: str, tables: tuple[str, ...]):
    """float32 copies of the benchmark's weights; under the control every
    matrix (contracted axis -2) and table (per row) is rounded to fp8 and
    everything is kept in bfloat16."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    if precision == "f32":
        return p
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")

    def walk(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[name] = walk(leaf)
            elif name in tables:
                out[name] = fp8_round(leaf, -1).astype(jnp.bfloat16)
            elif name.endswith("_w") or (name == "w" and leaf.ndim >= 2):
                out[name] = fp8_round(leaf, -2).astype(jnp.bfloat16)
            else:
                out[name] = leaf.astype(jnp.bfloat16)
        return out

    return walk(p)


def _mm(x, w, low: bool):
    """A weight matmul; under the control its input is rounded to fp8."""
    if low:
        x = fp8_round(x, -1).astype(jnp.bfloat16)
    return x @ w


def _matmul_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "f32" else "default")


def _ln(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


def _heads(x, n_heads: int):
    b, s, h = x.shape
    return x.reshape(b, s, n_heads, h // n_heads).transpose(0, 2, 1, 3)


def _attention(q, k, v, allowed):
    """softmax(q k^T / sqrt(d) + mask) v, per head."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    scores = jnp.where(allowed, scores.astype(jnp.float32), -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    b, h, s, _ = ctx.shape
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)


# ---- BERT-family encoder (MiniLM-L6: post-LN, erf gelu) ---------------------


def _encoder_hidden(p, ids, mask, types, cfg: dict, low: bool):
    emb = p["embeddings"]
    s = ids.shape[1]
    x = emb["word"][ids] + emb["position"][jnp.arange(s)][None] \
        + emb["type"][types]
    eps = cfg["layer_norm_eps"]
    x = _ln(x, emb["ln_scale"], emb["ln_bias"], eps)
    allowed = (mask[:, None, None, :] > 0)
    nh = cfg["num_attention_heads"]

    def layer(x, lp):
        qkv = _mm(x, lp["qkv_w"], low) + lp["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx = _attention(_heads(q, nh), _heads(k, nh), _heads(v, nh), allowed)
        x = _ln(x + _mm(ctx, lp["attn_out_w"], low) + lp["attn_out_b"],
                lp["ln1_scale"], lp["ln1_bias"], eps)
        m = jax.nn.gelu(_mm(x, lp["mlp_in_w"], low) + lp["mlp_in_b"],
                        approximate=False)
        x = _ln(x + _mm(m, lp["mlp_out_w"], low) + lp["mlp_out_b"],
                lp["ln2_scale"], lp["ln2_bias"], eps)
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    return x


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _embed_jit(p, ids, mask, cfg_items, low):
    cfg = dict(cfg_items)
    hidden = _encoder_hidden(p, ids, mask, jnp.zeros_like(ids), cfg, low
                             ).astype(jnp.float32)
    m = mask.astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(hidden * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _score_jit(p, ids, mask, types, cfg_items, low):
    cfg = dict(cfg_items)
    hidden = _encoder_hidden(p, ids, mask, types, cfg, low)
    pooled = jnp.tanh(_mm(hidden[:, 0, :], p["pooler"]["w"], low)
                      + p["pooler"]["b"])
    return (_mm(pooled, p["head"]["w"], low) + p["head"]["b"]
            )[:, 0].astype(jnp.float32)


def _cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


def _blocks(n: int, block: int):
    for start in range(0, n, block):
        yield start, min(n, start + block)


def _pad_rows(a: np.ndarray, rows: int, width: int) -> np.ndarray:
    return np.pad(a, ((0, rows - a.shape[0]), (0, width - a.shape[1])))


def embed_texts(params: dict, cfg: dict, texts: list[str], max_length: int,
                precision: str = "f32", block: int = 64) -> np.ndarray:
    """Unit sentence vectors (masked mean pool, L2 normalise), in blocks of
    rows so that it fits beside whatever else the device holds."""
    p = _prepare(params, precision, tables=("word", "position", "type"))
    ids, mask = encode_single(texts, cfg["vocab_size"], max_length)
    out = []
    with _matmul_precision(precision):
        for a, b in _blocks(len(texts), block):
            i = _pad_rows(ids[a:b], block, ids.shape[1])
            m = _pad_rows(mask[a:b], block, ids.shape[1])
            out.append(np.asarray(_embed_jit(
                p, jnp.asarray(i), jnp.asarray(m), _cfg_items(cfg),
                precision != "f32"))[: b - a])
    return np.concatenate(out).astype(np.float32)


def score_pairs(params: dict, cfg: dict, pairs: list[tuple[str, str]],
                max_length: int, precision: str = "f32",
                block: int = 32) -> np.ndarray:
    """Cross-encoder relevance of (query, document): [CLS] through a tanh
    pooler and a scalar head."""
    p = _prepare(params, precision, tables=("word", "position", "type"))
    ids, mask, types = encode_pairs(pairs, cfg["vocab_size"], max_length)
    out = []
    with _matmul_precision(precision):
        for a, b in _blocks(len(pairs), block):
            args = [jnp.asarray(_pad_rows(x[a:b], block, ids.shape[1]))
                    for x in (ids, mask, types)]
            out.append(np.asarray(_score_jit(
                p, *args, _cfg_items(cfg), precision != "f32"))[: b - a])
    return np.concatenate(out).astype(np.float32)


# ---- GPT-2 decoder (pre-LN, tanh gelu, tied head) ----------------------------


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "n_out", "low"))
def _gpt2_logits_jit(p, ids, first, cfg_items, n_out: int, low: bool):
    """Logits of positions ``first .. first + n_out`` of ONE sequence
    ``ids`` (S,)."""
    cfg = dict(cfg_items)
    s = ids.shape[0]
    nh = cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    x = (p["wte"][ids] + p["wpe"][jnp.arange(s)])[None]
    allowed = jnp.tril(jnp.ones((s, s), bool))[None, None]

    def layer(x, lp):
        h1 = _ln(x, lp["ln1_scale"], lp["ln1_bias"], eps)
        qkv = _mm(h1, lp["qkv_w"], low) + lp["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx = _attention(_heads(q, nh), _heads(k, nh), _heads(v, nh), allowed)
        x = x + _mm(ctx, lp["attn_out_w"], low) + lp["attn_out_b"]
        h2 = _ln(x, lp["ln2_scale"], lp["ln2_bias"], eps)
        m = jax.nn.gelu(_mm(h2, lp["mlp_in_w"], low) + lp["mlp_in_b"],
                        approximate=True)
        return x + _mm(m, lp["mlp_out_w"], low) + lp["mlp_out_b"], None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    h = jax.lax.dynamic_slice_in_dim(x[0], first, n_out, axis=0)
    h = _ln(h, p["ln_f_scale"], p["ln_f_bias"], eps)
    return _mm(h, p["wte"].T, low).astype(jnp.float32)


def gpt2_logits(prepared: dict, cfg: dict, ids: list[int], first: int,
                precision: str = "f32", pad_to: int = 64) -> np.ndarray:
    """Next-token logits at positions ``first .. len(ids)-1`` of one
    sequence. The sequence is right-padded to a multiple of ``pad_to`` (a
    causal model's earlier positions do not see the padding), so a run's
    sequences share a few compiled shapes."""
    n = len(ids)
    width = -(-n // pad_to) * pad_to
    arr = np.zeros((width,), np.int32)
    arr[:n] = ids
    with _matmul_precision(precision):
        out = _gpt2_logits_jit(prepared, jnp.asarray(arr), first,
                               _cfg_items(cfg), n - first,
                               precision != "f32")
    return np.asarray(out)


def prepare_decoder(params: dict, precision: str = "f32") -> dict:
    return _prepare(params, precision, tables=("wte", "wpe"))
