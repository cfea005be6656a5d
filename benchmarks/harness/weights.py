"""Weights from ``--seed``, made on the device in one jitted call per model,
in the type they are served in (bfloat16). The program receives them as the
parameter trees its constructors take (``SentenceEmbedderModel(params=)``,
``CrossEncoderModel(params=, head=)``, ``TPUDecoderChat(params=)``); the
plain reference reads the SAME arrays, upcast to float32, so the two differ
by the precision of the computation alone. Biases and layer-norm gains are
random too (a zero bias would hide a dropped bias)."""

from __future__ import annotations

import functools
import json


def seed_key(seed: int, stream: int):
    """A PRNG key from any non-negative ``--seed`` (the driver's pass 2**31)
    and a stream number (which model, which chunk of rows)."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    key = jax.random.fold_in(key, seed // (2 ** 31 - 1))
    return jax.random.fold_in(key, stream)


def _tree(key, spec: dict, dtype):
    """``spec``: name -> (shape, kind, scale) or a nested dict."""
    import jax
    import jax.numpy as jnp

    out = {}
    for n, (name, item) in enumerate(sorted(spec.items())):
        sub = jax.random.fold_in(key, n)
        if isinstance(item, dict):
            out[name] = _tree(sub, item, dtype)
            continue
        shape, kind, scale = item
        v = jax.random.normal(sub, tuple(shape), jnp.float32) * scale
        if kind == "gain":
            v = 1.0 + v
        out[name] = v.astype(dtype)
    return out


def encoder_spec(cfg: dict, head: bool) -> dict:
    n, h, i = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    w, b, g = "w", "b", "gain"
    spec = {
        "embeddings": {
            "word": ((cfg["vocab_size"], h), w, 0.02),
            "position": ((cfg["max_position_embeddings"], h), w, 0.02),
            "type": ((cfg["type_vocab_size"], h), w, 0.02),
            "ln_scale": ((h,), g, 0.02), "ln_bias": ((h,), b, 0.02),
        },
        "layers": {
            "qkv_w": ((n, h, 3 * h), w, 0.02), "qkv_b": ((n, 3 * h), b, 0.02),
            "attn_out_w": ((n, h, h), w, 0.02),
            "attn_out_b": ((n, h), b, 0.02),
            "ln1_scale": ((n, h), g, 0.02), "ln1_bias": ((n, h), b, 0.02),
            "mlp_in_w": ((n, h, i), w, 0.02), "mlp_in_b": ((n, i), b, 0.02),
            "mlp_out_w": ((n, i, h), w, 0.02), "mlp_out_b": ((n, h), b, 0.02),
            "ln2_scale": ((n, h), g, 0.02), "ln2_bias": ((n, h), b, 0.02),
        },
        "pooler": {"w": ((h, h), w, 0.02), "b": ((h,), b, 0.02)},
    }
    if head:
        # a head wide enough that scores spread (std ~1), as a trained
        # cross-encoder's do
        spec["head"] = {"w": ((h, 1), w, 0.1), "b": ((1,), b, 0.1)}
    return spec


def decoder_spec(cfg: dict) -> dict:
    n, h = cfg["n_layer"], cfg["n_embd"]
    i = cfg.get("n_inner") or 4 * h
    w, b, g = "w", "b", "gain"
    return {
        "wte": ((cfg["vocab_size"], h), w, 0.02),
        "wpe": ((cfg["n_positions"], h), w, 0.01),
        "layers": {
            "ln1_scale": ((n, h), g, 0.02), "ln1_bias": ((n, h), b, 0.02),
            "qkv_w": ((n, h, 3 * h), w, 0.02), "qkv_b": ((n, 3 * h), b, 0.02),
            "attn_out_w": ((n, h, h), w, 0.02),
            "attn_out_b": ((n, h), b, 0.02),
            "ln2_scale": ((n, h), g, 0.02), "ln2_bias": ((n, h), b, 0.02),
            "mlp_in_w": ((n, h, i), w, 0.02), "mlp_in_b": ((n, i), b, 0.02),
            "mlp_out_w": ((n, i, h), w, 0.02), "mlp_out_b": ((n, h), b, 0.02),
        },
        "ln_f_scale": ((h,), g, 0.02), "ln_f_bias": ((h,), b, 0.02),
    }


@functools.lru_cache(maxsize=None)
def _maker(spec_json: str):
    import jax
    import jax.numpy as jnp

    spec = json.loads(spec_json)
    return jax.jit(lambda key: _tree(key, spec, jnp.bfloat16))


def make_params(seed: int, stream: int, spec: dict) -> dict:
    """The whole tree in ONE jitted call, bfloat16, on the default device."""
    return _maker(json.dumps(spec, sort_keys=True))(seed_key(seed, stream))


# one stream number per model, so that the embedder and the cross-encoder
# of one seed do not share weights
STREAM_EMBEDDER, STREAM_RERANKER, STREAM_DECODER, STREAM_ROWS = 1, 2, 3, 4


def warm_chunks(seed: int, rows: int, dim: int, chunk: int = 1 << 17):
    """The index's warm state as seeded device chunks of unit rows
    (``chip_smoke.py:warm_chunks`` with the seed as an argument): the same
    rows for every index instance and for the reference."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        v = jax.random.normal(key, (min(chunk, rows), dim))
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)

    base = seed_key(seed, STREAM_ROWS)
    for start in range(0, rows, chunk):
        n = min(chunk, rows - start)
        yield start, make(jax.random.fold_in(base, start))[:n]
