"""A model layout made of new files only: the toy decoder with the key names
most published configs use (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``intermediate_size``, ``max_position_embeddings``)
where GPT-2's file says ``n_embd``, ``n_layer``, ``n_head``, ``n_inner``,
``n_positions``. It brings what a layout has to bring: the mapping onto the
program's ``DecoderConfig``, its own weight spec, its own copy of the plain
reference forward (float32 at ``highest``; ``fp8`` is the control) and its
own counts. What a later PR ships for a drawn architecture has this form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.layouts import DecoderLayout
from harness.reference import fp8_round


def _sizes(model: dict):
    h = model["hidden_size"]
    return model["num_hidden_layers"], h, model["intermediate_size"] or 4 * h


def _round_weights(params: dict):
    """The control's weights: matrices rounded to fp8 along the contracted
    axis, tables along their rows, the rest bfloat16."""
    def walk(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[name] = walk(leaf)
            elif name in ("wte", "wpe"):
                out[name] = fp8_round(leaf, -1).astype(jnp.bfloat16)
            elif name.endswith("_w"):
                out[name] = fp8_round(leaf, -2).astype(jnp.bfloat16)
            else:
                out[name] = leaf.astype(jnp.bfloat16)
        return out

    return walk(params)


def _norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    return ((x32 - mu) / jnp.sqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _matmul(x, w, low: bool):
    if low:
        x = fp8_round(x, -1).astype(jnp.bfloat16)
    return x @ w


@functools.partial(jax.jit, static_argnames=("heads", "eps", "n_out", "low"))
def _forward(p, ids, first, heads: int, eps: float, n_out: int, low: bool):
    """Logits of positions ``first .. first + n_out`` of one sequence:
    pre-LN blocks, causal attention, tanh gelu, the head tied to ``wte``."""
    s = ids.shape[0]
    x = p["wte"][ids] + p["wpe"][jnp.arange(s)]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def split(t):
        return t.reshape(s, heads, -1).transpose(1, 0, 2)

    def block(x, lp):
        a = _norm(x, lp["ln1_scale"], lp["ln1_bias"], eps)
        q, k, v = jnp.split(_matmul(a, lp["qkv_w"], low) + lp["qkv_b"], 3, -1)
        q, k, v = split(q), split(k), split(v)
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores.astype(jnp.float32), -1e9)
        probs = jax.nn.softmax(scores, -1).astype(v.dtype)
        ctx = jnp.einsum("hqk,hkd->hqd", probs, v).transpose(1, 0, 2)
        x = x + _matmul(ctx.reshape(s, -1), lp["attn_out_w"], low) \
            + lp["attn_out_b"]
        m = _norm(x, lp["ln2_scale"], lp["ln2_bias"], eps)
        m = jax.nn.gelu(_matmul(m, lp["mlp_in_w"], low) + lp["mlp_in_b"],
                        approximate=True)
        return x + _matmul(m, lp["mlp_out_w"], low) + lp["mlp_out_b"], None

    x, _ = jax.lax.scan(block, x, p["layers"])
    h = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    h = _norm(h, p["ln_f_scale"], p["ln_f_bias"], eps)
    return _matmul(h, p["wte"].T, low).astype(jnp.float32)


class ToyRenamed(DecoderLayout):
    def program_config(self, model: dict):
        from pathway_tpu.models.decoder import DecoderConfig

        n, h, i = _sizes(model)
        return DecoderConfig(
            vocab_size=model["vocab_size"], hidden=h, layers=n,
            heads=model["num_attention_heads"], intermediate=i,
            max_position=model["max_position_embeddings"],
            layer_norm_eps=model["layer_norm_eps"], dtype=jnp.bfloat16)

    def weight_spec(self, model: dict, role: str) -> dict:
        n, h, i = _sizes(model)
        w, b, g = "w", "b", "gain"
        return {
            "wte": ((model["vocab_size"], h), w, 0.02),
            "wpe": ((model["max_position_embeddings"], h), w, 0.01),
            "layers": {
                "ln1_scale": ((n, h), g, 0.02), "ln1_bias": ((n, h), b, 0.02),
                "qkv_w": ((n, h, 3 * h), w, 0.02),
                "qkv_b": ((n, 3 * h), b, 0.02),
                "attn_out_w": ((n, h, h), w, 0.02),
                "attn_out_b": ((n, h), b, 0.02),
                "ln2_scale": ((n, h), g, 0.02), "ln2_bias": ((n, h), b, 0.02),
                "mlp_in_w": ((n, h, i), w, 0.02),
                "mlp_in_b": ((n, i), b, 0.02),
                "mlp_out_w": ((n, i, h), w, 0.02),
                "mlp_out_b": ((n, h), b, 0.02),
            },
            "ln_f_scale": ((h,), g, 0.02), "ln_f_bias": ((h,), b, 0.02),
        }

    # -- the plain reference ----------------------------------------------

    def prepare(self, params: dict, precision: str = "f32") -> dict:
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return p if precision == "f32" else _round_weights(p)

    def logits(self, prepared: dict, model: dict, ids: list, first: int,
               precision: str = "f32") -> np.ndarray:
        n = len(ids)
        padded = np.zeros((-(-n // 64) * 64,), np.int32)
        padded[:n] = ids      # a causal model's positions see none after them
        with jax.default_matmul_precision(
                "highest" if precision == "f32" else "default"):
            return np.asarray(_forward(
                prepared, jnp.asarray(padded), first,
                model["num_attention_heads"], model["layer_norm_eps"],
                n - first, precision != "f32"))

    # -- work counted from the shapes ---------------------------------------

    def matmul_param_count(self, model: dict) -> int:
        n, h, i = _sizes(model)
        return n * (4 * h * h + 2 * h * i) + model["vocab_size"] * h

    def param_bytes(self, model: dict, itemsize: int = 2) -> float:
        n, h, i = _sizes(model)
        small = n * (9 * h + i) + 2 * h       # biases and norm gains
        return float((self.matmul_param_count(model) + small) * itemsize)

    def kv_bytes_per_token(self, model: dict, itemsize: int = 2) -> float:
        n, h, _i = _sizes(model)
        return float(2 * n * h * itemsize)

    def decode_step_bytes(self, model: dict, live_kv_tokens: float,
                          itemsize: int = 2) -> float:
        return self.param_bytes(model, itemsize) \
            + live_kv_tokens * self.kv_bytes_per_token(model, itemsize)

    def decode_step_flops(self, model: dict, batch: float,
                          live_kv_tokens: float) -> float:
        n, h, _i = _sizes(model)
        return 2.0 * self.matmul_param_count(model) * batch \
            + 4.0 * n * h * live_kv_tokens

    def prefill_flops(self, model: dict, prompt_tokens: int) -> float:
        n, h, _i = _sizes(model)
        head = model["vocab_size"] * h
        return 2.0 * (self.matmul_param_count(model) - head) * prompt_tokens \
            + 2.0 * n * prompt_tokens * prompt_tokens * h + 2.0 * head


layout = ToyRenamed()
