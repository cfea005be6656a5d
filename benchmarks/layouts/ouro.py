"""Model layout ``ouro`` (ByteDance Ouro, ``model_type`` ``ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a decoder
whose layer stack runs ``total_ut_steps`` times over the SAME weights, with
a key-value cache of its own for every pass, sandwich RMSNorm, rotary
positions, SwiGLU, an untied head and a learned exit gate. It brings the
mapping onto the program's ``DecoderConfig``, the weight spec, the plain
reference and the counts.

The model's entry holds the published ``config.json`` keys as they are:
nothing is cut (``harness/layouts.py`` says what a layout gives).

The equations (``h`` is (tokens, hidden); all float32 in the reference).
Layer ``l``, pass ``u``, with ``rms(x; g) = x / sqrt(mean(x^2) + eps) g``:

    a     = rms(h; g1_l)
    q,k,v = a Wq_l, a Wk_l, a Wv_l      heads of head_dim, no bias; q and k
                                        rotated (rope_theta, the whole head,
                                        halves against each other)
    K[u,l], V[u,l] <- append k, v       the cache of THIS pass of this layer
    c     = softmax(q K[u,l]^T / sqrt(head_dim) + causal) V[u,l]
    h     = h + rms(c Wo_l; g2_l)       the norm AFTER attention, before the
                                        residual add
    m     = rms(h; g3_l)
    h     = h + rms((silu(m Wgate_l) * (m Wup_l)) Wdown_l; g4_l)

    h_0 = E[ids]
    for u in 0 .. total_ut_steps - 1:
        for l in 0 .. layers - 1: layer(l, u)
        h = rms(h; g_final);  H_u = h;  lambda_u = sigmoid(H_u w_exit + b_exit)
    (the NORMED state H_u is what pass u + 1 starts from)
    exit distribution: p_u = lambda_u prod_{j<u} (1 - lambda_j) for every
      pass but the last, which takes the remainder
    exit step s = the first u whose cumulative p reaches
      early_exit_threshold, else the last; a threshold of 1 or more means
      the last pass for every token
    logits = H_s W_head

``config.json`` gives the shapes and ``total_ut_steps``; *assumed* from the
released ``modeling_ouro.py`` (the configuration's file lists each): where
the four norms of a layer sit, that the final norm closes EVERY pass and
feeds the next, that the gate is a ``Linear(hidden -> 1)`` with a bias, that
a cache is kept for every (pass, layer), no attention biases. Every pass is
computed for every token at any threshold: the gate picks among the
states, it skips nothing. The cache-sharing variants the paper discusses
for decoding (the last pass's cache for all, or an average) are another
result and are not written here.

The reference is float32 at ``highest``, one sequence, NO cache (pass ``u``
of a position attends pass ``u`` of the earlier positions because each pass
is one causal forward over the whole sequence), no kernels, importing
nothing of the program. It casts weights layer by layer and attends in
blocks of queries. ``precision="bf16"`` is the same forward as plain
bfloat16 would compute it (a diagnostic); ``"fp8"`` is the control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.layouts import DecoderLayout
from harness.reference import fp8_round


def _dims(model: dict) -> dict:
    if any(t != "full_attention" for t in model["layer_types"]) \
            or model.get("use_sliding_window"):
        raise ValueError("the ouro layout has full attention layers only")
    if model.get("rope_scaling"):
        raise ValueError("the ouro layout scales no rotary frequencies")
    return {
        "h": model["hidden_size"], "hd": model["head_dim"],
        "nq": model["num_attention_heads"],
        "nkv": model["num_key_value_heads"],
        "i": model["intermediate_size"], "v": model["vocab_size"],
        "layers": model["num_hidden_layers"],
        "loops": model["total_ut_steps"],
        "eps": model["rms_norm_eps"], "theta": float(model["rope_theta"]),
        "threshold": float(model["early_exit_threshold"]),
    }


# ---- the plain reference ---------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _w(leaf, low: str):
    """A weight matrix as the reference reads it: float32; under ``low``
    bfloat16, and for the control (``"fp8"``) first rounded to fp8 along
    its contracted axis."""
    leaf = leaf.astype(jnp.float32)
    if low == "fp8":
        leaf = fp8_round(leaf, -2)
    return leaf.astype(jnp.bfloat16) if low else leaf


def _mm(x, w, low: str):
    """``x @ w``; under ``low`` both sides bfloat16 and the product kept in
    bfloat16 (``"fp8"``: ``x`` rounded to fp8 first)."""
    if low == "fp8":
        x = fp8_round(x, -1)
    if low:
        x = x.astype(jnp.bfloat16)
    return (x @ _w(w, low)).astype(jnp.float32)


def _rotary(t, theta: float):
    """``t`` (S, n, hd) at positions 0..S-1, halves rotated against each
    other."""
    s, _n, hd = t.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _attention(x, lp, d: dict, low: str, block: int = 512):
    s = x.shape[0]
    nq, nkv, hd = d["nq"], d["nkv"], d["hd"]
    qkv = _mm(x, lp["qkv_w"], low)
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    q = _rotary(q.reshape(s, nq, hd), d["theta"])
    k = _rotary(k.reshape(s, nkv, hd), d["theta"])
    v = v.reshape(s, nkv, hd)
    q = q.reshape(s, nkv, nq // nkv, hd)
    keys = jnp.arange(s)
    out = []
    for a in range(0, s, block):        # blocks of queries: scores fit
        qs = a + jnp.arange(min(block, s - a))
        ok = keys[None, :] <= qs[:, None]
        sc = jnp.einsum("qngd,knd->ngqk", q[a:a + block], k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("ngqk,knd->qngd", p, v))
    ctx = jnp.concatenate(out, axis=0).reshape(s, nq * hd)
    return _mm(ctx, lp["attn_out_w"], low)


def _kept(t, low: str):
    """The residual stream as bfloat16 keeps it (``"bf16"`` only)."""
    return t.astype(jnp.bfloat16).astype(jnp.float32) if low == "bf16" else t


@functools.partial(jax.jit, static_argnames=("dims", "low"))
def _layer(x, lp, dims, low: str):
    """One layer over one sequence ``x`` (S, H), one pass of it; ``lp`` that
    layer's leaves as the benchmark made them (bfloat16), cast here."""
    d = dict(dims)
    eps = d["eps"]
    attn = _attention(_rms(x, lp["ln1_scale"], eps), lp, d, low)
    a = _kept(x + _rms(attn, lp["ln1p_scale"], eps), low)
    m = _rms(a, lp["ln2_scale"], eps)
    y = _mm(jax.nn.silu(_mm(m, lp["mlp_in_w"], low))
            * _mm(m, lp["mlp_up_w"], low), lp["mlp_out_w"], low)
    return _kept(a + _rms(y, lp["ln2p_scale"], eps), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _close_pass(x, scale, exit_w, exit_b, eps: float, low: str):
    """The final norm, which closes EVERY pass, and the exit gate's logit
    of the normed state: ``(H_u, z_u)``."""
    h = _kept(_rms(x, scale, eps), low)
    z = _mm(h, exit_w, low)[:, 0] + exit_b.astype(jnp.float32)[0]
    return h, z


def exit_rule(z, threshold: float):
    """``z`` (loops, S) the gate's logits: ``(p (loops, S), step (S,))``:
    the exit distribution and the exit step (the module's text)."""
    z = np.asarray(z, np.float64)
    lam = 1.0 / (1.0 + np.exp(-z))
    p = np.zeros_like(lam)
    left = np.ones_like(lam[0])         # prod_{j<u} (1 - lambda_j)
    for u in range(len(lam) - 1):
        p[u] = lam[u] * left
        left = left * (1.0 - lam[u])
    p[-1] = left
    step = np.full(lam.shape[1:], len(lam) - 1, np.int64)
    if threshold < 1.0:
        cum = np.cumsum(p, axis=0)
        for u in range(len(lam) - 2, -1, -1):
            step = np.where(cum[u] >= threshold, u, step)
    return p, step


@functools.partial(jax.jit, static_argnames=("low",))
def _head(h, head, low: str):
    if low == "fp8":
        h = fp8_round(h, -1)
        head = fp8_round(head.astype(jnp.float32), -1)
    if low:
        h, head = h.astype(jnp.bfloat16), head.astype(jnp.bfloat16)
    return (h @ head.astype(h.dtype).T).astype(jnp.float32)


def reference_forward(params: dict, model: dict, ids, first: int,
                      precision: str = "f32", exits: bool = False):
    """Logits (float32) of positions ``first .. len(ids) - 1`` of ONE
    sequence through every pass of the whole model; with ``exits`` also
    those positions' exit distribution ``p`` (loops, n) and exit step."""
    d = _dims(model)
    low = "" if precision == "f32" else precision
    ids = jnp.asarray(np.asarray(ids, np.int32))
    dims = tuple(sorted(d.items()))
    states, zs = [], []
    with jax.default_matmul_precision("default" if low else "highest"):
        x = params["wte"][ids].astype(jnp.float32)
        if low == "fp8":
            x = fp8_round(x, -1)
        for _u in range(d["loops"]):
            for j in range(d["layers"]):
                lp = jax.tree.map(lambda a: a[j], params["layers"])
                x = _layer(x, lp, dims, low)
            x, z = _close_pass(x, params["ln_f_scale"], params["exit_w"],
                               params["exit_b"], d["eps"], low)
            states.append(x[first:])
            zs.append(np.asarray(z)[first:])
        p, step = exit_rule(np.stack(zs), d["threshold"])
        h = jnp.take_along_axis(
            jnp.stack(states), jnp.asarray(step)[None, :, None], axis=0)[0]
        out = np.asarray(_head(h, params["lm_head"], low))
    return (out, p, step) if exits else out


# ---- the layout -------------------------------------------------------------

class Ouro(DecoderLayout):
    def program_config(self, model: dict):
        from pathway_tpu.models.decoder import DecoderConfig

        if model["torch_dtype"] != "bfloat16":
            raise ValueError("the decoder cells state bfloat16")
        d = _dims(model)
        return DecoderConfig(
            vocab_size=d["v"], hidden=d["h"], layers=d["layers"],
            heads=d["nq"], kv_heads=d["nkv"], head_size=d["hd"],
            intermediate=d["i"],
            max_position=model["max_position_embeddings"],
            layer_norm_eps=d["eps"], dtype=jnp.bfloat16,
            norm="rmsnorm", sandwich_norm=True, positions="rotary",
            rope_theta=d["theta"], mlp="swiglu", bias=False,
            tied_head=bool(model["tie_word_embeddings"]),
            loops=d["loops"], exit_gate=True,
            exit_threshold=d["threshold"],
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        d = _dims(model)
        h, hd, nq, nkv, n = d["h"], d["hd"], d["nq"], d["nkv"], d["layers"]
        w, g = "w", "gain"
        return {
            "wte": ((d["v"], h), w, 0.02),
            "layers": {
                "ln1_scale": ((n, h), g, 0.02),
                "qkv_w": ((n, h, (nq + 2 * nkv) * hd), w, 0.02),
                "attn_out_w": ((n, nq * hd, h), w, 0.02),
                "ln1p_scale": ((n, h), g, 0.02),
                "ln2_scale": ((n, h), g, 0.02),
                "mlp_in_w": ((n, h, d["i"]), w, 0.02),
                "mlp_up_w": ((n, h, d["i"]), w, 0.02),
                "mlp_out_w": ((n, d["i"], h), w, 0.02),
                "ln2p_scale": ((n, h), g, 0.02),
            },
            "ln_f_scale": ((h,), g, 0.02),
            "lm_head": ((d["v"], h), w, 0.02),
            "exit_w": ((h, 1), w, 0.02),
            "exit_b": ((1,), "b", 0.02),
        }

    # -- the plain reference ----------------------------------------------

    def prepare(self, params: dict, precision: str = "f32") -> dict:
        """The benchmark's own arrays as they are: the reference casts them
        layer by layer as it goes."""
        if precision not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        return params

    def logits(self, prepared: dict, model: dict, ids: list, first: int,
               precision: str = "f32") -> np.ndarray:
        return reference_forward(prepared, model, ids, first, precision)

    # -- work counted from the shapes ---------------------------------------

    def attention_params(self, model: dict) -> int:
        d = _dims(model)
        return d["h"] * (d["nq"] + 2 * d["nkv"]) * d["hd"] \
            + d["nq"] * d["hd"] * d["h"]            # q k v, o

    def layer_params(self, model: dict) -> int:
        """Matrix parameters of one layer (read once a PASS)."""
        d = _dims(model)
        return self.attention_params(model) + 3 * d["h"] * d["i"]

    def matmul_param_count(self, model: dict) -> int:
        """Matrix parameters the model HOLDS: the layers once (the passes
        share them), embedding and untied head."""
        d = _dims(model)
        return d["layers"] * self.layer_params(model) + 2 * d["v"] * d["h"]

    def param_count(self, model: dict) -> int:
        """Every parameter: the matrices, four gains a layer, the final
        norm, the exit gate and its bias."""
        d = _dims(model)
        return self.matmul_param_count(model) + d["layers"] * 4 * d["h"] \
            + d["h"] + d["h"] + 1

    def param_bytes(self, model: dict, itemsize: int = 2) -> float:
        return float(self.param_count(model) * itemsize)

    def kv_bytes_per_token_layer(self, model: dict, itemsize: int = 2) -> int:
        d = _dims(model)
        return 2 * d["nkv"] * d["hd"] * itemsize

    def kv_tokens(self, model: dict, context: float) -> float:
        """Cached positions one sequence of ``context`` tokens keeps live
        over all CACHE layers: one for every pass of every layer."""
        d = _dims(model)
        return float(d["loops"] * d["layers"] * context)

    def kv_bytes_per_token(self, model: dict, itemsize: int = 2) -> float:
        """K and V of one cached position over every pass of every layer."""
        d = _dims(model)
        return float(d["loops"] * d["layers"]
                     * self.kv_bytes_per_token_layer(model, itemsize))

    def decode_step_bytes(self, model: dict, live_kv_tokens: float,
                          itemsize: int = 2, batch: float = 1.0) -> float:
        """One decode step reads the layers' weights once a PASS, the head,
        the final norm and the gate once, its token rows, and the live
        cache of every cache layer (``live_kv_tokens``: the batch's
        contexts summed)."""
        d = _dims(model)
        layer = self.layer_params(model) + 4 * d["h"]
        total = d["loops"] * d["layers"] * layer + d["v"] * d["h"] \
            + 2 * d["h"] + 1 + batch * d["h"]
        return total * itemsize + self.kv_tokens(model, live_kv_tokens) \
            * self.kv_bytes_per_token_layer(model, itemsize)

    def decode_step_flops(self, model: dict, batch: float,
                          live_kv_tokens: float) -> float:
        """``batch`` tokens through the layers' matrices once a pass and
        the head once; a head scores and weighs every live key of every
        cache layer."""
        d = _dims(model)
        per_token = d["loops"] * d["layers"] * self.layer_params(model) \
            + d["v"] * d["h"] + d["loops"] * d["h"]
        return 2.0 * per_token * batch + 4.0 * d["nq"] * d["hd"] \
            * self.kv_tokens(model, live_kv_tokens)

    def prefill_flops(self, model: dict, prompt_tokens: int) -> float:
        """``total_ut_steps`` causal forwards over the prompt through the
        layers' matrices, causal attention each, the gate, and the head for
        the last position only."""
        d = _dims(model)
        n = prompt_tokens
        passes = d["loops"] * d["layers"]
        return 2.0 * (passes * self.layer_params(model)
                      + d["loops"] * d["h"]) * n \
            + 4.0 * d["nq"] * d["hd"] * passes * n * (n + 1) / 2 \
            + 2.0 * d["v"] * d["h"]


layout = Ouro()
