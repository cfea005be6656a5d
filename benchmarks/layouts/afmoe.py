"""Model layout ``afmoe`` (Arcee Trinity: ``model_type`` ``afmoe``): a
decoder of window and full attention layers, gated attention, sandwich
RMSNorm, leading dense SwiGLU layers and then routed experts with a shared
expert, an untied head. It brings the mapping onto the program's
``DecoderConfig``, the weight spec, the plain reference and the counts.

The model's entry holds the published ``config.json`` keys and the cut
(``harness/layouts.py`` says what a layout gives):

* ``layers_kept``: which published layers this chip's pipeline stage runs
  (``num_hidden_layers`` of them; ``num_dense_layers`` of those dense);
  ``layer_types`` stays whole and is read at the kept indices;
* ``num_experts`` experts HELD of ``num_experts_published`` (the router
  keeps its published width), from ``experts_held_first`` on;
* ``vocab_size`` rows of ``vocab_size_published`` (embedding and head).

The layer, as published (``config.json``, the family's released ``afmoe``
code; *assumed* where the config does not fix it):

    h0 = E[ids] * sqrt(hidden)                                   (mup)
    a  = h + RMS_post_attn(Attn(RMS_in(h)))
    h' = a + RMS_post_mlp(MLP(RMS_pre_mlp(a)))       (sandwich, eps 1e-5)
    Attn(x): q = x Wq, k = x Wk, v = x Wv; per-head RMS(q), RMS(k); rotary
      positions on window layers, none on full layers (*assumed*); causal
      softmax over the last `sliding_window` keys (window) or all (full),
      scale head_dim^-1/2, a key-value head shared by heads/kv_heads query
      heads; o = (ctx * sigmoid(x Wg)) Wo
    dense MLP(x) = (silu(x W1) * (x W3)) W2
    expert layer: s = sigmoid(x Wr) in float32 over ALL experts; the top
      `num_experts_per_tok` of s + b (b the balance bias, choice only);
      w = s[top] / sum s[top] * route_scale;
      MLP(x) = Shared(x) + sum_i w_i Expert_i(x), over the experts HELD
    logits = RMS_f(h) Wout

Departures from the published description: none in the mathematics; the
share (experts held, vocabulary slice, layers kept) is the configuration's.
What the absent experts would add is left out here as in the program.

The reference is float32 at ``highest``, one sequence, no cache, no
batching, importing nothing of the program. It casts weights layer by
layer and experts one at a time, attends in blocks of queries, and so
never holds the 8.6 GB of bfloat16 weights a second time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.layouts import DecoderLayout
from harness.reference import fp8_round


# ---- the entry, read once --------------------------------------------------

def _kinds(model: dict) -> list:
    """(window | full, dense | moe) of every kept layer."""
    kept = model["layers_kept"]
    if len(kept) != model["num_hidden_layers"]:
        raise ValueError("layers_kept does not name num_hidden_layers layers")
    out = []
    for i, layer in enumerate(kept):
        attn = {"sliding_attention": "window", "full_attention": "full"}[
            model["layer_types"][layer]]
        out.append((attn, "dense" if i < model["num_dense_layers"]
                    else "moe"))
    return out


def _runs(model: dict) -> list:
    """Stacks of consecutive like layers: [(kind, count)]."""
    out: list = []
    for kind in _kinds(model):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def _dims(model: dict) -> dict:
    return {
        "h": model["hidden_size"], "hd": model["head_dim"],
        "nq": model["num_attention_heads"],
        "nkv": model["num_key_value_heads"],
        "i": model["intermediate_size"], "w": model["moe_intermediate_size"],
        "experts": model["num_experts_published"],
        "held": model["num_experts"], "first": model["experts_held_first"],
        "k": model["num_experts_per_tok"],
        "shared": model["num_shared_experts"], "v": model["vocab_size"],
        "window": model["sliding_window"], "eps": model["rms_norm_eps"],
    }


# ---- the plain reference ---------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _w(leaf, low: bool):
    """A weight matrix as the reference reads it: float32, or under the
    control rounded to fp8 along its contracted axis."""
    leaf = leaf.astype(jnp.float32)
    return fp8_round(leaf, -2).astype(jnp.bfloat16) if low else leaf


def _mm(x, w, low: bool):
    if low:
        x = fp8_round(x, -1).astype(jnp.bfloat16)
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


def _swiglu(x, w1, w3, w2, low: bool):
    return _mm(jax.nn.silu(_mm(x, w1, low)) * _mm(x, w3, low), w2, low)


def _attention(x, lp, d: dict, window: bool, theta: float, low: bool,
               block: int = 512):
    s = x.shape[0]
    nq, nkv, hd = d["nq"], d["nkv"], d["hd"]
    qkv = _mm(x, lp["qkv_w"], low)
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    q = _rms(q.reshape(s, nq, hd), lp["q_norm_scale"], d["eps"])
    k = _rms(k.reshape(s, nkv, hd), lp["k_norm_scale"], d["eps"])
    v = v.reshape(s, nkv, hd)
    if window:      # rotary on window layers only (assumed)
        q, k = _rotary(q, theta), _rotary(k, theta)
    q = q.reshape(s, nkv, nq // nkv, hd)
    keys = jnp.arange(s)
    out = []
    for a in range(0, s, block):        # blocks of queries: scores fit
        qs = a + jnp.arange(min(block, s - a))
        ok = keys[None, :] <= qs[:, None]
        if window:
            ok = ok & (qs[:, None] - keys[None, :] < d["window"])
        sc = jnp.einsum("qngd,knd->ngqk", q[a:a + block], k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("ngqk,knd->qngd", p, v))
    ctx = jnp.concatenate(out, axis=0).reshape(s, nq * hd)
    return _mm(ctx * jax.nn.sigmoid(_mm(x, lp["gate_w"], low)),
               lp["attn_out_w"], low)


def _experts(x, lp, d: dict, scale: float, norm: bool, low: bool):
    """Shared(x) + sum_i w_i Expert_i(x) over the experts held. Also, for
    every token, how near a tie its choice was AS FAR AS THIS SHARE SEES IT
    (for the tests and for the check ``answer_moe``): the least distance
    of a HELD expert's score from the boundary it would have to cross to
    join or leave the token's top k (the k+1-th score for one inside, the
    k-th for one outside). A swap between two experts held elsewhere
    changes nothing here; with every expert held this is the k-th-to-
    k+1-th margin."""
    if low:     # the control: the router is a weight matmul like the others
        s32 = jax.nn.sigmoid(_mm(x, lp["router_w"], True))
    else:
        s32 = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), lp["router_w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
    biased = s32 + lp["router_bias"].astype(jnp.float32)
    top, idx = jax.lax.top_k(biased, d["k"] + 1)
    last_in, first_out = top[:, d["k"] - 1, None], top[:, d["k"], None]
    here = jax.lax.dynamic_slice_in_dim(biased, d["first"], d["held"], 1)
    margin = jnp.where(here >= last_in, here - first_out,
                       last_in - here).min(axis=-1)
    idx = idx[:, :d["k"]]
    w = jnp.take_along_axis(s32, idx, axis=-1)
    if norm:
        w = w / w.sum(axis=-1, keepdims=True)
    w = w * scale
    # weight of every HELD expert for every token (0 where not picked)
    local = idx - d["first"]
    per = jnp.zeros((x.shape[0], d["held"]), jnp.float32)
    per = per.at[jnp.arange(x.shape[0])[:, None],
                 jnp.clip(local, 0, d["held"] - 1)].add(
        jnp.where((local >= 0) & (local < d["held"]), w, 0.0))

    def one(acc, inp):              # one expert at a time, cast on the way
        w1, w3, w2, we = inp
        return acc + we[:, None] * _swiglu(x, w1, w3, w2, low), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x, jnp.float32),
                        (lp["moe_in_w"], lp["moe_up_w"], lp["moe_out_w"],
                         per.T))
    if d["shared"]:
        y = y + _swiglu(x, lp["shared_in_w"], lp["shared_up_w"],
                        lp["shared_out_w"], low)
    return y, margin


@functools.partial(jax.jit, static_argnames=("dims", "kind", "theta",
                                             "scale", "norm", "low"))
def _layer(x, lp, dims, kind, theta: float, scale: float, norm: bool,
           low: bool):
    """One layer over one sequence ``x`` (S, H); ``lp`` that layer's leaves
    as the benchmark made them (bfloat16), cast here."""
    d = dict(dims)
    eps = d["eps"]
    attn = _attention(_rms(x, lp["ln1_scale"], eps), lp, d,
                      kind[0] == "window", theta, low)
    a = x + _rms(attn, lp["ln1p_scale"], eps)
    m = _rms(a, lp["ln2_scale"], eps)
    if kind[1] == "dense":
        y, margin = _swiglu(m, lp["mlp_in_w"], lp["mlp_up_w"],
                            lp["mlp_out_w"], low), None
    else:
        y, margin = _experts(m, lp, d, scale, norm, low)
    return a + _rms(y, lp["ln2p_scale"], eps), margin


@functools.partial(jax.jit, static_argnames=("eps", "n_out", "low"))
def _head(x, scale, head, first, eps: float, n_out: int, low: bool):
    h = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    h = _rms(h, scale, eps)
    if low:
        h = fp8_round(h, -1).astype(jnp.bfloat16)
        head = fp8_round(head.astype(jnp.float32), -1).astype(jnp.bfloat16)
    return (h @ head.astype(h.dtype).T).astype(jnp.float32)


def reference_forward(params: dict, model: dict, ids, first: int,
                      precision: str = "f32", margins: bool = False):
    """Logits (float32) of positions ``first .. len(ids) - 1`` of ONE
    sequence through the whole model; with ``margins`` also each of those
    positions' smallest router margin over the expert layers
    (:func:`_experts`: how near a tie its choice of the experts held
    was)."""
    d = _dims(model)
    low = precision != "f32"
    ids = jnp.asarray(np.asarray(ids, np.int32))
    n = int(ids.shape[0])
    dims = tuple(sorted(d.items()))
    worst = None
    with jax.default_matmul_precision("default" if low else "highest"):
        table = params["wte"]
        rows = table[ids].astype(jnp.float32)
        if low:
            rows = fp8_round(rows, -1)
        x = rows * math.sqrt(d["h"])
        for r, (kind, count) in enumerate(_runs(model)):
            stack = params["layers"][f"run{r}"]
            for j in range(count):
                lp = jax.tree.map(lambda a: a[j], stack)
                x, margin = _layer(
                    x, lp, dims, kind, float(model["rope_theta"]),
                    float(model["route_scale"]), bool(model["route_norm"]),
                    low)
                if margin is not None:
                    m = np.asarray(margin)[first:]
                    worst = m if worst is None else np.minimum(worst, m)
        out = np.asarray(_head(x, params["ln_f_scale"], params["lm_head"],
                               first, d["eps"], n - first, low))
    return (out, worst) if margins else out


# ---- the layout -------------------------------------------------------------

class Afmoe(DecoderLayout):
    def program_config(self, model: dict):
        from pathway_tpu.models.decoder import DecoderConfig
        from pathway_tpu.models.moe import MoEConfig

        if model["torch_dtype"] != "bfloat16":
            raise ValueError("the decoder cells state bfloat16")
        d = _dims(model)
        kinds = _kinds(model)
        return DecoderConfig(
            vocab_size=d["v"], hidden=d["h"], layers=len(kinds),
            heads=d["nq"], kv_heads=d["nkv"], head_size=d["hd"],
            intermediate=d["i"],
            max_position=model["max_position_embeddings"],
            layer_norm_eps=d["eps"], dtype=jnp.bfloat16,
            norm="rmsnorm", sandwich_norm=True, qk_norm=True,
            attn_gate=True,
            positions=tuple("rotary" if a == "window" else "none"
                            for a, _m in kinds),
            rope_theta=float(model["rope_theta"]), mlp="swiglu", bias=False,
            tied_head=bool(model["tie_word_embeddings"]),
            embed_scale=math.sqrt(d["h"]) if model["mup_enabled"] else 1.0,
            layer_types=tuple(a for a, _m in kinds),
            sliding_window=d["window"],
            dense_layers=model["num_dense_layers"],
            moe=MoEConfig(
                experts=d["experts"], per_token=d["k"], width=d["w"],
                shared=d["shared"], held=(d["first"], d["held"]),
                route_norm=bool(model["route_norm"]),
                route_scale=float(model["route_scale"])),
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        d = _dims(model)
        h, hd, nq, nkv = d["h"], d["hd"], d["nq"], d["nkv"]
        w, g = "w", "gain"

        def run(kind, n):
            out = {
                "ln1_scale": ((n, h), g, 0.02),
                "qkv_w": ((n, h, (nq + 2 * nkv) * hd), w, 0.02),
                "q_norm_scale": ((n, hd), g, 0.02),
                "k_norm_scale": ((n, hd), g, 0.02),
                "gate_w": ((n, h, nq * hd), w, 0.02),
                "attn_out_w": ((n, nq * hd, h), w, 0.02),
                "ln1p_scale": ((n, h), g, 0.02),
                "ln2_scale": ((n, h), g, 0.02),
                "ln2p_scale": ((n, h), g, 0.02),
            }
            if kind[1] == "dense":
                out.update({
                    "mlp_in_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_up_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_out_w": ((n, d["i"], h), w, 0.02)})
            else:
                e, ew, ws = d["held"], d["w"], d["shared"] * d["w"]
                out.update({
                    "router_w": ((n, h, d["experts"]), w, 0.02),
                    "router_bias": ((n, d["experts"]), "b", 0.02),
                    "moe_in_w": ((n, e, h, ew), w, 0.02),
                    "moe_up_w": ((n, e, h, ew), w, 0.02),
                    "moe_out_w": ((n, e, ew, h), w, 0.02)})
                if ws:
                    out.update({
                        "shared_in_w": ((n, h, ws), w, 0.02),
                        "shared_up_w": ((n, h, ws), w, 0.02),
                        "shared_out_w": ((n, ws, h), w, 0.02)})
            return out

        return {
            "wte": ((d["v"], h), w, 0.02),
            "layers": {f"run{r}": run(kind, n)
                       for r, (kind, n) in enumerate(_runs(model))},
            "ln_f_scale": ((h,), g, 0.02),
            "lm_head": ((d["v"], h), w, 0.02),
        }

    # -- the plain reference ----------------------------------------------

    def prepare(self, params: dict, precision: str = "f32") -> dict:
        """The benchmark's own arrays as they are: the reference casts them
        layer by layer as it goes (a float32 copy of all of them would not
        fit beside them)."""
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        return params

    def logits(self, prepared: dict, model: dict, ids: list, first: int,
               precision: str = "f32") -> np.ndarray:
        return reference_forward(prepared, model, ids, first, precision)

    def logits_margins(self, prepared: dict, model: dict, ids: list,
                       first: int):
        """:meth:`logits` in float32 and, beside each row, how near a tie
        that position's choice of experts was (check ``answer_moe``)."""
        return reference_forward(prepared, model, ids, first, "f32",
                                 margins=True)

    # -- work counted from the shapes ---------------------------------------

    def attention_params(self, model: dict) -> int:
        d = _dims(model)
        return d["h"] * (d["nq"] + 2 * d["nkv"]) * d["hd"] \
            + 2 * d["h"] * d["nq"] * d["hd"]       # q k v, gate, o

    def expert_params(self, model: dict) -> int:
        d = _dims(model)
        return 3 * d["h"] * d["w"]

    def layer_params(self, model: dict, kind: tuple) -> int:
        """Matrix parameters one layer HOLDS here."""
        d = _dims(model)
        if kind[1] == "dense":
            return self.attention_params(model) + 3 * d["h"] * d["i"]
        return self.attention_params(model) + d["h"] * d["experts"] \
            + (d["held"] + d["shared"]) * self.expert_params(model)

    def layer_params_per_token(self, model: dict, kind: tuple) -> float:
        """Matrix parameters one token MULTIPLIES in a layer: of the routed
        experts only its picks that fall on the experts held (evenly
        routed: per_token * held / published)."""
        d = _dims(model)
        if kind[1] == "dense":
            return float(self.layer_params(model, kind))
        picks = d["k"] * d["held"] / d["experts"]
        return self.attention_params(model) + d["h"] * d["experts"] \
            + (picks + d["shared"]) * self.expert_params(model)

    def matmul_param_count(self, model: dict) -> int:
        d = _dims(model)
        return sum(self.layer_params(model, k) for k in _kinds(model)) \
            + 2 * d["v"] * d["h"]          # embedding and untied head

    def param_bytes(self, model: dict, itemsize: int = 2) -> float:
        d = _dims(model)
        norms = sum(4 * d["h"] + 2 * d["hd"]
                    + (d["experts"] if k[1] == "moe" else 0)
                    for k in _kinds(model)) + d["h"]
        return float((self.matmul_param_count(model) + norms) * itemsize)

    def kv_bytes_per_token_layer(self, model: dict, itemsize: int = 2) -> int:
        d = _dims(model)
        return 2 * d["nkv"] * d["hd"] * itemsize

    def kv_tokens(self, model: dict, context: float) -> float:
        """Cached positions one sequence of ``context`` tokens keeps live
        over all layers: a window layer never more than its window."""
        d = _dims(model)
        return float(sum(min(context, d["window"]) if a == "window"
                         else context for a, _m in _kinds(model)))

    def kv_bytes_per_token(self, model: dict, itemsize: int = 2) -> float:
        """K and V of one cached position over all layers."""
        return float(len(_kinds(model))
                     * self.kv_bytes_per_token_layer(model, itemsize))

    def decode_step_bytes(self, model: dict, live_kv_tokens: float,
                          itemsize: int = 2, experts_touched=None,
                          batch: float = 1.0) -> float:
        """One decode step reads every parameter it multiplies and the
        live KV once. ``experts_touched``: distinct held experts read per
        expert layer (default: all held); ``live_kv_tokens`` the context
        of each of ``batch`` sequences summed."""
        d = _dims(model)
        touched = d["held"] if experts_touched is None else experts_touched
        total = 0.0
        for kind in _kinds(model):
            if kind[1] == "dense":
                total += self.layer_params(model, kind)
            else:
                total += self.attention_params(model) \
                    + d["h"] * d["experts"] \
                    + (touched + d["shared"]) * self.expert_params(model)
        total += d["v"] * d["h"] + batch * d["h"]   # the head, token rows
        kv = batch * self.kv_tokens(model, live_kv_tokens / max(batch, 1.0)) \
            * self.kv_bytes_per_token_layer(model, itemsize)
        return total * itemsize + kv

    def decode_step_flops(self, model: dict, batch: float,
                          live_kv_tokens: float) -> float:
        d = _dims(model)
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model)) + d["v"] * d["h"]
        keys = batch * self.kv_tokens(model, live_kv_tokens / max(batch, 1.0))
        return 2.0 * per_token * batch + 4.0 * d["nq"] * d["hd"] * keys

    def prefill_flops(self, model: dict, prompt_tokens: int) -> float:
        """One causal forward over the prompt: the matrices each token
        multiplies, causal attention (a window layer's queries read at
        most the window), the head for the last position only."""
        d = _dims(model)
        n, win = prompt_tokens, d["window"]
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model))
        pairs = 0.0
        for a, _m in _kinds(model):
            if a == "window" and n > win:
                pairs += win * (win + 1) / 2 + (n - win) * win
            else:
                pairs += n * (n + 1) / 2
        return 2.0 * per_token * n + 4.0 * d["nq"] * d["hd"] * pairs \
            + 2.0 * d["v"] * d["h"]


layout = Afmoe()
