"""Model layout ``kimi_linear`` (Kimi-Linear-48B-A3B: ``model_type``
``kimi_linear``, arXiv:2510.26692): a decoder whose layers are of two kinds,
three gated delta-rule ("KDA") layers to one latent-attention ("MLA") layer.
A KDA layer caches nothing a token: each head carries one float32 state that
every token decays channel by channel and corrects by the delta rule. The
MLA layers cache one compressed row a token and apply no positions (NoPE).
A leading dense SwiGLU layer, then routed experts behind a sigmoid router
with a balance bias, one shared expert, an untied head. It brings the
mapping onto the program's ``DecoderConfig``, the weight spec, the plain
reference and the counts.

The model's entry holds the published ``config.json`` keys and the cut
(``harness/layouts.py`` says what a layout gives):

* ``layers_kept``: which published layers (0-based) this chip's pipeline
  stage runs (``num_hidden_layers`` of them; the first
  ``first_k_dense_replace`` dense); a layer is MLA where its 1-based index is
  in ``linear_attn_config.full_attn_layers``, else KDA;
* ``num_experts`` experts HELD of ``num_experts_published`` (the router keeps
  its published width and its picks), from ``experts_held_first`` on;
* ``vocab_size`` rows of ``vocab_size_published`` (embedding and head).

The layer, as published (``config.json``, the paper's section 3, the
``KimiDeltaAttention`` of the flash-linear-attention library; *assumed*
where the config does not fix it):

    a  = h + Attn(RMS_in(h));  h' = a + MLP(RMS_post(a))          (eps 1e-5)
    KDA(x), 32 heads of d = 128 (linear_attn_config):
      q, k = L2(SiLU(conv4(x W_q|k))), v = SiLU(conv4(x W_v)): causal
        depthwise convolutions of 4 taps, no bias (*assumed*); L2 with eps
        1e-6 (*assumed*: the library's)
      g = -exp(A_log) softplus(x W_f_a W_f_b + dt_bias): per channel, rank
        128, no projection bias (*assumed*)
      beta = sigmoid(x W_b)
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
        S_0 = 0, float32 (*assumed*)
      o_t = S_t^T q_t / sqrt(d)
      out = (RMS(o) * sigmoid(x W_g_a W_g_b)) W_o: the norm a head over its
        values, gain shared by heads, eps = rms_norm_eps (*assumed*)
    MLA(x) (mla_use_nope: NO rotation anywhere): q = x W_UQ (q_lora_rank
      null); [c_kv | k_pe] = x W_DKV; c_kv <- RMS(c_kv); [k_nope | v] =
      c_kv W_UKV by head; score = (q_nope . k_nope + q_pe . k_pe) /
      sqrt(nope + rope), k_pe ONE a token shared by the heads; causal
      softmax; o = concat(ctx) W_O
    dense MLP(x) = (silu(x W1) * (x W3)) W2
    expert layer: s = sigmoid(x Wr) in float32 over ALL experts; the top
      `num_experts_per_token` of s + b (b the balance bias, choice only; one
      group); w = s[top] / sum s[top] * routed_scaling_factor;
      MLP(x) = Shared(x) + sum_i w_i Expert_i(x), over the experts HELD
    logits = RMS_f(h) Wout

Departures from the published description: none in the mathematics; the
share (experts held, vocabulary slice, layers kept) is the configuration's.
What the absent experts would add is left out here as in the program.

The reference is float32 at ``highest``, one sequence, no cache, no
batching, importing nothing of the program. Its KDA runs TOKEN BY TOKEN by
the recurrence as written above (the program's prefill runs a chunked form
of it, its decode an algebraically rearranged step); its MLA is NOT
absorbed: per-head keys and values from ``c_kv``. It casts weights layer by
layer and experts one at a time, and attends a block of queries at a time.
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
    """(kda | latent, dense | moe) of every kept layer."""
    kept = model["layers_kept"]
    if len(kept) != model["num_hidden_layers"]:
        raise ValueError("layers_kept does not name num_hidden_layers layers")
    if model["moe_layer_freq"] != 1:
        raise ValueError("every layer after the dense ones has experts")
    full = set(model["linear_attn_config"]["full_attn_layers"])
    return [("latent" if layer + 1 in full else "kda",
             "dense" if layer < model["first_k_dense_replace"] else "moe")
            for layer in kept]


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
    lin = model["linear_attn_config"]
    if not model["mla_use_nope"] or model["rope_scaling"] is not None:
        raise ValueError("the layout is written for NoPE latent attention")
    if model["moe_router_activation_func"] != "sigmoid" \
            or model["num_expert_group"] != 1:
        raise ValueError("the layout is written for one sigmoid router "
                         "group")
    return {
        "h": model["hidden_size"], "nq": model["num_attention_heads"],
        "rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "vd": model["v_head_dim"],
        "kh": lin["num_heads"], "kd": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "i": model["intermediate_size"], "w": model["moe_intermediate_size"],
        "experts": model["num_experts_published"],
        "held": model["num_experts"], "first": model["experts_held_first"],
        "k": model["num_experts_per_token"],
        "shared": model["num_shared_experts"], "v": model["vocab_size"],
        "eps": model["rms_norm_eps"],
        "scale": float(model["routed_scaling_factor"]),
        "norm": bool(model["moe_renormalize"]),
    }


# ---- the plain reference ---------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mm(x, w, low: bool):
    """``x @ w`` in float32, or under the control both sides rounded to fp8
    along the contracted axis and multiplied in bfloat16."""
    w = w.astype(jnp.float32)
    if low:
        x = fp8_round(x, -1).astype(jnp.bfloat16)
        w = fp8_round(w, -2).astype(jnp.bfloat16)
    return (x @ w).astype(jnp.float32)


def _swiglu(x, w1, w3, w2, low: bool):
    return _mm(jax.nn.silu(_mm(x, w1, low)) * _mm(x, w3, low), w2, low)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(x, lp, d: dict, low: bool):
    """A KDA layer over one sequence ``x`` (S, H), normed: the recurrence
    token by token from a zero state."""
    s = x.shape[0]
    nh, hd, taps = d["kh"], d["kd"], d["conv"]
    u = _mm(x, lp["kda_qkv_w"], low)                       # (S, 3 nh hd)
    w = lp["kda_conv_w"].astype(jnp.float32)               # (taps, 3 nh hd)
    past = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = sum(past[j:j + s] * w[j] for j in range(taps))
    y = jax.nn.silu(conv).reshape(s, 3, nh, hd)
    q, k, v = _l2(y[:, 0]) / math.sqrt(hd), _l2(y[:, 1]), y[:, 2]
    f = _mm(_mm(x, lp["kda_f_a_w"], low), lp["kda_f_b_w"], low
            ).reshape(s, nh, hd) \
        + lp["kda_dt_bias"].astype(jnp.float32).reshape(nh, hd)
    g = -jnp.exp(lp["kda_a_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(_mm(x, lp["kda_b_w"], low))       # (S, nh)

    def token(state, inp):
        qt, kt, vt, gt, bt = inp
        decayed = jnp.exp(gt)[:, :, None] * state          # Diag(e^g) S
        state = decayed - bt[:, None, None] * kt[:, :, None] * jnp.einsum(
            "hd,hdv->hv", kt, decayed)[:, None, :] \
            + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hdv,hd->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((nh, hd, hd), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(_mm(x, lp["kda_g_a_w"], low),
                              lp["kda_g_b_w"], low)).reshape(s, nh, hd)
    o = _rms(o, lp["kda_o_norm_scale"], d["eps"]) * gate
    return _mm(o.reshape(s, nh * hd), lp["attn_out_w"], low)


def _mla(x, lp, d: dict, low: bool, block: int = 512):
    """NoPE latent attention over one sequence ``x`` (S, H), normed, NOT
    absorbed; a block of queries at a time."""
    s = x.shape[0]
    nq, rank, nope, rope, vd = d["nq"], d["rank"], d["nope"], d["rope"], \
        d["vd"]
    q = _mm(x, lp["q_b_w"], low).reshape(s, nq, nope + rope)
    down = _mm(x, lp["kv_a_w"], low)
    c_kv = _rms(down[:, :rank], lp["kv_a_norm_scale"], d["eps"])
    k_pe = down[:, rank:]                                   # no rotation
    kv = _mm(c_kv, lp["kv_b_w"], low).reshape(s, nq, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    keys = jnp.arange(s)
    out = []
    for a in range(0, s, block):
        at = a + jnp.arange(min(block, s - a))
        qb = q[a:a + block]
        sc = (jnp.einsum("qnd,knd->nqk", qb[..., :nope], k_nope)
              + jnp.einsum("qnd,kd->nqk", qb[..., nope:], k_pe)) \
            / math.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where((keys[None, :] <= at[:, None])[None],
                                     sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nqk,knd->qnd", p, v))
    ctx = jnp.concatenate(out, axis=0).reshape(s, nq * vd)
    return _mm(ctx, lp["attn_out_w"], low)


def _experts(x, lp, d: dict, low: bool):
    """Shared(x) + sum_i w_i Expert_i(x) over the experts held. Also, for
    every token, how near a tie its choice was AS FAR AS THIS SHARE SEES IT
    (for the tests and for the check ``answer_moe``): the least distance of
    a HELD expert's biased score from the boundary it would have to cross to
    join or leave the token's top k (the k+1-th score for one inside, the
    k-th for one outside)."""
    if low:     # the control: the router is a weight matmul like the others
        s32 = jax.nn.sigmoid(_mm(x, lp["router_w"], True))
    else:       # float32 as published
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
    if d["norm"]:
        w = w / w.sum(axis=-1, keepdims=True)
    w = w * d["scale"]
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


@functools.partial(jax.jit, static_argnames=("dims", "kind", "low"))
def _layer(x, lp, dims, kind: tuple, low: bool):
    """One layer over one sequence ``x`` (S, H); ``lp`` that layer's leaves
    as the benchmark made them (bfloat16), cast here."""
    d = dict(dims)
    h = _rms(x, lp["ln1_scale"], d["eps"])
    a = x + (_kda(h, lp, d, low) if kind[0] == "kda" else _mla(h, lp, d, low))
    m = _rms(a, lp["ln2_scale"], d["eps"])
    if kind[1] == "dense":
        y, margin = _swiglu(m, lp["mlp_in_w"], lp["mlp_up_w"],
                            lp["mlp_out_w"], low), None
    else:
        y, margin = _experts(m, lp, d, low)
    return a + y, margin


@functools.partial(jax.jit, static_argnames=("eps", "n_out", "low"))
def _head(x, scale, head, first, eps: float, n_out: int, low: bool):
    h = _rms(jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0), scale,
             eps)
    if low:
        h = fp8_round(h, -1).astype(jnp.bfloat16)
        head = fp8_round(head.astype(jnp.float32), -1).astype(jnp.bfloat16)
    return (h @ head.astype(h.dtype).T).astype(jnp.float32)


def reference_forward(params: dict, model: dict, ids, first: int,
                      precision: str = "f32", margins: bool = False):
    """Logits (float32) of positions ``first .. len(ids) - 1`` of ONE
    sequence through the whole model; with ``margins`` also each of those
    positions' smallest router margin over the expert layers
    (:func:`_experts`)."""
    d = _dims(model)
    low = precision != "f32"
    ids = jnp.asarray(np.asarray(ids, np.int32))
    n = int(ids.shape[0])
    dims = tuple(sorted(d.items()))
    worst = None
    with jax.default_matmul_precision("default" if low else "highest"):
        x = params["wte"][ids].astype(jnp.float32)
        if low:
            x = fp8_round(x, -1)
        for r, (kind, count) in enumerate(_runs(model)):
            stack = params["layers"][f"run{r}"]
            for j in range(count):
                lp = jax.tree.map(lambda a: a[j], stack)
                x, margin = _layer(x, lp, dims, kind, low)
                if margin is not None:
                    m = np.asarray(margin)[first:]
                    worst = m if worst is None else np.minimum(worst, m)
        out = np.asarray(_head(x, params["ln_f_scale"], params["lm_head"],
                               first, d["eps"], n - first, low))
    return (out, worst) if margins else out


# ---- the layout -------------------------------------------------------------

class KimiLinear(DecoderLayout):
    def program_config(self, model: dict):
        from pathway_tpu.models.decoder import DecoderConfig
        from pathway_tpu.models.moe import MoEConfig

        if model["torch_dtype"] != "bfloat16":
            raise ValueError("the decoder cells state bfloat16")
        d = _dims(model)
        kinds = _kinds(model)
        return DecoderConfig(
            vocab_size=d["v"], hidden=d["h"], layers=len(kinds),
            heads=d["nq"], intermediate=d["i"],
            max_position=model["model_max_length"],
            layer_norm_eps=d["eps"], dtype=jnp.bfloat16,
            norm="rmsnorm", positions="none", mlp="swiglu", bias=False,
            tied_head=bool(model["tie_word_embeddings"]),
            layer_types=tuple(a for a, _m in kinds),
            kv_rank=d["rank"], nope_size=d["nope"], rope_size=d["rope"],
            v_size=d["vd"], kda_heads=d["kh"], kda_head=d["kd"],
            kda_conv=d["conv"],
            dense_layers=model["first_k_dense_replace"],
            moe=MoEConfig(
                experts=d["experts"], per_token=d["k"], width=d["w"],
                shared=d["shared"], held=(d["first"], d["held"]),
                route_norm=d["norm"], route_scale=d["scale"]),
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        d = _dims(model)
        h, nq, nh, hd = d["h"], d["nq"], d["kh"], d["kd"]
        w, g, b = "w", "gain", "b"

        def run(kind, n):
            out = {"ln1_scale": ((n, h), g, 0.02),
                   "ln2_scale": ((n, h), g, 0.02)}
            if kind[0] == "kda":
                out.update({
                    "kda_qkv_w": ((n, h, 3 * nh * hd), w, 0.02),
                    # the library's init: uniform over +-1/sqrt(taps)
                    "kda_conv_w": ((n, d["conv"], 3 * nh * hd), w, 0.3),
                    "kda_f_a_w": ((n, h, hd), w, 0.02),
                    "kda_f_b_w": ((n, hd, nh * hd), w, 0.02),
                    "kda_b_w": ((n, h, nh), w, 0.02),
                    # A = exp(A_log) about e (published init: 1 to 16)
                    "kda_a_log": ((n, nh), g, 0.7),
                    # the decay's bias spread so that channels keep a token
                    # for one step or for hundreds (softplus of -6 to +6)
                    "kda_dt_bias": ((n, nh * hd), b, 3.0),
                    "kda_g_a_w": ((n, h, hd), w, 0.02),
                    "kda_g_b_w": ((n, hd, nh * hd), w, 0.02),
                    "kda_o_norm_scale": ((n, hd), g, 0.02),
                    "attn_out_w": ((n, nh * hd, h), w, 0.02),
                })
            else:
                out.update({
                    "q_b_w": ((n, h, nq * (d["nope"] + d["rope"])), w, 0.02),
                    "kv_a_w": ((n, h, d["rank"] + d["rope"]), w, 0.02),
                    "kv_a_norm_scale": ((n, d["rank"]), g, 0.02),
                    "kv_b_w": ((n, d["rank"], nq * (d["nope"] + d["vd"])),
                               w, 0.02),
                    "attn_out_w": ((n, nq * d["vd"], h), w, 0.02),
                })
            if kind[1] == "dense":
                out.update({
                    "mlp_in_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_up_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_out_w": ((n, d["i"], h), w, 0.02)})
            else:
                e, ew, ws = d["held"], d["w"], d["shared"] * d["w"]
                out.update({
                    "router_w": ((n, h, d["experts"]), w, 0.02),
                    "router_bias": ((n, d["experts"]), b, 0.02),
                    "moe_in_w": ((n, e, h, ew), w, 0.02),
                    "moe_up_w": ((n, e, h, ew), w, 0.02),
                    "moe_out_w": ((n, e, ew, h), w, 0.02),
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
        layer by layer as it goes."""
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

    def attention_params(self, model: dict, kind: str) -> int:
        """Matrix parameters of one layer's attention: a KDA layer's q, k,
        v, o and its gates' projections; an MLA layer's four."""
        d = _dims(model)
        h, nq, nh, hd = d["h"], d["nq"], d["kh"], d["kd"]
        if kind == "kda":
            return 4 * h * nh * hd + 2 * (h * hd + hd * nh * hd) + h * nh
        return h * nq * (d["nope"] + d["rope"]) \
            + h * (d["rank"] + d["rope"]) \
            + d["rank"] * nq * (d["nope"] + d["vd"]) + nq * d["vd"] * h

    def expert_params(self, model: dict) -> int:
        d = _dims(model)
        return 3 * d["h"] * d["w"]

    def layer_params(self, model: dict, kind: tuple) -> int:
        """Matrix parameters one layer HOLDS here."""
        d = _dims(model)
        attn = self.attention_params(model, kind[0])
        if kind[1] == "dense":
            return attn + 3 * d["h"] * d["i"]
        return attn + d["h"] * d["experts"] \
            + (d["held"] + d["shared"]) * self.expert_params(model)

    def layer_params_per_token(self, model: dict, kind: tuple) -> float:
        """Matrix parameters one token MULTIPLIES in a layer: of the routed
        experts only its picks that fall on the experts held (evenly
        routed: per_token * held / published)."""
        d = _dims(model)
        if kind[1] == "dense":
            return float(self.layer_params(model, kind))
        picks = d["k"] * d["held"] / d["experts"]
        return self.attention_params(model, kind[0]) \
            + d["h"] * d["experts"] \
            + (picks + d["shared"]) * self.expert_params(model)

    def matmul_param_count(self, model: dict) -> int:
        d = _dims(model)
        return sum(self.layer_params(model, k) for k in _kinds(model)) \
            + 2 * d["v"] * d["h"]          # embedding and untied head

    def param_bytes(self, model: dict, itemsize: int = 2) -> float:
        d = _dims(model)
        small = 0
        for a, _m in _kinds(model):
            small += 2 * d["h"]
            small += (d["conv"] * 3 * d["kh"] * d["kd"] + d["kh"]
                      + d["kh"] * d["kd"] + d["kd"]) if a == "kda" \
                else d["rank"]
        return float((self.matmul_param_count(model) + small + d["h"])
                     * itemsize)

    def kda_layers(self, model: dict) -> int:
        return sum(a == "kda" for a, _m in _kinds(model))

    def state_bytes_per_slot(self, model: dict, itemsize: int = 2) -> int:
        """What a sequence keeps whatever its length: a float32 (d, d) state
        a head of every KDA layer and the last ``taps - 1`` inputs of its q,
        k and v convolutions."""
        d = _dims(model)
        nh, hd = d["kh"], d["kd"]
        return self.kda_layers(model) * (
            nh * hd * hd * 4 + (d["conv"] - 1) * 3 * nh * hd * itemsize)

    def kv_bytes_per_token_layer(self, model: dict, itemsize: int = 2) -> int:
        """An MLA layer's row: the normed latent and the shared key part."""
        d = _dims(model)
        return (d["rank"] + d["rope"]) * itemsize

    def kv_tokens(self, model: dict, context: float) -> float:
        """Cached positions one sequence of ``context`` tokens keeps live
        over all layers: the MLA layers keep all of them, a KDA layer
        none."""
        return float((len(_kinds(model)) - self.kda_layers(model)) * context)

    def kv_bytes_per_token(self, model: dict, itemsize: int = 2) -> float:
        """The MLA rows of one cached position over all layers."""
        return float((len(_kinds(model)) - self.kda_layers(model))
                     * self.kv_bytes_per_token_layer(model, itemsize))

    def recurrence_flops(self, model: dict) -> float:
        """One token through one KDA layer's recurrence: decaying the state,
        ``k^T S'``, the rank-one correction and ``S^T q``, each over every
        head's d x d state."""
        d = _dims(model)
        return 7.0 * d["kh"] * d["kd"] * d["kd"]

    def recurrence_bytes(self, model: dict) -> float:
        """One token of one lane through every KDA layer's recurrence, at
        the least: its float32 state read once and written once, its
        float32 q, k, g and v read and beta beside them."""
        d = _dims(model)
        nh, hd = d["kh"], d["kd"]
        return float(self.kda_layers(model)
                     * (2 * nh * hd * hd + 4 * nh * hd + nh) * 4)

    def decode_step_bytes(self, model: dict, live_kv_tokens: float,
                          itemsize: int = 2, experts_touched=None,
                          batch: float = 1.0) -> float:
        """One decode step reads every parameter it multiplies, the live
        latent rows once, and every lane's KDA state once and writes it once.
        ``experts_touched``: distinct held experts read per expert layer
        (default: all held); ``live_kv_tokens`` the context of the ``batch``
        sequences summed."""
        d = _dims(model)
        touched = d["held"] if experts_touched is None else experts_touched
        total = 0.0
        for kind in _kinds(model):
            total += self.attention_params(model, kind[0])
            if kind[1] == "dense":
                total += 3 * d["h"] * d["i"]
            else:
                total += d["h"] * d["experts"] \
                    + (touched + d["shared"]) * self.expert_params(model)
        total += d["v"] * d["h"] + batch * d["h"]   # the head, token rows
        kv = self.kv_tokens(model, live_kv_tokens) \
            * self.kv_bytes_per_token_layer(model, itemsize)
        return total * itemsize + kv \
            + 2.0 * batch * self.state_bytes_per_slot(model, itemsize)

    def decode_step_flops(self, model: dict, batch: float,
                          live_kv_tokens: float) -> float:
        """``batch`` tokens through the matrices and the KDA recurrence;
        MLA ABSORBED, the cheaper form for a single query."""
        d = _dims(model)
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model)) + d["v"] * d["h"]
        keys = self.kv_tokens(model, live_kv_tokens)
        return 2.0 * per_token * batch \
            + batch * self.kda_layers(model) * self.recurrence_flops(model) \
            + 2.0 * d["nq"] * (2 * d["rank"] + d["rope"]) * keys

    def prefill_flops(self, model: dict, prompt_tokens: int) -> float:
        """One causal forward over the prompt: the matrices each token
        multiplies, the KDA recurrence token by token, causal MLA EXPANDED,
        the head for the last position only."""
        d = _dims(model)
        n = prompt_tokens
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model))
        mla = len(_kinds(model)) - self.kda_layers(model)
        pairs = mla * n * (n + 1) / 2
        return 2.0 * per_token * n \
            + n * self.kda_layers(model) * self.recurrence_flops(model) \
            + 2.0 * d["nq"] * (d["nope"] + d["rope"] + d["vd"]) * pairs \
            + 2.0 * d["v"] * d["h"]


layout = KimiLinear()
