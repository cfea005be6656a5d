"""Model layout ``deepseek_v2`` (DeepSeek-V2: ``model_type`` ``deepseek_v2``,
arXiv:2405.04434): a decoder whose attention caches ONE compressed row a
token a layer (multi-head latent attention), with a leading dense SwiGLU
layer and then routed experts behind a softmax router that is limited to a
few device groups a token, two shared experts, YaRN rotary positions on a
part of each head, an untied head. It brings the mapping onto the program's
``DecoderConfig``, the weight spec, the plain reference and the counts.

The model's entry holds the published ``config.json`` keys and the cut
(``harness/layouts.py`` says what a layout gives):

* ``layers_kept``: which published layers this chip's pipeline stage runs
  (``num_hidden_layers`` of them; the first ``first_k_dense_replace`` dense);
* ``n_routed_experts`` experts HELD of ``n_routed_experts_published`` (the
  router keeps its published width, its groups and its picks), from
  ``experts_held_first`` on: one device group of ``n_group``;
* ``vocab_size`` rows of ``vocab_size_published`` (embedding and head).

The layer, as published (``config.json``, the paper's sections 2.1-2.2, the
released ``modeling_deepseek.py``; *assumed* where the config does not fix
it):

    h0 = E[ids]                                              (no scale)
    a  = h + Attn(RMS_in(h));  h' = a + MLP(RMS_post(a))     (eps 1e-6)
    Attn(x): c_q = RMS(x W_DQ); [q_nope | q_pe] = c_q W_UQ, by head;
      [c_kv | k_pe] = x W_DKV; c_kv <- RMS(c_kv); k_pe <- rope(k_pe): ONE
      rotary key a token, shared by all heads; q_pe <- rope(q_pe);
      [k_nope | v] = c_kv W_UKV, by head;
      score = (q_nope . k_nope + q_pe . k_pe) (nope + rope)^-1/2 m^2,
      causal softmax over all keys, ctx = softmax . v, o = concat(ctx) W_O
    rope: YaRN. inv_freq blended between theta^(-2i/d) and the same over
      `factor` by a linear ramp between the dimensions at which
      `original_max_position_embeddings` positions make `beta_fast` and
      `beta_slow` rotations; m = 0.1 mscale_all_dim ln(factor) + 1; cos and
      sin carry m(mscale) / m(mscale_all_dim). Pairs half-split (*assumed*:
      the released code permutes interleaved pairs to this before rotating,
      a relabelling of weight columns)
    dense MLP(x) = (silu(x W1) * (x W3)) W2
    expert layer: s = softmax(x Wr) in float32 over ALL experts; a group's
      score is the largest s among its experts; the best `topk_group` of
      the `n_group` groups stay, the others' s become 0; the top
      `num_experts_per_tok` of what is left; w = s[top] *
      routed_scaling_factor (not renormalised, no bias);
      MLP(x) = Shared(x) + sum_i w_i Expert_i(x), over the experts HELD
    logits = RMS_f(h) Wout

Departures from the published description: none in the mathematics; the
share (experts held, vocabulary slice, layers kept) is the configuration's.
What the absent experts would add is left out here as in the program.

The reference is float32 at ``highest``, one sequence, no cache, no
batching, importing nothing of the program, and NOT absorbed: per-head keys
and values from ``c_kv``, exactly the equations above. It casts weights
layer by layer and experts one at a time, and attends a group of heads and
a block of queries at a time, so that it fits beside the 7.6 GB of
bfloat16 weights.
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
    """dense | moe of every kept layer."""
    kept = model["layers_kept"]
    if len(kept) != model["num_hidden_layers"]:
        raise ValueError("layers_kept does not name num_hidden_layers layers")
    if model["moe_layer_freq"] != 1:
        raise ValueError("every layer after the dense ones has experts")
    return ["dense" if layer < model["first_k_dense_replace"] else "moe"
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
    rs = model["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("the layout is written for YaRN rotary scaling")
    if model["topk_method"] != "group_limited_greedy" \
            or model["scoring_func"] != "softmax":
        raise ValueError("the layout is written for the softmax router "
                         "limited to device groups")
    return {
        "h": model["hidden_size"], "nq": model["num_attention_heads"],
        "q_rank": model["q_lora_rank"], "rank": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "vd": model["v_head_dim"],
        "i": model["intermediate_size"], "w": model["moe_intermediate_size"],
        "experts": model["n_routed_experts_published"],
        "held": model["n_routed_experts"],
        "first": model["experts_held_first"],
        "k": model["num_experts_per_tok"],
        "groups": model["n_group"], "topk_group": model["topk_group"],
        "shared": model["n_shared_experts"], "v": model["vocab_size"],
        "eps": model["rms_norm_eps"], "theta": float(model["rope_theta"]),
        "factor": float(rs["factor"]),
        "original": rs["original_max_position_embeddings"],
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]),
        "mscale": float(rs["mscale"]),
        "mscale_all_dim": float(rs["mscale_all_dim"]),
        "scale": float(model["routed_scaling_factor"]),
        "norm": bool(model["norm_topk_prob"]),
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


def _m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(d: dict) -> np.ndarray:
    """``rope / 2`` rotary frequencies: ``theta``'s own at the dimensions
    that turn more than ``beta_fast`` times over ``original`` positions,
    the same over ``factor`` at those that turn fewer than ``beta_slow``
    times, a linear ramp between the two dimensions."""
    dim, base = d["rope"], d["theta"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dimension_of(rotations: float) -> float:
        return dim * math.log(d["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dimension_of(d["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(d["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    stretched = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (plain / d["factor"] * stretched + plain * (1 - stretched)
            ).astype(np.float32)


def _rotary(t, d: dict):
    """``t`` (S, n, rope) at positions 0..S-1, halves rotated against each
    other, YaRN's frequencies and amplitude."""
    s, _n, rope = t.shape
    half = rope // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(yarn_frequencies(d))
    amp = _m(d["factor"], d["mscale"]) / _m(d["factor"], d["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(x, w1, w3, w2, low: str):
    return _mm(jax.nn.silu(_mm(x, w1, low)) * _mm(x, w3, low), w2, low)


def _attention(x, lp, d: dict, low: str, block: int = 256,
               head_group: int = 16):
    """Latent attention over one sequence ``x`` (S, H), NOT absorbed: every
    head's keys and values come out of ``c_kv`` through ``W_UKV``. A group
    of heads (its queries, keys and values) and a block of queries at a
    time (scores of 16 heads x 256 x 16k in float32 are 0.27 GB; of all at
    once 35 GB; all heads' queries 1.6 GB, keys and values 2.1 GB)."""
    s = x.shape[0]
    nq, rank, nope, rope, vd = d["nq"], d["rank"], d["nope"], d["rope"], \
        d["vd"]
    c_q = _rms(_mm(x, lp["q_a_w"], low), lp["q_a_norm_scale"], d["eps"])
    down = _mm(x, lp["kv_a_w"], low)
    c_kv = _rms(down[:, :rank], lp["kv_a_norm_scale"], d["eps"])
    k_pe = _rotary(down[:, None, rank:], d)[:, 0]           # (S, rope)
    w_uq = lp["q_b_w"].reshape(-1, nq, nope + rope)
    w_ukv = lp["kv_b_w"].reshape(rank, nq, nope + vd)
    m = _m(d["factor"], d["mscale_all_dim"])
    scale = m * m / math.sqrt(nope + rope)
    pad = -s % block
    keys = jnp.arange(s)
    out = []
    for g in range(0, nq, head_group):
        n = min(head_group, nq - g)
        q = _mm(c_q, w_uq[:, g:g + n].reshape(-1, n * (nope + rope)), low
                ).reshape(s, n, nope + rope)
        q_nope, q_pe = q[..., :nope], _rotary(q[..., nope:], d)
        kv = _mm(c_kv, w_ukv[:, g:g + n].reshape(rank, n * (nope + vd)), low
                 ).reshape(s, n, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def attend(inp, k_nope=k_nope, v=v):
            qn, qp, at = inp                # (block, n, .), (block,)
            sc = (jnp.einsum("qnd,knd->nqk", qn, k_nope)
                  + jnp.einsum("qnd,kd->nqk", qp, k_pe)) * scale
            ok = keys[None, :] <= at[:, None]
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v)

        def blocks(a):
            a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            return a.reshape((s + pad) // block, block, *a.shape[1:])

        ctx = jax.lax.map(attend, (blocks(q_nope), blocks(q_pe),
                                   blocks(keys)))
        out.append(ctx.reshape(s + pad, n, vd)[:s])
    ctx = jnp.concatenate(out, axis=1).reshape(s, nq * vd)
    return _mm(ctx, lp["attn_out_w"], low)


def _experts(x, lp, d: dict, low: str):
    """Shared(x) + sum_i w_i Expert_i(x) over the experts held. Also, for
    every token, how near a tie its routing was AS FAR AS THIS SHARE SEES IT
    (for the tests and for the check ``answer_moe``), in LOGITS: a lower
    precision moves a score by a share of itself, so a tie is a small
    RATIO of two scores (a difference of their logarithms), whatever their
    size. The least of: over the scores the group step left, the distance
    of a HELD expert's score from the boundary it would have to cross to
    join or leave the token's top k; the distance of a HELD group's score
    from the boundary it would have to cross to join or leave the token's
    ``topk_group`` groups; and the distance between the ``topk_group``-th
    and the next group's score, where choosing the next group instead
    would change which held experts are picked (where it would not, the
    same distance among that other choice's scores counts in its place: a
    swap between two groups held elsewhere changes nothing here)."""
    if low == "fp8":    # the control: the router is a matmul like the others
        logits = _mm(x, lp["router_w"], low)
    else:               # float32 as published, also where the rest is bfloat16
        logits = jnp.dot(x.astype(jnp.float32),
                         lp["router_w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    s32 = jax.nn.softmax(logits, axis=-1)
    ls = jax.nn.log_softmax(logits, axis=-1)        # the margins' scale
    t, e = s32.shape
    per, tg, k = e // d["groups"], d["topk_group"], d["k"]
    group = ls.reshape(t, d["groups"], per).max(axis=-1)
    rank = jnp.argsort(jnp.argsort(-group, axis=-1), axis=-1)
    best = -jnp.sort(-group, axis=-1)
    inf = jnp.full((t,), jnp.inf)
    g_margin = best[:, tg - 1] - best[:, tg] if tg < d["groups"] else inf
    # a group with held experts, against the boundary of the groups kept
    g_held = inf
    if tg < d["groups"]:
        for g in range(d["first"] // per,
                       (d["first"] + d["held"] - 1) // per + 1):
            g_held = jnp.minimum(g_held, jnp.where(
                rank[:, g] < tg, group[:, g] - best[:, tg],
                best[:, tg - 1] - group[:, g]))

    def world(keep):
        """The top k of the scores the groups ``keep`` (T, G) leave: how
        near a HELD expert's score lies to the boundary it would have to
        cross to join or leave them, which held experts are among them,
        and the picks."""
        left = jnp.where(jnp.repeat(keep, per, axis=1), ls, -jnp.inf)
        top, idx = jax.lax.top_k(left, k + 1)
        last_in, first_out = top[:, k - 1, None], top[:, k, None]
        here = jax.lax.dynamic_slice_in_dim(left, d["first"], d["held"], 1)
        near = jnp.where(here >= last_in, here - first_out,
                         last_in - here).min(axis=-1)
        return near, here >= last_in, idx[:, :k]

    # the reference's own choice of groups, and the choice a program makes
    # whose 3rd and 4th group scores lie the other way round
    m_a, in_a, idx = world(rank < tg)
    m_b, in_b, idx_b = world((rank < tg - 1) | (rank == tg))
    same = (in_a == in_b).all(axis=-1)
    if d["norm"]:       # renormalised weights see every pick, held or not
        same = same & (jnp.sort(idx, -1) == jnp.sort(idx_b, -1)).all(axis=-1)
    margin = jnp.minimum(
        jnp.minimum(m_a, g_held),
        jnp.maximum(g_margin, jnp.where(same, m_b, 0.0)))
    w = jnp.take_along_axis(s32, idx, axis=-1)
    if d["norm"]:
        w = w / w.sum(axis=-1, keepdims=True)
    w = w * d["scale"]
    # weight of every HELD expert for every token (0 where not picked)
    local = idx - d["first"]
    each = jnp.zeros((t, d["held"]), jnp.float32)
    each = each.at[jnp.arange(t)[:, None],
                   jnp.clip(local, 0, d["held"] - 1)].add(
        jnp.where((local >= 0) & (local < d["held"]), w, 0.0))

    def one(acc, inp):              # one expert at a time, cast on the way
        w1, w3, w2, we = inp
        return acc + we[:, None] * _swiglu(x, w1, w3, w2, low), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x, jnp.float32),
                        (lp["moe_in_w"], lp["moe_up_w"], lp["moe_out_w"],
                         each.T))
    if d["shared"]:
        y = y + _swiglu(x, lp["shared_in_w"], lp["shared_up_w"],
                        lp["shared_out_w"], low)
    return y, margin


@functools.partial(jax.jit, static_argnames=("dims", "kind", "low"))
def _layer(x, lp, dims, kind: str, low: str):
    """One layer over one sequence ``x`` (S, H); ``lp`` that layer's leaves
    as the benchmark made them (bfloat16), cast here."""
    d = dict(dims)

    def kept(t):        # the residual stream as bfloat16 keeps it
        return t.astype(jnp.bfloat16).astype(jnp.float32) \
            if low == "bf16" else t

    a = kept(x + _attention(_rms(x, lp["ln1_scale"], d["eps"]), lp, d, low))
    m = _rms(a, lp["ln2_scale"], d["eps"])
    if kind == "dense":
        y, margin = _swiglu(m, lp["mlp_in_w"], lp["mlp_up_w"],
                            lp["mlp_out_w"], low), None
    else:
        y, margin = _experts(m, lp, d, low)
    return kept(a + y), margin


@functools.partial(jax.jit, static_argnames=("eps", "n_out", "low"))
def _head(x, scale, head, first, eps: float, n_out: int, low: str):
    h = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    h = _rms(h, scale, eps)
    if low == "fp8":
        h = fp8_round(h, -1)
        head = fp8_round(head.astype(jnp.float32), -1)
    if low:
        h, head = h.astype(jnp.bfloat16), head.astype(jnp.bfloat16)
    return (h @ head.astype(h.dtype).T).astype(jnp.float32)


def reference_forward(params: dict, model: dict, ids, first: int,
                      precision: str = "f32", margins: bool = False):
    """Logits (float32) of positions ``first .. len(ids) - 1`` of ONE
    sequence through the whole model; with ``margins`` also each of those
    positions' smallest router margin over the expert layers
    (:func:`_experts`)."""
    d = _dims(model)
    low = "" if precision == "f32" else precision
    ids = jnp.asarray(np.asarray(ids, np.int32))
    n = int(ids.shape[0])
    dims = tuple(sorted(d.items()))
    worst = None
    with jax.default_matmul_precision("default" if low else "highest"):
        x = params["wte"][ids].astype(jnp.float32)
        if low == "fp8":
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

class DeepseekV2(DecoderLayout):
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
            max_position=model["max_position_embeddings"],
            layer_norm_eps=d["eps"], dtype=jnp.bfloat16,
            norm="rmsnorm", positions="rotary", rope_theta=d["theta"],
            mlp="swiglu", bias=bool(model["attention_bias"]),
            tied_head=bool(model["tie_word_embeddings"]),
            q_rank=d["q_rank"] or 0, kv_rank=d["rank"], nope_size=d["nope"],
            rope_size=d["rope"], v_size=d["vd"],
            rope_factor=d["factor"], rope_original=d["original"],
            rope_beta_fast=d["beta_fast"], rope_beta_slow=d["beta_slow"],
            rope_mscale=d["mscale"], rope_mscale_all_dim=d["mscale_all_dim"],
            dense_layers=sum(k == "dense" for k in kinds),
            moe=MoEConfig(
                experts=d["experts"], per_token=d["k"], width=d["w"],
                shared=d["shared"], held=(d["first"], d["held"]),
                route_norm=d["norm"], route_scale=d["scale"],
                score="softmax", groups=d["groups"],
                groups_per_token=d["topk_group"]),
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        d = _dims(model)
        h, nq = d["h"], d["nq"]
        w, g = "w", "gain"

        def run(kind, n):
            out = {
                "ln1_scale": ((n, h), g, 0.02),
                "q_a_w": ((n, h, d["q_rank"]), w, 0.02),
                "q_a_norm_scale": ((n, d["q_rank"]), g, 0.02),
                "q_b_w": ((n, d["q_rank"], nq * (d["nope"] + d["rope"])),
                          w, 0.02),
                "kv_a_w": ((n, h, d["rank"] + d["rope"]), w, 0.02),
                "kv_a_norm_scale": ((n, d["rank"]), g, 0.02),
                "kv_b_w": ((n, d["rank"], nq * (d["nope"] + d["vd"])),
                           w, 0.02),
                "attn_out_w": ((n, nq * d["vd"], h), w, 0.02),
                "ln2_scale": ((n, h), g, 0.02),
            }
            if kind == "dense":
                out.update({
                    "mlp_in_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_up_w": ((n, h, d["i"]), w, 0.02),
                    "mlp_out_w": ((n, d["i"], h), w, 0.02)})
            else:
                e, ew, ws = d["held"], d["w"], d["shared"] * d["w"]
                out.update({
                    "router_w": ((n, h, d["experts"]), w, 0.02),
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
        if precision not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        return params

    def logits(self, prepared: dict, model: dict, ids: list, first: int,
               precision: str = "f32") -> np.ndarray:
        return reference_forward(prepared, model, ids, first, precision)

    def logits_margins(self, prepared: dict, model: dict, ids: list,
                       first: int):
        """:meth:`logits` in float32 and, beside each row, how near a tie
        that position's routing was (check ``answer_moe``)."""
        return reference_forward(prepared, model, ids, first, "f32",
                                 margins=True)

    # -- work counted from the shapes ---------------------------------------

    def attention_params(self, model: dict) -> int:
        d = _dims(model)
        h, nq = d["h"], d["nq"]
        return h * d["q_rank"] + d["q_rank"] * nq * (d["nope"] + d["rope"]) \
            + h * (d["rank"] + d["rope"]) \
            + d["rank"] * nq * (d["nope"] + d["vd"]) + nq * d["vd"] * h

    def expert_params(self, model: dict) -> int:
        d = _dims(model)
        return 3 * d["h"] * d["w"]

    def layer_params(self, model: dict, kind: str) -> int:
        """Matrix parameters one layer HOLDS here."""
        d = _dims(model)
        if kind == "dense":
            return self.attention_params(model) + 3 * d["h"] * d["i"]
        return self.attention_params(model) + d["h"] * d["experts"] \
            + (d["held"] + d["shared"]) * self.expert_params(model)

    def layer_params_per_token(self, model: dict, kind: str) -> float:
        """Matrix parameters one token MULTIPLIES in a layer: ``W_UKV``
        once (ONE expansion of its latent row, or the two absorbed
        products: the same count); of the routed experts only its picks
        that fall on the experts held (evenly routed: per_token * held /
        published)."""
        d = _dims(model)
        if kind == "dense":
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
        norms = len(_kinds(model)) * (2 * d["h"] + d["q_rank"] + d["rank"]) \
            + d["h"]
        return float((self.matmul_param_count(model) + norms) * itemsize)

    def kv_bytes_per_token_layer(self, model: dict, itemsize: int = 2) -> int:
        """The normed latent and the rotated shared key: nothing per head."""
        d = _dims(model)
        return (d["rank"] + d["rope"]) * itemsize

    def kv_tokens(self, model: dict, context: float) -> float:
        """Cached positions one sequence of ``context`` tokens keeps live
        over all layers (every layer keeps all of them)."""
        return float(len(_kinds(model)) * context)

    def kv_bytes_per_token(self, model: dict, itemsize: int = 2) -> float:
        return float(len(_kinds(model))
                     * self.kv_bytes_per_token_layer(model, itemsize))

    def decode_step_bytes(self, model: dict, live_kv_tokens: float,
                          itemsize: int = 2, experts_touched=None,
                          batch: float = 1.0) -> float:
        """One decode step reads every parameter it multiplies and the
        live latent rows once. ``experts_touched``: distinct held experts
        read per expert layer (default: all held)."""
        d = _dims(model)
        touched = d["held"] if experts_touched is None else experts_touched
        total = 0.0
        for kind in _kinds(model):
            if kind == "dense":
                total += self.layer_params(model, kind)
            else:
                total += self.attention_params(model) \
                    + d["h"] * d["experts"] \
                    + (touched + d["shared"]) * self.expert_params(model)
        total += d["v"] * d["h"] + batch * d["h"]   # the head, token rows
        kv = self.kv_tokens(model, live_kv_tokens) \
            * self.kv_bytes_per_token_layer(model, itemsize)
        return total * itemsize + kv

    def decode_step_flops(self, model: dict, batch: float,
                          live_kv_tokens: float) -> float:
        """``batch`` tokens through the matrices; attention ABSORBED, the
        cheaper form for a single query: a head scores a key over the
        latent row's ``rank + rope`` values and weighs its ``rank``."""
        d = _dims(model)
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model)) + d["v"] * d["h"]
        keys = self.kv_tokens(model, live_kv_tokens)
        return 2.0 * per_token * batch \
            + 2.0 * d["nq"] * (2 * d["rank"] + d["rope"]) * keys

    def prefill_flops(self, model: dict, prompt_tokens: int) -> float:
        """One causal forward over the prompt: the matrices each token
        multiplies (``W_UKV`` once a token: each latent row expanded ONCE),
        causal attention EXPANDED (a head's key ``nope + rope`` values, its
        value ``v``), the head for the last position only."""
        d = _dims(model)
        n = prompt_tokens
        per_token = sum(self.layer_params_per_token(model, k)
                        for k in _kinds(model))
        pairs = len(_kinds(model)) * n * (n + 1) / 2
        return 2.0 * per_token * n \
            + 2.0 * d["nq"] * (d["nope"] + d["rope"] + d["vd"]) * pairs \
            + 2.0 * d["v"] * d["h"]


layout = DeepseekV2()
