"""Routed experts as the decoder's MLP: a router over ALL published experts,
a grouped matmul over the experts HELD here, a shared expert.

The layer is told which experts it holds (``MoEConfig.held = (first,
count)``: the chip's share of an expert-parallel deployment, or all of
them). It routes every token over the router's full width, keeps the
assignments that fall on its own experts, computes ``sum_i w_i Expert_i(x)``
over those, adds the shared expert (whole on every chip) and passes that
PARTIAL result on. No capacity, no dropped token, and nothing that stands in
for the absent chips or their exchange: the parts of all shares, the shared
expert counted once, add up to the uncut layer (``tests/test_pipeline_moe.py``).

Routing is float32 as published: ``s = sigmoid(x Wr)`` (or ``softmax``:
``MoEConfig.score``) over all experts; the top ``per_token`` of ``s + b``
(``b`` the per-expert balance bias, used for the CHOICE only; a router may
have none); ``w = s[top] / sum s[top] * route_scale``, or unnormalised.
With ``groups`` the experts lie in that many equal device groups and a
token may use ``groups_per_token`` of them: a group's score is its best
expert's, the best groups stay, the others' scores become 0 before the top
``per_token`` are taken (group-limited routing).

The grouped matmul sorts the assignments by expert and walks (row tile,
expert) pairs in one ``while_loop``: each visit reads ONE expert's three
matrices and multiplies one tile of rows, so a decode step of 16 tokens
reads only the experts somebody picked and a prefill piece reads each held
expert about once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    experts: int = 8            # published: the router's width
    per_token: int = 2
    width: int = 0              # every expert's SwiGLU width
    shared: int = 0             # always-on shared experts, each of `width`
    held: tuple | None = None   # (first, count) held here; None: all
    route_norm: bool = True
    route_scale: float = 1.0
    score: str = "sigmoid"      # sigmoid | softmax
    groups: int = 1             # device groups the experts lie in
    groups_per_token: int = 1   # of which a token may use this many

    @property
    def bias(self) -> bool:
        """A sigmoid router carries the per-expert balance bias on its
        choice (leaf ``router_bias``); a softmax router has none."""
        return self.score == "sigmoid"

    @property
    def held_range(self) -> tuple:
        return tuple(self.held) if self.held is not None else (
            0, self.experts)


def init_moe_params(rng: jax.Array, hidden: int, moe: MoEConfig,
                    dtype=jnp.float32, scale: float = 0.02) -> dict:
    """One layer's router, balance bias, held experts (leading axis: the
    experts held, the ``ep`` axis of a deployment) and shared expert."""
    ks = jax.random.split(rng, 8)
    _first, count = moe.held_range
    w = moe.width

    def init(key, shape):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    out = {
        "router_w": init(ks[0], (hidden, moe.experts)).astype(jnp.float32),
        "router_bias": (jax.random.normal(ks[1], (moe.experts,)) * scale
                        ).astype(jnp.float32),
        "moe_in_w": init(ks[2], (count, hidden, w)),
        "moe_up_w": init(ks[3], (count, hidden, w)),
        "moe_out_w": init(ks[4], (count, w, hidden)),
    }
    if moe.shared:
        ws = moe.shared * w
        out["shared_in_w"] = init(ks[5], (hidden, ws))
        out["shared_up_w"] = init(ks[6], (hidden, ws))
        out["shared_out_w"] = init(ks[7], (ws, hidden))
    return out


def moe_partition_specs(moe: MoEConfig, ep_axis: str = "ep") -> dict:
    """Experts shard their leading axis over ``ep``; router and shared
    expert are whole on every chip."""
    from jax.sharding import PartitionSpec as P

    out = {
        "router_w": P(None, None), "router_bias": P(None),
        "moe_in_w": P(ep_axis, None, None),
        "moe_up_w": P(ep_axis, None, None),
        "moe_out_w": P(ep_axis, None, None),
    }
    if moe.shared:
        out.update(shared_in_w=P(None, None), shared_up_w=P(None, None),
                   shared_out_w=P(None, None))
    return out


def route(tokens: jax.Array, mp: dict, moe: MoEConfig):
    """``(idx (T, k) int32, w (T, k) float32, s (T, E) float32)``: the
    experts each token picks, over the router's FULL width, and their
    weights. Float32 at ``highest`` whatever the activations' type."""
    logits = jnp.dot(tokens.astype(jnp.float32),
                     mp["router_w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1) if moe.score == "softmax" \
        else jax.nn.sigmoid(logits)
    choice = s + mp["router_bias"].astype(jnp.float32) if moe.bias else s
    if moe.groups > 1:
        T, E = choice.shape
        best = choice.reshape(T, moe.groups, E // moe.groups).max(axis=-1)
        _g, gi = jax.lax.top_k(best, moe.groups_per_token)
        kept = jnp.zeros((T, moe.groups), jnp.bool_).at[
            jnp.arange(T)[:, None], gi].set(True)
        choice = jnp.where(jnp.repeat(kept, E // moe.groups, axis=1),
                           choice, 0.0)
    _top, idx = jax.lax.top_k(choice, moe.per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if moe.route_norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * moe.route_scale, s


def _swiglu(x, w_in, w_up, w_out, dtype):
    a = jnp.dot(x, w_in.astype(dtype), preferred_element_type=dtype)
    b = jnp.dot(x, w_up.astype(dtype), preferred_element_type=dtype)
    return jnp.dot(jax.nn.silu(a) * b, w_out.astype(dtype),
                   preferred_element_type=dtype)


def _expert(w: jax.Array, layer, g):
    """Expert ``g``'s matrix out of ``w``: (E, a, b), or a whole stack of
    layers (n, E, a, b) read at ``layer``. A stack is read IN PLACE (the
    slice feeds the matmul): handing the loop one layer's slice instead
    would copy that layer's experts, picked or not, at every step."""
    if w.ndim == 3:
        return jax.lax.dynamic_index_in_dim(w, g, 0, False)
    return jax.lax.dynamic_slice(
        w, (layer, g, 0, 0), (1, 1, *w.shape[2:]))[0, 0]


def grouped_swiglu(xs: jax.Array, gid: jax.Array, n_groups: int, mp: dict,
                   dtype, tile: int = 128) -> jax.Array:
    """``Expert_{gid[r]}(xs[r])`` for every row whose group is held
    (``gid[r] < n_groups``); the other rows come out zero. ``xs`` (A, H) is
    SORTED by ``gid`` (not-held rows, ``gid == n_groups``, last); A is a
    multiple of ``tile``. One visit per (row tile, group) pair that has
    rows: experts nobody picked are never read. ``mp["moe_layer"]``, where
    present, says which layer of stacked expert leaves (n, E, ., .) this
    is."""
    A, H = xs.shape
    layer = mp.get("moe_layer")
    counts = jnp.zeros((n_groups + 1,), jnp.int32).at[gid].add(1)
    ends = jnp.cumsum(counts)                   # rows of group g end here
    n_rows = ends[n_groups - 1]                 # rows that are held
    rows = jnp.arange(tile, dtype=jnp.int32)

    def cond(state):
        r, _ys = state
        return r < n_rows

    def visit(state):
        r, ys = state
        t0 = (r // tile) * tile
        g = gid[r]
        r_end = jnp.minimum(t0 + tile, ends[g])
        x_t = jax.lax.dynamic_slice(xs, (t0, 0), (tile, H))
        mine = ((t0 + rows >= r) & (t0 + rows < r_end))[:, None]
        y_t = _swiglu(
            jnp.where(mine, x_t, jnp.zeros((), x_t.dtype)),
            _expert(mp["moe_in_w"], layer, g),
            _expert(mp["moe_up_w"], layer, g),
            _expert(mp["moe_out_w"], layer, g),
            dtype)
        old = jax.lax.dynamic_slice(ys, (t0, 0), (tile, H))
        ys = jax.lax.dynamic_update_slice(
            ys, jnp.where(mine, y_t, old), (t0, 0))
        return r_end, ys

    _r, ys = jax.lax.while_loop(
        cond, visit, (jnp.int32(0), jnp.zeros((A, H), dtype)))
    return ys


def moe_mlp(x: jax.Array, mp: dict, moe: MoEConfig, dtype,
            tile: int = 128):
    """The expert layer's MLP over ``x`` (B, S, H), already normed
    (float32, as the norm leaves it): ``Shared(x) + sum_i w_i Expert_i(x)``
    over the picks that fall on the experts held. The ROUTER reads ``x``
    as it is — float32 end to end, as published: rounding its input to the
    compute type first flips near-tied choices for nothing — and the
    experts read it in ``dtype``. Returns ``(y (B, S, H) dtype, counts
    (2,) uint32)``: ``counts`` = (assignments that fell on held experts,
    all assignments), for the ``moe_assignments`` counter."""
    B, S, H = x.shape
    T, k = B * S, moe.per_token
    first, count = moe.held_range
    tokens = x.reshape(T, H).astype(dtype)
    with jax.named_scope("moe.route"):
        idx, w, _s = route(x.reshape(T, H), mp, moe)
        local = idx - first
        held = (local >= 0) & (local < count)
        gid = jnp.where(held, local, count).reshape(T * k)
        order = jnp.argsort(gid, stable=True)
        counts = jnp.stack([jnp.sum(held), jnp.int32(T * k)]
                           ).astype(jnp.uint32)
    with jax.named_scope("moe.experts"):
        tile = min(tile, max(8, 1 << (T * k - 1).bit_length()))
        A = -(-T * k // tile) * tile
        pad = A - T * k
        order_p = jnp.concatenate([order, jnp.zeros((pad,), order.dtype)])
        gid_p = jnp.concatenate(
            [gid[order], jnp.full((pad,), count, gid.dtype)])
        xs = tokens[order_p // k]
        ys = grouped_swiglu(xs, gid_p, count, mp, dtype, tile)
        # back to assignment order, then the weighted sum over the picks
        inverse = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        per_pick = ys[inverse].reshape(T, k, H)
        wk = jnp.where(held, w, 0.0)
        y = jnp.einsum("tk,tkh->th", wk.astype(jnp.float32),
                       per_pick.astype(jnp.float32))
    if moe.shared:
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(tokens, mp["shared_in_w"], mp["shared_up_w"],
                            mp["shared_out_w"], dtype).astype(jnp.float32)
    return y.astype(dtype).reshape(B, S, H), counts
