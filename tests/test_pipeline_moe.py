"""Pipeline-parallel encoder and the decoder's routed experts — exactness
against the sequential encoder / the uncut, unsharded layer on the virtual
8-device mesh. (The two MoE tests were the encoder top-1 layer's, ported one
for one to the layer that took its place: shapes-and-routing, and
sharded-matches-unsharded; the third ties a chip's SHARE to the model.)"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.models import MINILM_L6, init_params
from pathway_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_mlp,
    moe_partition_specs,
    route,
)
from pathway_tpu.models.pipeline import encode_pipelined
from pathway_tpu.models.transformer import encode


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(
        MINILM_L6, layers=4, hidden=32, heads=4, intermediate=64,
        vocab_size=128, max_position=16, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128)
    mask = jnp.concatenate(
        [jnp.ones((4, 12), jnp.int32), jnp.zeros((4, 4), jnp.int32)], axis=1
    )
    return cfg, params, ids, mask


@pytest.mark.parametrize("pp,n_micro", [(2, 2), (4, 4), (2, 4)])
def test_pipeline_matches_sequential(tiny, pp, n_micro):
    cfg, params, ids, mask = tiny
    ref = encode(params, ids, mask, cfg)
    mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))
    out = encode_pipelined(params, ids, mask, cfg, mesh, n_microbatches=n_micro)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


def test_pipeline_validates_divisibility(tiny):
    cfg, params, ids, mask = tiny
    mesh = Mesh(np.array(jax.devices()[:3]), ("pp",))
    with pytest.raises(ValueError, match="divide"):
        encode_pipelined(params, ids, mask, cfg, mesh, n_microbatches=2)
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="divide"):
        encode_pipelined(params, ids, mask, cfg, mesh2, n_microbatches=3)


def _plain_moe(x, mp, moe, held=None):
    """The layer written out per expert in float32: every expert held
    weighted by the tokens that picked it, plus the shared expert."""
    tokens = x.reshape(-1, x.shape[-1])
    idx, w, _s = route(tokens, mp, moe)
    first, count = held or (0, moe.experts)
    y = jnp.zeros_like(tokens)
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        a = jax.nn.silu(tokens @ mp["moe_in_w"][e]) * (
            tokens @ mp["moe_up_w"][e])
        y = y + we[:, None] * (a @ mp["moe_out_w"][e])
    if moe.shared:
        y = y + (jax.nn.silu(tokens @ mp["shared_in_w"]) * (
            tokens @ mp["shared_up_w"])) @ mp["shared_out_w"]
    return y.reshape(x.shape)


def test_moe_shapes_routing_and_no_token_dropped(tiny):
    cfg, _params, _ids, _mask = tiny
    moe = MoEConfig(experts=4, per_token=2, width=48, shared=1,
                    route_scale=2.448)
    mp = init_moe_params(jax.random.PRNGKey(2), cfg.hidden, moe, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.hidden))
    y, counts = moe_mlp(x, mp, moe, jnp.float32)
    assert y.shape == x.shape
    # every token's every pick is computed: no capacity, nothing dropped,
    # however uneven the routing (all 32 tokens x 2 picks are held here)
    assert counts.tolist() == [64, 64]
    idx, w, s = route(x.reshape(32, -1), mp, moe)
    assert idx.shape == (32, 2) and s.shape == (32, 4)
    assert bool(jnp.all(idx[:, 0] != idx[:, 1]))
    assert np.allclose(np.asarray(w.sum(-1)), 2.448, atol=1e-5)
    assert float(jnp.max(jnp.abs(y - _plain_moe(x, mp, moe)))) < 1e-5
    # the balance bias moves the CHOICE and never the weights
    biased = dict(mp, router_bias=mp["router_bias"].at[3].add(10.0))
    idx_b, w_b, _ = route(x.reshape(32, -1), biased, moe)
    assert bool(jnp.all(jnp.any(idx_b == 3, axis=-1)))
    assert np.allclose(np.asarray(w_b.sum(-1)), 2.448, atol=1e-5)
    # uneven on purpose: one expert takes every token, none is lost
    y_b, counts_b = moe_mlp(x, biased, moe, jnp.float32)
    assert counts_b.tolist() == [64, 64]
    assert float(jnp.max(jnp.abs(y_b - _plain_moe(x, biased, moe)))) < 1e-5


def test_moe_ep_sharded_matches_unsharded(tiny):
    cfg, _params, _ids, _mask = tiny
    moe = MoEConfig(experts=8, per_token=2, width=48, shared=1)
    mp = init_moe_params(jax.random.PRNGKey(4), cfg.hidden, moe, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.hidden))
    ref, _ = moe_mlp(x, mp, moe, jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:8]), ("ep",))
    specs = moe_partition_specs(moe)
    mp_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in mp.items()
    }
    with mesh:
        out, _ = jax.jit(lambda x, mp: moe_mlp(x, mp, moe, jnp.float32))(
            x, mp_sharded)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_the_eight_shares_sum_to_the_uncut_layer(tiny):
    """The guide's share test: 16 experts over 8 chips, 2 held each. Every
    share routes over all 16, computes its own experts' part and the shared
    expert; the parts of all 8, the shared expert counted ONCE, add up to
    the uncut layer, and each share's held assignments to all of them."""
    cfg, _params, _ids, _mask = tiny
    whole = MoEConfig(experts=16, per_token=4, width=48, shared=1,
                      route_scale=2.448)
    mp = init_moe_params(jax.random.PRNGKey(6), cfg.hidden, whole, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.hidden))
    uncut, counts = moe_mlp(x, mp, whole, jnp.float32)
    assert counts.tolist() == [128, 128]
    shared_only = _plain_moe(x, mp, whole, held=(0, 0))
    total, held_sum = jnp.zeros_like(uncut), 0
    for chip in range(8):
        share = dataclasses.replace(whole, held=(2 * chip, 2))
        mine = {**mp, **{k: mp[k][2 * chip:2 * chip + 2]
                         for k in ("moe_in_w", "moe_up_w", "moe_out_w")}}
        part, c = moe_mlp(x, mine, share, jnp.float32)
        assert float(jnp.max(jnp.abs(
            part - _plain_moe(x, mine, share, held=(2 * chip, 2))))) < 1e-5
        total = total + (part - shared_only)
        held_sum += int(c[0])
        assert int(c[1]) == 128
    assert held_sum == 128
    assert float(jnp.max(jnp.abs(total + shared_only - uncut))) < 2e-5


def test_the_eight_groups_shares_sum_to_the_uncut_layer(tiny):
    """The same for the router that is limited to device groups: 32
    experts in 8 groups of 4, a token may use 3 groups and 6 experts,
    softmax scores times 16, not renormalised, no bias; every chip holds
    ONE group. The parts of all 8, the two shared experts counted ONCE, add
    up to the uncut layer, which is the plain reference's of the
    ``deepseek_v2`` layout; a token reaches a chip's group 3 times in 8."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest as M

    cfg, _params, _ids, _mask = tiny
    whole = MoEConfig(experts=32, per_token=6, width=24, shared=2,
                      route_norm=False, route_scale=16.0, score="softmax",
                      groups=8, groups_per_token=3)
    mp = init_moe_params(jax.random.PRNGKey(8), cfg.hidden, whole, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.hidden))
    uncut, counts = moe_mlp(x, mp, whole, jnp.float32)
    assert counts.tolist() == [192, 192]
    ref = M.load_named_module(M.load_manifest(), "layouts", "deepseek_v2")
    d = {"groups": 8, "topk_group": 3, "k": 6, "first": 0, "held": 32,
         "norm": False, "scale": 16.0, "shared": 2}
    with jax.default_matmul_precision("highest"):
        want, margin = ref._experts(x.reshape(32, -1), mp, d, False)
    assert float(margin.min()) > 1e-6       # no near-tie decides this
    assert float(jnp.max(jnp.abs(uncut.reshape(32, -1) - want))) < 2e-5
    shared_only = _plain_moe(x, mp, whole, held=(0, 0))
    total, held_sum, reached = jnp.zeros_like(uncut), 0, 0
    idx, _w, _s = route(x.reshape(32, -1), mp, whole)
    for chip in range(8):
        share = dataclasses.replace(whole, held=(4 * chip, 4))
        mine = {**mp, **{k: mp[k][4 * chip:4 * chip + 4]
                         for k in ("moe_in_w", "moe_up_w", "moe_out_w")}}
        part, c = moe_mlp(x, mine, share, jnp.float32)
        assert float(jnp.max(jnp.abs(
            part - _plain_moe(x, mine, share, held=(4 * chip, 4))))) < 1e-5
        total = total + (part - shared_only)
        held_sum += int(c[0])
        reached += int(jnp.any(idx // 4 == chip, axis=-1).sum())
        assert int(c[1]) == 192
    assert held_sum == 192
    assert reached <= 3 * 32        # a token's picks lie in at most 3 groups
    assert float(jnp.max(jnp.abs(total + shared_only - uncut))) < 2e-5
