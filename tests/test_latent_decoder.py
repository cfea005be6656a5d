"""Latent attention, the latent slot pool and group-limited routing against
the plain reference of the ``deepseek_v2`` layout
(``benchmarks/layouts/deepseek_v2.py`` — the ONE copy of the reference: the
tests import the layout the benchmark runs; it is NOT absorbed and keeps no
cache).

Small widths with the published ratios (8 heads of 24 | 8 | 24: three
values without positions to one rotary; 32 experts in 8 groups, 3 groups and
6 experts a token, one group held), seeded random weights, CPU. What is
compared is LOGITS, never sampled tokens. Tolerances, and why:

* ``F32_TOL`` = 2e-5: program and reference both in float32 on the same
  weights differ by the order of summation alone (read 2e-7 to 8e-7 on
  logits of size 0.7; the absorbed read associates ``q W_UK^T c`` the other
  way round and reads the same). A bfloat16 computation reads 1e-2 and more
  and a dropped term 2e-3 and more: both fail it, which the tests below
  show for bfloat16, the shared rotary key, YaRN's ``m^2`` and its ramp, the
  inner norms, the group step and the router's ``16``.
* a router near-tie does not decide a test: the reference reports every
  position's smallest routing margin over the expert layers (the held
  group against the 3rd | 4th group boundary, 6th | 7th expert for the
  experts held; in logits: a tie is a small ratio of two scores), and a
  position under ``TIE_EPS`` = 1e-5 is skipped (counted: at most a tenth)
  rather than given a looser tolerance. In float32 the two sides' scores
  differ by about 3e-7 of themselves.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import manifest as M  # noqa: E402
from harness import weights as W  # noqa: E402

from pathway_tpu.models import decoder as D  # noqa: E402

F32_TOL = 2e-5
TIE_EPS = 1e-5

MODEL = {
    "layout": "deepseek_v2", "model_type": "deepseek_v2",
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 8,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "intermediate_size": 128,
    "moe_intermediate_size": 24, "n_routed_experts": 4,
    "n_routed_experts_published": 32, "experts_held_first": 0,
    "num_experts_per_tok": 6, "n_group": 8, "topk_group": 3,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "routed_scaling_factor": 16.0, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy", "vocab_size": 251,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "max_position_embeddings": 4096, "torch_dtype": "bfloat16",
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "layers_kept": [0, 1, 2],
}
PROMPT = 40
WIDTH = 32 + 8          # what a latent layer caches a token


def _reference():
    return M.load_named_module(M.load_manifest(), "layouts", "deepseek_v2")


@pytest.fixture(scope="module")
def layout():
    return M.resolve(M.load_manifest(), "layouts", "deepseek_v2")


@pytest.fixture(scope="module")
def net(layout):
    """(bfloat16 weights as the benchmark makes them, the same in float32,
    the program's config in bfloat16 and in float32)."""
    params = W.make_params(7, W.STREAM_DECODER,
                           layout.weight_spec(MODEL, "decoder"))
    cfg = layout.program_config(MODEL)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return params, p32, cfg, dataclasses.replace(cfg, dtype=jnp.float32)


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(1, 251, PROMPT).astype(
        np.int32).tolist()


def ref_rows(params, seq, first, model=MODEL):
    """Reference logits of positions first.. and which of them no router
    near-tie touches."""
    logits, margin = _reference().reference_forward(
        params, model, seq, first, margins=True)
    return logits, margin > TIE_EPS


def worst(got, want, sound):
    assert sound.sum() >= 0.9 * len(sound), "too many near-ties to judge"
    return float(np.abs(got[sound] - want[sound]).max())


def _forward(p, cfg, prompt):
    ids = np.asarray([prompt], np.int32)
    return np.asarray(jax.jit(lambda p, i, m: D.forward(p, i, m, cfg))(
        p, ids, np.ones_like(ids)))[0]


def test_the_configuration_says_latent_attention_by_its_fields(net):
    _p, _p32, cfg, _c32 = net
    assert cfg.latent and (cfg.head_dim, cfg.v_dim, cfg.latent_width) == (
        32, 24, WIDTH)
    assert cfg.runs() == ((("latent", "rotary", "dense"), 0, 1),
                          (("latent", "rotary", "moe"), 1, 2))
    assert cfg.n_layers_of("latent") == 3 and not cfg.n_layers_of("full")
    assert not D.gpt2_block(cfg) and D.gpt2_block(D.GPT2_MEDIUM)
    assert (cfg.moe.score, cfg.moe.groups, cfg.moe.groups_per_token,
            cfg.moe.route_norm, cfg.moe.bias, cfg.moe.held) == (
                "softmax", 8, 3, False, False, (0, 4))
    # the program's own initialiser and counts follow the configuration
    own = D.init_params(jax.random.PRNGKey(0), cfg)
    made = W.make_params(7, W.STREAM_DECODER, M.resolve(
        M.load_manifest(), "layouts", "deepseek_v2").weight_spec(
            MODEL, "decoder"))
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(
        lambda a: a.shape, made)
    assert jax.tree.structure(D.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, own))
    cast = D.cast_params_for_inference(own, cfg)
    run1 = cast["layers"]["run1"]
    assert run1["router_w"].dtype == jnp.float32       # as published
    assert run1["kv_b_w"].dtype == jnp.bfloat16
    assert run1["kv_a_norm_scale"].dtype == jnp.float32
    assert "router_bias" not in run1 and "qkv_w" not in run1
    assert D.count_params(own) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(made))
    # YaRN: the program's frequencies and scale are the reference's own
    d = _reference()._dims(MODEL)
    assert np.allclose(D.yarn_inv_freq(cfg, 8),
                       _reference().yarn_frequencies(d), rtol=1e-6)
    inv = D.yarn_inv_freq(cfg, 8)
    plain = 10000.0 ** (-np.arange(4) / 4)
    assert inv[0] == pytest.approx(plain[0]) \
        and inv[-1] == pytest.approx(plain[-1] / 40, rel=1e-6) \
        and plain[1] / 40 < inv[1] < plain[1]          # inside the ramp
    m = 0.1 * 0.707 * np.log(40) + 1
    assert D.attn_scale(cfg) == pytest.approx(m * m / np.sqrt(32))
    # GPT-2 and the grouped-query block stay what they were
    assert not D.GPT2_SMALL.latent and D.GPT2_SMALL.v_dim == 64 \
        and D.GPT2_SMALL.layer_kind(0) == ("full", "learned", "dense")


def test_forward_logits_match_the_reference(net, prompt):
    params, p32, cfg, cfg32 = net
    want, sound = ref_rows(params, prompt, 0)
    assert worst(_forward(p32, cfg32, prompt), want, sound) < F32_TOL
    # left-padded rows see the same positions as their unpadded equivalents
    padded = np.zeros((1, PROMPT + 8), np.int32)
    padded[0, 8:] = prompt
    pm = (np.arange(PROMPT + 8) >= 8).astype(np.int32)[None]
    got = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg32))(p32, padded, pm))[0, 8:]
    assert worst(got, want, sound) < F32_TOL
    # the tolerance is tight enough that bfloat16 in float32's place fails
    assert worst(_forward(params, cfg, prompt), want, sound) > 100 * F32_TOL


@pytest.mark.parametrize("dropped", [
    {"rope_mscale_all_dim": 0.0}, {"rope_factor": 1.0},
    {"rope_original": 16}, {"rope_theta": 500000.0},
    {"moe": {"groups": 1}}, {"moe": {"groups_per_token": 4}},
    {"moe": {"route_scale": 1.0}}, {"moe": {"route_norm": True}},
    {"moe": {"score": "sigmoid"}}, {"moe": {"shared": 0}},
    {"moe": {"held": (4, 4)}}, "k_pe", "inner_norms",
], ids=str)
def test_a_dropped_term_fails_the_tolerance(net, prompt, dropped,
                                            monkeypatch):
    """YaRN's softmax scale ``m^2``, its blend and its ramp, the group
    step, how many groups, the router's ``16``, its normalisation, its
    softmax, the shared experts, WHICH group is held, the shared rotary key
    and the norms inside the low-rank projections: leaving any one out
    moves the logits by far more than the tolerance."""
    params, p32, _cfg, cfg32 = net
    broken = cfg32
    if dropped == "k_pe":
        # the one rotary key a token drops out of every score
        p32 = jax.tree.map(lambda a: a, p32)
        for run in p32["layers"].values():
            run["kv_a_w"] = run["kv_a_w"].at[:, :, 32:].set(0.0)
    elif dropped == "inner_norms":
        real = D._rms
        monkeypatch.setattr(D, "_rms", lambda x, scale, eps: (
            x.astype(jnp.float32) * scale if scale.shape[-1] in (48, 32)
            else real(x, scale, eps)))
    else:
        change = dict(dropped)
        if "moe" in change:
            change["moe"] = dataclasses.replace(cfg32.moe, **change["moe"])
        broken = dataclasses.replace(cfg32, **change)
        if broken.moe.bias:     # a sigmoid router's leaf, as initialised
            p32 = {**p32, "layers": {
                r: {**run, "router_bias": jnp.zeros(
                    run["router_w"].shape[::2])} if "router_w" in run
                else run for r, run in p32["layers"].items()}}
    want, sound = ref_rows(params, prompt, 0)
    assert worst(_forward(p32, broken, prompt), want, sound) > 50 * F32_TOL


def test_absorbed_and_expanded_are_the_same_read(net):
    """``q_nope W_UK^T . c`` then ``W_UV`` against per-head keys and values
    out of ``c W_UKV``: one mathematics, two orders of multiplication. The
    rule takes the cheaper by the operations a key row costs: a decode
    step's queries absorbed, a prefill piece's expanded."""
    _params, p32, cfg, cfg32 = net
    lp = jax.tree.map(lambda a: a[0], p32["layers"]["run1"])
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 8, 5, 32)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2, 1, 23, WIDTH)), jnp.float32)
    live = np.ones((2, 1, 5, 23), bool)
    live[0, :, :, 17:] = False
    bias = jnp.where(live, 0.0, -1e9).astype(jnp.float32)
    a = D._latent_ctx(q, c, lp, bias, cfg32, absorbed=True)
    e = D._latent_ctx(q, c, lp, bias, cfg32, absorbed=False)
    assert a.shape == e.shape == (2, 8, 5, 24)
    assert float(jnp.abs(a - e).max()) < 2e-6 * float(jnp.abs(e).max())
    # by hand: the expanded keys and values are what the equations say
    k, v = D.latent_expand(c, lp, cfg32)
    w = lp["kv_b_w"].reshape(32, 8, 48)
    assert np.allclose(k[..., :24], jnp.einsum(
        "bcr,rnd->bncd", c[:, 0, :, :32], w[..., :24]), atol=1e-5)
    assert np.allclose(v, jnp.einsum(
        "bcr,rnd->bncd", c[:, 0, :, :32], w[..., 24:]), atol=1e-5)
    assert np.array_equal(k[:, 3, :, 24:], c[:, 0, :, 32:])   # ONE rotary key
    # the rule, at the toy's widths and at the published ones
    assert D.latent_absorbed(cfg32, 16) and not D.latent_absorbed(cfg32, 128)
    big = dataclasses.replace(cfg, heads=128, q_rank=1536, kv_rank=512,
                              nope_size=128, rope_size=64, v_size=128)
    assert D.latent_absorbed(big, 1) and D.latent_absorbed(big, 170)
    assert not D.latent_absorbed(big, 512)


def _pieces(prompt, bucket, piece, left):
    ids = np.zeros((1, bucket), np.int32)
    mask = np.zeros((1, bucket), np.int32)
    n = len(prompt)
    if left:
        ids[0, bucket - n:], mask[0, bucket - n:] = prompt, 1
        pos = np.clip(np.cumsum(mask, 1) - 1, 0, None)
    else:
        ids[0, :n], mask[0, :n] = prompt, 1
        pos = np.minimum(np.arange(bucket), n - 1)[None]
    return [(ids[:, o:o + piece], mask[:, o:o + piece],
             pos[:, o:o + piece].astype(np.int32), o)
            for o in range(0, bucket, piece)]


def _prefill(p32, cfg32, pool, slot, prompt, left=True, blockwise=False):
    """``blockwise``: the read through the chunk kernel (the ``flash``
    argument forces what the shape rule chooses for long rows)."""
    n_prompt = np.asarray([len(prompt)], np.int32)
    for ids, mask, pos, o in _pieces(prompt, 48, 16, left):
        first, last = o == 0, o == 32
        if last and not left:
            pool = jax.jit(lambda p, i, m, ps, pl, lc: D.pool_prefill_chunk(
                p, i, m, ps, pl, np.int32(slot), np.int32(o), n_prompt,
                cfg32, first=first, last=True, last_col=lc,
                flash=blockwise))(
                    p32, ids, mask, pos, pool, np.int32(len(prompt) - 33))
        else:
            pool = jax.jit(lambda p, i, m, ps, pl: D.pool_prefill_chunk(
                p, i, m, ps, pl, np.int32(slot), np.int32(o), n_prompt,
                cfg32, first=first, last=last, flash=blockwise))(
                    p32, ids, mask, pos, pool)
    return pool


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_pool_prefill_then_decode_matches_the_full_forward(
        net, prompt, spec):
    """Chunked prefill (expanded reads) then decode (absorbed reads; plain
    and self-speculative) through the latent slot pool against the
    reference's full forward. The pool holds ONE array a run of layers,
    ``kv_rank + rope`` values a token, and nothing per head."""
    params, p32, _cfg, cfg32 = net
    pool = D.pool_init(p32, cfg32, 2, 96, arena_blocks=3, arena_block=16)
    kv = {n: a.shape for n, a in pool.items() if D._is_kv(n)}
    assert kv == {"cl0": (1, 2, 1, 96, WIDTH), "cl1": (2, 2, 1, 96, WIDTH)}
    assert D.pool_ring(pool) == 0
    assert not any(n[0] in "kv" for n in pool if n not in ("logits",))
    comp = D.pool_component_bytes(pool)
    assert comp == {"slot_pool_latent": 3 * 2 * 96 * WIDTH * 4,
                    "prefix_arena": 3 * 3 * 16 * WIDTH * 4}
    assert comp["slot_pool_latent"] == 2 * 96 * D.kv_token_bytes(cfg32, 4)
    pool = _prefill(p32, cfg32, pool, 1, prompt)
    seq = list(prompt)

    def check():
        want, sound = ref_rows(params, seq, len(seq) - 1)
        if sound[0]:
            assert np.abs(np.asarray(pool["logits"][1]) - want[0]).max() \
                < F32_TOL, len(seq)
        return bool(sound[0])

    judged = int(check())
    lanes = np.asarray([False, True])
    if spec:
        step = jax.jit(lambda p, pl: D.pool_decode_spec(
            p, pl, lanes, cfg32, 1, draft_layers=1, n_spec=3))
        for _ in range(6):
            pool, toks, n_emit = step(p32, pool)
            seq += [int(t) for t in toks[0, 1, :int(n_emit[0, 1])]]
            judged += check()
    else:
        step = jax.jit(lambda p, pl: D.pool_decode_chunk(
            p, pl, lanes, jax.random.PRNGKey(0), cfg32, 1))
        for _ in range(10):
            pool, toks = step(p32, pool)
            seq.append(int(toks[0, 1]))
            judged += check()
    assert judged >= 6
    # a token reaches this chip's group with probability 3/8; of its 6
    # picks those in the group are held
    held, every = np.asarray(pool["moe_counts"]).sum(axis=0)
    assert 0 < held < every / 2 and every % 6 == 0
    # slot 0 was never written
    assert not np.asarray(pool["cl1"][:, 0]).any()


@pytest.mark.parametrize("left", [True, False], ids=["left", "right"])
def test_chunked_prefill_through_the_blockwise_read_matches_the_reference(
        net, prompt, left):
    """The pieces' attention through the generalised chunk kernel (a key of
    24 + 8 beside a value of 24; every block of latent rows becomes a
    head's keys and values inside the kernel's walk; the first piece's
    leading rows wholly left padding): the next-token logits are the
    reference's, and the rows the pool holds are those the dense read
    leaves."""
    params, p32, _cfg, cfg32 = net
    dense = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt,
                     left)
    block = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt,
                     left, blockwise=True)
    want, sound = ref_rows(params, prompt, PROMPT - 1)
    assert sound[0]
    for pool in (dense, block):
        assert np.abs(np.asarray(pool["logits"][1]) - want[0]).max() \
            < F32_TOL
    live = np.asarray(dense["slot_mask"][1]) > 0
    assert live.sum() == PROMPT
    for name in ("cl0", "cl1"):
        assert np.abs(np.asarray(dense[name][:, 1, 0])[:, live]
                      - np.asarray(block[name][:, 1, 0])[:, live]
                      ).max() < 1e-5


def test_one_shot_admission_and_generate_ride_the_block(net, prompt):
    """``pool_admit`` (one whole-prompt prefill) leaves the rows and the
    logits the pieces leave; ``generate`` (prefill, then ``decode_step``
    over its own cache) emits the reference's greedy tokens."""
    params, p32, _cfg, cfg32 = net
    ids = np.zeros((1, 48), np.int32)
    mask = np.zeros((1, 48), np.int32)
    ids[0, 8:], mask[0, 8:] = prompt, 1
    once = jax.jit(lambda p, i, m, pl: D.pool_admit(
        p, i, m, pl, np.int32(1), cfg32))(
            p32, ids, mask, D.pool_init(p32, cfg32, 2, 96))
    pieces = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt)
    assert np.abs(np.asarray(once["logits"][1])
                  - np.asarray(pieces["logits"][1])).max() < F32_TOL
    assert np.abs(np.asarray(once["cl1"][:, 1, 0, 8:48])
                  - np.asarray(pieces["cl1"][:, 1, 0, 8:48])).max() < 1e-5
    toks = np.asarray(jax.jit(lambda p, i, m: D.generate(
        p, i, m, cfg32, 6))(p32, ids, mask))[0]
    want, sure = _greedy_by_reference(params, prompt, 6)
    assert sure and toks.tolist() == want


class WordIds:
    """``t<id>`` words in, ids out (and back): no EOS."""

    eos_id = None

    def encode(self, text):
        return [int(w[1:]) for w in text.split()]

    def decode(self, ids):
        return " ".join(f"t{int(i)}" for i in ids)


def _chat(p32, cfg32, **kw):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    return TPUDecoderChat(
        params=p32, cfg=cfg32, tokenizer=WordIds(), max_new_tokens=8,
        temperature=0.0, max_prompt_tokens=64, continuous=True, n_slots=2,
        chunk_steps=8, prefill_chunk=16, **kw)


def _serve(p32, cfg32, prompts, **kw):
    chat = _chat(p32, cfg32, **kw)
    try:
        reqs = [chat._server.submit(list(p), 8) for p in prompts]
        for r in reqs:
            assert r.done.wait(timeout=300)
        return [list(r.tokens) for r in reqs], dict(chat._server.stats), chat
    finally:
        chat.close()


def _greedy_by_reference(params, prompt, n):
    """The reference's own greedy continuation, and whether any of its
    steps was decided by a near-tie (of the router, or of the argmax)."""
    seq, sure = list(prompt), True
    for _ in range(n):
        logits, sound = ref_rows(params, seq, len(seq) - 1)
        top = np.sort(logits[0])[-2:]
        sure = sure and bool(sound[0]) and top[1] - top[0] > 1e-4
        seq.append(int(logits[0].argmax()))
    return seq[len(prompt):], sure


def test_the_server_serves_the_block_with_its_defaults(net, prompt):
    """Chunked prefill, batched admission, eager refill, the prefix cache
    and self-speculative decoding, all default-on, through
    ``TPUDecoderChat`` -> ``_ContinuousServer``: the greedy tokens are the
    reference's, and the server's pool and arena hold latent rows only."""
    params, p32, _cfg, cfg32 = net
    other = [int(t) for t in np.random.default_rng(3).integers(1, 251, 23)]
    streams, stats, chat = _serve(p32, cfg32, [prompt, other])
    assert chat._server.spec_decode and chat._server.prefix is not None
    assert stats["prefill_chunks"] >= 4 and stats["spec_dispatches"] > 0
    for p, got in zip((prompt, other), streams):
        want, sure = _greedy_by_reference(params, p, 8)
        assert sure, "choose another seed: the reference's own choice is a tie"
        assert got == want
    # the arena's budget is counted in latent rows: a block of 16 tokens of
    # 3 layers costs 16 x 3 x 40 x 4 bytes, not 2 x heads x head times that
    assert chat._server._prefix_kwargs["block_bytes"] == 16 * 3 * WIDTH * 4


def test_a_prefix_hit_gives_the_logits_of_a_miss(net):
    """A repeated prefix HITS (its latent rows come out of the arena as one
    channel a run) and the tokens are those of a miss; nothing is declined:
    no layer here keeps a ring that could have wrapped."""
    _params, p32, _cfg, cfg32 = net
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(1, 251, 32)]
    a = shared + [int(t) for t in rng.integers(1, 251, 4)]
    b = shared + [int(t) for t in rng.integers(1, 251, 6)]
    miss, _stats, _chat_ = _serve(p32, cfg32, [b], prefix_cache=False)
    chat = _chat(p32, cfg32, prefix_block=16)
    try:
        srv = chat._server
        assert D._kv_channels(srv.pool) == [("cl0", "arena_cl0"),
                                            ("cl1", "arena_cl1")]
        assert srv.pool["arena_cl1"].shape[1:] == (2, 1, 16, WIDTH)
        for p in (a, b):
            r = srv.submit(p, 8)
            assert r.done.wait(timeout=300)
            got = list(r.tokens)
        assert srv.stats["prefix_hit_requests"] == 1
        assert srv.stats["prefix_hit_tokens"] == 32
        assert srv.stats["prefix_declined"] == 0
        assert got == miss[0]
    finally:
        chat.close()


@pytest.mark.parametrize("mechanism,kwargs", [
    ("paged_kv", {"paged_kv": True}),
    ("paged_kv", {"paged_kv": True, "paged_kernel": True}),
    ("flash_prefill", {"flash_prefill": True}),
    ("kv_quant", {"kv_quant": "int8"}),
    ("weight_quant", {"weight_quant": "int8"}),
    ("weight_quant", {"weight_quant": "int8", "wq_kernel": True}),
    ("disagg", {"disagg": True}),
    ("mesh", {"mesh": "2 devices"}),
])
def test_what_latent_attention_cannot_ride_refuses_by_type(
        net, mechanism, kwargs):
    """Each default-off mechanism written for GPT-2's block alone raises a
    typed error at construction that names it and says why here: no silent
    fallback to another path."""
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    _params, p32, _cfg, cfg32 = net
    if "mesh" in kwargs:
        from jax.sharding import Mesh

        kwargs = {"mesh": Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2),
                               ("data", "fsdp", "tp"))}
    with pytest.raises(D.UnsupportedForLayout) as err:
        TPUDecoderChat(params=p32, cfg=cfg32, tokenizer=WordIds(),
                       max_new_tokens=8, max_prompt_tokens=64,
                       continuous=True, n_slots=2, **kwargs)
    assert err.value.mechanism == mechanism
    assert isinstance(err.value, TypeError) and mechanism in str(err.value)
    assert "latent attention" in str(err.value)
    # the block's own test says the same, and why
    assert not D.gpt2_block(cfg32)
    with pytest.raises(D.UnsupportedForLayout, match="latent attention"):
        D.require_gpt2_block(cfg32, mechanism)


def _margin_of(logits):
    """The reference's margin for ONE token whose router logits are given
    (32 experts in 8 groups of 4, 3 groups and 6 experts a token, experts
    0-3 held: group 0)."""
    ref = _reference()
    d = ref._dims(MODEL)
    h = len(logits)
    lp = {"router_w": jnp.eye(h, dtype=jnp.float32),
          "moe_in_w": jnp.zeros((4, h, 8)), "moe_up_w": jnp.zeros((4, h, 8)),
          "moe_out_w": jnp.zeros((4, 8, h)),
          "shared_in_w": jnp.zeros((h, 16)), "shared_up_w": jnp.zeros((h, 16)),
          "shared_out_w": jnp.zeros((16, h))}
    _y, margin = ref._experts(jnp.asarray([logits], jnp.float32), lp, d, "")
    return float(margin[0])


@pytest.mark.parametrize("case,want", [
    # groups by best score: 5 > 0 (held) > 6 > 7; held experts 0, 1 picked,
    # 1.4 over the seventh; the held group 1.0 over the fourth; the third
    # and fourth 0.5 apart, but their swap leaves the held picks as they are
    ("held group second, far from the fourth", 1.0),
    # the held group second, the third and fourth within 0.03 of it: a
    # lower precision may rank it fourth, and every held pick goes
    ("held group second, the fourth just under it", 0.03),
    # the held group fifth: the third and fourth may swap, nothing held moves
    ("held group out, two others swap", 1.0),
    # a held expert the sixth pick, the seventh 0.02 under it
    ("held expert sixth, the seventh just under it", 0.02),
], ids=lambda c: c if isinstance(c, str) else "")
def test_the_margin_is_the_held_experts_and_groups_distance(case, want):
    """The margin that decides which positions ``answer_moe`` judges, on
    router logits written out: in LOGITS (a ratio of scores), the least
    distance a held expert or a held group would have to move to change
    what this share computes."""
    lg = np.full(32, -9.0, np.float32)
    if case == "held group second, far from the fourth":
        lg[20], lg[21], lg[22] = 3.0, 2.9, 2.8      # group 5
        lg[0], lg[1] = 2.5, 2.4                     # group 0: held
        lg[24], lg[25] = 2.0, 1.0                   # group 6
        lg[28] = 1.5                                # group 7: fourth
    elif case == "held group second, the fourth just under it":
        lg[20], lg[21], lg[22] = 3.0, 2.9, 2.8
        lg[0], lg[1] = 2.5, 2.4
        lg[24], lg[25] = 2.49, 1.0
        lg[28] = 2.47
    elif case == "held group out, two others swap":
        lg[20], lg[21], lg[22] = 3.0, 2.9, 2.8
        lg[24], lg[25], lg[26] = 2.7, 2.6, 2.5
        lg[28] = 2.0                                # third
        lg[12] = 1.99                               # fourth: a swap
        lg[0] = 1.0                                 # held group: fifth
    else:
        lg[20], lg[21], lg[22] = 3.0, 2.9, 2.8
        lg[24], lg[25] = 2.7, 2.6
        lg[0] = 2.5                                 # sixth pick, held
        lg[26] = 2.48                               # seventh
    assert _margin_of(lg) == pytest.approx(want, abs=1e-5)


def test_the_router_limits_a_token_to_its_best_groups():
    """``route`` against the steps written out in numpy: softmax over all
    32, a group's score its best expert's, the best 3 of 8 groups, the top
    6 of what is left, the scores themselves times 16."""
    from pathway_tpu.models.moe import MoEConfig, route

    moe = MoEConfig(experts=32, per_token=6, width=8, score="softmax",
                    groups=8, groups_per_token=3,
                    route_norm=False, route_scale=16.0)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    wr = rng.normal(size=(16, 32)).astype(np.float32)
    idx, w, s = route(jnp.asarray(x), {"router_w": jnp.asarray(wr)}, moe)
    z = (x.astype(np.float64) @ wr.astype(np.float64))
    p = np.exp(z - z.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    assert np.allclose(np.asarray(s), p, atol=1e-6)
    groups = p.reshape(64, 8, 4).max(-1)
    keep = np.argsort(-groups, axis=1)[:, :3]
    for t in range(64):
        left = np.where(np.isin(np.arange(32) // 4, keep[t]), p[t], 0.0)
        want = np.argsort(-left)[:6]
        assert set(np.asarray(idx[t]).tolist()) == set(want.tolist())
        assert set((np.asarray(idx[t]) // 4).tolist()) <= set(keep[t].tolist())
        assert np.allclose(np.sort(np.asarray(w[t])),
                           np.sort(16.0 * p[t][want]), rtol=1e-5)
    # with every group allowed the step changes nothing
    free = dataclasses.replace(moe, groups_per_token=8)
    plain = dataclasses.replace(moe, groups=1, groups_per_token=1)
    a, _w, _s = route(jnp.asarray(x), {"router_w": jnp.asarray(wr)}, free)
    b, _w, _s = route(jnp.asarray(x), {"router_w": jnp.asarray(wr)}, plain)
    assert np.array_equal(np.sort(a, 1), np.sort(b, 1))
    assert not np.array_equal(np.sort(np.asarray(idx), 1), np.sort(b, 1))
