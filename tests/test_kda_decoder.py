"""Gated delta-rule (KDA) layers beside NoPE latent attention, the recurrent
state in the slot pool, and Kimi-Linear's experts against the plain
reference of the ``kimi_linear`` layout (``benchmarks/layouts/kimi_linear.py``
— the ONE copy of the reference: the tests import the layout the benchmark
runs; its KDA runs token by token by the recurrence as published, and it
keeps no cache).

Small widths with the published pattern (8 kept layers: KDA with the dense
MLP, then KDA, KDA, MLA, KDA, KDA, KDA, MLA with experts; 2 KDA heads of 16,
4 MLA heads of 16 | 8 | 16, 32 experts of which 4 are held, 4 a token),
seeded random weights as the benchmark makes them, CPU. What is compared is
LOGITS, never sampled tokens. Tolerances, and why:

* ``F32_TOL`` = 2e-5: program and reference both in float32 on the same
  weights differ by the order of summation alone (read 8e-7 on logits of
  size 0.6: the chunked form sums a chunk's corrections through one
  triangular solve, the step reads the old state once for two products,
  the reference applies the recurrence as written). The state kept in
  bfloat16 reads 1e-3 and more, the fp8 control 1e-1 and more, a rotation
  on the latent layers 1e-2 and more: each fails it, as the tests below
  show.
* ``STATE_TOL`` = 1e-5 of the state's own size: the chunked form against
  the step over the same tokens (read 5e-6 for chunks of 64, whose decays
  reach e^-250).
* a router near-tie does not decide a test: the reference reports every
  position's smallest routing margin over the expert layers, and a position
  under ``TIE_EPS`` = 1e-5 is skipped (counted: at most a tenth).
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
from pathway_tpu.models.moe import MoEConfig, init_moe_params, moe_mlp  # noqa: E402,E501

F32_TOL = 2e-5
STATE_TOL = 1e-5
TIE_EPS = 1e-5

MODEL = {
    "layout": "kimi_linear", "model_type": "kimi_linear",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4, 8],
                           "kda_layers": [1, 2, 3, 5, 6, 7], "head_dim": 16,
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "intermediate_size": 128, "moe_intermediate_size": 24,
    "num_experts": 4, "num_experts_published": 32, "experts_held_first": 0,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "num_expert_group": 1, "moe_router_activation_func": "sigmoid",
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "vocab_size": 251, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "tie_word_embeddings": False, "model_max_length": 4096,
    "torch_dtype": "bfloat16", "num_hidden_layers": 8,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "layers_kept": list(range(8)),
}
PROMPT = 40
WIDTH = 32 + 8          # what a latent layer caches a token
KINDS = (("kda", "none", "dense"), ("kda", "none", "moe"),
         ("latent", "none", "moe"))


def _reference():
    return M.load_named_module(M.load_manifest(), "layouts", "kimi_linear")


@pytest.fixture(scope="module")
def layout():
    return M.resolve(M.load_manifest(), "layouts", "kimi_linear")


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


def ref_rows(params, seq, first, precision="f32"):
    """Reference logits of positions first.. and which of them no router
    near-tie touches."""
    logits, margin = _reference().reference_forward(
        params, MODEL, seq, first, precision, margins=True)
    return logits, margin > TIE_EPS


def worst(got, want, sound):
    assert sound.sum() >= 0.9 * len(sound), "too many near-ties to judge"
    return float(np.abs(got[sound] - want[sound]).max())


def _forward(p, cfg, prompt):
    ids = np.asarray([prompt], np.int32)
    return np.asarray(jax.jit(lambda p, i, m: D.forward(p, i, m, cfg))(
        p, ids, np.ones_like(ids)))[0]


def test_the_configuration_says_two_kinds_of_layer_by_its_fields(net,
                                                                 layout):
    params, _p32, cfg, _c32 = net
    k, kd, lat, kdm = KINDS[0], KINDS[1], KINDS[2], KINDS[1]
    assert cfg.runs() == ((k, 0, 1), (kd, 1, 2), (lat, 3, 1), (kdm, 4, 3),
                          (lat, 7, 1))
    assert cfg.has_state and cfg.latent and not D.gpt2_block(cfg)
    assert (cfg.n_layers_of("kda"), cfg.n_layers_of("latent")) == (6, 2)
    assert (cfg.kda_heads, cfg.kda_head, cfg.kda_conv) == (2, 16, 4)
    assert (cfg.moe.score, cfg.moe.bias, cfg.moe.route_norm,
            cfg.moe.route_scale, cfg.moe.held) == (
                "sigmoid", True, True, 2.446, (0, 4))
    # a token costs the latent layers' rows alone; a slot, its states
    assert D.kv_token_bytes(cfg, 2) == 2 * WIDTH * 2 \
        == layout.kv_bytes_per_token(MODEL)
    assert D.state_slot_bytes(cfg, 2) == 6 * (2 * 16 * 16 * 4 + 3 * 96 * 2) \
        == layout.state_bytes_per_slot(MODEL)
    # the program's own initialiser makes the benchmark's tree
    own = D.init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(
        lambda a: a.shape, params)
    assert jax.tree.structure(D.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, own))
    run = D.cast_params_for_inference(own, cfg)["layers"]["run0"]
    for leaf in ("kda_a_log", "kda_dt_bias", "kda_o_norm_scale"):
        assert run[leaf].dtype == jnp.float32
    assert run["kda_qkv_w"].dtype == jnp.bfloat16
    assert D.count_params(own) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    # the published Kimi-Linear stage the benchmark runs: 2,093M
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-ep8-rag.json")) as f:
        big = __import__("json").load(f)["models"]["decoder"]
    assert layout.matmul_param_count(big) == pytest.approx(2.093e9, rel=1e-3)
    assert layout.state_bytes_per_slot(big) == 6 * (32 * 128 * 128 * 4
                                                    + 3 * 3 * 4096 * 2)


def _steps(q, k, v, g, beta, S):
    out = []
    for t in range(q.shape[1]):
        S, o = D._kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        out.append(o)
    return S, jnp.stack(out, axis=2)


@pytest.mark.parametrize("chunk,cut", [(64, 37), (16, 37), (6, 20), (1, 9)],
                         ids=lambda v: str(v))
def test_the_chunked_form_is_the_recurrence_across_pieces(chunk, cut):
    """The chunked form over two pieces (the state the first leaves carried
    into the second; a piece that ends inside a chunk) against the step,
    token by token, from the same state, with decays from e^-0 to e^-27 a
    token: outputs and the state left agree to the state's rounding."""
    r = np.random.default_rng(chunk)
    B, T, H, d = 2, 53, 3, 8
    q = D._l2(jnp.asarray(r.normal(size=(B, T, H, d)), jnp.float32))
    k = D._l2(jnp.asarray(r.normal(size=(B, T, H, d)), jnp.float32))
    v = jnp.asarray(r.normal(size=(B, T, H, d)), jnp.float32)
    g = -jnp.asarray(r.uniform(0, 3, size=(B, T, H, d)) ** 3, jnp.float32)
    beta = jnp.asarray(r.uniform(size=(B, T, H)), jnp.float32)
    S0 = jnp.asarray(r.normal(size=(B, H, d, d)), jnp.float32)
    want_S, want_o = _steps(q, k, v, g, beta, S0)
    S1, o1 = D._kda_chunked(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut],
                            beta[:, :cut], S0, chunk)
    S2, o2 = D._kda_chunked(q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:],
                            beta[:, cut:], S1, chunk)
    scale = float(jnp.abs(want_S).max())
    assert float(jnp.abs(S2 - want_S).max()) < STATE_TOL * scale
    got = jnp.concatenate([o1, o2], axis=2)
    assert float(jnp.abs(got - want_o).max()) < STATE_TOL * scale


def test_forward_logits_match_the_reference(net, prompt):
    params, p32, cfg, cfg32 = net
    want, sound = ref_rows(params, prompt, 0)
    assert worst(_forward(p32, cfg32, prompt), want, sound) < F32_TOL
    # left padding feeds the convolutions zeros and leaves the state alone
    padded = np.zeros((1, PROMPT + 8), np.int32)
    padded[0, 8:] = prompt
    pm = (np.arange(PROMPT + 8) >= 8).astype(np.int32)[None]
    got = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg32))(p32, padded, pm))[0, 8:]
    assert worst(got, want, sound) < F32_TOL
    # the tolerance is tight enough that bfloat16 in float32's place fails,
    # and so does the reference's own fp8 control
    assert worst(_forward(params, cfg, prompt), want, sound) > 100 * F32_TOL
    low, _ = ref_rows(params, prompt, 0, "fp8")
    assert worst(low, want, sound) > 1000 * F32_TOL


@pytest.mark.parametrize("dropped", ["rotary", "conv", "decay", "o_norm"],
                         ids=str)
def test_a_dropped_term_fails_the_tolerance(net, prompt, dropped,
                                            monkeypatch):
    """The latent layers apply NO rotation (a rotary key and query there
    move the logits far past the tolerance), and the short convolution's
    earlier taps, the decay and the output norm each count."""
    params, p32, _cfg, cfg32 = net
    broken = cfg32
    if dropped == "rotary":
        broken = dataclasses.replace(cfg32, positions="rotary")
    elif dropped == "conv":
        p32 = {**p32, "layers": {
            r: {**run, "kda_conv_w": run["kda_conv_w"].at[:, :3].set(0.0)}
            if "kda_conv_w" in run else run
            for r, run in p32["layers"].items()}}
    elif dropped == "decay":
        monkeypatch.setattr(D, "_kda_gates", lambda *a: (
            lambda g, b, og: (jnp.zeros_like(g), b, og))(
                *_gates(*a)))
    else:
        real = D._rms
        monkeypatch.setattr(D, "_rms", lambda x, scale, eps: (
            x.astype(jnp.float32) * scale if scale.shape[-1] == 16
            else real(x, scale, eps)))
    want, sound = ref_rows(params, prompt, 0)
    assert worst(_forward(p32, broken, prompt), want, sound) > 50 * F32_TOL


_gates = D._kda_gates


def _prefill(p32, cfg32, pool, slot, prompt, P=8, bucket=48):
    """Left-padded chunked prefill of ``prompt`` into ``slot``, pieces of
    ``P``, the first with a real token opening the slot (``first``)."""
    ids = np.zeros((1, bucket), np.int32)
    mask = np.zeros((1, bucket), np.int32)
    ids[0, bucket - len(prompt):], mask[0, bucket - len(prompt):] = prompt, 1
    pos = np.clip(np.cumsum(mask[0]) - 1, 0, None)[None].astype(np.int32)
    n_prompt = np.asarray([len(prompt)], np.int32)
    offs = [o for o in range(0, bucket, P) if mask[0, o:o + P].any()]
    for j, o in enumerate(offs):
        pool = jax.jit(lambda p, i, m, ps, pl, first=j == 0,
                       last=j == len(offs) - 1, o=o: D.pool_prefill_chunk(
                           p, i, m, ps, pl, np.int32(slot), np.int32(o),
                           n_prompt, cfg32, first=first, last=last))(
            p32, ids[:, o:o + P], mask[:, o:o + P], pos[:, o:o + P], pool)
    return pool


def _decode(p32, cfg32, pool, params, seq, lanes, slot, n, check):
    step = jax.jit(lambda p, pl: D.pool_decode_chunk(
        p, pl, lanes, jax.random.PRNGKey(0), cfg32, 1))
    judged = 0
    for _ in range(n):
        pool, toks = step(p32, pool)
        seq.append(int(toks[0, slot]))
        judged += check(pool, seq)
    return pool, judged


@pytest.mark.parametrize("chunk", [64, 6], ids=["chunk64", "chunk6"])
def test_pool_prefill_in_pieces_then_decode_matches_the_full_forward(
        net, prompt, chunk, monkeypatch):
    """Chunked prefill (pieces of 8; with chunks of 6 every piece ends
    inside a chunk) then decode (the recurrence, one step a dispatch)
    through the slot pool against the reference's full forward. The pool
    holds a state a KDA run and a latent array a MLA run; a slot never
    written keeps zeros."""
    params, p32, _cfg, cfg32 = net
    monkeypatch.setattr(D, "_KDA_CHUNK", chunk)
    pool = D.pool_init(p32, cfg32, 2, 96)
    shapes = {n: a.shape for n, a in D._kv_stacks(pool).items()}
    assert shapes == {
        "sk0": (1, 2, 2, 16, 16), "sc0": (1, 2, 1, 3, 96),
        "sk1": (2, 2, 2, 16, 16), "sc1": (2, 2, 1, 3, 96),
        "cl2": (1, 2, 1, 96, WIDTH),
        "sk3": (3, 2, 2, 16, 16), "sc3": (3, 2, 1, 3, 96),
        "cl4": (1, 2, 1, 96, WIDTH)}
    assert pool["sk1"].dtype == jnp.float32
    comp = D.pool_component_bytes(pool)
    assert comp == {"slot_pool_latent": 2 * 2 * 96 * WIDTH * 4,
                    "slot_state": 2 * D.state_slot_bytes(cfg32, 4)}
    pool = _prefill(p32, cfg32, pool, 1, prompt)
    seq = list(prompt)

    def check(pool, seq):
        want, sound = ref_rows(params, seq, len(seq) - 1)
        if sound[0]:
            assert np.abs(np.asarray(pool["logits"][1]) - want[0]).max() \
                < F32_TOL, len(seq)
        return int(sound[0])

    judged = check(pool, seq)
    pool, more = _decode(p32, cfg32, pool, params, seq,
                         np.asarray([False, True]), 1, 10, check)
    assert judged + more >= 9
    assert not np.asarray(pool["sk3"][:, 0]).any()
    assert not np.asarray(pool["cl4"][:, 0]).any()


def test_a_state_kept_in_bfloat16_fails_the_tolerance(net, prompt,
                                                      monkeypatch):
    """The same decode with the state rounded to bfloat16 after every step
    and piece: the logits leave the tolerance."""
    params, p32, _cfg, cfg32 = net

    def rounded(fn):
        def wrapped(*a, **kw):
            S, o = fn(*a, **kw)
            return S.astype(jnp.bfloat16).astype(jnp.float32), o
        return wrapped

    monkeypatch.setattr(D, "_kda_step", rounded(D._kda_step))
    monkeypatch.setattr(D, "_kda_chunked", rounded(D._kda_chunked))
    pool = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt)
    seq, gaps = list(prompt), []

    def check(pool, seq):
        want, sound = ref_rows(params, seq, len(seq) - 1)
        if sound[0]:
            gaps.append(float(np.abs(np.asarray(pool["logits"][1])
                                     - want[0]).max()))
        return 0

    check(pool, seq)
    _decode(p32, cfg32, pool, params, seq, np.asarray([False, True]), 1, 10,
            check)
    assert max(gaps) > 10 * F32_TOL


def test_grouped_lanes_carry_their_own_states(net, prompt):
    """Two prompts of different lengths whose pieces go as ONE grouped
    dispatch (lane 0 on its second piece, lane 1 on its first): each lane
    gathers and writes back its own slot's state and tail, and both slots
    end as their pieces alone leave them, with the reference's logits."""
    params, p32, _cfg, cfg32 = net
    other = [int(t) for t in np.random.default_rng(3).integers(1, 251, 13)]
    alone = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 3, 96), 2, prompt,
                     P=16)
    alone = _prefill(p32, cfg32, alone, 0, other, P=16)
    # grouped: prompt in slot 2 (pieces at 16, 32 of a 48 bucket), other in
    # slot 0 (one piece at 32); the group pairs prompt's LAST with other's
    pool = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 3, 96), 2,
                    prompt[:-16], P=16, bucket=32)
    bucket = 48
    ids = np.zeros((2, 16), np.int32)
    mask = np.zeros((2, 16), np.int32)
    pos = np.zeros((2, 16), np.int32)
    ids[0], mask[0] = prompt[-16:], 1
    pos[0] = np.arange(PROMPT - 16, PROMPT)
    ids[1, 16 - 13:], mask[1, 16 - 13:] = other, 1
    pos[1] = np.clip(np.cumsum(mask[1]) - 1, 0, None)
    pool = jax.jit(lambda p, i, m, ps, pl: D.pool_prefill_chunk(
        p, i, m, ps, pl, np.asarray([2, 0], np.int32),
        np.asarray([32, 32], np.int32), np.asarray([PROMPT, 13], np.int32),
        cfg32, first=np.asarray([False, True]), last=np.asarray([True, True]),
        last_col=np.asarray([15, 15], np.int32)))(p32, ids, mask, pos, pool)
    del bucket
    for name in ("sk1", "sc1", "sk3"):
        for s in (0, 2):
            a, b = np.asarray(pool[name][:, s]), np.asarray(alone[name][:, s])
            assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1e-6), \
                (name, s)
    for s, p in ((2, prompt), (0, other)):
        want, sound = ref_rows(params, p, len(p) - 1)
        assert sound[0]
        assert np.abs(np.asarray(pool["logits"][s]) - want[0]).max() \
            < F32_TOL


def test_one_shot_admission_rides_the_state(net, prompt):
    """``pool_admit`` and ``pool_admit_batch`` (one whole-prompt prefill,
    left padding feeding nothing) leave the state and logits the pieces
    leave; ``generate``, which slices no state, refuses by type."""
    _params, p32, _cfg, cfg32 = net
    ids = np.zeros((1, 48), np.int32)
    mask = np.zeros((1, 48), np.int32)
    ids[0, 8:], mask[0, 8:] = prompt, 1
    once = jax.jit(lambda p, i, m, pl: D.pool_admit(
        p, i, m, pl, np.int32(1), cfg32))(
            p32, ids, mask, D.pool_init(p32, cfg32, 2, 96))
    two = jax.jit(lambda p, i, m, pl: D.pool_admit_batch(
        p, i, m, pl, np.asarray([1, 0], np.int32), cfg32))(
            p32, np.repeat(ids, 2, 0), np.repeat(mask, 2, 0),
            D.pool_init(p32, cfg32, 2, 96))
    pieces = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt)
    for pool in (once, two):
        assert np.abs(np.asarray(pool["logits"][1])
                      - np.asarray(pieces["logits"][1])).max() < F32_TOL
        for name in ("sk3", "sc3"):
            b = np.asarray(pieces[name][:, 1])
            assert np.abs(np.asarray(pool[name][:, 1]) - b).max() \
                < 1e-5 * np.abs(b).max()
    with pytest.raises(D.UnsupportedForLayout, match="recurrent state"):
        D.generate(p32, ids, mask, cfg32, 4)


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


def test_the_server_serves_the_state_with_its_defaults(net, prompt):
    """Through ``TPUDecoderChat`` -> ``_ContinuousServer`` with its
    defaults: chunked prefill, grouped pieces, one-shot admission and eager
    refill ride the state; self-speculative decoding, the prefix cache and
    batched admission resolve to off. Three requests on two slots (a slot is re-admitted and
    its state zeroed): the greedy tokens are the reference's, and each
    admission counts one ``recurrent_state_resets{cause=admit}``."""
    from pathway_tpu.engine import probes

    params, p32, _cfg, cfg32 = net
    rng = np.random.default_rng(4)
    prompts = [prompt, [int(t) for t in rng.integers(1, 251, 23)],
               [int(t) for t in rng.integers(1, 251, 30)]]
    before = probes.REGISTRY.labelled("recurrent_state_resets", "cause")
    chat = _chat(p32, cfg32)
    try:
        srv = chat._server
        assert not srv.spec_decode and srv.prefix is None
        assert not srv.batch_admit
        assert srv.prefill_group > 1
        assert srv._state_mib == round(
            2 * D.state_slot_bytes(cfg32, 4) / (1 << 20), 3)
        reqs = [srv.submit(list(p), 8) for p in prompts]
        for r in reqs:
            assert r.done.wait(timeout=300)
        streams = [list(r.tokens) for r in reqs]
        assert srv.stats["prefill_chunks"] >= 4
    finally:
        chat.close()
    for p, got in zip(prompts, streams):
        want, sure = _greedy_by_reference(params, p, 8)
        assert sure, "choose another seed: the reference's own choice is a tie"
        assert got == want
    after = probes.REGISTRY.labelled("recurrent_state_resets", "cause")
    assert after.get("admit", 0) - before.get("admit", 0) == 3


@pytest.mark.parametrize("mechanism,kwargs", [
    ("paged_kv", {"paged_kv": True}),
    ("flash_prefill", {"flash_prefill": True}),
    ("kv_quant", {"kv_quant": "int8"}),
    ("weight_quant", {"weight_quant": "int8"}),
    ("disagg", {"disagg": True}),
    ("mesh", {"mesh": "2 devices"}),
    ("spec_decode", {"spec_decode": True}),
    ("prefix_cache", {"prefix_cache": True}),
])
def test_what_a_recurrent_state_cannot_ride_refuses_by_type(
        net, mechanism, kwargs):
    """Each mechanism that a recurrent state cannot ride raises a typed
    error at construction that names it and says why: the paged pool, the
    kernels and the mesh are GPT-2's block's; the prefix cache and a
    speculative cycle move or roll back a cache by token."""
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
    assert "recurrent state" in str(err.value)


@pytest.mark.parametrize("fn", ["kv_extract", "kv_insert", "spec", "draft"])
def test_the_pool_functions_that_move_tokens_refuse_by_type(net, fn):
    _params, p32, _cfg, cfg32 = net
    pool = D.pool_init(p32, cfg32, 2, 96)
    lanes = np.asarray([True, False])
    call = {
        "kv_extract": lambda: D.kv_extract(pool, 0, 0, np.zeros(1, np.int32),
                                           cfg32),
        "kv_insert": lambda: D.kv_insert(pool, 0, 0, np.zeros(1, np.int32),
                                         cfg32),
        "spec": lambda: D.pool_decode_spec(p32, pool, lanes, cfg32, 1,
                                           draft_layers=1, n_spec=2),
        "draft": lambda: D.pool_decode_draft(p32, pool, lanes, cfg32,
                                             draft_layers=1, n_draft=2),
    }[fn]
    with pytest.raises(D.UnsupportedForLayout, match="recurrent state"):
        call()


def test_the_eight_shares_sum_to_the_uncut_layer():
    """The expert-parallel share test for Kimi-Linear's router (sigmoid
    scores with a balance bias on the choice, 8 picks of 32, renormalised and
    scaled by 2.446, one group): 8 chips hold 4 experts each; the parts of
    all 8, the shared expert counted ONCE, add up to the uncut layer, which
    is the plain reference's."""
    whole = MoEConfig(experts=32, per_token=8, width=24, shared=1,
                      route_norm=True, route_scale=2.446)
    mp = init_moe_params(jax.random.PRNGKey(8), 64, whole, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 64))
    uncut, counts = moe_mlp(x, mp, whole, jnp.float32)
    assert counts.tolist() == [256, 256]
    d = {"k": 8, "first": 0, "held": 32, "norm": True, "scale": 2.446,
         "shared": 1}
    with jax.default_matmul_precision("highest"):
        want, margin = _reference()._experts(x.reshape(32, -1), mp, d, False)
    assert float(margin.min()) > 1e-6       # no near-tie decides this
    assert float(jnp.max(jnp.abs(uncut.reshape(32, -1) - want))) < 2e-5
    with jax.default_matmul_precision("highest"):
        shared_only = (jax.nn.silu(x @ mp["shared_in_w"])
                       * (x @ mp["shared_up_w"])) @ mp["shared_out_w"]
    total, held_sum = jnp.zeros_like(uncut), 0
    for chip in range(8):
        share = dataclasses.replace(whole, held=(4 * chip, 4))
        mine = {**mp, **{k: mp[k][4 * chip:4 * chip + 4]
                         for k in ("moe_in_w", "moe_up_w", "moe_out_w")}}
        part, c = moe_mlp(x, mine, share, jnp.float32)
        total = total + (part - shared_only)
        held_sum += int(c[0])
    assert held_sum == 256
    assert float(jnp.max(jnp.abs(total + shared_only - uncut))) < 2e-5


def test_the_executables_name_the_kda_scopes(net):
    """A piece runs the chunked form under ``kda.chunk``, a decode chunk the
    step under ``kda.step``, both beside ``kda.conv``, ``kda.gate`` and
    ``kda.out``; the latent layers read absorbed in the chunk."""
    _params, p32, _cfg, cfg32 = net
    pool = D.pool_init(p32, cfg32, 2, 96)
    one = np.int32(0)
    ids = np.zeros((1, 16), np.int32)
    piece = jax.jit(lambda p, i, pl, s: D.pool_prefill_chunk(
        p, i, i, i, pl, s, s, s[None], cfg32, first=True, last=True)).lower(
            p32, ids, pool, one).as_text(debug_info=True)
    chunk = jax.jit(lambda p, pl, a, k: D.pool_decode_chunk(
        p, pl, a, k, cfg32, 4)).lower(
            p32, pool, np.ones(2, bool), jax.random.PRNGKey(0)).as_text(
                debug_info=True)
    for scope in ("kda.conv", "kda.gate", "kda.out"):
        assert scope in piece and scope in chunk
    assert "kda.chunk" in piece and "kda.step" not in piece
    assert "kda.step" in chunk and "kda.chunk" not in chunk
    assert "mla.absorb" in chunk
