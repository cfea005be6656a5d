"""A looped layer stack (the layers run ``loops`` times over shared weights,
a key-value cache of its own for every pass) against the plain reference of
the ``ouro`` layout (``benchmarks/layouts/ouro.py`` — the ONE copy of the
reference: the tests import the layout the benchmark runs; it keeps no
cache, so "pass u attends pass u of the earlier tokens" is simply that each
pass is one causal forward over the whole sequence).

Small widths (4 heads of 16, 3 layers, 4 passes: 12 cache layers), seeded
random weights, float32, CPU. What is compared is LOGITS, never sampled
tokens. Tolerances, and why:

* ``F32_TOL`` = 2e-5: program and reference both in float32 on the same
  weights differ by the order of summation alone (read 4e-7 to 7e-7 on
  logits of size 0.6). A bfloat16 computation reads 1e-2, and a dropped
  term 1e-3 and more: both fail it, which the tests below show for
  bfloat16, a pass left out, the norm between passes, either post-norm, the
  passes sharing one cache, the last pass reading an averaged cache and the
  gate's bias.
* the exit distribution to 1e-6: sigmoids and products of at most four
  factors of logits that agree to 1e-6.
* the exit STEP is a comparison against a threshold: a position whose
  cumulative probability lies within ``TIE_EPS`` = 1e-5 of the threshold
  is left out of the comparison of logits (none is, at the seeds here).
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
LAYERS, LOOPS, VOCAB = 3, 4, 251

MODEL = {
    "layout": "ouro", "model_type": "ouro", "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "layer_types": ["full_attention"] * LAYERS,
    "num_hidden_layers": LAYERS, "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": LOOPS, "early_exit_threshold": 1,
    "max_position_embeddings": 4096, "torch_dtype": "bfloat16",
}
PROMPT = 40


def _reference():
    return M.load_named_module(M.load_manifest(), "layouts", "ouro")


@pytest.fixture(scope="module")
def layout():
    return M.resolve(M.load_manifest(), "layouts", "ouro")


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
    return np.random.default_rng(0).integers(1, VOCAB, PROMPT).astype(
        np.int32).tolist()


def ref_rows(params, seq, first, model=MODEL):
    return _reference().reference_forward(params, model, seq, first)


def gap(got, want):
    return float(np.abs(np.asarray(got) - want).max())


def _forward(p, cfg, prompt):
    ids = np.asarray([prompt], np.int32)
    return np.asarray(jax.jit(lambda p, i, m: D.forward(p, i, m, cfg))(
        p, ids, np.ones_like(ids)))[0]


def test_the_configuration_says_a_looped_stack_by_its_fields(net, layout):
    params, _p32, cfg, cfg32 = net
    assert (cfg.loops, cfg.exit_gate, cfg.exit_threshold) == (LOOPS, True, 1.0)
    assert cfg.layers == LAYERS and cfg.uniform and cfg.runs() == (
        (("full", "rotary", "dense"), 0, LAYERS),)
    assert cfg.sandwich_norm and cfg.norm == "rmsnorm" and not cfg.bias \
        and not cfg.tied_head and cfg.mlp == "swiglu" and not cfg.qk_norm \
        and not cfg.attn_gate and cfg.moe is None
    assert not D.gpt2_block(cfg) and D.gpt2_block(D.GPT2_MEDIUM)
    # a stack run once is what it always was, and has no gate
    assert D.GPT2_SMALL.loops == 1 and not D.GPT2_SMALL.exit_gate
    assert "exit_w" not in D.init_params(
        jax.random.PRNGKey(0), dataclasses.replace(cfg, loops=1))
    # the program's own initialiser and specs carry the gate's two leaves
    own = D.init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(
        lambda a: a.shape, params)
    assert own["exit_w"].shape == (64, 1) and own["exit_b"].shape == (1,)
    assert jax.tree.structure(D.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, own))
    cast = D.cast_params_for_inference(own, cfg)
    assert cast["exit_w"].dtype == jnp.float32      # the gate, as a router
    assert cast["layers"]["qkv_w"].dtype == jnp.bfloat16
    assert D.count_params(own) == layout.param_count(MODEL)
    # the counts: a cache layer for every pass of every layer
    assert D.kv_token_bytes(cfg, 2) == LOOPS * LAYERS * 2 * 4 * 16 * 2 \
        == layout.kv_bytes_per_token(MODEL)
    assert D.kv_token_bytes(dataclasses.replace(cfg, loops=1), 2) \
        == LAYERS * 2 * 4 * 16 * 2


def test_forward_logits_match_the_reference(net, prompt):
    params, p32, cfg, cfg32 = net
    want = ref_rows(params, prompt, 0)
    assert gap(_forward(p32, cfg32, prompt), want) < F32_TOL
    # left-padded rows see the same positions as their unpadded equivalents
    padded = np.zeros((1, PROMPT + 8), np.int32)
    padded[0, 8:] = prompt
    pm = (np.arange(PROMPT + 8) >= 8).astype(np.int32)[None]
    got = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg32))(p32, padded, pm))[0, 8:]
    assert gap(got, want) < F32_TOL
    # the tolerance is tight enough that bfloat16 in float32's place fails,
    # in the program and in the reference's own bfloat16 forward alike
    assert gap(_forward(params, cfg, prompt), want) > 100 * F32_TOL
    assert gap(_reference().reference_forward(
        params, MODEL, prompt, 0, "bf16"), want) > 100 * F32_TOL


@pytest.mark.parametrize("dropped", [
    "a pass", "the norm between passes", "ln1p", "ln2p", "the gate's bias",
])
def test_a_dropped_term_fails_the_tolerance(net, prompt, dropped,
                                            monkeypatch):
    """A pass left out, the final norm applied once at the end and not
    between passes, either of the two norms that follow attention and the
    MLP, and (under a threshold that lets tokens leave early) the gate's
    bias: leaving any one out moves the logits by far more than the
    tolerance."""
    params, p32, _cfg, cfg32 = net
    model, broken = MODEL, cfg32
    if dropped == "a pass":
        broken = dataclasses.replace(cfg32, loops=LOOPS - 1)
    elif dropped == "the norm between passes":
        norm, logits = D._final_norm, D._logits
        monkeypatch.setattr(D, "_final_norm",
                            lambda p, x, c: x.astype(jnp.float32))
        monkeypatch.setattr(D, "_logits",
                            lambda p, x, c: logits(p, norm(p, x, c), c))
    elif dropped in ("ln1p", "ln2p"):
        real = D._norm
        monkeypatch.setattr(D, "_norm", lambda x, lp, name, c: (
            x.astype(jnp.float32) if name == dropped
            else real(x, lp, name, c)))
    else:
        model = dict(MODEL, early_exit_threshold=0.5)
        broken = dataclasses.replace(cfg32, exit_threshold=0.5)
        want = ref_rows(params, prompt, 0, model)
        assert gap(_forward(p32, broken, prompt), want) < F32_TOL
        p32 = {**p32, "exit_b": p32["exit_b"] * 0 - 1.0}
    want = ref_rows(params, prompt, 0, model)
    assert gap(_forward(p32, broken, prompt), want) > 50 * F32_TOL


@pytest.mark.parametrize("threshold", [0.5, 0.75, 0.88, 1.0, 1.5])
def test_the_exit_rule_is_the_references(net, prompt, threshold):
    """Under a threshold below 1 tokens leave at different passes: the
    states picked by step give the reference's logits, the exit
    distribution is the reference's to 1e-6 and sums to one. At 1 (as
    published) and above every token takes the last pass. Every pass still
    runs for every token: the gate picks among states, it skips nothing."""
    params, p32, _cfg, cfg32 = net
    model = dict(MODEL, early_exit_threshold=threshold)
    cfg_t = dataclasses.replace(cfg32, exit_threshold=threshold)
    want, p_ref, step_ref = _reference().reference_forward(
        params, model, prompt, 0, exits=True)
    ids = np.asarray([prompt], np.int32)
    step, p = jax.jit(lambda q, i, m: D.exit_profile(q, i, m, cfg_t))(
        p32, ids, np.ones_like(ids))
    step, p = np.asarray(step)[0], np.asarray(p)[:, 0]
    assert p.shape == (LOOPS, PROMPT) and np.abs(p - p_ref).max() < 1e-6
    assert np.abs(p.sum(axis=0) - 1).max() < 1e-6
    cum = np.cumsum(p_ref, axis=0)[:-1]
    sure = (np.abs(cum - threshold) > TIE_EPS).all(axis=0)
    assert sure.sum() >= 0.9 * PROMPT
    assert (step == step_ref)[sure].all()
    if threshold >= 1:
        assert (step == LOOPS - 1).all()
    else:
        assert len(set(step.tolist())) > 1, "choose a threshold that splits"
    got = _forward(p32, cfg_t, prompt)
    assert gap(got[sure], want[sure]) < F32_TOL
    # the rule by hand: two passes, logits 0 and anything: p = (1/2, 1/2)
    p2, s2 = D.loop_exit(jnp.zeros((2, 3)), 0.5)
    assert np.allclose(p2, 0.5) and (np.asarray(s2) == 0).all()
    _p, s3 = D.loop_exit(jnp.asarray([[40.0], [0.0], [0.0]]), 1.0)
    assert int(s3[0]) == 2      # 1 - sigmoid(40) is tiny, not nothing


def _pieces(prompt, bucket, piece):
    ids = np.zeros((1, bucket), np.int32)
    mask = np.zeros((1, bucket), np.int32)
    n = len(prompt)
    ids[0, bucket - n:], mask[0, bucket - n:] = prompt, 1
    pos = np.clip(np.cumsum(mask, 1) - 1, 0, None)
    return [(ids[:, o:o + piece], mask[:, o:o + piece],
             pos[:, o:o + piece].astype(np.int32), o)
            for o in range(0, bucket, piece)]


def _prefill(p32, cfg32, pool, slot, prompt, bucket=48, piece=16):
    n_prompt = np.asarray([len(prompt)], np.int32)
    first = True
    for ids, mask, pos, o in _pieces(prompt, bucket, piece):
        if not mask.any():
            continue            # wholly left padding: the server skips it
        last = o == bucket - piece
        pool = jax.jit(lambda p, i, m, ps, pl: D.pool_prefill_chunk(
            p, i, m, ps, pl, np.int32(slot), np.int32(o), n_prompt,
            cfg32, first=first, last=last))(p32, ids, mask, pos, pool)
        first = False
    return pool


def test_pool_prefill_in_pieces_then_decode_matches_the_full_forward(net):
    """Two slots at different lengths: prompts prefilled in pieces of 16
    (40 tokens cross two piece boundaries, 23 one), then decoded together
    through the pool, one step at a time and then a chunk of four, against
    the reference's full forward at every position. The pool holds ``loops
    x layers`` cache layers a run, and every pass writes its own."""
    params, p32, _cfg, cfg32 = net
    rng = np.random.default_rng(3)
    prompts = {0: [int(t) for t in rng.integers(1, VOCAB, 23)],
               1: [int(t) for t in rng.integers(1, VOCAB, 40)]}
    pool = D.pool_init(p32, cfg32, 2, 96, arena_blocks=3, arena_block=16)
    kv = {n: a.shape for n, a in pool.items() if D._is_kv(n)}
    assert kv == {"k": (LOOPS * LAYERS, 2, 4, 96, 16),
                  "v": (LOOPS * LAYERS, 2, 4, 96, 16)}
    assert pool["arena_k"].shape == (3, LOOPS * LAYERS, 4, 16, 16)
    comp = D.pool_component_bytes(pool)
    assert comp == {"slot_pool": 2 * LOOPS * LAYERS * 2 * 4 * 96 * 16 * 4,
                    "prefix_arena": 2 * 3 * LOOPS * LAYERS * 4 * 16 * 16 * 4}
    assert comp["slot_pool"] == 2 * 96 * D.kv_token_bytes(cfg32, 4)
    assert D.pool_bytes(pool) == sum(comp.values())
    for slot, prompt in prompts.items():
        pool = _prefill(p32, cfg32, pool, slot, prompt)
    # every pass of every layer wrote rows of its own
    k1 = np.asarray(pool["k"][:, 1, :, 8:48])
    assert all(np.abs(k1[u * LAYERS + l]).max() > 0
               for u in range(LOOPS) for l in range(LAYERS))
    assert np.abs(k1[:LAYERS] - k1[LAYERS:2 * LAYERS]).max() > 1e-3
    seqs = {s: list(p) for s, p in prompts.items()}

    def check():
        for slot, seq in seqs.items():
            want = ref_rows(params, seq, len(seq) - 1)
            assert gap(pool["logits"][slot], want[0]) < F32_TOL, \
                (slot, len(seq))

    check()
    lanes = np.asarray([True, True])
    step = jax.jit(lambda p, pl: D.pool_decode_chunk(
        p, pl, lanes, jax.random.PRNGKey(0), cfg32, 1))
    for _ in range(5):
        pool, toks = step(p32, pool)
        for slot in seqs:
            seqs[slot].append(int(toks[0, slot]))
        check()
    pool, toks = jax.jit(lambda p, pl: D.pool_decode_chunk(
        p, pl, lanes, jax.random.PRNGKey(0), cfg32, 4))(p32, pool)
    for slot in seqs:
        seqs[slot] += [int(t) for t in toks[:, slot]]
    check()
    # tokens sampled, by the pass their logits came from: all at the last
    assert np.asarray(pool["loop_exits"]).tolist() == [0, 0, 0, 2 * 9]


@pytest.mark.parametrize("fault", ["shared", "averaged"])
def test_a_cache_that_is_not_one_a_pass_fails_the_tolerance(
        net, prompt, fault):
    """The variants the paper discusses for decoding are ANOTHER result:
    with every pass reading the last pass's cache, or the last pass reading
    the passes' average, the next step's logits leave the reference's by
    far more than the tolerance (and with the cache as written they stay)."""
    params, p32, _cfg, cfg32 = net
    pool = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96), 1, prompt)
    step = jax.jit(lambda p, pl: D.pool_decode_chunk(
        p, pl, np.asarray([False, True]), jax.random.PRNGKey(0), cfg32, 1))
    sound, toks = step(p32, dict(pool))
    seq = list(prompt) + [int(toks[0, 1])]
    want = ref_rows(params, seq, len(seq) - 1)[0]
    assert gap(sound["logits"][1], want) < F32_TOL
    broken = dict(pool)
    for name in ("k", "v"):
        by_pass = pool[name].reshape(LOOPS, LAYERS, *pool[name].shape[1:])
        if fault == "shared":
            by_pass = jnp.broadcast_to(by_pass[-1:], by_pass.shape)
        else:
            by_pass = by_pass.at[-1].set(by_pass.mean(axis=0))
        broken[name] = by_pass.reshape(pool[name].shape)
    out, toks2 = step(p32, broken)
    assert int(toks2[0, 1]) == seq[-1]      # the staged logits were sound
    assert gap(out["logits"][1], want) > 50 * F32_TOL


def test_one_shot_admission_and_generate_ride_the_loop(net, prompt):
    """``pool_admit`` (one whole-prompt prefill) and ``pool_admit_batch``
    leave the rows of every pass and the logits the pieces leave;
    ``generate`` (prefill, then ``decode_step`` over its own cache of
    ``loops x layers`` layers) emits the reference's greedy tokens."""
    params, p32, _cfg, cfg32 = net
    ids = np.zeros((1, 48), np.int32)
    mask = np.zeros((1, 48), np.int32)
    ids[0, 8:], mask[0, 8:] = prompt, 1
    fresh = lambda: D.pool_init(p32, cfg32, 2, 96)      # noqa: E731
    once = jax.jit(lambda p, i, m, pl: D.pool_admit(
        p, i, m, pl, np.int32(1), cfg32))(p32, ids, mask, fresh())
    both = jax.jit(lambda p, i, m, pl: D.pool_admit_batch(
        p, i, m, pl, np.asarray([1, 0], np.int32), cfg32))(
            p32, np.repeat(ids, 2, 0), np.repeat(mask, 2, 0), fresh())
    pieces = _prefill(p32, cfg32, fresh(), 1, prompt)
    for pool in (once, both):
        assert gap(pool["logits"][1], np.asarray(pieces["logits"][1])) \
            < F32_TOL
        assert np.abs(np.asarray(pool["k"][:, 1, :, 8:48])
                      - np.asarray(pieces["k"][:, 1, :, 8:48])).max() < 1e-5
        assert int(pool["exit_step"][1]) == LOOPS - 1
    last_logits, cache = jax.jit(lambda p, i, m: D.prefill(
        p, i, m, cfg32, 64))(p32, ids, mask)
    assert cache["k"].shape == (LOOPS * LAYERS, 1, 4, 64, 16)
    assert gap(last_logits[0], ref_rows(params, prompt, PROMPT - 1)[0]) \
        < F32_TOL
    toks = np.asarray(jax.jit(lambda p, i, m: D.generate(
        p, i, m, cfg32, 6))(p32, ids, mask))[0]
    assert toks.tolist() == _greedy_by_reference(params, prompt, 6)


def _greedy_by_reference(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = ref_rows(params, seq, len(seq) - 1)[0]
        top = np.sort(logits)[-2:]
        assert top[1] - top[0] > 1e-4, "choose another seed: a near-tie"
        seq.append(int(logits.argmax()))
    return seq[len(prompt):]


def test_extract_then_insert_moves_every_passes_rows(net, prompt):
    """``kv_extract`` -> ``kv_insert`` through the arena carries ``loops x
    layers`` cache layers of a block: a slot seeded from the arena and
    prefilled from there on gives the logits of one that prefilled all of
    the prompt; seeded with the last pass's rows zeroed, it does not."""
    params, p32, _cfg, cfg32 = net
    fresh = lambda: D.pool_init(                        # noqa: E731
        p32, cfg32, 2, 96, arena_blocks=3, arena_block=16)
    # right-padded, as the prefix cache admits: token i at column i
    ids = np.zeros((1, 48), np.int32)
    mask = np.zeros((1, 48), np.int32)
    ids[0, :PROMPT], mask[0, :PROMPT] = prompt, 1
    pos = np.minimum(np.arange(48), PROMPT - 1)[None].astype(np.int32)
    n_prompt = np.asarray([PROMPT], np.int32)

    def piece(pool, slot, o, first):
        last = o == 32
        return jax.jit(lambda p, pl, lc: D.pool_prefill_chunk(
            p, ids[:, o:o + 16], mask[:, o:o + 16], pos[:, o:o + 16], pl,
            np.int32(slot), np.int32(o), n_prompt, cfg32, first=first,
            last=last, last_col=lc if last else None))(
                p32, pool, np.int32(PROMPT - 33))

    pool = fresh()
    for o in (0, 16, 32):
        pool = piece(pool, 1, o, o == 0)
    want = ref_rows(params, prompt, PROMPT - 1)[0]
    assert gap(pool["logits"][1], want) < F32_TOL
    idxs = np.asarray([2, 0], np.int32)
    pool = jax.jit(lambda pl: D.kv_extract(
        pl, np.int32(1), np.int32(0), idxs, cfg32))(pool)
    assert D._kv_channels(pool) == [("k", "arena_k"), ("v", "arena_v")]
    assert np.array_equal(np.asarray(pool["arena_k"][2]),
                          np.asarray(pool["k"][:, 1, :, :16]))
    blobs = jax.jit(lambda pl: D.kv_block_export(pl, idxs))(pool)
    assert blobs["k"].shape == (2, LOOPS * LAYERS, 4, 16, 16)
    for zero_last_pass in (False, True):
        seeded = dict(pool)
        if zero_last_pass:
            seeded["arena_k"] = pool["arena_k"].at[
                :, (LOOPS - 1) * LAYERS:].set(0.0)
        seeded = jax.jit(lambda pl: D.pool_admit_cached(
            pl, np.int32(0), idxs, cfg32))(seeded)
        seeded = piece(seeded, 0, 32, False)
        if zero_last_pass:
            assert gap(seeded["logits"][0], want) > 50 * F32_TOL
        else:
            assert gap(seeded["logits"][0], want) < F32_TOL
    # the block store's import is the export's inverse, all passes of it
    back = jax.jit(lambda pl, b: D.kv_block_import(
        pl, np.asarray([1, 2], np.int32), b))(fresh(), blobs)
    assert np.array_equal(np.asarray(back["arena_v"][1]),
                          np.asarray(pool["arena_v"][2]))


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


def test_the_server_serves_the_loop_with_its_defaults(net, prompt):
    """Chunked prefill, batched admission, eager refill and the prefix
    cache, all default-on, through ``TPUDecoderChat`` ->
    ``_ContinuousServer``: the greedy tokens are the reference's. The
    flag's default for self-speculative decoding resolves to plain chunks
    (a looped stack has no depth-prefix draft, as one layer has none). The
    counters say four passes a token, to the digit, and every emitted token
    at the last pass; the pool's bytes count the passes."""
    from pathway_tpu.engine import probes

    params, p32, _cfg, cfg32 = net
    other = [int(t) for t in np.random.default_rng(3).integers(1, VOCAB, 11)]
    probes.REGISTRY.remove("loop_passes", "loop_exit_step")
    chat = _chat(p32, cfg32)
    try:
        srv = chat._server
        assert not srv.spec_decode and srv.prefix is not None
        reqs = [srv.submit(list(p), 8) for p in (prompt, other)]
        for r in reqs:
            assert r.done.wait(timeout=300)
        stats = dict(srv.stats)
        for p, r in zip((prompt, other), reqs):
            assert list(r.tokens) == _greedy_by_reference(params, p, 8)
        assert stats["prefill_chunks"] == 3 and stats["spec_dispatches"] == 0
        # the arena's budget and the ledger count every pass's rows
        assert srv._prefix_kwargs["block_bytes"] \
            == 16 * LOOPS * LAYERS * 2 * 4 * 16 * 4
        slot_pool = probes.REGISTRY.gauge_value(
            "hbm_bytes", component="slot_pool")
        assert slot_pool == 2 * srv.cache_len * D.kv_token_bytes(cfg32, 4)
    finally:
        chat.close()
    passes = probes.REGISTRY.labelled("loop_passes", "phase")
    assert passes == {"prefill": float(LOOPS * (PROMPT + 11)),
                      "decode": float(LOOPS * stats["steps"])}
    by_pass = probes.REGISTRY.labelled("loop_passes", "pass")
    assert by_pass == {str(u): float(PROMPT + 11 + stats["steps"])
                       for u in range(1, LOOPS + 1)}
    assert probes.REGISTRY.labelled("loop_exit_step", "step") \
        == {str(LOOPS): float(stats["steps"])}
    assert stats["steps"] == 16


def test_a_prefix_hit_gives_the_logits_of_a_miss(net):
    """A repeated prefix HITS (every pass's rows come out of the arena)
    and the tokens are those of a miss."""
    _params, p32, _cfg, cfg32 = net
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(1, VOCAB, 32)]
    a = shared + [int(t) for t in rng.integers(1, VOCAB, 4)]
    b = shared + [int(t) for t in rng.integers(1, VOCAB, 6)]
    chat = _chat(p32, cfg32, prefix_cache=False)
    try:
        r = chat._server.submit(b, 8)
        assert r.done.wait(timeout=300)
        miss = list(r.tokens)
    finally:
        chat.close()
    chat = _chat(p32, cfg32, prefix_block=16)
    try:
        srv = chat._server
        assert srv.pool["arena_k"].shape[1:] == (LOOPS * LAYERS, 4, 16, 16)
        for p in (a, b):
            r = srv.submit(p, 8)
            assert r.done.wait(timeout=300)
            got = list(r.tokens)
        assert srv.stats["prefix_hit_requests"] == 1
        assert srv.stats["prefix_hit_tokens"] == 32
        assert got == miss
    finally:
        chat.close()


@pytest.mark.parametrize("mechanism,kwargs", [
    ("spec_decode", {"spec_decode": True}),
    ("paged_kv", {"paged_kv": True}),
    ("paged_kv", {"paged_kv": True, "paged_kernel": True}),
    ("flash_prefill", {"flash_prefill": True}),
    ("kv_quant", {"kv_quant": "int8"}),
    ("weight_quant", {"weight_quant": "int8"}),
    ("disagg", {"disagg": True}),
    ("mesh", {"mesh": "2 devices"}),
])
def test_what_a_looped_stack_cannot_ride_refuses_by_type(
        net, mechanism, kwargs):
    """The depth-prefix draft of the speculative cycle (when ASKED for),
    the paged pool, int8 caches and weights, the lane migration and the
    serving mesh raise a typed error at construction that names the
    mechanism and says why here: no silent fallback to another path."""
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
    assert "a looped stack" in str(err.value)


def test_a_depth_prefix_is_refused_where_it_is_asked_of_the_model(net):
    """``decode_step(n_layers=)``, ``pool_decode_draft`` and
    ``pool_decode_spec`` take the first layers as a shallower model: of a
    stack run several times they are not one."""
    _params, p32, _cfg, cfg32 = net
    pool = D.pool_init(p32, cfg32, 2, 32)
    lanes = np.asarray([True, False])
    with pytest.raises(D.UnsupportedForLayout, match="a looped stack"):
        D.pool_decode_draft(p32, pool, lanes, cfg32, draft_layers=1,
                            n_draft=2)
    with pytest.raises(D.UnsupportedForLayout, match="a looped stack"):
        D.pool_decode_spec(p32, pool, lanes, cfg32, 1, draft_layers=1,
                           n_spec=2)
    cache = {n: pool[n][:, :1] for n in ("k", "v")}
    with pytest.raises(D.UnsupportedForLayout, match="a looped stack"):
        D.decode_step(p32, jnp.zeros((1,), jnp.int32),
                      jnp.zeros((1,), jnp.int32), jnp.int32(0),
                      jnp.ones((1, 32), jnp.int32), cache, cfg32,
                      n_layers=1)
    with pytest.raises(D.UnsupportedForLayout, match="a looped stack"):
        D.require_gpt2_block(cfg32, "paged_kv")


def test_the_executables_hold_one_pass_body_under_its_scopes(net):
    """The loop over passes is a ``lax`` loop whose body is ONE pass: the
    piece's and the chunk's text hold the products of ONE layer body (a
    stack run once has as many, less the gate's), under the scopes
    ``decoder.pass`` and ``decoder.exit_gate``; a stack run once has
    neither scope."""
    _params, _p32, _cfg, cfg32 = net
    one = jax.ShapeDtypeStruct((), jnp.int32)
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def texts(c):
        p = jax.eval_shape(lambda: D.init_params(jax.random.PRNGKey(0), c))
        pool = jax.eval_shape(lambda: D.pool_init(None, c, 2, 64))
        piece = jax.jit(lambda p, i, pl, s: D.pool_prefill_chunk(
            p, i, i, i, pl, s, s, s[None], c, first=False, last=True)
        ).lower(p, ids, pool, one).as_text(debug_info=True)
        chunk = jax.jit(lambda p, pl, a, k: D.pool_decode_chunk(
            p, pl, a, k, c, 2)).lower(
                p, pool, jax.ShapeDtypeStruct((2,), jnp.bool_),
                jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(
                    debug_info=True)
        return piece, chunk

    once = texts(dataclasses.replace(cfg32, loops=1, exit_gate=False))
    for text, single in zip(texts(cfg32), once):
        assert "decoder.pass" in text and "decoder.exit_gate" in text
        assert "decoder.attn.full" in text
        assert text.count("stablehlo.dot_general") \
            == single.count("stablehlo.dot_general") + 1
        assert text.count("stablehlo.while") \
            == single.count("stablehlo.while") + 1
        assert "decoder.pass" not in single \
            and "decoder.exit_gate" not in single
