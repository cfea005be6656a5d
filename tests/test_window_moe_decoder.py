"""The configuration-driven decoder block against the plain reference of the
``afmoe`` layout (``benchmarks/layouts/afmoe.py`` — the ONE copy of the
reference: the tests import the layout the benchmark runs).

Small widths, seeded random weights, CPU. What is compared is LOGITS, never
sampled tokens. Tolerances, and why:

* ``F32_TOL`` = 2e-5: program and reference both in float32 on the same
  weights differ by the order of summation alone (read 3e-7 to 5e-7 on
  logits of size 0.7). A bfloat16 computation reads 2e-2 to 6e-2 and a
  dropped term 1e-1 and more: both fail it, which two tests below show.
* a router near-tie does not decide a test: the reference reports every
  position's smallest router margin over the expert layers (how far a held
  expert's score lies from the top-4 boundary it would have to cross; the
  4th-to-5th margin where every expert is held), and a position under
  ``TIE_EPS`` = 1e-5 is skipped (counted: none may be skipped silently)
  rather than given a looser tolerance. In float32 the
  two sides' scores differ by about 1e-7, so a margin over 1e-5 cannot
  flip.
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
    "layout": "afmoe", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1, "vocab_size": 251,
    "sliding_window": 16, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "mup_enabled": True,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "torch_dtype": "bfloat16", "num_hidden_layers": 5, "num_dense_layers": 1,
    "layers_kept": [0, 6, 7, 8, 9],
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 3,
}
PROMPT = 40         # past the window of 16 and the ring of 24: it wraps


@pytest.fixture(scope="module")
def layout():
    return M.resolve(M.load_manifest(), "layouts", "afmoe")


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


def ref_rows(layout, params, seq, first):
    """Reference logits of positions first.. and which of them no router
    near-tie touches."""
    logits, margin = M.load_named_module(
        M.load_manifest(), "layouts", "afmoe").reference_forward(
            params, MODEL, seq, first, margins=True)
    return logits, margin > TIE_EPS


def worst(got, want, sound):
    assert sound.sum() >= 0.9 * len(sound), "too many near-ties to judge"
    return float(np.abs(got[sound] - want[sound]).max())


def test_the_layers_are_grouped_into_stacks_of_like_layers(net):
    _p, _p32, cfg, _c32 = net
    assert cfg.runs() == (
        (("window", "rotary", "dense"), 0, 1),
        (("window", "rotary", "moe"), 1, 1),
        (("full", "none", "moe"), 2, 1),
        (("window", "rotary", "moe"), 3, 2))
    assert not cfg.uniform and D.GPT2_SMALL.uniform
    assert D.gpt2_block(D.GPT2_MEDIUM) and not D.gpt2_block(cfg)
    # the program's own initialiser and counts follow the configuration
    own = D.init_params(jax.random.PRNGKey(0), cfg)
    made = W.make_params(7, W.STREAM_DECODER, M.resolve(
        M.load_manifest(), "layouts", "afmoe").weight_spec(MODEL, "decoder"))
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(
        lambda a: a.shape, made)
    assert jax.tree.structure(D.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, own))
    cast = D.cast_params_for_inference(own, cfg)
    run1 = cast["layers"]["run1"]
    assert run1["router_w"].dtype == jnp.float32       # as published
    assert run1["moe_in_w"].dtype == jnp.bfloat16
    assert run1["ln2p_scale"].dtype == jnp.float32
    assert D.count_params(own) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(made))


def test_forward_logits_match_the_reference(layout, net, prompt):
    params, p32, cfg, cfg32 = net
    ids = np.asarray([prompt], np.int32)
    mask = np.ones_like(ids)
    want, sound = ref_rows(layout, params, prompt, 0)
    got = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg32))(p32, ids, mask))[0]
    assert worst(got, want, sound) < F32_TOL
    # left-padded rows see the same positions as their unpadded equivalents
    padded = np.zeros((1, PROMPT + 8), np.int32)
    padded[0, 8:] = prompt
    pm = (np.arange(PROMPT + 8) >= 8).astype(np.int32)[None]
    got = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg32))(p32, padded, pm))[0, 8:]
    assert worst(got, want, sound) < F32_TOL
    # the tolerance is tight enough that bfloat16 in float32's place fails
    low = np.asarray(jax.jit(
        lambda p, i, m: D.forward(p, i, m, cfg))(params, ids, mask))[0]
    assert worst(low, want, sound) > 100 * F32_TOL


@pytest.mark.parametrize("dropped", [
    {"qk_norm": False}, {"attn_gate": False}, {"sandwich_norm": False},
    {"embed_scale": 1.0}, {"positions": "none"}, {"positions": "rotary"},
    {"sliding_window": 4096}, {"rope_theta": 500000.0},
    {"moe": {"route_scale": 1.0}}, {"moe": {"route_norm": False}},
    {"moe": {"shared": 0}}, {"moe": {"held": (0, 4)}},
])
def test_a_dropped_term_fails_the_tolerance(layout, net, prompt, dropped):
    """GQA, q/k norm, gate, sandwich norm, the embedding scale, each kind
    of positions, the window, the router's scaling, the shared expert and
    WHICH experts are held: leaving any one out moves the logits by far
    more than the tolerance."""
    params, p32, _cfg, cfg32 = net
    change = dict(dropped)
    if "moe" in change:
        change["moe"] = dataclasses.replace(cfg32.moe, **change["moe"])
    broken = dataclasses.replace(cfg32, **change)
    ids = np.asarray([prompt], np.int32)
    want, sound = ref_rows(layout, params, prompt, 0)
    try:
        got = np.asarray(jax.jit(lambda p, i, m: D.forward(
            p, i, m, broken))(p32, ids, np.ones_like(ids)))[0]
    except KeyError:
        return      # the leaves the term needs are not even there
    assert worst(got, want, sound) > 50 * F32_TOL


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
    """``blockwise``: every layer's read through the chunk kernel (the
    ``flash`` argument forces what the shape rule chooses for long rows)."""
    pieces = _pieces(prompt, 48, 16, left)
    n_prompt = np.asarray([len(prompt)], np.int32)
    for ids, mask, pos, o in pieces:
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


@pytest.mark.parametrize("spec", [False, True])
def test_pool_prefill_then_decode_matches_the_full_forward(
        layout, net, prompt, spec):
    """Chunked prefill then decode (plain and self-speculative) through the
    dense slot pool against the reference's full forward, with a prompt
    (40) longer than the window (16) and than a window layer's ring (24):
    the ring wraps in the prefill and again while decoding."""
    params, p32, _cfg, cfg32 = net
    pool = D.pool_init(p32, cfg32, 2, 96, window_slack=8)
    # one pair of arrays per run of like layers: rings for window runs
    assert D.pool_ring(pool) == 24
    assert {n: a.shape[0::3][:2] for n, a in pool.items() if n[0] == "k"} \
        == {"kw0": (1, 24), "kw1": (1, 24), "kf2": (1, 96), "kw3": (2, 24)}
    comp = D.pool_component_bytes(pool)
    assert comp["slot_pool"] == 2 * 1 * 2 * 2 * 96 * 16 * 4
    assert comp["slot_pool_window"] == 2 * 4 * 2 * 2 * 24 * 16 * 4
    pool = _prefill(p32, cfg32, pool, 1, prompt)
    seq = list(prompt)

    def check():
        want, sound = ref_rows(layout, params, seq, len(seq) - 1)
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
    held, every = np.asarray(pool["moe_counts"]).sum(axis=0)
    assert 0 < held < every and every % 4 == 0


@pytest.mark.parametrize("left", [True, False], ids=["left", "right"])
def test_chunked_prefill_through_the_blockwise_read_matches_the_reference(
        layout, net, prompt, left):
    """The pieces of a prompt longer than the window and the ring, every
    layer's attention through the chunk kernel (grouped query 8 / 2, window
    16, the ring wrapping between pieces, the first piece's leading rows
    wholly left padding): the next-token logits are the reference's inside
    the same tolerance the dense read is held to, and the decode steps that
    follow read what those pieces wrote."""
    params, p32, _cfg, cfg32 = net
    pool = _prefill(p32, cfg32,
                    D.pool_init(p32, cfg32, 2, 96, window_slack=8), 1,
                    prompt, left=left, blockwise=True)
    seq, judged = list(prompt), 0
    step = jax.jit(lambda p, pl: D.pool_decode_chunk(
        p, pl, np.asarray([False, True]), jax.random.PRNGKey(0), cfg32, 1))
    for _ in range(4):
        want, sound = ref_rows(layout, params, seq, len(seq) - 1)
        if sound[0]:
            judged += 1
            assert np.abs(np.asarray(pool["logits"][1]) - want[0]).max() \
                < F32_TOL, len(seq)
        pool, toks = step(p32, pool)
        seq.append(int(toks[0, 1]))
    assert judged >= 3


@pytest.mark.parametrize("blockwise", [False, True],
                         ids=["dense", "blockwise"])
def test_one_shot_admission_lays_the_ring_out_as_the_pieces_do(
        net, prompt, blockwise):
    """``pool_admit`` (one dispatch) and chunked prefill leave the same
    logits, and the window layers' rings hold the same last columns."""
    _params, p32, _cfg, cfg32 = net
    ids = np.zeros((1, 48), np.int32)
    mask = np.zeros((1, 48), np.int32)
    ids[0, 8:], mask[0, 8:] = prompt, 1
    a = jax.jit(lambda p, pl: D.pool_admit(
        p, ids, mask, pl, np.int32(1), cfg32))(
            p32, D.pool_init(p32, cfg32, 2, 96, window_slack=8))
    b = _prefill(p32, cfg32, D.pool_init(p32, cfg32, 2, 96, window_slack=8),
                 1, prompt, blockwise=blockwise)
    assert np.abs(np.asarray(a["logits"][1]) - np.asarray(b["logits"][1])
                  ).max() < F32_TOL
    for name in ("kw0", "vw1", "kw3"):
        assert np.abs(np.asarray(a[name][:, 1]) - np.asarray(b[name][:, 1])
                      ).max() < F32_TOL
    batch = jax.jit(lambda p, pl: D.pool_admit_batch(
        p, np.repeat(ids, 2, 0), np.repeat(mask, 2, 0), pl,
        np.asarray([0, 1], np.int32), cfg32))(
            p32, D.pool_init(p32, cfg32, 2, 96, window_slack=8))
    assert np.abs(np.asarray(batch["logits"]) - np.asarray(a["logits"][1])
                  ).max() < F32_TOL


# the toy's 8 heads and pieces of 16 against a slot's row: a full layer's
# scores pass 32 MiB at 65,536 columns; a window layer's ring of 24 never does
@pytest.mark.parametrize("cache_len,kernels", [
    (96, 0),          # every tier-1 row: the dense read, as it always was
    (65_536, 0),      # 8 x 16 x 65,536 x 4 B = 32 MiB exactly: still dense
    (65_552, 1),      # past it: the one full layer reads blockwise, and the
])                    # window layers' [ring | own] of 40 columns stay dense
def test_the_shape_rule_chooses_the_read_of_each_kind_of_layer(
        net, cache_len, kernels):
    """One expression on shapes (``blockwise_chunk_read``) decides, for the
    row each kind of layer has in hand; nothing else does: no flag, no
    model's name."""
    _params, p32, _cfg, cfg32 = net
    assert D.blockwise_chunk_read(cfg32.heads, 16, cache_len) == bool(kernels)
    assert not D.blockwise_chunk_read(cfg32.heads, 16, 24 + 16)
    # the answer cell's rows, and the serving defaults of GPT-2 medium
    assert D.blockwise_chunk_read(48, 512, 8304)
    assert D.blockwise_chunk_read(48, 512, 4352 + 512)
    assert not D.blockwise_chunk_read(16, 64, 656)
    pool = jax.eval_shape(
        lambda: D.pool_init(None, cfg32, 2, cache_len, window_slack=8))
    piece = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    traced = jax.make_jaxpr(lambda p, i, m, ps, pl: D.pool_prefill_chunk(
        p, i, m, ps, pl, np.int32(1), np.int32(16),
        np.asarray([40], np.int32), cfg32, first=False, last=False))(
            p32, piece, piece, piece, pool)
    assert str(traced).count("pallas_call") == kernels


def _blocks_by_definition(T, rows, ring, window, start, lo, hi, bk):
    """Key blocks with a key some query of the piece sees, by brute force
    over every (query, key row): the definition, not the arithmetic."""
    if ring:
        held = [max((c for c in range(start) if c % ring == r), default=-1)
                for r in range(ring)] + list(range(start, start + T))
    else:
        held = list(range(rows))
    seen = set()
    for row, c in enumerate(held):
        if not lo <= c <= hi:
            continue
        if any(c <= q and (not window or q - c < window)
               for q in range(start, start + T)):
            seen.add(row // bk)
    return len(seen), -(-len(held) // bk)


def test_the_server_counts_the_blocks_its_pieces_visit(
        layout, net, prompt, monkeypatch):
    """``prefill_attn_blocks{layer, visited}``: with the rule's threshold
    at zero (and blocks of 16 key rows, ``flash_block_k``'s own hook) the
    toy's pieces read blockwise through the server's defaults;
    the greedy tokens stay the reference's, and the counter holds, piece by
    piece, what the definition gives (and what the device's own reduction
    gives for the same piece)."""
    from pathway_tpu.engine import probes
    from pathway_tpu.internals.http_server import registry_text
    from pathway_tpu.models import flash_attention as FA

    params, p32, _cfg, cfg32 = net
    monkeypatch.setattr(D, "_DENSE_SCORE_BYTES", 0)
    monkeypatch.setattr(FA, "_BLOCK_K", 16)     # a toy row is one block else
    probes.REGISTRY.remove("prefill_attn_blocks")
    streams, stats, chat = _serve(p32, cfg32, [prompt], prefix_cache=False)
    want, sure = _greedy_by_reference(layout, params, prompt, 8)
    assert sure and streams[0] == want
    C, W, T = chat._server.cache_len, 16, 16
    R = D.pool_ring(chat._server.pool)
    lo, bucket = 64 - len(prompt), 64
    expect = {("full", 1): 0, ("full", 0): 0, ("window", 1): 0,
              ("window", 0): 0}
    layers = {"full": cfg32.n_layers_of("full"),
              "window": cfg32.n_layers_of("window")}
    pieces = [o for o in range(0, bucket, T) if o + T > lo]
    assert stats["prefill_chunks"] == len(pieces) == 3
    for start in pieces:
        hi = start + T - 1
        for kind, rows, ring, window in (("full", C, 0, 0),
                                         ("window", R + T, R, W)):
            bk = FA.chunk_block(rows, T, 4, 16, 4)
            seen, n = _blocks_by_definition(
                T, rows, ring, window, start, lo, hi, bk)
            expect[(kind, 1)] += seen * layers[kind]
            expect[(kind, 0)] += (n - seen) * layers[kind]
        # the host's arithmetic is the device's reduction
        cols = np.arange(C)
        kcol = np.where((cols >= lo) & (cols <= hi), cols, -1)
        _t, n_live, _b = FA.chunk_live_blocks(
            jnp.asarray(kcol), jnp.int32(start), T, 0,
            FA.chunk_block(C, T, 4, 16, 4))
        assert int(n_live) * layers["full"] == D.prefill_blocks_visited(
            cfg32, T, C, R, start, lo, hi)[("full", 1)]
    got = {(s["labels"]["layer"], int(s["labels"]["visited"])): s["value"]
           for s in probes.REGISTRY.snapshot()["counters"][
               "prefill_attn_blocks"]["series"]}
    assert got == expect and expect[("window", 1)] > 0 < expect[("full", 0)]
    text = registry_text()
    assert "# TYPE pathway_tpu_prefill_attn_blocks counter" in text
    assert 'prefill_attn_blocks_total{layer="full",visited="1"}' in text
    # a dense read counts nothing: the family says how often the kernel runs
    monkeypatch.undo()
    assert D.prefill_blocks_visited(cfg32, T, C, R, 16, lo, 31) == {}


class WordIds:
    """``t<id>`` words in, ids out (and back): no EOS."""

    eos_id = None

    def encode(self, text):
        return [int(w[1:]) for w in text.split()]

    def decode(self, ids):
        return " ".join(f"t{int(i)}" for i in ids)


def _serve(p32, cfg32, prompts, **kw):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    chat = TPUDecoderChat(
        params=p32, cfg=cfg32, tokenizer=WordIds(), max_new_tokens=8,
        temperature=0.0, max_prompt_tokens=64, continuous=True, n_slots=2,
        chunk_steps=8, prefill_chunk=16, **kw)
    try:
        reqs = [chat._server.submit(list(p), 8) for p in prompts]
        for r in reqs:
            assert r.done.wait(timeout=300)
        return [list(r.tokens) for r in reqs], dict(chat._server.stats), chat
    finally:
        chat.close()


def _greedy_by_reference(layout, params, prompt, n):
    """The reference's own greedy continuation, and whether any of its
    steps was decided by a near-tie (of the router, or of the argmax)."""
    seq, sure = list(prompt), True
    for _ in range(n):
        logits, sound = ref_rows(layout, params, seq, len(seq) - 1)
        top = np.sort(logits[0])[-2:]
        sure = sure and bool(sound[0]) and top[1] - top[0] > 1e-4
        seq.append(int(logits[0].argmax()))
    return seq[len(prompt):], sure


def test_the_server_serves_the_block_with_its_defaults(layout, net, prompt):
    """Chunked prefill, batched admission, eager refill, the prefix cache
    and self-speculative decoding, all default-on, through
    ``TPUDecoderChat`` -> ``_ContinuousServer``: the greedy tokens are the
    reference's (a prompt of 40 over a window of 16: the rings wrap)."""
    params, p32, _cfg, cfg32 = net
    other = [int(t) for t in np.random.default_rng(3).integers(1, 251, 23)]
    streams, stats, chat = _serve(p32, cfg32, [prompt, other])
    assert chat._server.spec_decode and chat._server.prefix is not None
    assert stats["prefill_chunks"] >= 4 and stats["spec_dispatches"] > 0
    for p, got in zip((prompt, other), streams):
        want, sure = _greedy_by_reference(layout, params, p, 8)
        assert sure, "choose another seed: the reference's own choice is a tie"
        assert got == want


def test_a_prefix_hit_gives_the_logits_of_a_miss_or_is_declined(net):
    """Inside the ring (prompt 20 over blocks of 16, ring 16 + 256 capped
    by the cache) a repeated prefix HITS and its tokens are those of a
    miss; a prompt that outgrew the ring is DECLINED at insertion (the ring
    has lost its early window-layer rows), so its repeat is a miss and
    still right: never a silently wrong hit."""
    _params, p32, _cfg, cfg32 = net
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(1, 251, 16)]
    a = shared + [int(t) for t in rng.integers(1, 251, 4)]
    b = shared + [int(t) for t in rng.integers(1, 251, 6)]
    miss, _stats, _chat = _serve(p32, cfg32, [b], prefix_cache=False)
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    chat = TPUDecoderChat(
        params=p32, cfg=cfg32, tokenizer=WordIds(), max_new_tokens=8,
        temperature=0.0, max_prompt_tokens=64, continuous=True, n_slots=2,
        chunk_steps=8, prefill_chunk=16, prefix_block=16)
    try:
        srv = chat._server
        for p in (a, b):
            r = srv.submit(p, 8)
            assert r.done.wait(timeout=300)
            got = list(r.tokens)
        assert srv.stats["prefix_hit_requests"] == 1
        assert srv.stats["prefix_declined"] == 0
        assert got == miss[0]
    finally:
        chat.close()
    # a ring of 16 + 8 = 24 < the bucket of 32 a miss is padded to (on the
    # left): insertion declines
    import pathway_tpu.models.decoder as dec

    real = dec.pool_init

    def small_ring(*args, **kw):
        kw["window_slack"] = 8
        return real(*args, **kw)

    dec.pool_init = small_ring
    try:
        chat = TPUDecoderChat(
            params=p32, cfg=cfg32, tokenizer=WordIds(), max_new_tokens=8,
            temperature=0.0, max_prompt_tokens=64, continuous=True,
            n_slots=2, chunk_steps=8, prefill_chunk=16, prefix_block=16,
            spec_decode=False)
        srv = chat._server
        assert dec.pool_ring(srv.pool) == 24
        for p in (a, b):
            r = srv.submit(p, 8)
            assert r.done.wait(timeout=300)
            got = list(r.tokens)
        assert srv.stats["prefix_declined"] == 2
        assert srv.stats["prefix_hit_requests"] == 0
        assert got == miss[0]
    finally:
        dec.pool_init = real
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
def test_what_the_layout_cannot_ride_refuses_by_type(net, mechanism, kwargs):
    """Each default-off mechanism written for GPT-2's block alone raises a
    typed error at construction that names it: no silent fallback."""
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


def test_the_server_compiles_nothing_under_traffic(net, prompt):
    """Every executable the loop can dispatch (prefill pieces, admit
    buckets, the prefix cache's copies, every chunk step count and
    speculative cycle count) is built before the loop starts, with the
    server's defaults."""
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    _params, p32, _cfg, cfg32 = net
    chat = TPUDecoderChat(
        params=p32, cfg=cfg32, tokenizer=WordIds(), max_new_tokens=8,
        temperature=0.0, max_prompt_tokens=64, continuous=True, n_slots=2,
        chunk_steps=8, prefill_chunk=16)
    compiles = []

    def on(event, duration, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        srv = chat._server
        assert srv.warm_seconds > 0
        assert set(srv._spec_fns) == {2, 1} and set(srv._chunk_fns) == {8, 4}
        rng = np.random.default_rng(5)
        reqs = [srv.submit(list(prompt), 8)] + [
            srv.submit([int(t) for t in rng.integers(1, 251, n)], 8)
            for n in (3, 9, 17, 30, 33, 64)]
        for r in reqs:
            assert r.done.wait(timeout=300)
        assert compiles == []
        spans = chat.recent_traces()
        names = [e["name"] for e in spans[-1]["events"]]
        for a, b in zip(("enqueue", "submit", "admit", "first_token",
                         "drain"), ("submit", "admit", "first_token",
                                    "drain", "done")):
            assert names.index(a) < names.index(b)
    finally:
        chat.close()
        jax.monitoring.unregister_event_duration_listener(on)


# ---- the ring write (PR 33) -------------------------------------------------
#
# A piece enters a window layer's ring as at most two stretches of contiguous
# rows (``decoder._ring_put``). The rows below carry their own cache column
# as their value, so what the ring holds can be read back and compared with a
# plain numpy ring: column mod R, pads skipped.

RING, PIECE_T = 24, 16
RING_CASES = {
    # (start, the piece's mask) in turn
    "ends at the ring's end": [(8, [1] * 16)],
    "wraps": [(16, [1] * 16)],
    "first columns are left padding": [(0, [0] * 5 + [1] * 11)],
    "unaligned start, right padding": [(0, [1] * 16), (19, [1] * 9 + [0] * 7)],
    "a second lap over a first lap's rows": [
        (0, [0] * 3 + [1] * 13), (16, [1] * 16), (32, [1] * 16),
        (48, [1] * 16)],
    "a ring no longer than the piece": [(5, [1] * 16), (21, [1] * 16)],
}


def _column_rows(start, heads=2, d=4):
    """(1, heads, T, d): every value of row j is its column, start + j."""
    col = (start + np.arange(PIECE_T, dtype=np.float32))[None, None, :, None]
    return np.broadcast_to(col, (1, heads, PIECE_T, d)).copy()


@pytest.mark.parametrize("case", list(RING_CASES))
def test_a_piece_enters_the_ring_as_a_plain_numpy_ring_takes_it(net, case):
    """Elementwise, the whole stack: the rows of the layer and slot written
    are the numpy ring's, every other row of every layer and slot is as it
    was; and the ring the write leaves is the one the next piece's read is
    told about — ``kcol_w``'s columns (``_ring_cols`` under ``_live_at``) and
    ``prefill_blocks_visited``'s count."""
    from pathway_tpu.models import flash_attention as FA

    _params, _p32, _cfg, cfg32 = net
    R = PIECE_T if case.startswith("a ring no longer") else RING
    rng = np.random.default_rng(1)
    stack = -1.0 - rng.random((2, 3, 2, R, 4)).astype(np.float32)
    want = stack.copy()
    layer, slot, C = 1, 2, 96
    row_mask = np.zeros((1, C), np.int32)
    put = jax.jit(D._ring_put)
    got = jnp.asarray(stack)
    for start, mask in RING_CASES[case]:
        new, real = _column_rows(start), np.asarray(mask) > 0
        got = put(got, new, np.int32(layer), np.int32(slot), np.int32(start),
                  real)
        for j in np.flatnonzero(real):                  # the numpy ring
            want[layer, slot, :, (start + j) % R, :] = new[0, :, j, :]
        row_mask[0, start:start + PIECE_T] = mask
    assert np.array_equal(np.asarray(got), want)
    # what the NEXT piece's read is told: ring row r holds column cols[r]
    nxt = start + PIECE_T
    cols = D._ring_cols(jnp.reshape(jnp.int32(nxt - 1), (1,)), R)
    live = np.asarray(D._live_at(jnp.asarray(row_mask), cols))[0]
    told = np.where(live, np.asarray(cols)[0], -1)
    held = want[layer, slot, 0, :, 0]
    assert (told >= 0).any() and np.array_equal(held[told >= 0],
                                                told[told >= 0])
    # ... and the counter's host arithmetic sees the ring the write left
    real_cols = np.flatnonzero(row_mask[0])
    lo, hi = int(real_cols.min()), nxt + PIECE_T - 1
    if R != RING or real_cols.size != real_cols.max() - lo + 1:
        return      # the server's live columns are one stretch [lo, hi]
    bk = FA.chunk_block(R + PIECE_T, PIECE_T, 4, 16, 4)
    kcol = np.concatenate([np.where(held >= lo, held, -1).astype(np.int64),
                           nxt + np.arange(PIECE_T)])
    _k, seen = FA.blocks_seen(np, kcol, nxt, PIECE_T, cfg32.sliding_window,
                              bk)
    counted = D.prefill_blocks_visited(cfg32, PIECE_T, C, R, nxt, lo, hi,
                                       flash=True)
    assert counted[("window", 1)] == int(seen.sum()) * cfg32.n_layers_of(
        "window")


def test_the_pieces_leave_the_ring_one_shot_prefill_would(net, prompt):
    """Through ``pool_prefill_chunk``: a prompt of 40 in three pieces, left
    padded (the first piece's first columns are padding; the ring of 24 wraps
    in the third) and right padded from an unaligned start (the prefix-cache
    admission), against ``prefill``'s cache, which keeps a window layer's
    keys at full length: ring row r holds the last real column congruent to
    r, to the element."""
    _params, p32, _cfg, cfg32 = net
    for left in (True, False):
        pool = _prefill(p32, cfg32,
                        D.pool_init(p32, cfg32, 2, 96, window_slack=8), 1,
                        prompt, left=left)
        ids, mask = np.zeros((1, 48), np.int32), np.zeros((1, 48), np.int32)
        at = slice(8, 48) if left else slice(0, 40)
        ids[0, at], mask[0, at] = prompt, 1
        _logits, cache = jax.jit(lambda p: D.prefill(
            p, ids, mask, cfg32, 48))(p32)
        for name in ("kw0", "vw1", "kw3", "vw3"):
            ring = np.asarray(pool[name][:, 1])         # (layers, nh, 24, hd)
            full = np.asarray(cache[name][:, 0])        # (layers, nh, 48, hd)
            for r in range(24):
                c = max(c for c in np.flatnonzero(mask[0]) if c % 24 == r)
                assert np.abs(ring[:, :, r] - full[:, :, c]).max() < F32_TOL
