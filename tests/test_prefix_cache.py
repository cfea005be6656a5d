"""Radix-tree KV prefix cache (PATHWAY_TPU_PREFIX_CACHE) + the content
caches that ride along (PATHWAY_TPU_TOKENIZE_CACHE /
PATHWAY_TPU_EMBED_DEDUP).

The device contract: a cache-hit admission seeds a slot by COPYING arena
blocks (``pool_admit_cached``) and prefills only the uncached suffix —
so generated tokens must equal the cold path exactly at every block
split, and with the kill switch off the serving output is byte-identical
to the plain chunked-admission path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.engine import probes
from pathway_tpu.engine.prefix_cache import HostTierStore, PrefixCache
from pathway_tpu.models import decoder as D
from tests.utils import ToyCharTokenizer

TINY = D.DecoderConfig(
    vocab_size=128, hidden=32, layers=2, heads=4, intermediate=64,
    max_position=128, dtype=jnp.float32,
)
NEW = 8
# 16 chars -> exactly 2 blocks at prefill_chunk=8 (block == chunk here)
HEAD = "rag sys prompt: "
B = 8


@pytest.fixture(scope="module")
def tiny_params():
    return D.init_params(jax.random.PRNGKey(0), TINY)


# -- host-side radix tree (no jax) ------------------------------------------


def _toks(*blocks):
    """Build a token list out of whole blocks: _toks(1, 2) -> block of
    1s then a block of 2s."""
    out = []
    for b in blocks:
        out.extend([b] * B)
    return out


def _cache(n_blocks=8):
    return PrefixCache(n_blocks=n_blocks, block=B, block_bytes=100)


def test_radix_insert_match_roundtrip():
    c = _cache()
    node, first_new, new_ids = c.insert(_toks(1, 2, 3))
    assert first_new == 0 and new_ids == [0, 1, 2]  # low ids first
    n, ids, m = c.match(_toks(1, 2, 3))
    assert (n, ids, m) == (3, [0, 1, 2], node)
    # partial-block tails never match; shorter prefixes match their blocks
    assert c.match(_toks(1) + [1] * (B - 1))[0] == 1
    assert c.match(_toks(9, 9))[0] == 0
    # re-insert is a no-op (nothing newly allocated)
    assert c.insert(_toks(1, 2, 3))[2] == []
    assert c.used_blocks == 3


def test_radix_split_mid_edge():
    c = _cache()
    c.insert(_toks(1, 2, 3, 4))
    node2, first_new, new_ids = c.insert(_toks(1, 2, 9))
    # blocks 1,2 were already cached: only one new block allocates
    assert first_new == 2 and len(new_ids) == 1
    # both full prefixes still match with their original arena ids
    n, ids, _ = c.match(_toks(1, 2, 3, 4))
    assert n == 4 and ids == [0, 1, 2, 3]
    n, ids, _ = c.match(_toks(1, 2, 9))
    assert n == 3 and ids[:2] == [0, 1]
    # the returned handle's root-path covers EXACTLY the matched blocks
    n, _, m = c.match(_toks(1, 2, 5))
    assert n == 2
    path_blocks = []
    while m is not None:
        path_blocks = m.blocks + path_blocks
        m = m.parent
    assert path_blocks == [0, 1]


def test_radix_refcount_protects_live_blocks():
    c = _cache(n_blocks=2)
    c.insert(_toks(1, 2))
    n, _, node = c.match(_toks(1, 2))
    assert n == 2
    c.acquire(node)
    # arena full + the only resident prefix is referenced: nothing evicts
    _, _, new_ids = c.insert(_toks(7, 8))
    assert new_ids == []
    assert c.match(_toks(1, 2))[0] == 2
    # released, the LRU leaf gives its blocks up to the new insert
    c.release(node)
    _, _, new_ids = c.insert(_toks(7, 8))
    assert len(new_ids) == 2
    assert c.match(_toks(1, 2))[0] == 0
    assert c.match(_toks(7, 8))[0] == 2


def test_radix_lru_eviction_respects_budget():
    c = _cache(n_blocks=4)
    c.insert(_toks(1, 2))
    c.insert(_toks(3, 4))
    assert c.used_blocks == 4
    c.match(_toks(1, 2))  # touch: makes (3,4) the LRU leaf
    c.insert(_toks(5, 6))
    assert c.used_blocks <= c.capacity_blocks == 4
    assert c.match(_toks(1, 2))[0] == 2   # recently used: survived
    assert c.match(_toks(3, 4))[0] == 0   # LRU: evicted
    assert c.match(_toks(5, 6))[0] == 2


def test_radix_partial_alloc_when_exhausted():
    c = _cache(n_blocks=3)
    node, _, new_ids = c.insert(_toks(1, 2, 3, 4, 5))
    # only 3 arena blocks exist: the tail is simply not cached
    assert len(new_ids) == 3
    assert c.match(_toks(1, 2, 3, 4, 5))[0] == 3
    assert c.used_blocks == 3


def test_prefix_probes_ledger():
    probes.reset_prefix_stats()
    c = _cache(n_blocks=2)
    c.insert(_toks(1, 2))
    c.insert(_toks(3, 4))  # evicts (1,2)
    probes.record_prefix("requests", 2)
    probes.record_prefix("hit_requests", 1)
    probes.record_prefix("hit_tokens", 16)
    probes.record_prefix("miss_tokens", 16)
    s = probes.prefix_stats()
    assert s["hit_rate"] == 0.5
    assert s["prefill_tokens_saved"] == 16
    assert s["counts"]["inserted_blocks"] == 4
    assert s["evicted_blocks"] == 2
    assert s["cached_bytes"] == 200  # 2 resident blocks * 100 bytes
    probes.reset_prefix_stats()
    assert probes.prefix_stats()["counts"] == {}


# -- device-side arena copies ------------------------------------------------


def test_kv_extract_insert_roundtrip(tiny_params):
    """Slot KV -> arena -> second slot is an exact copy."""
    S, n_slots, cache_len = 16, 4, 64
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(1, 97, (1, S)), jnp.int32)
    mask = jnp.ones((1, S), jnp.int32)
    pool = D.pool_init(tiny_params, TINY, n_slots, cache_len,
                       arena_blocks=4, arena_block=B)
    pool = D.pool_admit(tiny_params, ids, mask, pool, jnp.int32(0), TINY)
    # left-padded admission: token 0 sits at cache column cache_len - S
    base = cache_len - S
    idxs = jnp.asarray([2, 0], jnp.int32)
    pool = D.kv_extract(pool, jnp.int32(0), jnp.int32(base), idxs, TINY)
    pool = D.pool_admit_cached(pool, jnp.int32(1), idxs, TINY)
    got_k = np.asarray(pool["k"])[:, 1, :, : 2 * B]
    want_k = np.asarray(pool["k"])[:, 0, :, base : base + 2 * B]
    np.testing.assert_array_equal(got_k, want_k)
    got_v = np.asarray(pool["v"])[:, 1, :, : 2 * B]
    want_v = np.asarray(pool["v"])[:, 0, :, base : base + 2 * B]
    np.testing.assert_array_equal(got_v, want_v)
    np.testing.assert_array_equal(
        np.asarray(pool["slot_mask"])[1, : 2 * B + 1],
        [1] * (2 * B) + [0],
    )


# -- serving: cached admission == cold path ----------------------------------


def _serve(tiny_params, prompts, *, prefix_cache, sequential=False,
           prefix_cache_mb=4.0, n_slots=4):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    chat = TPUDecoderChat(
        params=tiny_params, cfg=TINY, tokenizer=ToyCharTokenizer(64),
        max_new_tokens=NEW, temperature=0.0, max_prompt_tokens=32,
        continuous=True, n_slots=n_slots, chunk_steps=4, pipeline_depth=2,
        prefill_chunk=8, prefix_cache=prefix_cache,
        prefix_cache_mb=prefix_cache_mb,
    )
    try:
        srv = chat._server
        if sequential:
            reqs = []
            for p in prompts:
                r = chat.submit_batch([p], max_new_tokens=NEW)[0]
                assert r.done.wait(timeout=120)
                reqs.append(r)
        else:
            reqs = chat.submit_batch(prompts, max_new_tokens=NEW)
            for r in reqs:
                assert r.done.wait(timeout=120)
        stats = dict(srv.stats)
        used = srv.prefix.used_blocks if srv.prefix is not None else 0
        cap = srv.prefix.capacity_blocks if srv.prefix is not None else 0
        return [r.text for r in reqs], stats, (used, cap, srv.prefix)
    finally:
        chat.close()


@pytest.fixture(scope="module")
def split_prompts():
    # tails of 1..9 chars cross every suffix split: 1-token suffixes,
    # mid-block suffixes, a full-block suffix, and a suffix spilling into
    # a second prefill piece (17..25 prompt tokens, 2 cached blocks)
    return [HEAD + "t" * n for n in range(1, 10)]


@pytest.fixture(scope="module")
def static_truth(tiny_params, split_prompts):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    static = TPUDecoderChat(
        params=tiny_params, cfg=TINY, tokenizer=ToyCharTokenizer(64),
        max_new_tokens=NEW, temperature=0.0, max_prompt_tokens=32,
    )
    return static.__wrapped__(split_prompts, max_new_tokens=NEW)


def test_kill_switch_byte_equality(tiny_params, split_prompts, static_truth,
                                   monkeypatch):
    """PATHWAY_TPU_PREFIX_CACHE=0: no arena, no radix tree, and output
    byte-identical to the plain chunked-admission path."""
    monkeypatch.setenv("PATHWAY_TPU_PREFIX_CACHE", "0")
    got, stats, (_, _, prefix) = _serve(
        tiny_params, split_prompts, prefix_cache=None
    )
    assert prefix is None
    assert stats["prefix_requests"] == 0
    assert got == static_truth


def test_cached_admit_token_equality_every_split(tiny_params, split_prompts,
                                                 static_truth):
    """Sequential shared-head requests: the first inserts, the rest admit
    from the arena — tokens equal the cold path at every suffix split."""
    got, stats, _ = _serve(
        tiny_params, split_prompts, prefix_cache=True, sequential=True
    )
    assert stats["prefix_hit_requests"] >= len(split_prompts) - 1
    assert stats["prefix_hit_tokens"] > 0
    assert got == static_truth


def test_cache_on_burst_equality(tiny_params, split_prompts, static_truth):
    """Same-tick admissions (misses) and later hits share one answer."""
    got, _, _ = _serve(tiny_params, split_prompts, prefix_cache=True)
    assert got == static_truth


def test_serving_lru_respects_byte_budget(tiny_params):
    """A 3-block arena serving 6 distinct 2-block prompts must evict
    instead of growing: used_blocks <= capacity at all times (checked at
    the end; the free list can never go negative mid-run either)."""
    # block_bytes for TINY at block 8: 2 * L2 * H4 * 8 * hd8 * 4B = 4 KiB
    prompts = [c * 16 + "?" for c in "abcdef"]
    _, stats, (used, cap, prefix) = _serve(
        tiny_params, prompts, prefix_cache=True, sequential=True,
        prefix_cache_mb=0.013,
    )
    assert cap == 3
    assert 0 < used <= cap
    assert prefix.stats()["cached_bytes"] == used * prefix.block_bytes
    assert stats["prefix_requests"] == len(prompts)


# -- tier 2: HBM -> host demotion store (PATHWAY_TPU_PREFIX_T2_MB) -----------


def _K(v):
    """One block key: the token tuple of a block of repeated ``v``s."""
    return tuple([v] * B)


def _blob(vals):
    """Per-channel host blobs in the block-major export layout."""
    return {"k": np.asarray([[v, v + 0.5] for v in vals], np.float32)}


def test_host_tier_put_take_pop_once():
    st = HostTierStore(8, block_bytes=100)
    assert st.put((), [_K(1), _K(2)], _blob([1, 2])) == 2
    assert st.used_blocks == 2
    keys, blobs = st.take((), [_K(1), _K(2)])
    assert keys == [_K(1), _K(2)]
    np.testing.assert_array_equal(blobs["k"], _blob([1, 2])["k"])
    # pop-once: the promotion owns the entry now
    assert st.take((), [_K(1), _K(2)]) == ([], None)
    assert st.used_blocks == 0


def test_host_tier_chains_across_entries():
    """A tier-1 match point deeper than one demoted edge still recovers
    the whole continuation: take() chains path -> deeper path."""
    st = HostTierStore(8, block_bytes=100)
    st.put((), [_K(1)], _blob([1]))
    st.put((_K(1),), [_K(2), _K(3)], _blob([2, 3]))
    keys, blobs = st.take((), [_K(1), _K(2), _K(3)])
    assert keys == [_K(1), _K(2), _K(3)]
    np.testing.assert_array_equal(blobs["k"], _blob([1, 2, 3])["k"])


def test_host_tier_refiles_divergent_tail():
    """An edge matched only partway hands back the matched half and
    re-files the tail under the deeper path — mirroring the radix
    tree's mid-edge split, so no demoted bytes are lost."""
    st = HostTierStore(8, block_bytes=100)
    st.put((), [_K(1), _K(2), _K(3)], _blob([1, 2, 3]))
    keys, blobs = st.take((), [_K(1), _K(2), _K(9)])
    assert keys == [_K(1), _K(2)]
    np.testing.assert_array_equal(blobs["k"], _blob([1, 2])["k"])
    keys, blobs = st.take((_K(1), _K(2)), [_K(3)])
    assert keys == [_K(3)]
    np.testing.assert_array_equal(blobs["k"], _blob([3])["k"])
    assert st.used_blocks == 0


def test_host_tier_lru_eviction_and_trim():
    st = HostTierStore(3, block_bytes=100)
    st.put((), [_K(1), _K(2)], _blob([1, 2]))
    st.put((), [_K(3), _K(4)], _blob([3, 4]))  # evicts oldest-in (1,2)
    assert st.used_blocks == 2
    assert st.take((), [_K(1)]) == ([], None)
    assert st.take((), [_K(3)])[0] == [_K(3)]
    # an edge wider than the whole budget is trimmed, never rejected
    st2 = HostTierStore(2, block_bytes=100)
    assert st2.put((), [_K(i) for i in range(4)], _blob(range(4))) == 2
    assert st2.stats() == {
        "capacity_blocks": 2, "used_blocks": 2, "edges": 1,
        "cached_bytes": 200,
    }


def test_tier2_demote_promote_roundtrip_unit():
    """PrefixCache with a tier-2 budget: eviction demotes the dropped
    edge's bytes through the export callback, match_t2 recovers them
    byte-identically from the tier-1 match point, and the entry pops
    exactly once."""
    probes.reset_prefix_stats()
    arena = {}
    c = PrefixCache(
        n_blocks=2, block=B, block_bytes=100, tier2_blocks=4,
        export=lambda ids: {"k": np.stack([arena[i] for i in ids])},
    )
    assert c.tier2 is not None
    _, _, new_ids = c.insert(_toks(1, 2))
    for i, a in enumerate(new_ids):
        arena[a] = np.full((3,), 10.0 + i, np.float32)
    want = np.stack([arena[a] for a in new_ids])
    c.insert(_toks(3, 4))  # arena full: evicts AND demotes (1, 2)
    n, _, node = c.match(_toks(1, 2))
    assert n == 0
    assert probes.prefix_stats()["t2_demoted_blocks"] == 2
    assert c.stats()["tier2"]["used_blocks"] == 2
    hit = c.match_t2(_toks(1, 2), 2, node, n)
    assert hit is not None
    keys, blobs = hit
    assert keys == [_K(1), _K(2)]
    np.testing.assert_array_equal(blobs["k"], want)
    assert c.match_t2(_toks(1, 2), 2, node, n) is None
    assert probes.prefix_stats()["t2_hit_blocks"] == 2


def test_tier2_budget_zero_is_single_tier():
    """tier2_blocks=0 (or no export callback) never constructs the host
    store — eviction frees instead of demoting, bytes drop."""
    c = PrefixCache(n_blocks=2, block=B, block_bytes=100, tier2_blocks=0,
                    export=lambda ids: {})
    assert c.tier2 is None
    c2 = PrefixCache(n_blocks=2, block=B, block_bytes=100, tier2_blocks=4)
    assert c2.tier2 is None


# -- serving: churn -> demote -> tier-2 hit -> promote -> tier-1 hit ---------


def _serve_t2(tiny_params, prefix_t2_mb):
    """Churny single-stream trace against a 3-block tier-1 arena: six
    distinct 3-block heads evict each other (demoting under a tier-2
    budget), then the first head comes back — a tier-2 hit that
    promotes — and a final same-head request lands the tier-1 hit."""
    import time

    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    probes.reset_prefix_stats()
    chat = TPUDecoderChat(
        params=tiny_params, cfg=TINY, tokenizer=ToyCharTokenizer(64),
        max_new_tokens=NEW, temperature=0.0, max_prompt_tokens=32,
        continuous=True, n_slots=4, chunk_steps=4, pipeline_depth=2,
        prefill_chunk=8, prefix_cache=True, prefix_cache_mb=0.013,
        prefix_t2_mb=prefix_t2_mb,
    )
    texts = []
    try:
        srv = chat._server
        # A promotion is adopted only if it reaches the tree before the
        # SAME request's own prefill inserts the head (else it is, validly,
        # dropped as stale). The four prefill ticks are asynchronous
        # dispatches (~0.6 ms in all), so the staging thread wins only when
        # the loop happens to block on an earlier request's drain — about
        # half the runs. The trace under test is the round trip, not that
        # race: hold the request's insert (on the loop thread, where
        # adoption runs too) until its promotion has been staged and
        # adopted.
        insert = srv._prefix_insert

        def insert_after_promotions(*args, **kwargs):
            deadline = time.monotonic() + 10.0
            while srv._t2_pending > 0 and time.monotonic() < deadline:
                if srv._promote_ready:
                    srv._drain_promotions()
                else:
                    time.sleep(0.001)
            return insert(*args, **kwargs)

        srv._prefix_insert = insert_after_promotions

        def run(p):
            r = chat.submit_batch([p], max_new_tokens=NEW)[0]
            assert r.done.wait(timeout=120)
            texts.append(r.text)

        for c in "abcdef":
            run(c * 24 + "?")
        run("a" * 24 + "?")
        assert srv.t2_drain(timeout=30.0)
        run("a" * 24 + "!")
        resident = srv.prefix.match(chat.tokenizer.encode("a" * 24))[0]
        return texts, dict(srv.stats), srv.prefix, resident
    finally:
        chat.close()


@pytest.fixture(scope="module")
def t2_off_truth(tiny_params):
    """Single-tier reference arm (budget 0): the byte-equality truth for
    the tier-2 serving trace."""
    texts, stats, prefix, _ = _serve_t2(tiny_params, 0.0)
    assert prefix.tier2 is None
    assert stats["t2_hit_requests"] == 0
    return texts


def test_tier2_serving_demote_promote_roundtrip(tiny_params, t2_off_truth):
    texts, stats, prefix, resident = _serve_t2(tiny_params, 0.1)
    assert prefix.tier2 is not None
    # the returning head missed tier 1 but hit the host tier...
    assert stats["t2_hit_requests"] >= 1
    s = probes.prefix_stats()
    assert s["t2_lookups"] >= 1 and s["t2_hits"] >= 1
    assert s["hit_rate_t2"] > 0.0
    # ...after churn demoted whole evicted edges into it...
    assert s["t2_demoted_blocks"] >= 3 * 3
    # ...and the promotion landed the head back in the device arena (the
    # final request admits against it)
    assert stats["t2_promoted_blocks"] >= 1
    assert resident == 3
    assert stats["prefix_hit_requests"] >= 1
    # async promotion never forks the numerics: tokens byte-identical to
    # the single-tier arm
    assert texts == t2_off_truth


def test_tier2_kill_switch_budget_zero(tiny_params, t2_off_truth,
                                       monkeypatch):
    """PATHWAY_TPU_PREFIX_T2_MB=0 (the default): no host store, no
    probe/promotion machinery, byte-identical serving."""
    monkeypatch.setenv("PATHWAY_TPU_PREFIX_T2_MB", "0")
    texts, stats, prefix, _ = _serve_t2(tiny_params, None)
    assert prefix.tier2 is None
    assert stats["t2_hit_requests"] == 0
    assert probes.prefix_stats()["t2_lookups"] == 0
    assert texts == t2_off_truth


# -- tokenizer / BPE encode memos (PATHWAY_TPU_TOKENIZE_CACHE) ---------------


@pytest.fixture()
def python_tokenize_path(monkeypatch):
    """Force the Python encode path: the native batch path may pick a
    different pad width below the pow2 bucket, so parity runs compare
    Python-vs-Python."""
    from pathway_tpu.models import tokenizer as tok_mod

    monkeypatch.setattr(tok_mod, "_native_tok", None)
    monkeypatch.setattr(tok_mod, "_native_wp", None)


def test_hash_tokenizer_memo_parity(monkeypatch, python_tokenize_path):
    from pathway_tpu.models.tokenizer import HashTokenizer

    texts = ["alpha beta", "gamma", "alpha beta", ""]
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "0")
    cold = HashTokenizer(vocab_size=1000)(texts, pad_to=16)
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "1")
    tok = HashTokenizer(vocab_size=1000)
    warm1 = tok(texts, pad_to=16)
    warm2 = tok(texts, pad_to=16)  # fully memoized second pass
    assert len(tok._memo) == 3  # deduped ("alpha beta" once)
    for a, b, c in zip(cold, warm1, warm2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_wordpiece_memo_parity(monkeypatch, python_tokenize_path):
    from pathway_tpu.models.tokenizer import WordPieceTokenizer

    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "hello", "world",
             "hel", "##lo", "##rld", "wo"]
    texts = ["hello world", "world", "hello world"]
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "0")
    cold = WordPieceTokenizer(vocab)(texts, pad_to=8)
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "1")
    tok = WordPieceTokenizer(vocab)
    warm1 = tok(texts, pad_to=8)
    warm2 = tok(texts, pad_to=8)
    assert len(tok._memo) == 2
    for a, b, c in zip(cold, warm1, warm2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_bpe_memo_parity(monkeypatch):
    from pathway_tpu.models.bpe import BPETokenizer, bytes_to_unicode

    b2u = bytes_to_unicode()
    syms = sorted({b2u[b] for b in range(256)})
    vocab = {s: i for i, s in enumerate(syms)}
    pair = (b2u[ord("a")], b2u[ord("b")])
    vocab[pair[0] + pair[1]] = len(vocab)
    tok_off = BPETokenizer(vocab, [pair])
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "0")
    cold = [tok_off.encode(t) for t in ("abba", "cab", "abba")]
    assert not tok_off._encode_memo
    monkeypatch.setenv("PATHWAY_TPU_TOKENIZE_CACHE", "1")
    tok_on = BPETokenizer(vocab, [pair])
    warm1 = [tok_on.encode(t) for t in ("abba", "cab", "abba")]
    warm2 = [tok_on.encode(t) for t in ("abba", "cab", "abba")]
    assert len(tok_on._encode_memo) == 2
    assert cold == warm1 == warm2
    # memoized lists are copies: mutating a result must not poison the memo
    warm1[0].append(999)
    assert tok_on.encode("abba") == cold[0]


# -- embedding dedup (PATHWAY_TPU_EMBED_DEDUP) -------------------------------


def test_embed_dedup_parity(monkeypatch):
    import dataclasses

    from pathway_tpu.models import MINILM_L6, SentenceEmbedderModel
    from pathway_tpu.xpacks.llm import embedders

    cfg = dataclasses.replace(
        MINILM_L6, layers=1, hidden=16, heads=2, intermediate=32,
        vocab_size=500, max_position=32,
    )
    model = SentenceEmbedderModel(cfg=cfg, max_length=16)
    texts = ["aa bb", "cc dd", "aa bb", "ee"]
    ref = list(model.embed_batch(texts))

    monkeypatch.setenv("PATHWAY_TPU_EMBED_DEDUP", "1")
    emb = embedders.SentenceTransformerEmbedder(model)
    got1 = emb.__wrapped__(texts)
    assert emb.dedup_stats == {"hits": 1, "misses": 3}
    got2 = emb.__wrapped__(texts)
    assert emb.dedup_stats["hits"] == 5
    # two-phase: an all-hit submit never opens a device handle
    handle = emb.submit_batch(["aa bb", "cc dd"])
    assert handle[0] == "dedup" and handle[1] is None
    (got3,) = emb.resolve_batch([handle])
    for g in (got1, got2):
        for a, b in zip(g, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(got3, ref[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    monkeypatch.setenv("PATHWAY_TPU_EMBED_DEDUP", "0")
    before = dict(emb.dedup_stats)
    raw = emb.submit_batch(texts)
    assert raw[0] == "raw"
    (got_off,) = emb.resolve_batch([raw])
    assert emb.dedup_stats == before
    for a, b in zip(got_off, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
