"""Pieces of several waiting prompts as ONE grouped prefill dispatch
(``pool_prefill_chunk`` over (G, T) pieces, every lane's slot, offset and
flags traced) against the same pieces dispatched one at a time.

CPU, float32, small widths, seeded weights. What is compared is the whole
pool — every run array, ``slot_mask``, ``pos``, ``write``, ``logits`` and the
device counters — to 1e-6 of each array's largest value (the expert walk's
tiles hold other rows when a group's tokens are sorted by expert, so a float
may round differently: 1.3e-6 on keys of 3.2 is the most read here), and
the greedy tokens of the decode that follows, which must be identical. The lanes of a group sit at different offsets and
mix first, middle and last pieces; one lane's first piece lies partly in the
left padding, and one prompt is right-padded with its last real token in the
middle of its last piece (the prefix cache's layout, ``last_col``)."""

import functools
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

T, SLOTS, COLUMNS = 8, 4, 64
TOL = 1e-6

# small widths in the published layouts' shapes (the same as the layouts'
# own tests): window, full and routed-expert layers; latent attention with
# group-limited routing; a stack run three times
AFMOE = {
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
DEEPSEEK = {
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
OURO = {
    "layout": "ouro", "model_type": "ouro", "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "layer_types": ["full_attention"] * 3,
    "num_hidden_layers": 3, "vocab_size": 251, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": 3, "early_exit_threshold": 1,
    "max_position_embeddings": 4096, "torch_dtype": "bfloat16",
}


def _layout_net(model):
    layout = M.resolve(M.load_manifest(), "layouts", model["layout"])
    params = W.make_params(7, W.STREAM_DECODER,
                           layout.weight_spec(model, "decoder"))
    cfg = layout.program_config(model)
    import dataclasses

    return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
            dataclasses.replace(cfg, dtype=jnp.float32))


def _gpt2_net():
    cfg = D.DecoderConfig(vocab_size=64, hidden=16, layers=2, heads=2,
                          intermediate=32, max_position=64, dtype=jnp.float32)
    return D.init_params(jax.random.PRNGKey(3), cfg), cfg


NETS = {
    "afmoe": lambda: _layout_net(AFMOE),
    "latent": lambda: _layout_net(DEEPSEEK),
    "looped": lambda: _layout_net(OURO),
    "gpt2": _gpt2_net,
}


def _left(prompt, bucket):
    """A left-padded prompt's pieces, as the server cuts them: pieces wholly
    in the padding skipped, the first live one opens the row."""
    ids = np.zeros((1, bucket), np.int32)
    mask = np.zeros((1, bucket), np.int32)
    ids[0, bucket - len(prompt):] = prompt
    mask[0, bucket - len(prompt):] = 1
    pos = np.clip(np.cumsum(mask[0]) - 1, 0, None)[None, :].astype(np.int32)
    pieces = [(ids[:, o:o + T], mask[:, o:o + T], pos[:, o:o + T], o)
              for o in range(0, bucket, T) if mask[0, o:o + T].any()]
    return pieces, len(prompt), T - 1


def _right(prompt):
    """A right-padded prompt (the prefix cache's layout: token i at column
    i): its last real token may lie mid-piece."""
    n = len(prompt)
    width = -(-n // T) * T
    ids = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), np.int32)
    ids[0, :n] = prompt
    mask[0, :n] = 1
    pos = np.minimum(np.arange(width), n - 1)[None, :].astype(np.int32)
    pieces = [(ids[:, o:o + T], mask[:, o:o + T], pos[:, o:o + T], o)
              for o in range(0, width, T)]
    return pieces, n, (n - 1) - (width - T)


def _admissions(vocab):
    """slot -> (tick admitted, pieces, prompt length, last piece's column):
    lanes that start apart, so that a tick's group mixes first, middle and
    last pieces at different offsets; slot 2's first piece is half padding,
    slot 3's prompt right-padded and ending mid-piece."""
    rng = np.random.default_rng(11)

    def prompt(n):
        return rng.integers(1, vocab, n).astype(np.int32).tolist()

    return {0: (0, *_left(prompt(32), 32)), 1: (1, *_left(prompt(13), 16)),
            2: (0, *_left(prompt(20), 24)), 3: (2, *_right(prompt(19)))}


@functools.lru_cache(maxsize=None)
def _one_fn(cfg, first, last, with_col, flash):
    if with_col:
        return jax.jit(lambda p, i, m, ps, pl, s, st, n, c: D.pool_prefill_chunk(
            p, i, m, ps, pl, s, st, n, cfg, first=first, last=last,
            last_col=c, flash=flash))
    return jax.jit(lambda p, i, m, ps, pl, s, st, n: D.pool_prefill_chunk(
        p, i, m, ps, pl, s, st, n, cfg, first=first, last=last, flash=flash))


@functools.lru_cache(maxsize=None)
def _group_fn(cfg, flash):
    return jax.jit(lambda p, i, m, ps, pl, s, st, n, f, la, c:
                   D.pool_prefill_chunk(p, i, m, ps, pl, s, st, n, cfg,
                                        first=f, last=la, last_col=c,
                                        flash=flash))


def _prefill(params, cfg, pool, adm, grouped: bool, flash: bool = False):
    """Every slot's pieces, one a tick from its admission on: each alone,
    or a tick's pieces as ONE group where two or more wait. Returns the
    pool and the widths of the dispatches made."""
    left = {s: list(a[1]) for s, a in adm.items()}
    widths = []
    tick = 0
    while any(left.values()):
        lanes = []
        for s in sorted(left):
            if adm[s][0] > tick or not left[s]:
                continue
            ids, mask, pos, off = left[s].pop(0)
            first = off == adm[s][1][0][3]
            last = not left[s]
            lanes.append((s, ids, mask, pos, off, np.asarray([adm[s][2]],
                          np.int32), first, last, adm[s][3]))
        tick += 1
        if not lanes:
            continue
        if grouped and len(lanes) > 1:
            col = lambda i, dt: np.asarray([ln[i] for ln in lanes], dt)
            cat = lambda i: np.concatenate([ln[i] for ln in lanes])
            pool = _group_fn(cfg, flash)(
                params, cat(1), cat(2), cat(3), pool, col(0, np.int32),
                col(4, np.int32), cat(5), col(6, bool), col(7, bool),
                np.asarray([ln[8] if ln[7] else T - 1 for ln in lanes],
                           np.int32))
            widths.append(len(lanes))
            continue
        for s, ids, mask, pos, off, n, first, last, lc in lanes:
            with_col = last and lc != T - 1
            args = (params, ids, mask, pos, pool, np.int32(s), np.int32(off),
                    n) + ((np.int32(lc),) if with_col else ())
            pool = _one_fn(cfg, first, last, with_col, flash)(*args)
            widths.append(1)
    return pool, widths


def _stale_pool(params, cfg, quant=False):
    """A pool whose slots held earlier occupants: every mask row live, so a
    first piece that failed to clear its row would attend garbage."""
    pool = D.pool_init(params, cfg, SLOTS, COLUMNS, kv_quant=quant,
                       window_slack=8)
    return {**pool, "slot_mask": jnp.ones_like(pool["slot_mask"])}


def _decode(params, cfg, pool):
    chunk = jax.jit(lambda p, pl, a, k: D.pool_decode_chunk(p, pl, a, k, cfg,
                                                            6))
    _pool, toks = chunk(params, pool, np.ones(SLOTS, bool),
                        jax.random.PRNGKey(0))
    return np.asarray(toks)


def _assert_pools_agree(a, b):
    assert set(a) == set(b)
    for name in a:
        x, y = np.asarray(a[name]), np.asarray(b[name])
        if np.issubdtype(x.dtype, np.floating):
            scale = max(1.0, float(np.abs(x).max()))
            assert np.abs(x.astype(np.float64) - y).max() <= TOL * scale, \
                name
        else:
            assert np.array_equal(x, y), name


@pytest.mark.parametrize("net,flash,quant", [
    ("afmoe", False, False), ("afmoe", True, False), ("latent", False, False),
    ("latent", True, False), ("looped", False, False), ("gpt2", False, False),
    ("gpt2", False, True)])
def test_a_group_leaves_the_pool_as_its_pieces_one_at_a_time(net, flash,
                                                              quant):
    params, cfg = NETS[net]()
    adm = _admissions(cfg.vocab_size)
    one, w1 = _prefill(params, cfg, _stale_pool(params, cfg, quant), adm,
                       grouped=False, flash=flash)
    grp, wg = _prefill(params, cfg, _stale_pool(params, cfg, quant), adm,
                       grouped=True, flash=flash)
    # the schedule holds groups of 2, 3 and 4 lanes, the first ticks' first
    # pieces beside later ones' middle and last
    assert set(w1) == {1} and {2, 3, 4} <= set(wg) and sum(wg) == sum(w1)
    _assert_pools_agree(one, grp)
    assert np.asarray(grp["write"]).tolist() == [
        32, 16, 24, 19]      # each slot's next column: after its last token
    toks = _decode(params, cfg, one)
    assert np.array_equal(toks, _decode(params, cfg, grp))


def _cell(config, layout, slots, columns):
    import json

    with open(os.path.join(BENCH, "configs", config)) as f:
        model = json.load(f)["models"]["decoder"]
    cfg = M.resolve(M.load_manifest(), "layouts", layout).program_config(
        model)
    params = jax.eval_shape(lambda: D.cast_params_for_inference(
        D.init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, slots, columns))
    return cfg, params, pool


@pytest.mark.parametrize("config,layout,slots,columns,piece,width,lo,hi", [
    ("trinity-large-ep8-rag.json", "afmoe", 16, 8304, 512, 4, 70, 80),
    ("deepseek-v2-ep8-rag.json", "deepseek_v2", 8, 16528, 512, 2, 190, 205),
    ("ouro-2.6b-rag.json", "ouro", 4, 656, 256, 1, 250, 260)])
def test_the_width_rule_at_the_published_configurations(
        config, layout, slots, columns, piece, width, lo, hi):
    """From the program's own parameter tree: Trinity's stage reads 8.3 GB of
    layers for 74 FLOP a byte, DeepSeek-V2's 196 (just under the ridge of
    205: two lanes), Ouro's 256 (over it: pieces stay alone)."""
    cfg, params, pool = _cell(config, layout, slots, columns)
    assert lo < D.prefill_intensity(cfg, params, piece) < hi
    assert D.prefill_group_width(cfg, params, piece, pool) == width


def test_the_width_rule_gives_one_for_paged_pools_and_the_mesh():
    params, cfg = _gpt2_net()
    pool = D.pool_init(params, cfg, SLOTS, COLUMNS)
    # a dense f32 piece of 8 tokens is 4 FLOP a byte: as wide as the slots
    assert D.prefill_group_width(cfg, params, T, pool) == SLOTS
    paged = D.paged_pool_init(params, cfg, SLOTS, COLUMNS, n_blocks=33,
                              block=8)
    assert D.prefill_group_width(cfg, params, T, paged) == 1
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    assert D.prefill_group_width(cfg, params, T, pool, mesh) == 1
    # past the ridge a piece stays alone
    assert D.prefill_group_width(cfg, params, 512, pool) == 1


# ---- the server -------------------------------------------------------------


class _Ids:
    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _chat(monkeypatch, ridge=None, **kw):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    if ridge is not None:
        monkeypatch.setattr(D, "PREFILL_RIDGE", ridge)
    params, cfg = _gpt2_net()
    return TPUDecoderChat(
        params=params, cfg=cfg, tokenizer=_Ids(), max_new_tokens=4,
        temperature=0.0, max_prompt_tokens=32, continuous=True,
        n_slots=SLOTS, chunk_steps=4, pipeline_depth=2, prefill_chunk=T,
        prefix_cache=False, **kw)


PROMPTS = [list(range(1 + k, 23 + k)) for k in range(6)]


def _serve(chat):
    srv = chat._server
    reqs = [srv.submit(list(p), 4) for p in PROMPTS]
    for r in reqs:
        assert r.done.wait(timeout=300)
    return [r.tokens for r in reqs]


def test_the_server_groups_waiting_pieces_and_answers_the_same(monkeypatch):
    """Four slots, prompts of three pieces: the server cuts its waiting
    pieces into groups of the rule's width and serves the same greedy
    tokens as a server whose pieces go alone; the warm start compiled the
    grouped piece, and the counter says how the pieces went."""
    from pathway_tpu.engine import probes

    alone = _chat(monkeypatch, ridge=0.0)
    try:
        assert alone._server.prefill_group == 1
        assert not any("group" in k for k in alone._server._prefill_fns)
        want = _serve(alone)
    finally:
        alone.close()
    monkeypatch.undo()
    probes.REGISTRY.remove("prefill_group_lanes")
    chat = _chat(monkeypatch)
    try:
        srv = chat._server
        assert srv.prefill_group == SLOTS
        assert (T, "group") in srv._prefill_fns
        assert srv._prefill_group_fn(T).__name__ == "piece"
        assert _serve(chat) == want
        lanes = probes.REGISTRY.labelled("prefill_group_lanes", "width")
        assert lanes.get(str(SLOTS), 0) > 0
        assert sum(lanes.values()) == srv.stats["prefill_chunks"]
    finally:
        chat.close()


def test_a_faulting_group_rewinds_its_lanes_and_no_other(monkeypatch):
    """Under supervision a fault in a grouped dispatch fails every request
    of its group (no retries left) and no other: the rest are answered."""
    monkeypatch.setenv("PATHWAY_TPU_SERVE_RESTARTS", "1")
    monkeypatch.setenv("PATHWAY_TPU_SERVE_RETRIES", "0")
    chat = _chat(monkeypatch, ridge=8.0)       # 8 / 4 FLOP a byte: 2 lanes
    try:
        srv = chat._server
        assert srv.prefill_group == 2
        real = srv._prefill_group_fn(T)
        hit = []

        def faulty(*args):
            if not hit:
                hit.extend(srv.slots[int(s)] for s in args[5])
                raise RuntimeError("injected")
            return real(*args)

        srv._prefill_fns[(T, "group")] = faulty
        reqs = [srv.submit(list(p), 4) for p in PROMPTS[:3]]
        for r in reqs:
            assert r.done.wait(timeout=300)
        assert len(hit) == 2 and srv.failed is None
        for r in reqs:
            if r in hit:
                assert r.text is None and r.error_reason == "failed"
            else:
                assert len(r.tokens) == 4
        assert srv.stats["request_failures"] == 2
    finally:
        chat.close()
