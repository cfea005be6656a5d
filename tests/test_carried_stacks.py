"""A run's cache rides the layer loop's carry (``decoder._scan_layers``, PR 33).

The program scans a layer index and carries a run's KV stacks whole, so that
every write of the slot pool is in place. What that must not change is any
byte: here each pool function runs on the same inputs as the program runs it
and with the layer loop replaced by the two written below, which share nothing
with ``_scan_layers`` — the same bodies, layer by layer, no ``lax.scan``:

* a COUNTED loop (``lax.fori_loop``) that indexes every leaf by the layer:
  the pools and tokens must be EQUAL, every leaf, bit for bit;
* plain Python ``for`` loops, every layer unrolled into the program: XLA's
  CPU backend then fuses each layer's reductions on its own, so float32
  differs in the last bits (1e-7 to 3e-6 read) and the comparison is to 2e-5,
  the tolerance a dropped term fails by a factor of fifty.

For a toy of each layout: GPT-2's single run (dense, int8, paged, on the CPU
mesh), afmoe's four runs (one of them two window layers, rings that wrap),
``deepseek_v2``'s two latent runs, ``ouro``'s stack run four times (PR 34: the
loop over passes by plain loops too, tokens leaving at different passes);
through the prefill pieces, the decode chunk and the speculative cycle with a
draft prefix that ends INSIDE a run (of a looped stack: its typed refusal).
"""

import contextlib
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
from tests import test_latent_decoder as LATENT  # noqa: E402
from tests import test_window_moe_decoder as AFMOE  # noqa: E402

SLOTS, CACHE_LEN, BUCKET, PIECE, PROMPT = 4, 96, 48, 16, 40
EXPERTS = ("moe_in_w", "moe_up_w", "moe_out_w")
SHORT = ("k", "v", "k_scale", "v_scale")


def _runs(cfg, params, kv, n_layers):
    """(kind, layers visited, the run's leaves, its names, its stacks)."""
    leaves = ([params["layers"]] if cfg.uniform else
              [params["layers"][f"run{r}"] for r in range(len(cfg.runs()))])
    for r, (kind, _first, n) in enumerate(cfg.runs(n_layers)):
        names = D._kv_names(cfg, r, kind)
        stacks = {s: (kv.get(name) if name else None)
                  for s, name in zip(SHORT, names)}
        assert stacks["k"] is not None      # every pass here has a pool
        yield kind, n, leaves[r], names, stacks


def _layer_leaves(lp, layer):
    """One layer's own leaves; its experts stay whole and are read by
    ``moe_layer``, as ``models/moe.py`` reads them."""
    out = {k: (a if k in EXPERTS else jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False),
        a)) for k, a in lp.items()}
    if "moe_in_w" in lp:
        out["moe_layer"] = layer
    return out


def _loops(counted: bool):
    """The contract of ``decoder._scan_layers`` by a counted loop or by
    plain ones: for each run of like layers, for each of its first layers,
    ``body`` on that layer's leaves, the run's stacks handed from one layer
    to the next. A LOOPED stack (PR 34): that, once a pass, ``body`` handed
    the pass's own index in the stacks (``u`` times the run's layers
    further down), the final norm closing every pass, the gate reading each
    pass's normed state and the exit rule picking among them."""
    def one_pass(cfg, params, x, kv, body, n_layers, u):
        out, counts = dict(kv), None
        for kind, n, lp, names, stacks in _runs(cfg, params, kv, n_layers):
            def one(layer, carry, kind=kind, lp=lp, base=u * n):
                x, st, c = carry
                x, st, cnt = body(x, _layer_leaves(lp, layer), st,
                                  layer + base if u else layer, kind)
                return x, st, (c if cnt is None else c + cnt)

            carry = (x, stacks, jnp.zeros((2,), jnp.uint32))
            if counted:
                carry = jax.lax.fori_loop(0, n, one, carry)
            else:
                for layer in range(n):
                    carry = one(jnp.int32(layer), carry)
            x, stacks, c = carry
            if "moe_in_w" in lp:
                counts = c if counts is None else counts + c
            for s, name in zip(SHORT, names):
                if name is not None and stacks[s] is not None:
                    out[name] = stacks[s]
        return x, out, counts

    def layers(cfg, params, x, kv, body, n_layers=None):
        if cfg.loops == 1:
            return (*one_pass(cfg, params, x, kv, body, n_layers, 0), None)
        assert n_layers is None and cfg.exit_gate and cfg.moe is None
        states, z = [], []
        for u in range(cfg.loops):
            x, kv, _counts = one_pass(cfg, params, x, kv, body, None, u)
            h = D._rms(x, params["ln_f_scale"], cfg.layer_norm_eps)
            z.append(jnp.einsum("bsh,ho->bs", h, params["exit_w"],
                                precision="highest") + params["exit_b"][0])
            x = h.astype(cfg.dtype)
            states.append(x)
        p, step = D.loop_exit(jnp.stack(z), cfg.exit_threshold)
        x = jnp.take_along_axis(jnp.stack(states),
                                step[None, :, :, None], axis=0)[0]
        return x, kv, None, (step, p)

    return layers


@contextlib.contextmanager
def layer_loop(counted: bool):
    was = D._scan_layers
    D._scan_layers = _loops(counted)
    try:
        yield
    finally:
        D._scan_layers = was


GPT2 = D.DecoderConfig(
    vocab_size=128, hidden=32, layers=4, heads=4, intermediate=64,
    max_position=128, dtype=jnp.float32,
)


def _gpt2(kind):
    params = D.init_params(jax.random.PRNGKey(0), GPT2)
    if kind == "paged":
        m = CACHE_LEN // 16
        pool = D.paged_pool_init(params, GPT2, SLOTS, CACHE_LEN,
                                 n_blocks=SLOTS * m + 1, block=16)
        pool["block_tbl"] = jnp.asarray(
            1 + np.arange(SLOTS * m, dtype=np.int32).reshape(SLOTS, m))
    else:
        pool = D.pool_init(params, GPT2, SLOTS, CACHE_LEN,
                           kv_quant=kind == "int8")
    if kind == "mesh":
        from pathway_tpu.parallel.mesh import make_serving_mesh

        mesh = make_serving_mesh(jax.devices(), data=1, fsdp=2, tp=4)
        params = D.shard_decoder_params(params, GPT2, mesh)
        pool = D.shard_pool(pool, GPT2, mesh)
    return GPT2, params, pool, 2        # a draft of 2 of the run's 4 layers


def _afmoe():
    layout = M.resolve(M.load_manifest(), "layouts", "afmoe")
    params = W.make_params(7, W.STREAM_DECODER,
                           layout.weight_spec(AFMOE.MODEL, "decoder"))
    cfg = dataclasses.replace(layout.program_config(AFMOE.MODEL),
                              dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pool = D.pool_init(p32, cfg, SLOTS, CACHE_LEN, window_slack=8)
    assert D.pool_ring(pool) == 24 and pool["kw3"].shape[0] == 2
    return cfg, p32, pool, 4            # ... ends inside the run of two


def _deepseek():
    layout = M.resolve(M.load_manifest(), "layouts", "deepseek_v2")
    params = W.make_params(7, W.STREAM_DECODER,
                           layout.weight_spec(LATENT.MODEL, "decoder"))
    cfg = dataclasses.replace(layout.program_config(LATENT.MODEL),
                              dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pool = D.pool_init(p32, cfg, SLOTS, CACHE_LEN)
    assert pool["cl1"].shape[0] == 2
    return cfg, p32, pool, 2            # ... inside the expert layers' run


def _ouro():
    """Three layers run four times, a threshold that lets tokens leave at
    different passes: twelve cache layers in the one pair of stacks."""
    from tests import test_looped_decoder as LOOPED

    layout = M.resolve(M.load_manifest(), "layouts", "ouro")
    params = W.make_params(7, W.STREAM_DECODER,
                           layout.weight_spec(LOOPED.MODEL, "decoder"))
    cfg = dataclasses.replace(layout.program_config(LOOPED.MODEL),
                              dtype=jnp.float32, exit_threshold=0.5)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pool = D.pool_init(p32, cfg, SLOTS, CACHE_LEN)
    assert pool["k"].shape[0] == 12
    return cfg, p32, pool, None         # no depth prefix is a draft of it


LAYOUTS = {
    "gpt2": lambda: _gpt2("dense"), "gpt2-int8": lambda: _gpt2("int8"),
    "gpt2-paged": lambda: _gpt2("paged"), "gpt2-mesh": lambda: _gpt2("mesh"),
    "afmoe": _afmoe, "deepseek_v2": _deepseek, "ouro": _ouro,
}


def _prefill(cfg, params, pool, slot):
    """A left-padded prompt of 40 in a bucket of 48, three pieces of 16
    (afmoe's ring of 24 wraps between them)."""
    ids = np.zeros((1, BUCKET), np.int32)
    mask = np.zeros((1, BUCKET), np.int32)
    ids[0, BUCKET - PROMPT:] = np.random.default_rng(slot).integers(
        1, cfg.vocab_size, PROMPT)
    mask[0, BUCKET - PROMPT:] = 1
    pos = np.clip(np.cumsum(mask, 1) - 1, 0, None).astype(np.int32)
    n = np.asarray([PROMPT], np.int32)
    for o in range(0, BUCKET, PIECE):
        pool = jax.jit(lambda p, i, m, ps, pl, o=o: D.pool_prefill_chunk(
            p, i, m, ps, pl, np.int32(slot), np.int32(o), n, cfg,
            first=o == 0, last=o == BUCKET - PIECE))(
                params, ids[:, o:o + PIECE], mask[:, o:o + PIECE],
                pos[:, o:o + PIECE], pool)
    return pool


def _run(name, op):
    cfg, params, pool, draft = LAYOUTS[name]()
    lanes = np.asarray([False, True, False, True])
    for slot in (1, 3):
        pool = _prefill(cfg, params, pool, slot)
    if op == "prefill":
        return pool, None
    if op == "decode":
        return jax.jit(lambda p, pl: D.pool_decode_chunk(
            p, pl, lanes, jax.random.PRNGKey(0), cfg, 5))(params, pool)
    if draft is None:
        with pytest.raises(D.UnsupportedForLayout, match="spec_decode"):
            D.pool_decode_spec(params, pool, lanes, cfg, 3, draft_layers=1,
                               n_spec=3)
        return pool, None
    pool, toks, n_emit = jax.jit(lambda p, pl: D.pool_decode_spec(
        p, pl, lanes, cfg, 3, draft_layers=draft, n_spec=3))(params, pool)
    return pool, (toks, n_emit)


@pytest.mark.parametrize("op", ["prefill", "decode", "spec"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_the_carried_stacks_give_the_plain_loops_bytes(name, op):
    pool, toks = _run(name, op)
    with layer_loop(counted=True):
        want_pool, want_toks = _run(name, op)
    assert sorted(pool) == sorted(want_pool)
    for leaf in pool:
        got, want = np.asarray(pool[leaf]), np.asarray(want_pool[leaf])
        assert got.dtype == want.dtype and np.array_equal(got, want), leaf
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        toks, want_toks))
    # the run was not empty: both lanes hold a prompt and have moved
    assert int(np.asarray(pool["write"])[1]) >= BUCKET
    assert np.asarray(pool["slot_mask"])[3].sum() >= PROMPT
    if op != "decode":
        return      # its pools went through the pieces too; an accepted
        #             draft is an argmax, not a thing to 2e-5
    with layer_loop(counted=False):
        want_pool, _toks = _run(name, op)
    for leaf in pool:
        got, want = np.asarray(pool[leaf]), np.asarray(want_pool[leaf])
        if got.dtype == np.int8:        # a payload may round the other way
            assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
        elif got.dtype.kind == "f" and leaf != "logits":
            assert np.abs(got - want).max() < 2e-5, leaf


def test_a_draft_leaves_the_pool_a_caller_keeps_as_it_was():
    """``pool_decode_draft`` writes its shallow rows into the carried stacks
    and drops them: the pool it was handed is untouched, and the drafts are
    the counted loop's."""
    cfg, params, pool, draft = _afmoe()
    pool = _prefill(cfg, params, pool, 1)
    before = jax.tree.map(np.asarray, pool)
    lanes = np.asarray([False, True, False, False])
    step = lambda: jax.jit(lambda p, pl: D.pool_decode_draft(  # noqa: E731
        p, pl, lanes, cfg, draft_layers=draft, n_draft=3))(params, pool)
    drafts = step()
    with layer_loop(counted=True):
        want = step()
    assert np.array_equal(np.asarray(drafts), np.asarray(want))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(a, np.asarray(b)), before, pool))
