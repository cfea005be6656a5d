"""The reranker bounds its own dispatch (``cross_encoder._MAX_SCORE_BYTES``):
the dense attention of one dispatch keeps rows x heads x S x S float32
scores a layer, so a batch that would pass the bound goes as several even
dispatches. Every batch the accepted cells send stays ONE dispatch, as it
was; the long-context cell's 384 pairs of 423 tokens (a bucket of 512) go as
three of 128. CPU, toy widths: counts and equality, never a time."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from pathway_tpu.models import MINILM_L6
from pathway_tpu.models import cross_encoder
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.tokenizer import HashTokenizer


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        MINILM_L6, layers=2, hidden=32, heads=4, intermediate=64,
        vocab_size=2048, max_position=64, dtype=jnp.float32)
    return CrossEncoderModel(
        cfg=cfg, max_length=48,
        tokenizer=HashTokenizer(vocab_size=2048, max_length=48))


@pytest.mark.parametrize("pairs,seq,sizes", [
    (512, 201, [512]),              # the accepted cells' rerank dispatch
    (32, 201, [32]),                # one request's candidates
    (384, 423, [128, 128, 128]),    # the long-context cell: 8 clients x 48
    (144, 423, [72, 72]),           # three of its clients in one epoch
    (48, 423, [48]),                # one
])
def test_dispatches_at_the_cells_shapes(pairs, seq, sizes):
    m = CrossEncoderModel.__new__(CrossEncoderModel)
    m.cfg, m.flash_prefill = MINILM_L6, False
    assert [len(r) for r in m._dispatch_rows(pairs, seq)] == sizes
    m.flash_prefill = True      # the tiled read keeps no scores
    assert [len(r) for r in m._dispatch_rows(pairs, seq)] == [pairs]


def test_a_split_batch_scores_as_the_whole_batch_in_order(model, monkeypatch):
    pairs = [(f"w{i} w{i + 1}", " ".join(f"w{j}" for j in range(i, i + 9)))
             for i in range(21)]
    whole = model.score_batch(pairs)
    assert len(set(np.round(whole, 6))) > 10        # an order to keep
    monkeypatch.setattr(cross_encoder, "_MAX_SCORE_BYTES",
                        8 * model.cfg.heads * 16 * 16 * 4)      # 8 rows
    out, n = model.score_submit(pairs)
    assert n == 21 and isinstance(out, tuple) and len(out) == 3
    split = model.score_resolve([(out, n)])[0]
    assert split.shape == whole.shape == (21,)
    assert float(np.abs(split - whole).max()) < 1e-6
