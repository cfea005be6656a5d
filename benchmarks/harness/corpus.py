"""Documents and queries from ``--seed``, made in bulk with numpy before the
window. Vocabulary and tokenizer rule are ``chip_smoke.py``'s (copied):
5,000 seeded words of 3..8 letters; a document is ``doc_words`` of them.

Documents are composed of seeded 10-word phrases (a pool of 4,096), so that
a hundred thousand distinct 100-word texts are ten joins each and not a
hundred lookups: no two documents are alike (10 phrases of 4,096), which is
what the engine, the tokenizer's memo and the embedder's dedup must see.
A query is a near-duplicate of one document: ``query_words`` of its words
(whole phrases) with a tenth of the phrases replaced, so it owns a nearest
neighbour with a margin that bfloat16 cannot reorder."""

from __future__ import annotations

import zlib

import numpy as np

PHRASE = 10


def make_vocab(rng, n: int = 5000) -> np.ndarray:
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    return np.array([
        "".join(rng.choice(letters, rng.integers(3, 9))) for _ in range(n)
    ])


class Corpus:
    def __init__(self, seed: int, doc_words: int = 100, n_phrases: int = 4096):
        if doc_words % PHRASE:
            raise ValueError(f"doc_words must be a multiple of {PHRASE}")
        self.doc_words = doc_words
        self.rng = np.random.default_rng(seed)
        vocab = make_vocab(self.rng)
        ids = self.rng.integers(0, len(vocab), (n_phrases, PHRASE))
        self.phrases = [" ".join(vocab[row]) for row in ids]
        self.per_doc = doc_words // PHRASE
        self._doc_phrases = np.zeros((0, self.per_doc), np.int64)

    def documents(self, n: int) -> list[str]:
        """The NEXT ``n`` documents (ids continue from the last call)."""
        rows = self.rng.integers(0, len(self.phrases), (n, self.per_doc))
        self._doc_phrases = np.concatenate([self._doc_phrases, rows])
        ph = self.phrases
        return [" ".join([ph[j] for j in row]) for row in rows.tolist()]

    def text_of(self, doc_id: int) -> str:
        return " ".join(self.phrases[j] for j in self._doc_phrases[doc_id])

    @property
    def n_documents(self) -> int:
        return len(self._doc_phrases)

    def queries(self, n: int, query_words: int, first_doc: int = 0,
                n_docs: int | None = None) -> list[tuple[int, str]]:
        """``n`` distinct queries ``(source_doc_id, text)`` over documents
        ``first_doc .. first_doc + n_docs``: the first ``query_words`` of the
        source with one phrase in ten (at least one) replaced."""
        n_docs = self.n_documents - first_doc if n_docs is None else n_docs
        k = query_words // PHRASE
        swaps = max(1, k // 10)
        src = first_doc + self.rng.integers(0, n_docs, n)
        body = self._doc_phrases[src][:, :k].copy()
        for s in range(swaps):
            at = self.rng.integers(0, k, n)
            body[np.arange(n), at] = self.rng.integers(
                0, len(self.phrases), n)
        ph = self.phrases
        seen: set[str] = set()
        out = []
        for d, row in zip(src.tolist(), body.tolist()):
            text = " ".join([ph[j] for j in row])
            while text in seen:  # distinct, whatever the draw
                row[-1] = int(self.rng.integers(0, len(ph)))
                text = " ".join([ph[j] for j in row])
            seen.add(text)
            out.append((d, text))
        return out


class WordTokenizer:
    """Seeded word-level tokenizer for the decoder (``chip_smoke.py``'s):
    one id per whitespace-separated word, no EOS (every request spends its
    whole budget), ids decode to ``t<id>`` so the served tokens can be read
    back from the reply. It is the BENCHMARK's object, handed to the
    program: it records the length of every prompt it encodes, keyed by the
    prompt's question, for the truncation check and the reference."""

    eos_id = None

    def __init__(self, vocab_size: int, seed: int):
        self.vocab_size = vocab_size
        self.seed = seed % (2 ** 32)
        self.prompts: dict[str, str] = {}

    def encode(self, text: str) -> list[int]:
        span = self.vocab_size - 1
        at = text.rfind("Question: ")
        if at >= 0:
            question = text[at + 10:].split("\n", 1)[0]
            self.prompts[question] = text
        return [
            1 + zlib.crc32(w.encode(), self.seed) % span for w in text.split()
        ]

    def decode(self, ids) -> str:
        return " ".join(f"t{int(i)}" for i in ids)
