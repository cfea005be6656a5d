"""The comparison that decides ``correct``: what the TIMED path produced, at
the timed sizes, against the plain references (each model's layout brings
its own: :mod:`harness.layouts`), each number beside a limit of its own
(the configuration's ``limits``).

A check is named in the mix's file (``"check"``) and found by
``manifest.resolve``: one of :data:`CHECKS`, or ``Check`` of a
``<path>/checks/<name>.py``. It has two halves:
``collect`` runs while the system is alive and takes only what the window
produced (index rows, replies); ``compare`` runs after the system's state is
freed and computes the references. ``control=True`` also computes every
number with the reference at fp8 in the program's place: those readings
must FAIL the limits, or the limits are too loose.
"""

from __future__ import annotations

import numpy as np

from . import reference as R
from .system import System


def _tokens_single(words: int) -> int:
    return words + 2            # [CLS] words [SEP]


def _tokens_pair(a_words: int, b_words: int) -> int:
    return a_words + b_words + 3


class Check:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.dep = config["deployment"]
        self.models = config["models"]
        self.layouts = config["layouts"]
        self.rng = np.random.default_rng(seed + 1)

    # numbers that need no reference: name -> value (limit 0)
    def exact(self, system: System, window) -> dict:
        return {}

    def collect(self, system: System, window) -> dict:
        return {}

    def compare(self, got: dict, params: dict, control: bool):
        """-> (numbers, control_numbers)"""
        return {}, {}

    def needed_flops(self, window) -> float:
        raise NotImplementedError

    def _embed(self, params, texts, precision="f32"):
        return self.layouts["embedder"].embed(
            params["embedder"], self.models["embedder"], texts,
            self.dep["embed_max_length"], precision)

    def _score(self, params, pairs, precision="f32"):
        return self.layouts["reranker"].score(
            params["reranker"], self.models["reranker"], pairs,
            self.dep["rerank_max_length"], precision)


class IngestCheck(Check):
    """Cell kind *ingest*: no document lost or duplicated (engine), the
    vectors as served (embedder), documents ingested in the window are found
    (index)."""

    def needed_flops(self, window) -> float:
        per_doc = self.layouts["embedder"].encoder_flops(
            self.models["embedder"], _tokens_single(self.dep["doc_words"]))
        return window.counters["docs_landed"] * per_doc

    def exact(self, system, window):
        want = self.dep["index_warm_rows"] + system.committed_docs
        return {
            "docs_lost_or_duplicated": abs(
                system.rows_seen - system.committed_docs) + sum(
                abs(len(ix) - want) for ix in system.instances),
        }

    def collect(self, system, window):
        ids = window.sample["window_doc_ids"]
        n = min(self.traffic["check_docs"], len(ids))
        if n == 0:
            return {"doc_ids": [], "texts": [], "rows": [], "not_found": 0}
        chosen = sorted(self.rng.choice(len(ids), n, replace=False).tolist())
        doc_ids = [ids[i] for i in chosen]
        key_of = {d: k for k, d in system.doc_of_key.items()}
        rows, not_found = [], 0
        for ix in system.instances:
            keys = [key_of.get(d) for d in doc_ids]
            if any(k is None or k not in ix._slot_of for k in keys):
                not_found += sum(
                    k is None or k not in ix._slot_of for k in keys)
                continue
            slots = np.asarray([ix._slot_of[k] for k in keys])
            served = np.asarray(ix._corpus[slots]).astype(np.float32)
            rows.append(served)
            # each served vector finds its own document first (in blocks:
            # a search holds 96 MB of scores a query at this capacity)
            for a in range(0, len(keys), 16):
                hits = ix.search(served[a:a + 16], 1)
                not_found += sum(not h or h[0][0] != k
                                 for h, k in zip(hits, keys[a:a + 16]))
        return {"doc_ids": doc_ids, "rows": rows, "not_found": not_found,
                "texts": [system.corpus.text_of(d) for d in doc_ids]}

    def compare(self, got, params, control):
        numbers = {"window_docs_not_found": got["not_found"]}
        ctrl = {}
        if not got["texts"] or not got["rows"]:
            numbers["embed_cos_gap"] = 1.0
            return numbers, ctrl
        ref = self._embed(params, got["texts"])
        numbers["embed_cos_gap"] = max(
            float(1.0 - np.sum(r / np.linalg.norm(r, axis=1, keepdims=True)
                               * ref, axis=1).min()) for r in got["rows"])
        if control:
            low = self._embed(params, got["texts"], "fp8")
            ctrl["embed_cos_gap"] = float(1.0 - np.sum(low * ref, axis=1).min())
        return numbers, ctrl


class RetrieveCheck(Check):
    """Cell kind *retrieve*: replies of ``POST /v1/retrieve`` through the
    reranking store. Every reply is checked for form; a seeded sample is
    compared with the references: the KNN distance of each returned
    document (``dist`` is the negative cosine; embedder + index), how far
    below the reference's ``candidates``-th best cosine it lies (exact
    search), and its cross-encoder score (reranker)."""

    def needed_flops(self, window) -> float:
        q = self.traffic["query_words"]
        per = self.layouts["embedder"].encoder_flops(
            self.models["embedder"], _tokens_single(q)) \
            + self.dep["rerank_candidates"] \
            * self.layouts["reranker"].encoder_flops(
                self.models["reranker"],
                _tokens_pair(q, self.dep["doc_words"]))
        return window.counters["requests_completed"] * per

    def reply_docs(self, reply):
        return reply

    def reply_k(self) -> int:
        return self.traffic["body"]["k"]

    def malformed(self, system, record) -> str | None:
        docs = self.reply_docs(record[3])
        if not isinstance(docs, list) or len(docs) != self.reply_k():
            return f"{len(docs) if isinstance(docs, list) else docs!r} docs"
        seen = set()
        last = float("inf")
        n_docs = self.dep["setup_commits"] * self.dep["commit_docs"]
        for d in docs:
            doc_id = d.get("metadata", {}).get("doc_id")
            if not isinstance(doc_id, int) or not 0 <= doc_id < n_docs:
                return f"doc_id {doc_id!r}"
            if doc_id in seen:
                return f"doc {doc_id} twice"
            seen.add(doc_id)
            if d.get("text") != system.corpus.text_of(doc_id):
                return f"text of doc {doc_id} altered"
            score = d.get("rerank_score")
            if not isinstance(score, float) or not score <= last:
                return f"rerank_score {score!r} after {last}"
            last = score
        return None

    def exact(self, system, window):
        bad = [m for m in (self.malformed(system, r)
                           for r in window.sample["records"]) if m]
        window.facts["first_malformed"] = bad[:3]
        return {"malformed_replies": len(bad)}

    def collect(self, system, window):
        records = window.sample["records"]
        n = min(self.traffic["check_requests"], len(records))
        chosen = sorted(self.rng.choice(len(records), n, replace=False
                                        ).tolist()) if n else []
        n_docs = self.dep["setup_commits"] * self.dep["commit_docs"]
        sample = []
        for i in chosen:
            rec = records[i]
            query = window.sample["queries"][rec[0]][1]
            docs = self.reply_docs(rec[3])
            if not isinstance(docs, list) or any(
                    not isinstance(d.get("metadata", {}).get("doc_id"), int)
                    or not 0 <= d["metadata"]["doc_id"] < n_docs
                    or not isinstance(d.get("rerank_score"), float)
                    or not isinstance(d.get("dist"), float) for d in docs):
                continue  # counted under malformed_replies
            sample.append({
                "query": query, "reply": rec[3],
                "docs": [(d["metadata"]["doc_id"], d["dist"],
                          d["rerank_score"]) for d in docs]})
        return {"sample": sample,
                "doc_texts": [system.corpus.text_of(i) for i in range(n_docs)]}

    def compare(self, got, params, control):
        return self.compare_docs(got, params, control)

    def compare_docs(self, got, params, control):
        sample = got["sample"]
        if not sample:
            return {"knn_dist_err": 1.0, "knn_rank_gap": 1.0,
                    "rerank_score_err": 1.0}, {}
        c = self.dep["rerank_candidates"]
        queries = [s["query"] for s in sample]
        out = []
        for precision in ("f32", "fp8") if control else ("f32",):
            qv = self._embed(params, queries, precision)
            dv = self._embed(params, got["doc_texts"], precision)
            if precision != "f32":
                qv = np.asarray(R.fp8_round(qv, -1))
                dv = np.asarray(R.fp8_round(dv, -1))
            cos = qv @ dv.T
            pairs = [(s["query"], got["doc_texts"][d])
                     for s in sample for d, _dist, _score in s["docs"]]
            scores = self._score(params, pairs, precision)
            out.append((cos, scores))
        cos, scores = out[0]
        kth = -np.sort(-cos, axis=1)[:, c - 1]
        dist_err = gap = score_err = 0.0
        at = 0
        for qi, s in enumerate(sample):
            for d, dist, score in s["docs"]:
                dist_err = max(dist_err, abs(dist + cos[qi, d]))
                gap = max(gap, float(kth[qi] - cos[qi, d]))
                score_err = max(score_err, abs(score - float(scores[at])))
                at += 1
        numbers = {"knn_dist_err": float(dist_err),
                   "knn_rank_gap": float(gap),
                   "rerank_score_err": float(score_err)}
        ctrl = {}
        if control:
            # the fp8 reference in the program's place: its distances (over
            # fp8 rows, as an fp8 index would hold them), its own best
            # `candidates`, its scores, read against float32
            cos8, scores8 = out[1]
            top8 = np.argsort(-cos8, axis=1)[:, :c]
            ctrl = {
                "knn_dist_err": float(np.abs(
                    np.take_along_axis(cos8, top8, 1)
                    - np.take_along_axis(cos, top8, 1)).max()),
                "knn_rank_gap": float((kth[:, None] - np.take_along_axis(
                    cos, top8, 1)).max()),
                "rerank_score_err": float(np.abs(scores8 - scores).max()),
            }
        return numbers, ctrl


class AnswerCheck(RetrieveCheck):
    """Cell kind *answer*: replies of ``POST /v2/answer``. Form of every
    reply (all the tokens, all the context documents, no prompt truncated);
    for a seeded sample the context documents as in :class:`RetrieveCheck`
    and, for the decoder, the widest gap by which a served token's logit
    lies below the reference's best, over the prompt the program built and
    the tokens it served (greedy decoding only)."""

    def needed_flops(self, window) -> float:
        dec = self.models["decoder"]
        srv = self.dep["decoder_server"]
        prompt = window.facts.get("prompt_tokens_median") or (
            self.dep["search_topk"] * self.dep["doc_words"])
        per = self.layouts["decoder"].answer_flops(
            dec, int(prompt), srv["max_new_tokens"])
        return super().needed_flops(window) \
            + window.counters["requests_completed"] * per

    def reply_docs(self, reply):
        return reply.get("context_docs") if isinstance(reply, dict) else None

    def reply_k(self) -> int:
        return self.dep["search_topk"]

    def tokens_of(self, reply) -> list[int] | None:
        try:
            return [int(t[1:]) for t in str(reply["response"]).split()
                    if t[0] == "t"]
        except (KeyError, TypeError, ValueError):
            return None

    def exact(self, system, window):
        out = super().exact(system, window)
        srv = self.dep["decoder_server"]
        short = truncated = unseen = 0
        lengths = []
        for rec in window.sample["records"]:
            toks = self.tokens_of(rec[3])
            if toks is None or len(toks) != srv["max_new_tokens"]:
                short += 1
            question = window.sample["queries"][rec[0]][1]
            prompt = system.tokenizer.prompts.get(question)
            if prompt is None:
                unseen += 1
                continue
            n = len(prompt.split())
            lengths.append(n)
            truncated += n > srv["max_prompt_tokens"]
        out["answers_short_of_tokens"] = short
        out["prompts_truncated"] = truncated
        out["prompts_never_built"] = unseen
        if lengths:
            window.facts["prompt_tokens_median"] = float(np.median(lengths))
            window.facts["prompt_tokens_max"] = int(max(lengths))
        return out

    def collect(self, system, window):
        got = super().collect(system, window)
        tok = system.tokenizer
        n = self.traffic["check_answers"]
        answers = []
        mismatch = 0
        for s in got["sample"][:n]:
            prompt = tok.prompts.get(s["query"])
            toks = self.tokens_of(s["reply"])
            if prompt is None or not toks:
                continue
            # the prompt the program built holds the question and every
            # context document it returned, in the order it returned them
            at = 0
            for d in self.reply_docs(s["reply"]):
                at = prompt.find(d["text"], at)
                if at < 0:
                    mismatch += 1
                    break
            answers.append((tok.encode(prompt), toks))
        got["answers"] = answers
        got["prompt_mismatch"] = mismatch
        return got

    def compare(self, got, params, control):
        numbers, ctrl = self.compare_docs(got, params, control)
        numbers["prompt_context_mismatch"] = got["prompt_mismatch"]
        dec, layout = self.models["decoder"], self.layouts["decoder"]
        cap = self.dep["decoder_server"]["max_prompt_tokens"]
        if not got["answers"]:
            numbers["token_logit_gap"] = 1e9
            return numbers, ctrl
        p32 = layout.prepare(params["decoder"], "f32")
        ref = []
        for prompt, toks in got["answers"]:
            prompt = prompt[-cap:]
            ref.append(layout.logits(p32, dec, prompt + toks[:-1],
                                     len(prompt) - 1, "f32"))
        del p32
        numbers["token_logit_gap"] = max(
            float((lg.max(axis=1) - lg[np.arange(len(toks)), toks]).max())
            for lg, (_p, toks) in zip(ref, got["answers"]))
        if control:
            p8 = layout.prepare(params["decoder"], "fp8")
            worst = 0.0
            for lg, (prompt, toks) in zip(ref, got["answers"]):
                prompt = prompt[-cap:]
                low = layout.logits(p8, dec, prompt + toks[:-1],
                                    len(prompt) - 1, "fp8")
                first = low.argmax(axis=1)
                worst = max(worst, float(
                    (lg.max(axis=1) - lg[np.arange(len(first)), first]).max()))
            ctrl["token_logit_gap"] = worst
        return numbers, ctrl


CHECKS = {"ingest": IngestCheck, "retrieve": RetrieveCheck,
          "answer": AnswerCheck}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; a number with no limit is a fault of
    the configuration's file, not a pass."""
    table, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the configuration")
        table[name] = {"value": value, "limit": limits[name]}
        ok = ok and bool(value <= limits[name])
    return ok, table
