"""Check ``answer_moe``: the built-in ``answer`` for a generator whose MLP is
routed experts. The form of every reply and the context documents are
judged as there; the decoder's number, ``token_logit_gap``, leaves out the
answer positions at which the REFERENCE's own choice of experts was a
near-tie.

Why: a token goes to the ``k`` best of the router's scores. Where the
``k``-th and the ``k+1``-th lie closer together than the served precision
resolves, the program may pick the other expert, and with one chip's share
of the experts behind a sandwich norm that is a discrete change of the
sublayer's output, as large as a precision below would make everywhere. It
says nothing about the arithmetic, so such a position is skipped, not
excused by a wider limit: the reference reports each position's smallest
``k``-th-to-``k+1``-th margin over the expert layers, positions under the
mix's ``tie_margin`` are left out and COUNTED
(``token_positions_near_tie``: a share of the sampled positions, with a
limit of its own, so that a check that skips everything cannot pass). The
control (the reference at fp8 in the program's place) is judged over the
same positions.
"""

import json
import sys

import numpy as np

from harness.checks import AnswerCheck


def _gaps(rows, picked):
    """How far each row's picked logit lies below the row's best."""
    return rows.max(axis=1) - rows[np.arange(len(picked)), picked]


class Check(AnswerCheck):
    def compare(self, got, params, control):
        numbers, ctrl = self.compare_docs(got, params, control)
        numbers["prompt_context_mismatch"] = got["prompt_mismatch"]
        dec, layout = self.models["decoder"], self.layouts["decoder"]
        cap = self.dep["decoder_server"]["max_prompt_tokens"]
        eps = float(self.traffic["tie_margin"])
        answers = [(prompt[-cap:], toks) for prompt, toks in got["answers"]]
        if not answers:
            numbers.update(token_positions_near_tie=1.0, token_logit_gap=1e9)
            return numbers, ctrl
        prepared = layout.prepare(params["decoder"], "f32")
        rows, margins = zip(*(
            layout.logits_margins(prepared, dec, prompt + toks[:-1],
                                  len(prompt) - 1)
            for prompt, toks in answers))
        rows = np.concatenate(rows)
        served = np.concatenate([toks for _p, toks in answers])
        seen = {"margin": np.concatenate(margins)}
        kept = seen["margin"] >= eps
        numbers["token_positions_near_tie"] = float(1.0 - kept.mean())
        if not kept.any():
            numbers["token_logit_gap"] = 1e9
            return numbers, ctrl
        seen["gap"] = _gaps(rows, served)
        numbers["token_logit_gap"] = float(seen["gap"][kept].max())
        if control:
            low = layout.prepare(params["decoder"], "fp8")
            first = np.concatenate([
                layout.logits(low, dec, prompt + toks[:-1], len(prompt) - 1,
                              "fp8").argmax(axis=1)
                for prompt, toks in answers])
            seen["control_gap"] = _gaps(rows, first)
            ctrl["token_logit_gap"] = float(seen["control_gap"][kept].max())
        # what the margin and the limit were set from: every sampled
        # position's margin and gap, kept or not
        print(json.dumps({"phase": "answer_moe_positions", "tie_margin": eps,
                          **{k: [round(float(x), 6) for x in v]
                             for k, v in seen.items()}}),
              file=sys.stderr, flush=True)
        return numbers, ctrl
