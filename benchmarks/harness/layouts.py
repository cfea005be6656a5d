"""Model layouts: the one seam an architecture plugs into. Everything the
harness knows of a model's SHAPE sits behind the layout that the model's
entry in the configuration's file names (``models.<role>.layout``; where
the key is absent, ``manifest.ROLE_LAYOUT``'s for the role). The builders,
the checks and the readers hand the layout the entry as it stands in the
file and read none of its keys themselves.

A layout gives, for the model ``model`` (that entry, a plain dict):

* ``program_config(model)``: the program's own config object;
* ``weight_spec(model, role)``: the tree of ``weights.make_params`` in the
  form the program's constructor takes;
* the plain reference of its role, float32 at ``highest``, importing
  nothing of the program (``precision="fp8"`` is the control): an encoder's
  ``embed(params, model, texts, max_length, precision)`` and
  ``score(params, model, pairs, max_length, precision)``; a decoder's
  ``prepare(params, precision)`` and
  ``logits(prepared, model, ids, first, precision)``;
* the work counted from its shapes: an encoder's ``encoder_flops`` and
  ``encoder_bytes``; a decoder's ``matmul_param_count``, ``param_bytes``,
  ``kv_bytes_per_token``, ``prefill_flops``, ``decode_step_flops``,
  ``decode_step_bytes`` and, from those, ``answer_flops``.

The two built-ins wrap the BERT and GPT-2 functions of ``weights.py``,
``reference.py`` and ``work.py`` as they are. A new architecture ships
``<path>/layouts/<name>.py`` exporting ``layout``, with its own weight
spec, its own reference and its own counts (``manifest.resolve``).
"""

from __future__ import annotations

from . import reference as R
from . import weights as W
from . import work


class DecoderLayout:
    """What every decoder layout shares: the FLOPs of one greedy answer
    from its own prefill and step counts."""

    def answer_flops(self, model: dict, prompt_tokens: int,
                     new_tokens: int) -> float:
        return work.answer_flops(model, prompt_tokens, new_tokens,
                                 self.prefill_flops, self.decode_step_flops)


class Bert:
    """BERT-family encoder (MiniLM-L6: post-LN, erf gelu), for the roles
    ``embedder`` (masked mean pool) and ``reranker`` (pooler and a scalar
    head)."""

    def program_config(self, model: dict):
        import jax.numpy as jnp

        from pathway_tpu.models.transformer import TransformerConfig

        if model["torch_dtype"] != "bfloat16":
            raise ValueError("the encoder cells state bfloat16")
        return TransformerConfig(
            vocab_size=model["vocab_size"], hidden=model["hidden_size"],
            layers=model["num_hidden_layers"],
            heads=model["num_attention_heads"],
            intermediate=model["intermediate_size"],
            max_position=model["max_position_embeddings"],
            type_vocab=model["type_vocab_size"],
            layer_norm_eps=model["layer_norm_eps"], dtype=jnp.bfloat16,
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        return W.encoder_spec(model, head=role == "reranker")

    embed = staticmethod(R.embed_texts)
    score = staticmethod(R.score_pairs)
    encoder_flops = staticmethod(work.encoder_flops)
    encoder_bytes = staticmethod(work.encoder_bytes)


class Gpt2(DecoderLayout):
    """GPT-2 decoder (pre-LN, tanh gelu, learned positions, tied head)."""

    def program_config(self, model: dict):
        import jax.numpy as jnp

        from pathway_tpu.models.decoder import DecoderConfig

        return DecoderConfig(
            vocab_size=model["vocab_size"], hidden=model["n_embd"],
            layers=model["n_layer"], heads=model["n_head"],
            intermediate=model.get("n_inner") or 4 * model["n_embd"],
            max_position=model["n_positions"],
            layer_norm_eps=model["layer_norm_epsilon"], dtype=jnp.bfloat16,
        )

    def weight_spec(self, model: dict, role: str) -> dict:
        return W.decoder_spec(model)

    prepare = staticmethod(R.prepare_decoder)
    logits = staticmethod(R.gpt2_logits)
    matmul_param_count = staticmethod(work.decoder_matmul_param_count)
    param_bytes = staticmethod(work.decoder_param_bytes)
    kv_bytes_per_token = staticmethod(work.kv_bytes_per_token)
    prefill_flops = staticmethod(work.prefill_flops)
    decode_step_flops = staticmethod(work.decode_step_flops)
    decode_step_bytes = staticmethod(work.decode_step_bytes)


LAYOUTS = {"bert": Bert(), "gpt2": Gpt2()}
