"""pathway_tpu.models — TPU-native model family for the LLM xpack.

The reference calls external torch models (sentence-transformers MiniLM for
embedding, ms-marco cross-encoders for reranking — see
``/root/reference/python/pathway/xpacks/llm/embedders.py:270`` and
``rerankers.py:186``). Here the models are first-class citizens of the
framework: pure-JAX transformer encoders with bfloat16 MXU-friendly matmuls,
explicit tensor-parallel PartitionSpecs, and a contrastive training step used
by the multi-chip dry run.
"""

from pathway_tpu.models.transformer import (
    TransformerConfig,
    MINILM_L6,
    MINILM_L12,
    BGE_SMALL,
    init_params,
    encode,
    param_partition_specs,
    count_params,
)
from pathway_tpu.models.embedder import (
    SentenceEmbedderModel,
    mean_pool,
)
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.decoder import (
    DecoderConfig,
    GPT2_SMALL,
    GPT2_MEDIUM,
)
from pathway_tpu.models.bpe import BPETokenizer
from pathway_tpu.models.tokenizer import HashTokenizer, load_tokenizer
from pathway_tpu.models.train import (
    contrastive_loss,
    init_decoder_train_state,
    init_train_state,
    lm_loss,
    make_decoder_train_step,
    make_train_step,
)

__all__ = [
    "TransformerConfig",
    "MINILM_L6",
    "MINILM_L12",
    "BGE_SMALL",
    "init_params",
    "encode",
    "param_partition_specs",
    "count_params",
    "SentenceEmbedderModel",
    "mean_pool",
    "CrossEncoderModel",
    "DecoderConfig",
    "GPT2_SMALL",
    "GPT2_MEDIUM",
    "BPETokenizer",
    "HashTokenizer",
    "load_tokenizer",
    "contrastive_loss",
    "make_train_step",
    "init_train_state",
    "lm_loss",
    "init_decoder_train_state",
    "make_decoder_train_step",
    "MoEConfig",
    "init_moe_params",
    "moe_mlp",
    "moe_partition_specs",
    "encode_pipelined",
]

from pathway_tpu.models.moe import (  # noqa: E402
    MoEConfig,
    init_moe_params,
    moe_mlp,
    moe_partition_specs,
)
from pathway_tpu.models.pipeline import encode_pipelined  # noqa: E402
