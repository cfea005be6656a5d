"""Work counted from shapes: the operations and bytes an algorithm NEEDS,
the same whatever implements it (an XLA fusion today, a Pallas kernel
tomorrow). Copied from ``bench.py:flops_per_doc`` and extended; the
program's own copies may drift, these may not. ``cfg`` is a plain dict with
the published keys of the configuration's file."""

from __future__ import annotations


def encoder_flops(cfg: dict, seq: int) -> float:
    """Dense-matmul FLOPs (mul+add) of one BERT-family encoder forward over
    ``seq`` tokens: QKV, attention output and the two MLP matmuls, plus the
    score and context products of attention."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 2 * seq * h * (3 * h + h + 2 * i) + 4 * seq * seq * h
    return float(cfg["num_hidden_layers"] * per_layer)


def encoder_layer_param_count(cfg: dict) -> int:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = h * 3 * h + 3 * h + h * h + h + 2 * h * i + i + h + 4 * h
    return cfg["num_hidden_layers"] * per_layer


def encoder_bytes(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Least HBM bytes of one encoder batch: every layer parameter read
    once, the gathered embedding rows, ids and mask in, vectors out."""
    h = cfg["hidden_size"]
    return float(encoder_layer_param_count(cfg) * itemsize
                 + batch * seq * h * itemsize + 2 * batch * seq * 4
                 + batch * h * 4)


def knn_scan_bytes(capacity: int, dim: int, itemsize: int = 2) -> float:
    """One exact search reads every row of the index once."""
    return float(capacity * dim * itemsize)


def knn_scan_flops(queries: float, capacity: int, dim: int) -> float:
    return 2.0 * queries * capacity * dim


def decoder_matmul_param_count(cfg: dict) -> int:
    """Parameters every decoded token multiplies: the layers' four matrices
    and the tied LM head (``wte`` read as the output projection)."""
    h, i = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    per_layer = h * 3 * h + h * h + 2 * h * i
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * h


def decoder_param_bytes(cfg: dict, itemsize: int = 2) -> float:
    h, i = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    small = cfg["n_layer"] * (3 * h + h + i + h + 4 * h) + 2 * h
    return float((decoder_matmul_param_count(cfg) + small) * itemsize)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one cached position over all layers."""
    return float(2 * cfg["n_layer"] * cfg["n_embd"] * itemsize)


def decode_step_bytes(cfg: dict, live_kv_tokens: float,
                      itemsize: int = 2) -> float:
    """One decode step reads every parameter and the live KV once."""
    return decoder_param_bytes(cfg, itemsize) \
        + live_kv_tokens * kv_bytes_per_token(cfg, itemsize)


def decode_step_flops(cfg: dict, batch: float, live_kv_tokens: float) -> float:
    """``batch`` tokens through the matrices, attention over the live KV."""
    return 2.0 * decoder_matmul_param_count(cfg) * batch \
        + 4.0 * cfg["n_layer"] * cfg["n_embd"] * live_kv_tokens


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """One causal forward over the prompt: the layer matrices for every
    token, causal attention (half the square), the LM head for the last
    position only (the one logit row a greedy answer needs)."""
    h = cfg["n_embd"]
    layers = decoder_matmul_param_count(cfg) - cfg["vocab_size"] * h
    return 2.0 * layers * prompt_tokens \
        + 2.0 * cfg["n_layer"] * prompt_tokens * prompt_tokens * h \
        + 2.0 * cfg["vocab_size"] * h


def answer_flops(cfg: dict, prompt_tokens: int, new_tokens: int,
                 prefill=prefill_flops, step=decode_step_flops) -> float:
    """Decoder FLOPs one greedy answer needs: one prefill, then
    ``new_tokens - 1`` single-token steps over a growing cache (the first
    token comes from the prefill's logits). Counted once per answer.
    ``prefill`` and ``step`` are the model layout's counts (GPT-2's where
    none are given)."""
    total = prefill(cfg, prompt_tokens)
    for t in range(1, new_tokens):
        total += step(cfg, 1, prompt_tokens + t)
    return total


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s, and which of the two bounds."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
