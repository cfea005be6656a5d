"""Runtime configuration (reference ``internals/config.py``).

Worker-topology / persistence env vars: PATHWAY_THREADS /
PATHWAY_PROCESSES / PATHWAY_PROCESS_ID / PATHWAY_FIRST_PORT,
PATHWAY_IGNORE_ASSERTS, PATHWAY_RUNTIME_TYPECHECKING,
PATHWAY_PERSISTENT_STORAGE, PATHWAY_LICENSE_KEY (accepted, unused — no
license gating in this build). The persistent XLA compilation cache is
placed by JAX's own ``JAX_COMPILATION_CACHE_DIR`` (see
:func:`enable_compile_cache`).

Every performance knob — the ``PATHWAY_TPU_*`` family plus
``PATHWAY_FUSION`` — is declared exactly once in :data:`FLAG_REGISTRY`
below: env name, type, default, clamp, and the documentation line.
``PathwayConfig``'s accessor properties and the README's two flag
tables are both generated from it (``python -m
pathway_tpu.internals.config`` prints the tables;
``tests/test_flag_registry.py`` pins README == registry), so the docs
cannot drift from the code again. All flags are read per USE, not
cached at import, so tests can flip them per-run with
``monkeypatch.setenv``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
from dataclasses import dataclass, field
from typing import Any

_TRUTHY = ("1", "true", "yes", "on")


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in _TRUTHY


def _parse_kv_quant(raw: str) -> str:
    """``int8`` (or any truthy spelling) enables int8 KV storage; every
    other value — including the kill switch ``0`` — is full precision."""
    return "int8" if raw.strip().lower() in (
        "1", "true", "yes", "on", "int8"
    ) else ""


def _parse_weight_quant(raw: str) -> str:
    """``int8`` (or any truthy spelling) enables weight-only int8
    storage; every other value — the kill switch ``0`` included — keeps
    full-precision weights byte-identically."""
    return "int8" if raw.strip().lower() in (
        "1", "true", "yes", "on", "int8"
    ) else ""


@dataclass(frozen=True)
class Tunable:
    """Search-space declaration for one flag — what the autotuner
    (``pathway_tpu/tuning/``) may try. ``kind`` is ``"int"`` /
    ``"float"`` (a ``[lo, hi]`` range walked additively by ``step`` or
    multiplicatively — a doubling ladder — when ``log=True``) or
    ``"choice"`` (an explicit value tuple; the only legal kind for
    ``bool``/``str`` flags). Bounds must be finite and contain the
    flag's default — rule ``GL204`` (``tunable-bounds``) enforces it."""

    kind: str = "int"  # "int" | "float" | "choice"
    lo: float | None = None
    hi: float | None = None
    step: float | None = None
    log: bool = False
    choices: tuple = ()

    def candidates(self) -> tuple[str, ...]:
        """The deterministic candidate ladder, as raw env-var strings
        (the tuner feeds them through the flag's own parser)."""
        if self.kind == "choice":
            return tuple(str(c) for c in self.choices)
        vals: list[float] = []
        v = float(self.lo)
        while v <= float(self.hi) + 1e-9:
            vals.append(v)
            v = v * 2.0 if self.log else v + float(self.step or 1)
        if self.kind == "int":
            return tuple(str(int(round(x))) for x in vals)
        return tuple(str(x) for x in vals)

    def contains(self, raw: Any) -> bool:
        """Is ``raw`` (an env-var string or parsed value) inside the
        declared space? Used to validate tuned-config artifacts."""
        if self.kind == "choice":
            return str(raw) in {str(c) for c in self.choices}
        try:
            v = float(raw)
        except (TypeError, ValueError):
            return False
        return float(self.lo) <= v <= float(self.hi)


@dataclass(frozen=True)
class Flag:
    """One runtime knob: its env var, how to read it, and its one-line
    doc. ``attr`` is the ``PathwayConfig`` property name; ``group``
    places the flag in a README
    table (``pipeline`` / ``query`` / ``observability``); ``minimum``
    clamps explicit
    values (defaults are trusted as-is, matching the historical
    accessors); ``parse`` overrides the ``kind`` parser.

    ``kill_switch=True`` declares the PR-2..7 contract explicitly: the
    flag's off position must leave outputs byte-identical, and
    ``pinned_by`` names the test file holding the byte-equality pin.
    The contract is analyzer-enforced (rule ``GL301``,
    ``python -m pathway_tpu.analysis check``): the file must exist and
    reference the env var, so renaming or deleting a pinning test fails
    CI instead of silently un-pinning the switch.

    ``reload`` declares WHEN the value is consumed: ``"live"`` flags are
    re-read on every use, so flipping them mid-process takes effect
    immediately; ``"construction"`` flags are read once when the
    consuming object is built (a server, scheduler, chaos site, lock,
    the SLO watchdog singleton) and flipping them later silently
    no-ops. :func:`flag_overrides` refuses construction flags unless
    the caller owns construction (``construction=True``), which is how
    the autotuner avoids the mid-trial-no-op bug class.

    ``tunable`` (a :class:`Tunable`) declares the search space the
    autotuner may explore; None means hand-tuned only."""

    env: str
    kind: str  # "bool" | "int" | "float" | "str"
    default: Any
    doc: str
    attr: str
    group: str | None = None
    minimum: float | None = None
    parse: Any = None
    kill_switch: bool = False
    pinned_by: str | None = None
    reload: str = "live"  # "live" | "construction"
    tunable: Tunable | None = None

    def parse_raw(self, raw: str) -> Any:
        """Parse one raw env-var string with this flag's own semantics
        (kind parser / ``parse`` override / ``minimum`` clamp) — the
        single code path for environment, override and tuned-config
        values alike."""
        if self.kind == "bool":
            return raw.strip().lower() in _TRUTHY
        if self.parse is not None:
            return self.parse(raw)
        val = {"int": int, "float": float, "str": str}[self.kind](raw)
        if self.minimum is not None:
            val = max(type(val)(self.minimum), val)
        return val

    def read(self) -> Any:
        raw = _raw_flag_value(self.env)
        if raw is None:
            return self.default
        return self.parse_raw(raw)

    def render_default(self) -> str:
        if self.kind == "bool":
            return "1" if self.default else "0"
        if self.kind == "str":
            return str(self.default) if self.default else "0"
        return str(self.default)


FLAG_REGISTRY: list[Flag] = [
    # ---- ungrouped (documented in prose, not a README table) ----------
    Flag(
        env="PATHWAY_FUSION", kind="bool", default=True, attr="fusion",
        reload="construction",
        kill_switch=True, pinned_by="tests/test_fusion.py",
        doc="Stateless operator-chain fusion (scheduler plan rewrite, "
            "`engine/graph.py:fuse_chains`); read per scheduler "
            "construction.",
    ),
    Flag(
        env="PATHWAY_EXCHANGE_DEBUG", kind="bool", default=False,
        attr="exchange_debug",
        doc="Verbose multi-process exchange logging (stderr) in "
            "`engine/exchange.py`; read per message, so it can be "
            "flipped without re-importing.",
    ),
    Flag(
        env="PATHWAY_DISABLE_NATIVE", kind="bool", default=False,
        reload="construction",
        attr="disable_native",
        doc="Skip loading the optional native extension in "
            "`pathway_tpu/native/` and use the pure-Python fallbacks "
            "(diagnostic escape hatch; read once at first native call).",
    ),
    Flag(
        env="PATHWAY_SPAWN_ARGS", kind="str", default="",
        attr="spawn_args",
        doc="Extra whitespace-separated argv appended by `pathway spawn` "
            "re-exec (internal plumbing between the CLI wrapper and the "
            "spawned workers).",
    ),
    Flag(
        env="PATHWAY_COORDINATOR", kind="str", default="",
        attr="coordinator",
        doc="`host:port` of the jax.distributed coordinator for "
            "multi-process runs; empty derives "
            "`localhost:PATHWAY_FIRST_PORT` (see "
            "`parallel/distributed.py:from_env`).",
    ),
    # ---- ingest / engine / serving knobs (README 'pipeline' table) ----
    Flag(
        env="PATHWAY_TPU_PIPELINE", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_embedder_pipeline.py",
        attr="tpu_pipeline", group="pipeline",
        doc="Pipelined `embed_submit`: a background tokenizer worker "
            "feeds a bounded queue and a dispatch worker stages the next "
            "batch (`jax.device_put`) while the current one computes, "
            "launching a donated ping-pong executable. `0` restores the "
            "fully serial tokenize→h2d→dispatch path (byte-identical "
            "output either way — `tests/test_embedder_pipeline.py` pins "
            "it).",
    ),
    Flag(
        env="PATHWAY_TPU_PIPELINE_DEPTH", kind="int", default=2,
        reload="construction",
        tunable=Tunable("int", lo=1, hi=8, log=True),
        attr="tpu_pipeline_depth", group="pipeline", minimum=1,
        doc="Dispatch-ahead depth: how many batches may be staged/in "
            "flight beyond the one computing. Bounds live input buffers "
            "(donation ping-pongs them) and host run-ahead.",
    ),
    Flag(
        env="PATHWAY_TPU_PIPELINE_QUEUE", kind="int", default=8,
        reload="construction",
        tunable=Tunable("int", lo=2, hi=32, log=True),
        attr="tpu_pipeline_queue", group="pipeline", minimum=1,
        doc="Tokenizer→dispatch queue bound; `embed_submit` blocks "
            "(backpressure) once this many tokenized batches wait.",
    ),
    Flag(
        env="PATHWAY_TPU_CHUNKED_PREFILL", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_chunk_admission.py",
        attr="chunked_prefill", group="pipeline",
        doc="Continuous serving: admit a long prompt in "
            "`PATHWAY_TPU_PREFILL_CHUNK`-token pieces interleaved with "
            "decode chunks, instead of stalling every active lane for "
            "one monolithic prefill dispatch.",
    ),
    Flag(
        env="PATHWAY_TPU_PREFILL_CHUNK", kind="int", default=64,
        reload="construction",
        tunable=Tunable("int", lo=8, hi=256, log=True),
        attr="prefill_chunk", group="pipeline", minimum=8,
        doc="Piece size for chunked prefill (pow2-rounded, min 8). "
            "Prompt buckets at or below it prefill one-shot.",
    ),
    Flag(
        env="PATHWAY_TPU_EAGER_REFILL", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_chunk_admission.py",
        attr="eager_refill", group="pipeline",
        doc="Free a serving slot the moment its request's token budget "
            "is covered by dispatched chunks (tokens drain later from "
            "in-flight snapshots), instead of waiting for the drain "
            "thread — the next queued request admits at the same chunk "
            "boundary.",
    ),
    Flag(
        env="PATHWAY_TPU_KNN_F32_SCORES", kind="bool", default=False,
        attr="knn_f32_scores", group="pipeline",
        doc="Brute-force KNN scoring with f32 *operands* (not just f32 "
            "accumulation). Recovers the bf16-operand recall loss at "
            "~2× the gemm cost; flip it when recall@k matters more than "
            "ingest throughput.",
    ),
    Flag(
        env="PATHWAY_TPU_FUSED_H2D", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_embedder_pipeline.py",
        attr="fused_h2d", group="pipeline",
        doc="Ingest host→device transfer as one fused int16 ids+mask "
            "staging copy instead of per-array puts.",
    ),
    Flag(
        env="PATHWAY_TPU_COLUMNAR_SUBSCRIBE", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_engine_closeout.py",
        attr="columnar_subscribe", group="pipeline",
        doc="`pw.io.subscribe` formats row callbacks COLUMNARLY on a "
            "named background thread (`pathway:subscribe:<node>`) per "
            "epoch, instead of row-by-row on the engine thread. "
            "Callback order, flush/end placement, and exception "
            "propagation are pinned by `tests/test_engine_closeout.py`.",
    ),
    Flag(
        env="PATHWAY_TPU_DRAIN_COALESCE", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_engine_closeout.py",
        attr="drain_coalesce", group="pipeline",
        doc="Deferred-UDF drainer merges consecutive resolved chunks "
            "into one injected engine batch when the scheduler has no "
            "other pending work (or the group hits "
            "`PATHWAY_TPU_DRAIN_COALESCE_MAX`), cutting per-chunk epoch "
            "overhead on the config-4 path.",
    ),
    Flag(
        env="PATHWAY_TPU_DRAIN_COALESCE_MAX", kind="int", default=8,
        tunable=Tunable("int", lo=1, hi=32, log=True),
        attr="drain_coalesce_max", group="pipeline", minimum=1,
        doc="Most resolved chunks merged into one drain injection "
            "(bounds the latency a coalesced group can add while the "
            "engine stays busy).",
    ),
    Flag(
        env="PATHWAY_TPU_EPOCH_CLOSEOUT", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_engine_closeout.py",
        attr="epoch_closeout", group="pipeline",
        doc="Epoch close-out cuts: batches that are provably "
            "single-sign/distinct carry a consolidation proof through "
            "column transforms, so `consolidate()` short-circuits "
            "instead of re-scanning; the end-of-time sweep visits only "
            "nodes that define `on_time_end`.",
    ),
    Flag(
        env="PATHWAY_TPU_BATCH_ADMIT", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_chunk_admission.py",
        attr="batch_admit", group="pipeline",
        doc="Continuous serving: requests waiting at the same chunk "
            "boundary with the same prompt bucket admit through ONE "
            "grouped `pool_admit_batch` prefill (pow2 group sizes) "
            "instead of one dispatch per request. Byte-equal tokens "
            "either way (`tests/test_chunk_admission.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_PREFILL_OVERLAP", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_chunk_admission.py",
        attr="prefill_overlap", group="pipeline",
        doc="Serving loop dispatches the next decode chunk BEFORE "
            "scanning for admissions, so admission prefills overlap "
            "in-flight decode instead of serializing ahead of it.",
    ),
    Flag(
        env="PATHWAY_TPU_CHUNK_AUTOTUNE", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_chunk_admission.py",
        attr="chunk_autotune", group="pipeline",
        doc="Serving loop adapts `chunk_steps` to queue pressure (small "
            "chunks while requests wait → lower admission latency; "
            "EMA-sized chunks when idle → fewer dispatches). Moves "
            "chunk boundaries only, never per-slot token streams.",
    ),
    Flag(
        env="PATHWAY_TPU_PREFIX_CACHE", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_prefix_cache.py",
        attr="prefix_cache", group="pipeline",
        doc="Radix-tree KV prefix cache for continuous serving: "
            "block-aligned prompt prefixes keep their KV in a device "
            "arena, and a request whose prompt head is cached admits by "
            "COPYING arena blocks instead of re-prefilling them (see "
            "\"Prefix KV cache\" below). `0` removes the arena and the "
            "tree entirely — serving output is byte-identical to the "
            "plain chunked-admission path (`tests/test_prefix_cache.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_PREFIX_CACHE_MB", kind="float", default=64,
        reload="construction",
        tunable=Tunable("float", lo=8, hi=256, log=True),
        attr="prefix_cache_mb", group="pipeline", minimum=0,
        doc="HBM byte budget for the prefix arena; the block count is "
            "derived from the model's per-block KV footprint, and LRU "
            "eviction keeps residency inside it. `0` (or a budget below "
            "one block) disables the cache.",
    ),
    Flag(
        env="PATHWAY_TPU_PREFIX_BLOCK", kind="int", default=0,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "8", "16", "32", "64")),
        attr="prefix_block", group="pipeline", minimum=0,
        doc="Cache block size in tokens; `0` = auto (the prefill "
            "chunk). Always pow2-rounded up to a multiple of "
            "`PATHWAY_TPU_PREFILL_CHUNK` so cached prefixes end on "
            "prefill-piece boundaries.",
    ),
    Flag(
        env="PATHWAY_TPU_SPEC_DECODE", kind="bool", default=True,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_spec_decode.py",
        attr="spec_decode", group="pipeline",
        doc="Self-speculative decoding for greedy continuous serving: "
            "the first `PATHWAY_TPU_SPEC_DECODE_DRAFT_LAYERS` layers "
            "draft `PATHWAY_TPU_SPEC_DECODE_K` tokens per cycle and ONE "
            "full-model dispatch verifies them all, advancing "
            "1+accepted tokens per weight stream. Token streams are "
            "byte-identical to plain greedy decode "
            "(`tests/test_spec_decode.py`); the server latches spec off "
            "when the measured acceptance rate stays under 0.25, and "
            "sampling requests (temperature > 0) always take the plain "
            "path.",
    ),
    Flag(
        env="PATHWAY_TPU_SPEC_DECODE_DRAFT_LAYERS", kind="int",
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1", "2")),
        default=0, attr="spec_draft_layers", group="pipeline", minimum=0,
        doc="Draft-stack depth for self-speculative decode; `0` = auto "
            "(`max(1, layers // 4)`), always clamped to `layers - 1`. "
            "Deeper drafts agree with the full model more often but "
            "cost more per drafted token.",
    ),
    Flag(
        env="PATHWAY_TPU_SPEC_DECODE_K", kind="int", default=3,
        reload="construction",
        tunable=Tunable("int", lo=1, hi=8, step=1),
        attr="spec_k", group="pipeline", minimum=1,
        doc="Draft tokens proposed per speculative cycle (the verify "
            "pass scores k+1 positions in one dispatch). Larger k "
            "amortizes more weight streaming at high acceptance and "
            "wastes more draft compute at low acceptance.",
    ),
    Flag(
        env="PATHWAY_TPU_KV_QUANT", kind="str", default="",
        reload="construction",
        kill_switch=True, pinned_by="tests/test_kv_quant.py",
        attr="kv_quant", group="pipeline", parse=_parse_kv_quant,
        doc="`int8` stores the KV slot pool AND the prefix-cache arena "
            "as symmetric per-(layer, slot, head, token) int8 with f32 "
            "scales, dequantized on read inside attention — ~1.9× KV "
            "capacity per HBM byte at head_dim 64, so the same budget "
            "holds ~2× the slots + cached prefix blocks. `0` (default) "
            "keeps full-precision KV byte-identically "
            "(`tests/test_kv_quant.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_WEIGHT_QUANT", kind="str", default="",
        reload="construction",
        kill_switch=True, pinned_by="tests/test_weight_quant.py",
        attr="weight_quant", group="pipeline", parse=_parse_weight_quant,
        tunable=Tunable(kind="choice", choices=("0", "int8")),
        doc="`int8` stores every large weight matrix of the decoder "
            "(qkv/attn-out/MLP, wte + tied LM head), the MiniLM embedder "
            "and the cross-encoder as symmetric per-output-channel int8 "
            "with f32 scales, dequantized inside the matmul read "
            "(`models/decoder.py:quantize_params`) — ~4× fewer weight "
            "bytes streamed per decode step on a memory-bound roofline, "
            "at ≥0.99 greedy top-1 agreement. `0` (default) serves "
            "full-precision weights byte-identically "
            "(`tests/test_weight_quant.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_WQ_KERNEL", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_weight_quant.py",
        attr="wq_kernel", group="pipeline",
        doc="Route the quantized decoder matmuls through the Pallas "
            "fused int8-weight kernel (`models/wq_matmul.py`): the int8 "
            "tile is widened and scaled inside VMEM, so a full-precision "
            "weight copy never exists. Requires "
            "`PATHWAY_TPU_WEIGHT_QUANT=int8`; `0` (default) keeps the "
            "XLA fused-dequant einsums, which are the numerical "
            "reference (`tests/test_weight_quant.py`). Off-TPU the "
            "kernel runs interpreted, like flash/paged attention.",
    ),
    Flag(
        env="PATHWAY_TPU_PAGED_KV", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_paged_kv.py",
        attr="paged_kv", group="pipeline",
        doc="Paged KV store for continuous serving: slots reference "
            "fixed-size blocks in one global pool through a per-slot "
            "block table, admission allocates only the blocks a request "
            "can actually reach, and cached prompt prefixes are PINNED "
            "copy-on-write instead of copied (see \"Paged KV & paged "
            "attention\" below). Greedy token streams are byte-identical "
            "to the dense pool across the spec x prefix x int8 grid, and "
            "`0` (default) keeps the dense right-padded pool bit-exactly "
            "(`tests/test_paged_kv.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_PAGED_KV_BLOCK", kind="int", default=0,
        reload="construction",
        attr="paged_kv_block", group="pipeline", minimum=0,
        doc="Paged KV block size in tokens; `0` = auto (the prefix-cache "
            "block, itself pow2-rounded from the prefill chunk). The "
            "serving cache length rounds UP to a block multiple, and the "
            "prefix block is forced equal so pinned prefixes stay "
            "block-aligned.",
    ),
    Flag(
        env="PATHWAY_TPU_PAGED_KV_BLOCKS", kind="int", default=0,
        reload="construction",
        attr="paged_kv_blocks", group="pipeline", minimum=0,
        doc="Total physical blocks in the paged pool; `0` = auto (every "
            "slot's worst case plus the prefix-cache budget plus the "
            "sentinel — capacity-equivalent to dense + arena). Setting "
            "it LOWER oversubscribes: admission takes only what each "
            "request needs, `PagedPoolOOM` requeues what no longer fits.",
    ),
    Flag(
        env="PATHWAY_TPU_PAGED_KERNEL", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_paged_kv.py",
        attr="paged_kernel", group="pipeline",
        doc="Pallas paged-attention decode kernel (requires "
            "`PATHWAY_TPU_PAGED_KV`): plain decode chunks walk the block "
            "table directly with int8 dequant fused into the attention "
            "read, skipping the gather/scatter the reference path pays. "
            "Online softmax is allclose-not-bitwise vs dense attention, "
            "so the kernel rides its own kill switch; spec decode always "
            "uses the reference path. `tests/test_paged_kv.py` pins "
            "kernel numerics against `_attn_ctx` at every (heads, block, "
            "seq) corner.",
    ),
    Flag(
        env="PATHWAY_TPU_FLASH_PREFILL", kind="bool", default=False,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_flash_prefill.py",
        attr="flash_prefill", group="pipeline",
        doc="Tiled online-softmax Pallas flash attention for every "
            "prefill/encode path (`models/flash_attention.py`): "
            "whole-prompt admits, chunked-prefill pieces (int8 dequant "
            "fused into the cache tile read; dense rows and, via the "
            "block table, paged pools), and the encoder stacks through "
            "the `core(q, k, v)` seam — no more materialized "
            "`(B, 1, S, S)` score/mask tensors, O(S) attention memory. "
            "Online softmax is allclose-not-bitwise vs the dense path, "
            "so `0` (default) keeps today's dense attention "
            "byte-identically (`tests/test_flash_prefill.py`). A "
            "chunked-prefill piece over a LONG row takes the chunk kernel "
            "whatever this says: by its shapes "
            "(`models.decoder.blockwise_chunk_read`).",
    ),
    Flag(
        env="PATHWAY_TPU_FLASH_BLOCK_Q", kind="int", default=0,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "64", "128", "256", "512")),
        attr="flash_block_q", group="pipeline", minimum=0,
        doc="Flash-prefill query tile size in tokens; `0` = auto (one "
            "128 tile, shrunk to the 8-rounded sequence when shorter). "
            "Native TPU compilation wants multiples of the (8, 128) "
            "register shape.",
    ),
    Flag(
        env="PATHWAY_TPU_FLASH_BLOCK_K", kind="int", default=0,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "64", "128", "256", "512")),
        attr="flash_block_k", group="pipeline", minimum=0,
        doc="Flash-prefill key/value tile size in tokens; `0` = auto. "
            "For chunk-vs-cache reads the tile must divide the cache "
            "row, so the effective size is the largest divisor of "
            "`cache_len` at most this value.",
    ),
    Flag(
        env="PATHWAY_TPU_DISAGG", kind="bool", default=False,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "1")),
        kill_switch=True, pinned_by="tests/test_disagg.py",
        attr="disagg", group="pipeline",
        doc="Disaggregated prefill/decode lanes for continuous serving: "
            "pending prefills form a prefill lane that dispatches at "
            "most `PATHWAY_TPU_DISAGG_PREFILL_BUDGET` pieces per loop "
            "tick while any slot is decoding, so a decode chunk never "
            "sits behind a burst of long-document prefills. A finished "
            "prefill MIGRATES into the decode lane by block-table "
            "handoff — zero-copy on one chip; `kv_block_export` / "
            "`kv_block_import` carry the blocks for the cross-device "
            "case. Greedy token streams are schedule-invariant, so `0` "
            "(default) is byte-identical (`tests/test_disagg.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_DISAGG_PREFILL_BUDGET", kind="int", default=1,
        reload="construction",
        tunable=Tunable("int", lo=1, hi=4, step=1),
        attr="disagg_prefill_budget", group="pipeline", minimum=1,
        doc="Prefill-lane width under `PATHWAY_TPU_DISAGG`: how many "
            "pending prefill pieces may dispatch per loop tick while "
            "the decode lane is non-empty (round-robin over waiting "
            "slots). With the decode lane idle the budget is ignored — "
            "there is nothing to protect, so prefill runs at full "
            "width.",
    ),
    Flag(
        env="PATHWAY_TPU_PREFIX_T2_MB", kind="float", default=0.0,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "16", "64")),
        kill_switch=True, pinned_by="tests/test_prefix_cache.py",
        attr="prefix_t2_mb", group="pipeline", minimum=0,
        doc="Host-RAM byte budget for the prefix cache's second tier: "
            "LRU eviction DEMOTES whole leaf edges to a pinned host "
            "`np` block store instead of dropping them, and an "
            "admission-time tier-2 match triggers async PROMOTION back "
            "into the device arena on the h2d `StageWorker`, so evicted "
            "prompt heads survive churn. Promoted bytes are exact "
            "copies of previously computed KV — greedy tokens are "
            "byte-identical, and `0` (default) keeps the single-tier "
            "cache bit-exactly (`tests/test_prefix_cache.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_TOKENIZE_CACHE", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_prefix_cache.py",
        attr="tokenize_cache", group="pipeline",
        doc="Content-keyed encode memo in the tokenizers "
            "(HashTokenizer / WordPiece batch paths and whole-text "
            "BPE): repeated texts — re-ingested chunks, the serving "
            "path's shared prompt template — skip re-encoding. "
            "Size-bounded LRU, per-row parity with the uncached path "
            "pinned by test.",
    ),
    Flag(
        env="PATHWAY_TPU_EMBED_DEDUP", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_prefix_cache.py",
        attr="embed_dedup", group="pipeline",
        doc="Content-keyed embedding reuse in "
            "`SentenceTransformerEmbedder`: byte-identical texts "
            "(re-ingested unchanged chunks) serve from a bounded LRU "
            "instead of re-dispatching; an all-hit microbatch never "
            "touches the device.",
    ),
    Flag(
        env="PATHWAY_TPU_MESH", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_mesh_serving.py",
        attr="mesh", group="pipeline",
        doc="GSPMD mesh-sharded serving: decoder/embedder params get "
            "Megatron `NamedSharding` annotations over a `(data, fsdp, "
            "tp)` mesh (`parallel/mesh.py:make_serving_mesh`), the "
            "paged/dense KV pool shards its head axis over `tp`, the "
            "Pallas paged-attention kernel runs per-shard via "
            "`shard_map`, and `answer_query` retrieval routes through "
            "the mesh-resident `ShardedIvfIndex`. `0` (default) — and "
            "`1` on a 1x1x1 mesh — leaves single-chip serving tokens "
            "byte-identical (`tests/test_mesh_serving.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_MESH_DATA", kind="int", default=1,
        reload="construction",
        attr="mesh_data", group="pipeline", minimum=1,
        doc="`data` axis length of the serving mesh (replica/batch "
            "dimension). `data * fsdp * tp` must equal the device "
            "count; impossible shapes raise a typed `MeshShapeError` "
            "at server construction instead of an XLA crash.",
    ),
    Flag(
        env="PATHWAY_TPU_MESH_FSDP", kind="int", default=1,
        reload="construction",
        attr="mesh_fsdp", group="pipeline", minimum=1,
        doc="`fsdp` axis length of the serving mesh: parameters not "
            "tensor-sharded by `tp` split their first divisible dim "
            "here (ZeRO-3-style layout; 1 = fully replicated "
            "remainder).",
    ),
    Flag(
        env="PATHWAY_TPU_MESH_TP", kind="int", default=0,
        reload="construction",
        attr="mesh_tp", group="pipeline", minimum=0,
        doc="`tp` (tensor-parallel) axis length of the serving mesh: "
            "attention heads, ffn features and the KV pool's head axis "
            "shard here. `0` = auto — every device left over after "
            "`data * fsdp`.",
    ),
    # ---- query-path knobs (README 'query' table) ----------------------
    Flag(
        env="PATHWAY_TPU_PAIR_BUCKETS", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_rerank_cascade.py",
        attr="pair_buckets", group="query",
        doc="Pow2 length-bucketed pair packing in the fused rerank. `0` "
            "pads every pair to the full `pair_seq` window (seed "
            "behavior).",
    ),
    Flag(
        env="PATHWAY_TPU_RERANK_CASCADE", kind="bool", default=False,
        kill_switch=True, pinned_by="tests/test_rerank_cascade.py",
        attr="rerank_cascade", group="query",
        doc="Two-stage early-exit rerank inside the single fused "
            "dispatch. `0` scores every candidate at full depth (seed "
            "behavior, bitwise with buckets off).",
    ),
    Flag(
        env="PATHWAY_TPU_RERANK_CASCADE_DEPTH", kind="int", default=0,
        attr="rerank_cascade_depth", group="query", minimum=0,
        doc="Encoder layers in the cheap pass; `0` = auto "
            "(`layers//2`).",
    ),
    Flag(
        env="PATHWAY_TPU_RERANK_CASCADE_SURVIVORS", kind="int",
        default=0, attr="rerank_cascade_survivors", group="query",
        minimum=0,
        doc="Candidates promoted to the full-depth pass; `0` = auto "
            "(`max(8, k//2)`).",
    ),
    Flag(
        env="PATHWAY_TPU_RERANK_SEED_WEIGHT", kind="float", default=0.25,
        attr="rerank_seed_weight", group="query",
        doc="Weight of the (normalized) retrieval score blended into "
            "the cheap-stage score.",
    ),
    Flag(
        env="PATHWAY_TPU_LATE_INTERACTION", kind="bool", default=False,
        kill_switch=True, pinned_by="tests/test_late_interaction.py",
        attr="late_interaction", group="query",
        doc="Late-interaction MaxSim cheap stage over the ingest-time "
            "compressed doc-token bank (int8 payloads, `LATE_DIM` per "
            "token). `0` keeps the truncated-encoder cheap pass "
            "(bitwise with the current cascade).",
    ),
    Flag(
        env="PATHWAY_TPU_LATE_DIM", kind="int", default=32,
        reload="construction",
        attr="late_dim", group="query", minimum=8,
        doc="Compressed per-token dimension of the late-interaction "
            "doc bank — the width MaxSim dots query tokens against.",
    ),
    Flag(
        env="PATHWAY_TPU_LLM_RERANK", kind="bool", default=False,
        kill_switch=True, pinned_by="tests/test_late_interaction.py",
        attr="llm_rerank", group="query",
        doc="Listwise LLM rerank over cascade survivors (RankLLM-style "
            "sliding window served by the continuous decoder). `0` "
            "returns the cross-encoder order untouched.",
    ),
    Flag(
        env="PATHWAY_TPU_QUERY_TICK_MS", kind="float", default=2.0,
        reload="construction",
        tunable=Tunable("float", lo=0.5, hi=8, log=True),
        attr="query_tick_ms", group="query", minimum=0,
        doc="Micro-batch window: how long the first queued query waits "
            "for companions before the tick dispatches.",
    ),
    Flag(
        env="PATHWAY_TPU_QUERY_MAX_BATCH", kind="int", default=64,
        reload="construction",
        tunable=Tunable("int", lo=8, hi=128, log=True),
        attr="query_max_batch", group="query", minimum=1,
        doc="Max queries coalesced into one tick (rows pad to pow2 "
            "buckets).",
    ),
    Flag(
        env="PATHWAY_TPU_QUERY_QUEUE", kind="int", default=256,
        reload="construction",
        attr="query_queue", group="query", minimum=1,
        doc="Pending-request bound; `submit` blocks (backpressure) "
            "beyond it.",
    ),
    # ---- observability knobs (README 'observability' table) -----------
    Flag(
        env="PATHWAY_TPU_METRICS", kind="bool", default=True,
        kill_switch=True, pinned_by="tests/test_observability.py",
        attr="metrics", group="observability",
        doc="Master kill switch for the observability layer: `0` turns "
            "every `MetricsRegistry` write (counters, gauges, latency "
            "histograms) and per-request span into a no-op. Token "
            "streams and pipeline outputs are byte-identical either way "
            "— instrumentation never touches compute. Scheduler "
            "operator attribution (`SchedulerStats`) is engine "
            "accounting and stays on.",
    ),
    Flag(
        env="PATHWAY_TPU_TRACE_RING", kind="int", default=256,
        attr="trace_ring", group="observability", minimum=1,
        doc="Completed request spans kept in the in-process ring buffer "
            "behind `recent_traces()` (per process, oldest evicted "
            "first).",
    ),
    Flag(
        env="PATHWAY_TPU_TRACE_DIR", kind="str", default="",
        attr="trace_dir", group="observability",
        doc="Flight recorder: when set, every completed span appends "
            "one JSON line to `<dir>/trace-<pid>.jsonl` (created on "
            "demand; write errors are swallowed — tracing must never "
            "break serving). Unset (default) disables the recorder.",
    ),
    Flag(
        env="PATHWAY_TPU_LOCK_SANITIZER", kind="bool", default=False,
        reload="construction",
        attr="lock_sanitizer", group="observability",
        doc="Runtime race harness (`pathway_tpu/analysis/runtime.py`): "
            "locks built through `analysis.runtime.make_lock` record "
            "per-thread held-lock sets, report lock-order inversions "
            "and writes to `guarded_by` fields outside their lock. Read "
            "once per lock CONSTRUCTION — when off (default) the "
            "constructor returns a plain `threading.Lock`/`RLock`, so "
            "the serving hot paths carry zero wrapper cost "
            "(`tests/test_perf_guard.py` pins the ON-arm overhead "
            "≤ 3%, tokens byte-identical either way).",
    ),
    Flag(
        env="PATHWAY_TPU_OP_METRICS", kind="bool", default=True,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_engine_telemetry.py",
        attr="op_metrics", group="observability",
        doc="Per-operator dataflow telemetry (registry "
            "`op_step_seconds` / `op_rows` / `op_held_rows` / "
            "`watermark_lag` / `engine_backlog` / `exchange_rows` / "
            "`consolidate_rows` families): `0` drops the engine-side "
            "registry writes while "
            "`SchedulerStats` accounting stays on. Read once per "
            "scheduler construction so the per-step hot path never "
            "touches the environment; pipeline outputs are "
            "byte-identical either way. Subordinate to "
            "`PATHWAY_TPU_METRICS`.",
    ),
    Flag(
        env="PATHWAY_TPU_PROFILE_DIR", kind="str", default="",
        attr="profile_dir", group="observability",
        doc="On-demand device profiling: when set, `GET "
            "/debug/profile?ms=N` on any REST server captures a "
            "`jax.profiler` trace of the next N milliseconds into a "
            "fresh subdirectory and returns its path. Unset (default) "
            "the endpoint refuses — profiling is opt-in because traces "
            "can be large and briefly perturb serving.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_TTFT_P95_MS", kind="float", default=0.0,
        reload="construction",
        attr="slo_ttft_p95_ms", group="observability",
        doc="SLO objective: serving TTFT p95 ceiling in ms "
            "(`engine/slo.py` watchdog). `0` (default) disables the "
            "objective.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_E2E_P95_MS", kind="float", default=0.0,
        reload="construction",
        attr="slo_e2e_p95_ms", group="observability",
        doc="SLO objective: request end-to-end p95 ceiling in ms. `0` "
            "(default) disables the objective.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_OCCUPANCY_MIN", kind="float", default=0.0,
        reload="construction",
        attr="slo_occupancy_min", group="observability",
        doc="SLO objective: continuous-batching occupancy floor "
            "(useful slot-steps / total, 0..1). `0` (default) disables "
            "the objective.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_PREFIX_HIT_MIN", kind="float", default=0.0,
        reload="construction",
        attr="slo_prefix_hit_min", group="observability",
        doc="SLO objective: prefix-KV-cache token hit-rate floor "
            "(0..1; only judged once the cache has seen requests). `0` "
            "(default) disables the objective.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_WINDOW_FAST_S", kind="float", default=60.0,
        reload="construction",
        attr="slo_window_fast_s", group="observability", minimum=1,
        doc="Fast burn-rate window in seconds: catches an SLO cliff "
            "quickly; the alert clears when this window recovers.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_WINDOW_SLOW_S", kind="float", default=600.0,
        reload="construction",
        attr="slo_window_slow_s", group="observability", minimum=1,
        doc="Slow burn-rate window in seconds: confirms a breach is "
            "sustained before the alert fires (both windows must burn "
            "above threshold).",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_BURN_THRESHOLD", kind="float", default=1.0,
        reload="construction",
        attr="slo_burn_threshold", group="observability",
        doc="Burn-rate alert threshold: alert when (violating fraction "
            "in window) / budget reaches this in BOTH windows. `1.0` "
            "means 'spending the error budget exactly as fast as "
            "allowed'.",
    ),
    Flag(
        env="PATHWAY_TPU_SLO_BUDGET", kind="float", default=0.1,
        reload="construction",
        attr="slo_budget", group="observability",
        doc="Error budget: the tolerated fraction of violating samples "
            "within a window (SRE error-budget fraction).",
    ),
    # ------------------------------------------------ fault tolerance
    Flag(
        env="PATHWAY_TPU_CHAOS", kind="float", default=0.0,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_chaos.py",
        attr="chaos", group="fault", minimum=0,
        doc="Deterministic fault injection (`engine/chaos.py`): the "
            "probability in [0, 1] that an armed chaos site raises a "
            "typed `InjectedFault` on one pass. Read once per site "
            "CONSTRUCTION — `0` (default) makes `chaos.site()` return "
            "None, so the serving hot paths pay one `is not None` "
            "check and outputs stay byte-identical.",
    ),
    Flag(
        env="PATHWAY_TPU_CHAOS_SEED", kind="int", default=0,
        reload="construction",
        attr="chaos_seed", group="fault",
        doc="Seed for the per-site chaos RNGs: the same (seed, site) "
            "pair yields the same fault schedule across runs and "
            "processes, so a chaos failure is replayable.",
    ),
    Flag(
        env="PATHWAY_TPU_CHAOS_SITES", kind="str", default="",
        reload="construction",
        attr="chaos_sites", group="fault",
        doc="Comma-separated chaos site names (or dotted prefixes, e.g. "
            "`decode` arms `decode.admit` and `decode.dispatch`) to "
            "arm. Empty (default) arms every site when "
            "`PATHWAY_TPU_CHAOS` > 0.",
    ),
    Flag(
        env="PATHWAY_TPU_SERVE_RESTARTS", kind="int", default=0,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_chaos.py",
        attr="serve_restarts", group="fault", minimum=0,
        doc="Supervised serving: how many times a crashed serving loop "
            "(`_ContinuousServer`, `QueryServer`) restarts with "
            "exponential backoff before latching failed. Also gates "
            "per-request isolation (a request-scoped error fails one "
            "request, not the server). `0` (default) keeps the "
            "historical latch-on-first-error behavior, byte-identical.",
    ),
    Flag(
        env="PATHWAY_TPU_SERVE_RETRIES", kind="int", default=1,
        reload="construction",
        attr="serve_retries", group="fault", minimum=0,
        doc="Per-request retry budget under supervised serving: a "
            "request whose admission work faults re-queues up to this "
            "many times before failing alone. Inert while "
            "`PATHWAY_TPU_SERVE_RESTARTS` is 0.",
    ),
    Flag(
        env="PATHWAY_TPU_REQUEST_DEADLINE_MS", kind="float", default=0.0,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_chaos.py",
        attr="request_deadline_ms", group="fault", minimum=0,
        doc="Per-request serving deadline in ms, enforced at admission "
            "and while queued: an expired request is SHED with a "
            "structured error (HTTP 503 + Retry-After on the REST "
            "path) instead of occupying a slot. `0` (default) disables "
            "deadlines; serving is byte-identical.",
    ),
    Flag(
        env="PATHWAY_TPU_SERVE_QUEUE", kind="int", default=0,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_chaos.py",
        attr="serve_queue", group="fault", minimum=0,
        doc="Continuous-server submit-queue watermark: a submit landing "
            "on a queue already this deep is shed immediately "
            "(structured error -> HTTP 503) instead of waiting "
            "unboundedly. `0` (default) keeps the unbounded queue, "
            "byte-identical.",
    ),
    Flag(
        env="PATHWAY_TPU_DEGRADATION", kind="bool", default=True,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_chaos.py",
        attr="degradation", group="fault",
        doc="SLO-driven degradation ladder (`engine/slo.py`): while the "
            "watchdog alerts, admission degrades progressively — clamp "
            "`max_new`, disable speculative decode, shed low-priority "
            "admissions — and walks back up as the fast window "
            "recovers. Inert without `PATHWAY_TPU_SLO_*` objectives "
            "(no alert can fire); `0` disables the ladder entirely, "
            "byte-identical.",
    ),
    Flag(
        env="PATHWAY_TPU_TENANT_SCHED", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_disagg.py",
        attr="tenant_sched", group="fault",
        doc="Multi-tenant admission scheduling: `submit(..., tenant=)` "
            "tags requests, the admission pop becomes weighted-fair "
            "(stride scheduling over `PATHWAY_TPU_TENANT_WEIGHTS`), and "
            "a tenant over its `PATHWAY_TPU_TENANT_BUDGET` in-flight "
            "token budget is first skipped, then PREEMPTED — the slot "
            "is rewound through the isolation path, its KV blocks are "
            "parked, and the request requeues (never sheds). The PR-10 "
            "degradation ladder keeps running as one policy among "
            "several. `0` (default) keeps the FIFO pop byte-identically "
            "(`tests/test_disagg.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_TENANT_BUDGET", kind="int", default=0,
        reload="construction",
        tunable=Tunable("choice", choices=("0", "64", "128", "256")),
        attr="tenant_budget", group="fault", minimum=0,
        doc="Per-tenant in-flight token budget under "
            "`PATHWAY_TPU_TENANT_SCHED`: a tenant at or over budget is "
            "skipped by the weighted-fair pop while others wait, and "
            "preempted when the queue has eligible work but no free "
            "slot. A tenant with nothing in flight is always eligible, "
            "so the budget throttles concurrency without deadlocking. "
            "`0` (default) = unlimited.",
    ),
    Flag(
        env="PATHWAY_TPU_TENANT_WEIGHTS", kind="str", default="",
        reload="construction",
        attr="tenant_weights", group="fault",
        doc="Comma-separated `tenant:weight` pairs (e.g. "
            "`prod:4,batch:1`) for the weighted-fair admission pop; "
            "unlisted tenants weigh 1. Service is proportional to "
            "weight via stride scheduling, and every tenant with a "
            "positive weight is starvation-free.",
    ),
    # ------------------------------------------------ fleet serving
    Flag(
        env="PATHWAY_TPU_FLEET", kind="bool", default=False,
        reload="construction",
        kill_switch=True, pinned_by="tests/test_fleet.py",
        attr="fleet", group="fleet",
        doc="Replicated serving fleet (`pathway_tpu/serving/`): a "
            "prefix-affinity router spreads requests over N supervised "
            "replicas and a fleet manager health-checks, respawns and "
            "scales them off the SLO burn signal. `0` (default) keeps "
            "the single-server path byte-identically — "
            "`serving.build_fleet` returns None and no router, ring or "
            "manager object is ever constructed "
            "(`tests/test_fleet.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_FLEET_REPLICAS", kind="int", default=2,
        reload="construction",
        attr="fleet_replicas", group="fleet", minimum=1,
        doc="Initial replica count the fleet manager spawns at start "
            "(clamped into `[PATHWAY_TPU_FLEET_MIN, "
            "PATHWAY_TPU_FLEET_MAX]`).",
    ),
    Flag(
        env="PATHWAY_TPU_FLEET_MIN", kind="int", default=1,
        reload="construction",
        attr="fleet_min", group="fleet", minimum=1,
        doc="Elasticity floor: scale-down never drops the fleet below "
            "this many replicas.",
    ),
    Flag(
        env="PATHWAY_TPU_FLEET_MAX", kind="int", default=4,
        reload="construction",
        attr="fleet_max", group="fleet", minimum=1,
        doc="Elasticity ceiling: scale-up stops here even while the "
            "SLO burn signal stays hot.",
    ),
    Flag(
        env="PATHWAY_TPU_FLEET_AFFINITY", kind="int", default=4,
        reload="construction",
        attr="fleet_affinity", group="fleet", minimum=0,
        doc="Prefix-affinity depth: how many prompt-head token BLOCKS "
            "(the prefix-cache block size, `PATHWAY_TPU_PREFIX_BLOCK` "
            "pow2-rounded from the prefill chunk) feed the consistent-"
            "hash ring key, so prompts sharing a RAG head land on the "
            "replica whose radix cache already holds it. `0` disables "
            "affinity and the router round-robins.",
    ),
    Flag(
        env="PATHWAY_TPU_FLEET_HEALTH_MS", kind="float", default=500.0,
        reload="construction",
        attr="fleet_health_ms", group="fleet", minimum=1,
        doc="Fleet-manager health-check cadence in ms: each pass probes "
            "every replica (`/healthz` + `/readyz` on HTTP replicas), "
            "drains dead ones from the ring, requeues their in-flight "
            "requests and respawns with bounded exponential backoff.",
    ),
    # ------------------------------------------------ autotuning
    Flag(
        env="PATHWAY_TPU_TUNED_CONFIG", kind="str", default="",
        kill_switch=True, pinned_by="tests/test_autotune.py",
        attr="tuned_config", group="tuning",
        doc="Path to a tuned-config JSON artifact (written by `python -m "
            "pathway_tpu.cli tune <profile>`): its `flags` section "
            "becomes the LOWEST-precedence value source for registry "
            "flags — explicit env vars and `flag_overrides()` scopes "
            "still win, flag-by-flag. Unset (default) every flag reads "
            "exactly as before the artifact existed, byte-identically "
            "(`tests/test_autotune.py`).",
    ),
    Flag(
        env="PATHWAY_TPU_TUNE_SEED", kind="int", default=0,
        attr="tune_seed", group="tuning",
        doc="Seed for the autotuner's candidate shuffling and trial "
            "traces: the same (seed, profile) pair replays the same "
            "search, trial for trial.",
    ),
    Flag(
        env="PATHWAY_TPU_TUNE_TRIALS", kind="int", default=0,
        attr="tune_trials", group="tuning", minimum=0,
        doc="Hard cap on autotuner trials per search; `0` = auto (the "
            "successive-halving schedule decides). The CLI `--smoke` "
            "mode forces a 2-trial cap for seconds-scale CI runs.",
    ),
    Flag(
        env="PATHWAY_TPU_TUNE_CHAOS_RATE", kind="float", default=0.25,
        attr="tune_chaos_rate", group="tuning", minimum=0,
        doc="Fault-injection rate for the autotuner's validation drill: "
            "surviving candidates re-run with `PATHWAY_TPU_CHAOS` at "
            "this rate (plus a restart budget) and are rejected unless "
            "every request still reaches a terminal state.",
    ),
]

_REGISTRY_BY_ENV: dict[str, Flag] = {f.env: f for f in FLAG_REGISTRY}


# --------------------------------------------------------------------- #
# override overlay + tuned-config artifact (the autotuner's substrate)

class FlagReloadError(RuntimeError):
    """Raised when :func:`flag_overrides` is asked to hot-flip a flag
    whose value is consumed at construction time (``reload=
    "construction"``) without the caller owning construction — the
    override would silently no-op on every already-built object."""


class TunedConfigError(ValueError):
    """Raised when ``PATHWAY_TPU_TUNED_CONFIG`` names an artifact that
    cannot be loaded (missing file, bad JSON, unknown or unparseable
    flag). Loud on purpose: a tuned config is explicit opt-in, and a
    silently dropped artifact would masquerade as a perf regression."""


_OVERRIDES_LOCK = threading.RLock()
_FLAG_OVERRIDES: dict[str, str] = {}


@contextlib.contextmanager
def flag_overrides(values: dict[str, Any], *, construction: bool = False):
    """Scoped flag values that never touch ``os.environ``.

    ``values`` maps registered env names to raw values (stringified with
    bool→``"1"``/``"0"``); inside the ``with`` block every
    :meth:`Flag.read` resolves them FIRST, ahead of the real environment
    and any tuned config. Scopes nest, restore exactly on exit (also on
    exception), and are process-global — the point is that trial servers
    running on background threads see them while child processes and
    concurrent tooling never do. Unknown env names raise ``KeyError``
    (the GL2xx choke-point discipline extends here: only declared flags
    have values), and ``reload="construction"`` flags raise
    :class:`FlagReloadError` unless ``construction=True`` says the
    caller builds the consuming objects inside the scope."""
    norm: dict[str, str] = {}
    for env, val in values.items():
        flag = _REGISTRY_BY_ENV.get(env)
        if flag is None:
            raise KeyError(
                f"flag_overrides: {env!r} is not in FLAG_REGISTRY — "
                "every override must name a declared flag"
            )
        if flag.reload == "construction" and not construction:
            raise FlagReloadError(
                f"flag_overrides: {env} is read at construction time; "
                "overriding it mid-flight would silently no-op. Pass "
                "construction=True if the consuming objects are built "
                "inside the scope."
            )
        if isinstance(val, bool):
            raw = "1" if val else "0"
        else:
            raw = str(val)
        flag.parse_raw(raw)  # surface bad values here, not at first read
        norm[env] = raw
    with _OVERRIDES_LOCK:
        saved = {env: _FLAG_OVERRIDES.get(env) for env in norm}
        _FLAG_OVERRIDES.update(norm)
    try:
        yield
    finally:
        with _OVERRIDES_LOCK:
            for env, prev in saved.items():
                if prev is None:
                    _FLAG_OVERRIDES.pop(env, None)
                else:
                    _FLAG_OVERRIDES[env] = prev


def load_tuned_config(path: str) -> dict[str, str]:
    """Parse one tuned-config artifact into ``{env: raw_value}``.

    Every key must be a registered flag (``PATHWAY_TPU_TUNED_CONFIG``
    itself excluded — no recursion) and every value must survive the
    flag's own parser; anything else raises :class:`TunedConfigError`
    with the artifact path in the message."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as exc:
        raise TunedConfigError(f"tuned config {path!r}: {exc}") from exc
    if not isinstance(data, dict) or not isinstance(data.get("flags"), dict):
        raise TunedConfigError(
            f"tuned config {path!r}: expected a JSON object with a "
            "'flags' mapping"
        )
    out: dict[str, str] = {}
    for env in sorted(data["flags"]):
        flag = _REGISTRY_BY_ENV.get(env)
        if flag is None or env == "PATHWAY_TPU_TUNED_CONFIG":
            raise TunedConfigError(
                f"tuned config {path!r}: {env!r} is not a tunable "
                "registry flag"
            )
        val = data["flags"][env]
        raw = ("1" if val else "0") if isinstance(val, bool) else str(val)
        try:
            flag.parse_raw(raw)
        except (TypeError, ValueError) as exc:
            raise TunedConfigError(
                f"tuned config {path!r}: {env}={raw!r} does not parse: "
                f"{exc}"
            ) from exc
        out[env] = raw
    return out


# keyed on (path, mtime_ns, size) so a rewritten artifact — or a test
# pointing the env var at a different tmp file — re-parses, while steady
# state costs one stat per read
_TUNED_CACHE: tuple[tuple[str, int, int], dict[str, str]] | None = None


def _tuned_flags() -> dict[str, str]:
    global _TUNED_CACHE
    path = _FLAG_OVERRIDES.get("PATHWAY_TPU_TUNED_CONFIG")
    if path is None:
        path = os.environ.get("PATHWAY_TPU_TUNED_CONFIG", "")
    if not path:
        return {}
    try:
        st = os.stat(path)
        key = (path, st.st_mtime_ns, st.st_size)
    except OSError as exc:
        raise TunedConfigError(f"tuned config {path!r}: {exc}") from exc
    if _TUNED_CACHE is not None and _TUNED_CACHE[0] == key:
        return _TUNED_CACHE[1]
    flags = load_tuned_config(path)
    _TUNED_CACHE = (key, flags)
    return flags


def _raw_flag_value(env: str) -> str | None:
    """One flag's raw string under the full precedence chain:
    ``flag_overrides`` scope > explicit environment > tuned-config
    artifact > (None — caller falls back to the declared default)."""
    raw = _FLAG_OVERRIDES.get(env)
    if raw is not None:
        return raw
    raw = os.environ.get(env)
    if raw is not None:
        return raw
    if env == "PATHWAY_TPU_TUNED_CONFIG":
        return None
    return _tuned_flags().get(env)


def tuned_config_snapshot() -> dict[str, Any]:
    """The ``tuning`` section of ``/v1/statistics``: which artifact (if
    any) is loaded, the flags it pins, and which of those an explicit
    env var out-ranks."""
    path = _FLAG_OVERRIDES.get("PATHWAY_TPU_TUNED_CONFIG")
    if path is None:
        path = os.environ.get("PATHWAY_TPU_TUNED_CONFIG", "")
    if not path:
        return {"enabled": False, "path": None, "flags": {},
                "shadowed_by_env": []}
    flags = _tuned_flags()
    return {
        "enabled": True,
        "path": path,
        "flags": dict(flags),
        "shadowed_by_env": sorted(
            env for env in flags if os.environ.get(env) is not None
        ),
    }


def env_interpolate(name: str) -> str | None:
    """Read one environment variable by (possibly dynamic) name.

    The audited choke point for the rare legitimate dynamic env read —
    YAML `$ENV` interpolation, user-named credentials. Everything
    declared in :data:`FLAG_REGISTRY` must be read through
    ``pathway_config`` instead; the analyzer (rule ``GL202``) flags any
    direct ``os.environ`` use outside this module."""
    return os.environ.get(name)


def environ_snapshot(**overrides: str) -> dict[str, str]:
    """A copy of the current process environment (plus ``overrides``),
    for handing a subprocess its inherited environment. The audited
    choke point for whole-environment access outside this module."""
    env = dict(os.environ)
    env.update(overrides)
    return env


def render_flag_table(group: str) -> str:
    """The README flag table for ``group``, generated from the registry
    (``tests/test_flag_registry.py`` pins the README copy to this)."""
    lines = [
        "| Env var | Default | What it controls |",
        "|---|---|---|",
    ]
    for f in FLAG_REGISTRY:
        if f.group == group:
            lines.append(
                f"| `{f.env}` | `{f.render_default()}` | {f.doc} |"
            )
    return "\n".join(lines)


@dataclass
class PathwayConfig:
    ignore_asserts: bool = field(
        default_factory=lambda: _env_bool("PATHWAY_IGNORE_ASSERTS")
    )
    runtime_typechecking: bool = field(
        default_factory=lambda: _env_bool("PATHWAY_RUNTIME_TYPECHECKING")
    )
    terminate_on_error: bool = field(
        default_factory=lambda: _env_bool("PATHWAY_TERMINATE_ON_ERROR", True)
    )
    license_key: str | None = field(
        default_factory=lambda: os.environ.get("PATHWAY_LICENSE_KEY")
    )
    replay_storage: str | None = field(
        default_factory=lambda: os.environ.get("PATHWAY_REPLAY_STORAGE")
    )
    persistence_mode: str | None = field(
        default_factory=lambda: os.environ.get("PATHWAY_PERSISTENCE_MODE")
    )
    snapshot_access: str | None = field(
        default_factory=lambda: os.environ.get("PATHWAY_SNAPSHOT_ACCESS")
    )
    continue_after_replay: bool = field(
        default_factory=lambda: _env_bool("PATHWAY_CONTINUE_AFTER_REPLAY", False)
    )
    process_id: int = field(
        default_factory=lambda: int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    )
    monitoring_server: str | None = field(
        default_factory=lambda: os.environ.get("PATHWAY_MONITORING_SERVER")
    )

    @property
    def threads(self) -> int:
        return int(os.environ.get("PATHWAY_THREADS", "1"))

    @property
    def processes(self) -> int:
        return int(os.environ.get("PATHWAY_PROCESSES", "1"))

    @property
    def first_port(self) -> int:
        return int(os.environ.get("PATHWAY_FIRST_PORT", "10000"))

    @property
    def persistent_storage(self) -> str | None:
        return os.environ.get("PATHWAY_PERSISTENT_STORAGE")


def _install_flag_properties() -> None:
    """Attach one read-per-use property per registry flag. Declared once
    in :data:`FLAG_REGISTRY`; the property is just ``Flag.read``."""
    for f in FLAG_REGISTRY:
        if hasattr(PathwayConfig, f.attr):  # never shadow a manual attr
            raise RuntimeError(f"duplicate config attr: {f.attr}")

        def _getter(self, _f=f):
            return _f.read()

        _getter.__name__ = f.attr
        setattr(PathwayConfig, f.attr, property(_getter, doc=f.doc))


_install_flag_properties()

pathway_config = PathwayConfig()

def enable_compile_cache() -> str:
    """The ONE place the persistent XLA compilation cache is configured,
    for library use, tests and the benchmark alike. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it stands
    and no directory is set here; where it is not, the cache lives at the
    fixed ``<checkout>/.jax_cache`` (the directory is part of the cache
    key's environment, so it must not move between runs). Returns the
    directory in effect. A cold run is JAX's own switch:
    ``JAX_ENABLE_COMPILATION_CACHE=false``."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache even fast compiles: streaming graphs compile many small
    # bucket-shaped kernels whose individual compile times sit under
    # the default threshold but add up across runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


_persistence_config: Any = None


def set_persistence_config(cfg: Any) -> None:
    global _persistence_config
    _persistence_config = cfg


def get_persistence_config() -> Any:
    """Explicitly set persistence config, else one auto-built from the
    PATHWAY_REPLAY_STORAGE family of env vars (``pathway spawn --record`` /
    ``pathway replay``)."""
    if _persistence_config is not None:
        return _persistence_config
    if pathway_config.replay_storage:
        from pathway_tpu import persistence as persistence_mod

        return persistence_mod.Config(
            backend=persistence_mod.Backend.filesystem(
                pathway_config.replay_storage
            ),
            persistence_mode=pathway_config.persistence_mode or "persisting",
            snapshot_access=pathway_config.snapshot_access,
            # replay-only runs stop at the end of the log unless asked to
            # continue; record / recovery runs must keep reading live data
            continue_after_replay=(
                pathway_config.continue_after_replay
                or pathway_config.snapshot_access != "replay"
            ),
        )
    return None


def set_license_key(key: str | None) -> None:
    pathway_config.license_key = key


def set_monitoring_config(*, server_endpoint: str | None) -> None:
    pathway_config.monitoring_server = server_endpoint


if __name__ == "__main__":
    # regenerate the README flag tables (paste between the
    # <!-- flags:<group> --> markers)
    for _group in (
        "pipeline", "query", "observability", "fault", "fleet", "tuning",
    ):
        print(f"<!-- flags:{_group} -->")
        print(render_flag_table(_group))
        print(f"<!-- /flags:{_group} -->")
        print()
