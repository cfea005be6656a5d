"""Sentence embedder: encoder + masked mean pooling + L2 normalise.

This is the TPU-native stand-in for sentence-transformers' MiniLM pipeline
(reference: SentenceTransformerEmbedder,
/root/reference/python/pathway/xpacks/llm/embedders.py:270-313 — which calls
``model.encode`` on CPU/GPU). Here the whole embed step — encode, pool,
normalise — is one jitted function; batches arrive padded to pow2 buckets so
each (batch, seq) bucket compiles once and is reused for the stream's life.

``embed_submit`` is PIPELINED by default (PATHWAY_TPU_PIPELINE=0 restores
the serial path): a background tokenizer worker feeds a bounded queue, a
dispatch worker stages the next batch onto the device (``jax.device_put``)
while the current one computes and launches a donated executable, so input
buffers ping-pong instead of accumulating one per batch in flight. Stage
busy-seconds land in the probes stage ledger (tokenize / h2d / dispatch /
drain) for bubble attribution.
"""

from __future__ import annotations

import functools
import threading
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.engine.async_runtime import StageWorker
from pathway_tpu.engine.probes import record_device_dispatch
from pathway_tpu.engine.tracing import region
from pathway_tpu.models.tokenizer import (
    HashTokenizer,
    load_tokenizer,
    pad_to_buckets,
)
from pathway_tpu.models.transformer import (
    TransformerConfig,
    MINILM_L6,
    encode,
    init_params,
)


def mean_pool(hidden: jax.Array, mask: jax.Array) -> jax.Array:
    """Masked mean over the sequence axis; hidden (B,S,H), mask (B,S)."""
    m = mask.astype(jnp.float32)[:, :, None]
    summed = jnp.sum(hidden * m, axis=1)
    counts = jnp.clip(jnp.sum(m, axis=1), 1.0, None)
    return summed / counts


@functools.partial(jax.jit, static_argnames=("cfg",))
def cast_params_for_inference(params, cfg: TransformerConfig):
    """Store weights in the compute dtype (bf16) for inference: HBM param
    reads halve and the per-layer casts become no-ops — measured 2-5x faster
    end-to-end on v5e vs f32-stored params. Training keeps f32 masters
    (models/train.py)."""
    return jax.tree.map(
        lambda p: p.astype(cfg.dtype) if p.dtype == jnp.float32 else p,
        params,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "flash"))
def embed_fn(params, input_ids, attention_mask, cfg: TransformerConfig,
             flash: bool = False):
    """One fused executable for the whole embed step. MUST stay jitted:
    run eagerly, every op is its own dispatch.

    ``flash`` (static, from the model's construction-time read of
    ``PATHWAY_TPU_FLASH_PREFILL``) routes attention through the
    non-causal flash kernel via ``encode``'s core seam."""
    hidden = encode(params, input_ids, attention_mask, cfg, flash=flash)
    pooled = mean_pool(hidden, attention_mask)
    return pooled / jnp.clip(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9, None
    )


# backends without input aliasing (CPU tests) ignore the donation and warn
# per bucket shape; the pipeline is still correct, just without the
# ping-pong buffer reuse, so the warning is pure noise there
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


@functools.partial(jax.jit, static_argnames=("cfg", "flash"),
                   donate_argnums=(1, 2))
def _embed_fn_donated(params, input_ids, attention_mask,
                      cfg: TransformerConfig, flash: bool = False):
    """``embed_fn`` with the token buffers donated back to XLA. The
    pipeline's staged inputs alternate between "being written by the h2d
    stage" and "owned by the in-flight dispatch", so donation caps live
    input buffers at the dispatch-ahead depth (ping-pong) instead of one
    pair per batch in flight."""
    return embed_fn(params, input_ids, attention_mask, cfg, flash=flash)


@functools.partial(jax.jit, static_argnames=("cfg", "flash"),
                   donate_argnums=(1,))
def _embed_fn_packed(params, packed, cfg: TransformerConfig,
                     flash: bool = False):
    """Fused-transfer variant: ``packed`` is ``stack([ids, mask])`` moved as
    ONE contiguous ``device_put``. Two small transfers per batch each pay a
    fixed runtime/transport overhead; halving the transfer count
    takes the h2d stage off the per-batch critical path. The split back
    into ids/mask happens inside the executable, where it is free."""
    return embed_fn(params, packed[0], packed[1], cfg, flash=flash)


@functools.partial(jax.jit, static_argnames=("cfg", "flash"),
                   donate_argnums=(1,))
def _token_states_packed(params, packed, proj, cfg: TransformerConfig,
                         flash: bool = False):
    """Token-level sibling of :func:`_embed_fn_packed` for the
    late-interaction doc bank: same fused single-transfer input, but the
    executable keeps PER-TOKEN states — full-depth encode, project to the
    compressed dc dim, L2-normalize, int8 per-token quant — instead of
    pooling. Returns ``(payload int8 (B, S, dc), scale f32 (B, S, 1))``."""
    from pathway_tpu.ops.late_bank import _project_tokens, _quant_tokens

    hidden = encode(params, packed[0], packed[1], cfg, flash=flash)
    return _quant_tokens(_project_tokens(hidden, packed[1], proj))


class _PendingEmbed:
    """Handle returned by the pipelined ``embed_submit``: tokenize and
    dispatch run on background stage workers; :meth:`wait` blocks until
    the batch is dispatched and yields the serial-path handle (f16 device
    array, row count). Stage failures surface here, at resolve time."""

    __slots__ = ("_event", "_value", "_error", "span")

    def __init__(self) -> None:
        from pathway_tpu.engine import tracing

        self._event = threading.Event()
        self._value = None
        self._error: BaseException | None = None
        self.span = tracing.NULL_SPAN  # replaced by _IngestPipeline.submit

    def wait(self):
        self._event.wait()
        if self._error is not None:
            raise self._error
        return self._value


class _IngestPipeline:
    """tokenize -> h2d -> dispatch behind ``embed_submit``.

    Two chained :class:`StageWorker` threads: the TOKENIZER worker turns
    raw-text batches (queue bound: PATHWAY_TPU_PIPELINE_QUEUE) into
    bucket-padded id/mask arrays; the DISPATCH worker stages them onto the
    device and launches the donated embed executable. Because dispatch
    only ENQUEUES device work, batch b+1's h2d copy and tokenization
    overlap batch b's compute; the dispatch queue bound
    (PATHWAY_TPU_PIPELINE_DEPTH) caps how far the host runs ahead.
    Single-threaded stages keep dispatch in submit order, so bucket
    executables are reused exactly as on the serial path."""

    def __init__(self, model: "SentenceEmbedderModel", depth: int, queue_bound: int):
        from pathway_tpu.engine import chaos
        from pathway_tpu.internals.config import pathway_config

        self._model = model
        # tags this pipeline's batch spans in the global trace ring
        self._trace_tag = f"embed:{id(model):x}"
        # fault tolerance, read once: with PATHWAY_TPU_SERVE_RESTARTS > 0
        # a transient h2d/dispatch failure is retried (bounded, backoff)
        # before it surfaces at resolve time
        self._chaos_h2d = chaos.site("embed.h2d")
        self._retries = (
            int(pathway_config.serve_retries)
            if int(pathway_config.serve_restarts) > 0 else 0
        )
        self._dispatch = StageWorker(
            self._dispatch_one, maxsize=depth, name="pathway-tpu:embed-dispatch"
        )
        self._tokenize = StageWorker(
            self._tokenize_one, maxsize=queue_bound, name="pathway-tpu:embed-tokenize"
        )

    def submit(self, texts: list[str], kind: str = "embed",
               dc: int = 0) -> _PendingEmbed:
        """Queue a batch for the stage chain. ``kind="embed"`` (default)
        is the pooled-vector path; ``kind="tokens"`` keeps per-token
        states for the late-interaction doc bank (``dc`` = compressed
        token dim) — same tokenize/h2d/dispatch workers, different
        executable at the dispatch stage."""
        from pathway_tpu.engine import tracing

        handle = _PendingEmbed()
        handle.span = tracing.start_span(
            "embed", server=self._trace_tag, texts=len(texts),
        )
        self._tokenize.submit((texts, handle, kind, dc))
        return handle

    def _tokenize_one(self, item) -> None:
        texts, handle, kind, dc = item
        try:
            model = self._model
            handle.span.event("admit")
            with region("pw.embed.tokenize", rows=len(texts)):
                ids, mask = model.tokenizer(
                    texts, max_length=model.max_length)
                ids, mask = pad_to_buckets(ids, mask)
            handle.span.event("tokenize", texts=len(texts))
        except BaseException as exc:  # noqa: BLE001 - surfaces at resolve
            handle._error = exc
            handle.span.finish(error=True)
            handle._event.set()
            return
        # blocks while `depth` batches are staged/dispatched ahead — the
        # backpressure that keeps input buffers ping-ponging
        self._dispatch.submit((ids, mask, len(texts), handle, kind, dc))

    def _dispatch_one(self, item) -> None:
        ids, mask, n, handle, kind, dc = item
        try:
            if self._retries > 0:
                from pathway_tpu.internals.udfs.retries import (
                    ExponentialBackoffRetryStrategy,
                )

                ExponentialBackoffRetryStrategy(
                    max_retries=self._retries, initial_delay=20,
                    backoff_factor=2, jitter_ms=10, max_delay_ms=1000,
                ).invoke_sync(
                    lambda: self._stage_and_dispatch(
                        ids, mask, n, handle, kind, dc
                    )
                )
            else:
                self._stage_and_dispatch(ids, mask, n, handle, kind, dc)
        except BaseException as exc:  # noqa: BLE001 - surfaces at resolve
            handle._error = exc
            handle.span.finish(error=True)
        handle._event.set()

    def _stage_and_dispatch(self, ids, mask, n, handle, kind="embed",
                            dc=0) -> None:
        from pathway_tpu.internals.config import pathway_config

        if self._chaos_h2d is not None:
            self._chaos_h2d.maybe_fail()
        model = self._model
        fused = pathway_config.fused_h2d
        with region("pw.embed.h2d", rows=n):
            if fused:
                # one contiguous transfer instead of two (ids and mask are
                # both int32, so the stack is a cheap host-side copy)
                dev_packed = jax.device_put(np.stack((ids, mask)))
            else:
                dev_ids = jax.device_put(ids)
                dev_mask = jax.device_put(mask)
        handle.span.event("h2d")
        flash = model.flash_prefill
        with region("pw.embed.dispatch", rows=n):
            if kind == "tokens":
                proj = model.late_projection_matrix(dc)
                if fused:
                    out = _token_states_packed(
                        model.params, dev_packed, proj, model.cfg,
                        flash=flash,
                    )
                else:
                    from pathway_tpu.ops.late_bank import doc_token_states

                    out = doc_token_states(
                        model.params, dev_ids, dev_mask, proj, model.cfg,
                        flash=flash,
                    )
                record_device_dispatch("token_bank_dispatch")
                # int8 payload + f32 scales: already transport-compact, no
                # precision cast needed before the drain
            else:
                if fused:
                    out = _embed_fn_packed(model.params, dev_packed,
                                           model.cfg, flash=flash)
                else:
                    out = _embed_fn_donated(
                        model.params, dev_ids, dev_mask, model.cfg,
                        flash=flash,
                    )
                record_device_dispatch("embed_dispatch")
                out = out.astype(jnp.float16)
            for leaf in jax.tree.leaves(out):
                try:
                    leaf.copy_to_host_async()
                except Exception:  # noqa: BLE001 - platform-optional fast path
                    pass
        handle.span.event("dispatch", rows=n)
        handle._value = (out, n)

    def close(self) -> None:
        self._tokenize.close()
        self._dispatch.close()


def _renorm(v: np.ndarray) -> np.ndarray:
    """Restore exact unit norm after the float16 transport quantization
    (~5e-4 relative per component; the norm drifts by up to ~1e-4)."""
    norms = np.linalg.norm(v, axis=-1, keepdims=True)
    np.clip(norms, 1e-9, None, out=norms)
    return v / norms


class SentenceEmbedderModel:
    """Host-facing embedder: str batch -> np.ndarray (B, H) unit vectors."""

    def __init__(
        self,
        cfg: TransformerConfig = MINILM_L6,
        params=None,
        tokenizer=None,
        max_length: int = 128,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.tokenizer = tokenizer or HashTokenizer(max_length=max_length)
        self.max_length = max_length
        if params is None:
            params = init_params(jax.random.PRNGKey(seed), cfg)
        # serving mesh (PATHWAY_TPU_MESH): encoder params commit onto
        # the (data, fsdp, tp) mesh with the Megatron NamedSharding
        # layout; embed dispatches then run GSPMD-partitioned. Off-mesh
        # (or 1x1x1) this is plain single-chip placement.
        from pathway_tpu.parallel.mesh import serving_mesh_from_flags

        # construction-time flag read (reload="construction"): the jit
        # caches key on the static flash arg, so a rebuilt model picks
        # up a flipped env var without invalidating other instances
        from pathway_tpu.internals.config import pathway_config

        # weight-only int8 (PATHWAY_TPU_WEIGHT_QUANT): the word table
        # and layer matmul weights store int8 + f32 scales, dequantized
        # inside the einsum read; scales come from the ORIGINAL params,
        # the compute-dtype cast covers everything else
        self.weight_quant = str(pathway_config.weight_quant or "")
        if self.weight_quant:
            from pathway_tpu.models.transformer import quantize_encoder_params

            self.params = quantize_encoder_params(
                params, out=cast_params_for_inference(params, cfg)
            )
        else:
            self.params = cast_params_for_inference(params, cfg)
        self.flash_prefill = bool(pathway_config.flash_prefill)
        if self.flash_prefill:
            from pathway_tpu.models import flash_attention as _fa

            _fa.configure_blocks(pathway_config.flash_block_q,
                                 pathway_config.flash_block_k)
        self.mesh = serving_mesh_from_flags()
        if self.mesh is not None:
            from pathway_tpu.models.transformer import shard_encoder_params

            self.params = shard_encoder_params(self.params, cfg, self.mesh)
        # HBM ledger: the embedder's physical param footprint (int8
        # payloads + scales when quantized), per device, at placement
        from pathway_tpu.engine.probes import record_hbm
        from pathway_tpu.models.decoder import params_device_bytes

        for dev, nbytes in params_device_bytes(self.params).items():
            record_hbm("weights.embedder", nbytes, device=dev)
        self._pipeline: _IngestPipeline | None = None
        self._pipeline_lock = threading.Lock()
        self._late_proj = None  # (hidden, dc), built at first token submit

    def _maybe_pipeline(self) -> _IngestPipeline | None:
        """The shared ingest pipeline, lazily built — or None when
        PATHWAY_TPU_PIPELINE=0 (the serial-path kill switch). The flag is
        read per call, so flipping the env var mid-process routes new
        submits immediately (an existing pipeline keeps draining)."""
        from pathway_tpu.internals.config import pathway_config

        if not pathway_config.tpu_pipeline:
            return None
        pipe = self._pipeline
        if pipe is None:
            with self._pipeline_lock:
                pipe = self._pipeline
                if pipe is None:
                    pipe = self._pipeline = _IngestPipeline(
                        self,
                        depth=pathway_config.tpu_pipeline_depth,
                        queue_bound=pathway_config.tpu_pipeline_queue,
                    )
        return pipe

    def close(self) -> None:
        """Stop the pipeline workers (drains queued batches first)."""
        with self._pipeline_lock:
            pipe, self._pipeline = self._pipeline, None
        if pipe is not None:
            pipe.close()

    def recent_traces(self, n: int | None = None) -> list[dict]:
        """Completed per-batch spans of this model's ingest pipeline
        (oldest first). Empty on the serial path
        (``PATHWAY_TPU_PIPELINE=0``) and under
        ``PATHWAY_TPU_METRICS=0``."""
        from pathway_tpu.engine import tracing

        return tracing.recent_traces(server=f"embed:{id(self):x}", n=n)

    @classmethod
    def from_local(cls, path: str, cfg: TransformerConfig = MINILM_L6, **kw):
        return cls(cfg=cfg, tokenizer=load_tokenizer(path), **kw)

    @classmethod
    def from_pretrained(cls, path: str, max_length: int = 128, **kw):
        """Load a local HF checkpoint dir (config + weights + tokenizer) —
        real all-MiniLM-L6-v2 weights in the fused-QKV pytree, WordPiece
        tokenization via the local tokenizer files."""
        from pathway_tpu.models.checkpoint import load_encoder_checkpoint

        params, cfg, _ = load_encoder_checkpoint(path)
        init = dict(
            cfg=cfg,
            params=params,
            tokenizer=load_tokenizer(path, max_length=max_length),
            max_length=max_length,
        )
        init.update(kw)  # explicit caller overrides win
        return cls(**init)

    @property
    def dim(self) -> int:
        return self.cfg.hidden

    def embed_batch(self, texts: list[str]) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.cfg.hidden), dtype=np.float32)
        return self.embed_resolve([self.embed_submit(texts)])[0]

    # -- two-phase path: dispatch many batches, drain with ONE round trip --
    def embed_submit(self, texts: list[str]):
        """Tokenize + dispatch WITHOUT waiting for the device; the returned
        handle resolves via :meth:`embed_resolve`. Each blocking fetch
        stalls the host on the device, so a stream of microbatches must
        dispatch back-to-back and drain once. The handle is cast to float16
        on device: embeddings are unit vectors, so the ~5e-4 relative error
        is far inside the pipeline's parity gate while the device->host
        transfer halves.

        Pipelined by default: tokenization and h2d staging happen on
        background stage workers, so this returns as soon as the batch is
        queued (backpressure: blocks once PATHWAY_TPU_PIPELINE_QUEUE
        batches wait). With PATHWAY_TPU_PIPELINE=0 the whole stage chain
        runs inline here, exactly as before."""
        pipe = self._maybe_pipeline()
        if pipe is not None:
            return pipe.submit(texts)
        (out, n) = self.embed_device(texts)
        out = out.astype(jnp.float16)
        # start the device->host copy NOW: by the time the epoch's last
        # chunk is dispatched and embed_resolve drains, earlier chunks'
        # transfers have already overlapped with later chunks' compute
        # (the drain was ~40% of the engine-streaming epoch otherwise)
        try:
            out.copy_to_host_async()
        except Exception:  # noqa: BLE001 - platform-optional fast path
            pass
        return (out, n)

    def embed_device(self, texts: list[str]):
        """Dispatch-only embed returning the FULL-PRECISION device array
        (f32) and the real row count — for consumers that keep the vectors
        on device (index appends, fused pipelines), where the float16
        transport cast of :meth:`embed_submit` would throw away precision
        for nothing."""
        ids, mask = self.tokenizer(texts, max_length=self.max_length)
        ids, mask = pad_to_buckets(ids, mask)
        out = embed_fn(self.params, jnp.asarray(ids), jnp.asarray(mask),
                       self.cfg, flash=self.flash_prefill)
        record_device_dispatch("embed_dispatch")
        return (out, len(texts))

    def embed_resolve(self, handles) -> list[np.ndarray]:
        """One device drain for every submitted handle -> [(n_i, dim) array].
        ``device_get`` on the whole list drains every transfer together —
        measured equal to a device-side concat WITHOUT the risk of compiling
        a fresh concat executable mid-stream when the chunk count changes.
        Accepts pipelined (:class:`_PendingEmbed`) and serial ``(out, n)``
        handles interchangeably, in any order relative to submission."""
        resolved = [
            h.wait() if isinstance(h, _PendingEmbed) else h for h in handles
        ]
        # the host WAITING for the device, and named so
        with region("pw.embed.drain", rows=sum(n for _, n in resolved)):
            fetched = jax.device_get([out for out, _ in resolved])
        record_device_dispatch("embed_drain")
        for h in handles:
            if isinstance(h, _PendingEmbed):
                h.span.event("drain")
                h.span.finish()
        return [
            _renorm(np.asarray(o)[:n].astype(np.float32))
            for o, (_, n) in zip(fetched, resolved)
        ]

    # -- token-level path: per-token states for the late-interaction bank --
    def late_projection_matrix(self, dc: int | None = None):
        """The shared ``(hidden, dc)`` down-projection (deterministic, so
        ingest-time bank rows and query-time token states agree without a
        checkpoint). ``dc`` defaults to ``PATHWAY_TPU_LATE_DIM``; cached
        per width."""
        from pathway_tpu.internals.config import pathway_config
        from pathway_tpu.ops.late_bank import late_projection

        dc = int(dc) if dc else int(pathway_config.late_dim)
        if self._late_proj is None or self._late_proj.shape[1] != dc:
            self._late_proj = late_projection(self.cfg.hidden, dc)
        return self._late_proj

    def token_bank_submit(self, texts: list[str], dc: int | None = None):
        """Dispatch-only token-state encode for the late-interaction doc
        bank: full-depth encode -> project to ``dc`` -> L2-normalize ->
        int8 per-token quant, one fused executable per batch. Rides the
        same StageWorker ingest pipeline as :meth:`embed_submit`
        (tokenize / h2d / dispatch overlap across batches); resolve via
        :meth:`token_bank_resolve`."""
        proj = self.late_projection_matrix(dc)
        pipe = self._maybe_pipeline()
        if pipe is not None:
            return pipe.submit(texts, kind="tokens", dc=proj.shape[1])
        from pathway_tpu.ops.late_bank import doc_token_states

        ids, mask = self.tokenizer(texts, max_length=self.max_length)
        ids, mask = pad_to_buckets(ids, mask)
        out = doc_token_states(
            self.params, jnp.asarray(ids), jnp.asarray(mask), proj, self.cfg,
            flash=self.flash_prefill,
        )
        record_device_dispatch("token_bank_dispatch")
        for leaf in jax.tree.leaves(out):
            try:
                leaf.copy_to_host_async()
            except Exception:  # noqa: BLE001 - platform-optional fast path
                pass
        return (out, len(texts))

    def token_bank_resolve(self, handles) -> list[tuple[np.ndarray, np.ndarray]]:
        """One device drain for submitted token-bank handles ->
        ``[(payload int8 (n, S, dc), scale f32 (n, S, 1))]`` per handle,
        sliced back to real row counts. Accepts pipelined and serial
        handles interchangeably, like :meth:`embed_resolve`."""
        resolved = [
            h.wait() if isinstance(h, _PendingEmbed) else h for h in handles
        ]
        with region("pw.embed.drain", rows=sum(n for _, n in resolved)):
            fetched = jax.device_get([out for out, _ in resolved])
        record_device_dispatch("token_bank_drain")
        for h in handles:
            if isinstance(h, _PendingEmbed):
                h.span.event("drain")
                h.span.finish()
        return [
            (np.asarray(q)[:n], np.asarray(s)[:n])
            for (q, s), (_, n) in zip(fetched, resolved)
        ]

    def __call__(self, texts: list[str]) -> np.ndarray:
        return self.embed_batch(texts)
