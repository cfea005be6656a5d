"""Chat-LLM UDFs (reference ``xpacks/llm/llms.py:27-707``).

``BaseChat`` subclasses are UDFs mapping a message list (or ``pw.Json``) to a
completion string. API clients (OpenAI/LiteLLM/Cohere) are async and gated on
their SDKs; ``HFPipelineChat`` runs a local ``transformers`` pipeline (CPU —
chats are not the TPU hot path; the embedder/reranker are).
"""

from __future__ import annotations

import logging
from typing import Any

import pathway_tpu as pw
from pathway_tpu.analysis.annotations import guarded_by
from pathway_tpu.internals import udfs
from pathway_tpu.internals.json import Json

logger = logging.getLogger(__name__)


def _messages_to_list(messages: Any) -> list[dict]:
    if isinstance(messages, Json):
        messages = messages.value
    if isinstance(messages, str):
        return [{"role": "user", "content": messages}]
    out = []
    for m in messages:
        if isinstance(m, Json):
            m = m.value
        out.append(dict(m))
    return out


def _prep_message_log(messages: list[dict], verbose: bool) -> str:
    if verbose:
        return str(messages)
    return str([
        {**m, "content": m.get("content", "")[:100]} for m in messages
    ])


# Serving failures travel the string-typed response channel as a
# reserved-prefix marker (the \x00 prefix cannot appear in decoded
# model output): the continuous server's resolve encodes WHY a request
# failed or was shed, and the REST layer (``xpacks/llm/servers.py``
# ``map_serving_errors``) decodes it into a structured JSON 500/503
# instead of the opaque null body it used to be.
SERVE_ERROR_MARKER = "\x00pathway_tpu:serve_error\x00"


def encode_serve_error(reason: str,
                       retry_after: float | None = None) -> str:
    import json as json_mod

    payload: dict = {"reason": reason}
    if retry_after is not None:
        payload["retry_after"] = retry_after
    return SERVE_ERROR_MARKER + json_mod.dumps(payload)


def decode_serve_error(text: Any) -> dict | None:
    """The structured error a serving response string carries, or None
    for ordinary responses."""
    import json as json_mod

    if not isinstance(text, str) or not text.startswith(SERVE_ERROR_MARKER):
        return None
    try:
        return json_mod.loads(text[len(SERVE_ERROR_MARKER):])
    except ValueError:
        return {"reason": "serve_failed"}


def _answer_key(ids, max_new: int) -> bytes:
    """What names one greedy answer: a digest of the prompt's ids and the
    token budget (a prompt is thousands of ids; the key is 16 bytes)."""
    import hashlib

    import numpy as np

    h = hashlib.blake2b(np.asarray(ids, np.int64).tobytes(), digest_size=16)
    h.update(int(max_new).to_bytes(8, "little"))
    return h.digest()


class BaseChat(pw.UDF):
    """Base chat UDF (reference ``BaseChat``, llms.py:27)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)

    def _accepts_call_arg(self, arg_name: str) -> bool:
        """Whether the underlying API accepts ``arg_name`` as a call kwarg."""
        return True


class OpenAIChat(BaseChat):
    """OpenAI chat-completions client (reference ``OpenAIChat``,
    llms.py:84-311)."""

    def __init__(
        self,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = "gpt-4o-mini",
        verbose: bool = False,
        **openai_kwargs,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.kwargs = dict(openai_kwargs)
        self.verbose = verbose
        if model is not None:
            self.kwargs["model"] = model

    async def __wrapped__(self, messages: list[dict] | Json, **kwargs) -> str | None:
        try:
            import openai
        except ImportError as exc:  # pragma: no cover - gated dependency
            raise ImportError("OpenAIChat requires the `openai` package") from exc
        messages = _messages_to_list(messages)
        kwargs = {**self.kwargs, **kwargs}
        logger.info("OpenAIChat: %s", _prep_message_log(messages, self.verbose))
        api_kwargs = {
            k: kwargs.pop(k)
            for k in ("api_key", "base_url", "organization")
            if k in kwargs
        }
        client = openai.AsyncOpenAI(**api_kwargs)
        ret = await client.chat.completions.create(messages=messages, **kwargs)
        return ret.choices[0].message.content


class LiteLLMChat(BaseChat):
    """LiteLLM multi-provider chat (reference ``LiteLLMChat``,
    llms.py:313-439)."""

    def __init__(
        self,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = None,
        verbose: bool = False,
        **litellm_kwargs,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.kwargs = dict(litellm_kwargs)
        self.verbose = verbose
        if model is not None:
            self.kwargs["model"] = model

    def __wrapped__(self, messages: list[dict] | Json, **kwargs) -> str | None:
        try:
            import litellm
        except ImportError as exc:  # pragma: no cover - gated dependency
            raise ImportError("LiteLLMChat requires the `litellm` package") from exc
        messages = _messages_to_list(messages)
        ret = litellm.completion(messages=messages, **{**self.kwargs, **kwargs})
        return ret.choices[0]["message"]["content"]


class HFPipelineChat(BaseChat):
    """Local HuggingFace ``transformers`` text-generation pipeline (reference
    ``HFPipelineChat``, llms.py:441-542). Runs host-side."""

    def __init__(
        self,
        model: str | None = None,
        call_kwargs: dict = {},
        device: str = "cpu",
        batch_size: int | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        **pipeline_kwargs,
    ):
        super().__init__(cache_strategy=cache_strategy)
        try:
            import transformers
        except ImportError as exc:  # pragma: no cover - gated dependency
            raise ImportError(
                "HFPipelineChat requires the `transformers` package"
            ) from exc
        self.pipeline = transformers.pipeline(
            "text-generation", model=model, device=device, **pipeline_kwargs
        )
        self.tokenizer = self.pipeline.tokenizer
        self.call_kwargs = dict(call_kwargs)
        if batch_size is not None:
            self.call_kwargs["batch_size"] = batch_size

    def crop_to_max_length(self, input_string: str, max_prompt_length: int = 500) -> str:
        tokens = self.tokenizer.tokenize(input_string)
        if len(tokens) > max_prompt_length:
            tokens = tokens[-max_prompt_length:]
            return self.tokenizer.convert_tokens_to_string(tokens)
        return input_string

    def __wrapped__(self, messages: list[dict] | Json | str, **kwargs) -> str | None:
        if isinstance(messages, (Json, list)):
            messages_decoded: Any = _messages_to_list(messages)
        else:
            messages_decoded = messages
        output = self.pipeline(messages_decoded, **{**self.call_kwargs, **kwargs})
        result = output[0]["generated_text"]
        if isinstance(result, list):  # chat format: last message is the reply
            result = result[-1]["content"]
        return result


class CohereChat(BaseChat):
    """Cohere chat client with RAG-style cited generation (reference
    ``CohereChat``, llms.py:544-684)."""

    def __init__(
        self,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = "command",
        **cohere_kwargs,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.kwargs = dict(cohere_kwargs)
        if model is not None:
            self.kwargs["model"] = model

    def __wrapped__(
        self, messages: list[dict] | Json, documents: list[dict] | Json | None = None,
        **kwargs,
    ) -> tuple[str, list[dict]]:
        try:
            import cohere
        except ImportError as exc:  # pragma: no cover - gated dependency
            raise ImportError("CohereChat requires the `cohere` package") from exc
        messages = _messages_to_list(messages)
        docs = None
        if documents is not None:
            docs = documents.value if isinstance(documents, Json) else list(documents)
        kwargs = {**self.kwargs, **kwargs}
        client = cohere.Client()
        message = messages[-1]["content"]
        chat_history = messages[:-1]
        ret = client.chat(
            message=message, chat_history=chat_history, documents=docs, **kwargs
        )
        cited_docs = [dict(c.__dict__) for c in (ret.citations or [])]
        return ret.text, cited_docs


class TPUDecoderChat(BaseChat):
    """TPU-native local chat: a GPT-2-family causal decoder generating ON
    DEVICE (``models/decoder.py``).

    Where the reference's local-LLM option (``HFPipelineChat``, reference
    llms.py:441-542) runs a torch pipeline host-side token by token, this
    UDF compiles prefill + KV-cached decode + sampling into ONE jitted
    call, so an engine microbatch of prompts costs a single dispatch.

    Construct either from a local GPT-2-family checkpoint directory
    (weights + ``vocab.json``/``merges.txt``) or from explicit
    ``params``/``cfg``/``tokenizer`` (any object with ``encode``/``decode``
    and an ``eos_id``)."""

    def __init__(
        self,
        checkpoint_path: str | None = None,
        params: dict | None = None,
        cfg=None,
        tokenizer=None,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        max_prompt_tokens: int = 512,
        seed: int = 0,
        cache_strategy: udfs.CacheStrategy | None = None,
        max_batch_size: int | None = 64,
        continuous: bool = False,
        n_slots: int = 16,
        chunk_steps: int = 16,
        pipeline_depth: int = 4,
        deferred: bool = False,
        chunked_prefill: bool | None = None,
        prefill_chunk: int | None = None,
        eager_refill: bool | None = None,
        prefix_cache: bool | None = None,
        prefix_cache_mb: float | None = None,
        prefix_block: int | None = None,
        spec_decode: bool | None = None,
        spec_draft_layers: int | None = None,
        spec_k: int | None = None,
        kv_quant: str | bool | None = None,
        paged_kv: bool | None = None,
        paged_kv_block: int | None = None,
        paged_kv_blocks: int | None = None,
        paged_kernel: bool | None = None,
        flash_prefill: bool | None = None,
        disagg: bool | None = None,
        disagg_prefill_budget: int | None = None,
        tenant_sched: bool | None = None,
        tenant_budget: int | None = None,
        tenant_weights: str | None = None,
        prefix_t2_mb: float | None = None,
        mesh=None,
        weight_quant: str | bool | None = None,
        wq_kernel: bool | None = None,
    ):
        # continuous=True: requests are served by a persistent slot-pool
        # loop (_ContinuousServer) — new rows admit into the IN-FLIGHT
        # decode at chunk boundaries instead of waiting for the previous
        # batch's full generation. deferred=True additionally runs the
        # UDF on the engine's fully-async path so the pump never blocks
        # on the decode (see SentenceTransformerEmbedder(deferred=...)).
        # Greedy decoding (temperature 0, no top-k/top-p) is deterministic
        # — declaring it lets the engine take the deferred two-phase path
        # (which re-derives values on retraction) instead of the blocking
        # replay-cache path.
        super().__init__(
            batch=True,
            deterministic=(
                float(temperature) == 0.0 and top_k is None and top_p is None
            ),
            max_batch_size=max_batch_size,
            cache_strategy=cache_strategy,
            executor=udfs.fully_async_executor() if deferred else None,
        )
        if checkpoint_path is not None:
            from pathway_tpu.models.bpe import BPETokenizer
            from pathway_tpu.models.checkpoint import load_decoder_checkpoint

            params, cfg = load_decoder_checkpoint(checkpoint_path, cfg)
            if tokenizer is None:
                tokenizer = BPETokenizer.from_dir(checkpoint_path)
        if params is None or cfg is None or tokenizer is None:
            raise ValueError(
                "TPUDecoderChat needs checkpoint_path or explicit "
                "params + cfg + tokenizer"
            )
        import jax

        from pathway_tpu.internals import config as _config_mod
        from pathway_tpu.internals.config import pathway_config
        from pathway_tpu.models.decoder import (
            cast_params_for_inference,
            params_device_bytes,
            quantize_params,
        )

        # weight-only int8 (PATHWAY_TPU_WEIGHT_QUANT): the large decoder
        # matrices store as symmetric per-output-channel int8 with f32
        # scales, dequantized inside the matmul read — ~4× fewer weight
        # bytes per decode step on a memory-bound roofline
        wq = pathway_config.weight_quant if weight_quant is None else weight_quant
        wq = "int8" if wq is True else ("" if wq in (False, None) else wq)
        self.weight_quant = _config_mod._parse_weight_quant(str(wq))
        wqk = pathway_config.wq_kernel if wq_kernel is None else bool(wq_kernel)
        self.wq_kernel = bool(self.weight_quant) and bool(wqk)
        if self.wq_kernel:
            # a CONFIG field, not a module global: jit caches built for
            # this server key on it, so a rebuilt server cannot serve
            # stale kernel-less traces
            import dataclasses

            cfg = dataclasses.replace(cfg, wq_kernel=True)
        if self.weight_quant:
            # raises UnsupportedForLayout("weight_quant") for a block
            # other than GPT-2's: no silent fall back to full precision
            self.params = jax.device_put(quantize_params(params, cfg))
        else:
            # compute-dtype weights: the decode phase reads the full
            # parameter set per step, so bf16 storage halves its HBM bill
            # (no-op for f32 configs)
            self.params = jax.device_put(cast_params_for_inference(params, cfg))
        # HBM ledger: the decoder's physical param footprint (int8
        # payloads + scales when quantized) at placement. The
        # continuous server re-records after mesh sharding with the
        # real per-device split.
        from pathway_tpu.engine.probes import record_hbm

        for dev, nbytes in params_device_bytes(self.params).items():
            record_hbm("weights.decoder", nbytes, device=dev)
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        # clamp the prompt cap so prompt + generation always fits the
        # model's positions (generate() raises on overflow; the cap makes
        # the default usable for any max_position)
        self.max_prompt_tokens = min(
            int(max_prompt_tokens), cfg.max_position - self.max_new_tokens
        )
        if self.max_prompt_tokens <= 0:
            raise ValueError(
                f"max_new_tokens ({self.max_new_tokens}) leaves no room "
                f"for a prompt within max_position ({cfg.max_position})"
            )
        self._seed = seed
        self._calls = 0  # advances the sampling key between calls
        # (rows, prompt_len, max_new, temperature, top_k, top_p) -> jitted
        # generate executable
        self._jitted: dict[tuple, Any] = {}
        # answers a greedy server has just given, by a digest of (prompt
        # ids, budget): the engine's deferred two-phase path RE-DERIVES a
        # deterministic UDF's value when the row is retracted (a REST
        # request's row is, in the epoch after its reply), and the value
        # is known — without this every answer was admitted, prefilled and
        # decoded a second time (slot admissions over replies read 2.0).
        # It lives a few rounds of the slots, as the retraction does; the
        # submitting and resolving threads share it under a lock.
        from collections import OrderedDict
        import threading

        self._answered: OrderedDict = OrderedDict()
        self._answered_lock = threading.Lock()
        self._answered_cap = max(64, 4 * int(n_slots))
        self._server: _ContinuousServer | None = None
        if continuous:
            self._server = _ContinuousServer(
                self.params, cfg, tokenizer,
                n_slots=n_slots, chunk_steps=chunk_steps,
                max_prompt_tokens=self.max_prompt_tokens,
                default_max_new=self.max_new_tokens,
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p, seed=seed,
                pipeline_depth=pipeline_depth,
                chunked_prefill=chunked_prefill,
                prefill_chunk=prefill_chunk,
                eager_refill=eager_refill,
                prefix_cache=prefix_cache,
                prefix_cache_mb=prefix_cache_mb,
                prefix_block=prefix_block,
                spec_decode=spec_decode,
                spec_draft_layers=spec_draft_layers,
                spec_k=spec_k,
                kv_quant=kv_quant,
                paged_kv=paged_kv,
                paged_kv_block=paged_kv_block,
                paged_kv_blocks=paged_kv_blocks,
                paged_kernel=paged_kernel,
                flash_prefill=flash_prefill,
                disagg=disagg,
                disagg_prefill_budget=disagg_prefill_budget,
                tenant_sched=tenant_sched,
                tenant_budget=tenant_budget,
                tenant_weights=tenant_weights,
                prefix_t2_mb=prefix_t2_mb,
                mesh=mesh,
                weight_quant=self.weight_quant,
            )
            # the two-phase engine protocol only exists in continuous
            # mode — exposing these as CLASS methods would activate the
            # pipelined path for batch-static instances too
            self.submit_batch = self._submit_batch_continuous
            self.resolve_batch = self._resolve_batch_continuous

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()

    def recent_traces(self, n: int | None = None) -> list[dict]:
        """Completed request spans of the continuous server (empty for
        batch-static instances and under ``PATHWAY_TPU_METRICS=0``)."""
        if self._server is None:
            return []
        return self._server.recent_traces(n=n)

    # two-phase protocol (continuous mode): submit enqueues every row into
    # the serving loop WITHOUT waiting; resolve blocks on the completions.
    # Combined with deferred=True the engine pump overlaps the decode.
    def _submit_batch_continuous(self, messages: list, **kwargs):
        if self._server is None:
            raise TypeError("submit_batch requires continuous=True")
        max_new = int(kwargs.pop("max_new_tokens", self.max_new_tokens))
        priority = int(kwargs.pop("priority", 1))
        tenant = str(kwargs.pop("tenant", "default")) or "default"
        if kwargs:
            # sampling params are compiled into the serving loop; per-call
            # overrides would silently apply to OTHER rows' chunks
            raise TypeError(
                f"continuous TPUDecoderChat cannot vary {sorted(kwargs)} "
                f"per call; set them on the constructor"
            )
        if max_new > self.max_new_tokens:
            # the slot pool's KV cache is sized from the constructor's
            # max_new_tokens; a longer request would clamp-overwrite the
            # last cache slot and return corrupted tokens
            raise ValueError(
                f"continuous TPUDecoderChat serves at most the "
                f"constructor's max_new_tokens ({self.max_new_tokens}) "
                f"per request; got {max_new}"
            )
        prompt_cap = min(
            self.max_prompt_tokens, self.cfg.max_position - max_new
        )
        if prompt_cap <= 0:
            raise ValueError(
                f"max_new_tokens ({max_new}) leaves no room for a prompt "
                f"within max_position ({self.cfg.max_position})"
            )
        reqs = []
        for m in messages:
            ids = self.tokenizer.encode(self._format_prompt(m))[-prompt_cap:]
            known = None
            if self.deterministic:
                with self._answered_lock:
                    known = self._answered.get(_answer_key(ids, max_new))
            if known is not None:
                # the same greedy answer again (a re-derivation): known
                req = _PendingCompletion(ids, max_new)
                req.text = known
                req.done.set()
                reqs.append(req)
                continue
            reqs.append(self._server.submit(
                ids, max_new, priority=priority, tenant=tenant,
            ))
        return reqs

    def _resolve_batch_continuous(self, handles) -> list:
        out = []
        for reqs in handles:
            texts = []
            for req in reqs:
                req.done.wait()
                if req.text is None:
                    # failed or shed: surface the structured reason
                    # through the string channel instead of a bare null
                    texts.append(encode_serve_error(
                        req.error_reason or "serve_failed",
                        retry_after=req.retry_after,
                    ))
                else:
                    texts.append(req.text)
                    if self.deterministic:
                        with self._answered_lock:
                            self._answered[
                                _answer_key(req.ids, req.max_new)] = req.text
                            while len(self._answered) > self._answered_cap:
                                self._answered.popitem(last=False)
            out.append(texts)
        return out

    def _format_prompt(self, messages) -> str:
        if isinstance(messages, str):
            return messages
        parts = [
            f"{m.get('role', 'user')}: {m.get('content', '')}"
            for m in _messages_to_list(messages)
        ]
        return "\n".join(parts) + "\nassistant:"

    def _generate_fn(self, rows: int, s: int, max_new: int, temp: float,
                     top_k, top_p):
        cache_key = (rows, s, max_new, temp, top_k, top_p)
        fn = self._jitted.get(cache_key)
        if fn is None:
            import jax

            from pathway_tpu.models import decoder as decoder_mod

            cfg = self.cfg

            def run(params, ids, mask, key):
                return decoder_mod.generate(
                    params, ids, mask, cfg, max_new,
                    temperature=temp, key=key,
                    eos_id=getattr(self.tokenizer, "eos_id", None),
                    top_k=top_k, top_p=top_p,
                )

            fn = jax.jit(run)
            self._jitted[cache_key] = fn
        return fn

    def _accepts_call_arg(self, arg_name: str) -> bool:
        return arg_name in ("max_new_tokens", "temperature", "top_k", "top_p")

    def __wrapped__(self, messages: list, **kwargs) -> list[str | None]:
        import jax
        import numpy as np

        from pathway_tpu.ops import next_pow2

        if self._server is not None:
            # continuous mode: route the blocking path through the same
            # slot pool (submit everything, then wait)
            return self.resolve_batch([self.submit_batch(messages, **kwargs)])[0]

        max_new = int(kwargs.pop("max_new_tokens", self.max_new_tokens))
        temp = float(kwargs.pop("temperature", self.temperature))
        top_k = kwargs.pop("top_k", self.top_k)
        # clamp into [1, vocab_size]: lax.top_k(k > vocab) raises an opaque
        # trace-time error; HF silently clamps to vocab size, so match that
        top_k = (
            None
            if top_k is None
            else min(max(1, int(top_k)), self.cfg.vocab_size)
        )
        top_p = kwargs.pop("top_p", self.top_p)
        top_p = None if top_p is None else float(top_p)
        if kwargs:
            # the sibling chat classes forward call kwargs to their APIs;
            # a compiled decoder has no such sink — reject, don't ignore
            raise TypeError(
                f"TPUDecoderChat got unsupported call kwargs: {sorted(kwargs)}"
            )
        # a per-call max_new_tokens shrinks the prompt budget so the
        # constructor's fit guarantee (prompt + generation <= max_position)
        # holds for every call, not just the default
        prompt_cap = min(
            self.max_prompt_tokens, self.cfg.max_position - max_new
        )
        if prompt_cap <= 0:
            raise ValueError(
                f"max_new_tokens ({max_new}) leaves no room for a prompt "
                f"within max_position ({self.cfg.max_position})"
            )
        prompts = [self._format_prompt(m) for m in messages]
        encoded = [
            self.tokenizer.encode(p)[-prompt_cap:] for p in prompts
        ]
        s = next_pow2(max((len(e) for e in encoded), default=1), 8)
        s = min(s, prompt_cap)
        rows = next_pow2(len(encoded), 1)
        ids = np.zeros((rows, s), np.int32)
        mask = np.zeros((rows, s), np.int32)
        for r, e in enumerate(encoded):  # LEFT-padded (decoder contract)
            e = e[-s:]
            if e:
                ids[r, s - len(e):] = e
                mask[r, s - len(e):] = 1
            else:
                mask[r, -1] = 1  # empty prompt: one live pad slot
        # advance the key per call: temperature>0 must SAMPLE across calls,
        # not replay one fixed draw (greedy decode ignores the key entirely)
        self._calls += 1
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._calls)
        toks = np.asarray(
            self._generate_fn(rows, s, max_new, temp, top_k, top_p)(
                self.params, ids, mask, key
            )
        )
        eos = getattr(self.tokenizer, "eos_id", None)
        out: list[str | None] = []
        for r in range(len(encoded)):
            t = toks[r].tolist()
            if eos is not None and eos in t:
                t = t[: t.index(eos)]
            out.append(self.tokenizer.decode(t))
        return out


class _PendingCompletion:
    """One in-flight continuous-batching request (host-side slot record)."""

    __slots__ = ("ids", "max_new", "tokens", "done", "text", "finished_at",
                 "first_token_at", "span", "retries", "error_reason",
                 "retry_after", "deadline", "priority", "tenant", "seq")

    def __init__(self, ids: list, max_new: int):
        import threading

        from pathway_tpu.engine import tracing

        self.ids = ids
        self.max_new = max_new
        self.tokens: list[int] = []
        self.done = threading.Event()
        self.text: str | None = None
        self.finished_at: float | None = None  # time.perf_counter()
        self.first_token_at: float | None = None  # first token DRAINED
        self.span = tracing.NULL_SPAN  # replaced by submit()
        # fault-tolerance bookkeeping: isolation/restart retry count, the
        # structured failure reason behind a text=None sentinel (resolve
        # encodes it via encode_serve_error), the shed Retry-After hint,
        # the absolute perf_counter deadline, and the admission priority
        # class (level-3 degradation sheds priority <= 0)
        self.retries = 0
        self.error_reason: str | None = None
        self.retry_after: float | None = None
        self.deadline: float | None = None
        self.priority = 1
        # multi-tenant admission class (PATHWAY_TPU_TENANT_SCHED): the
        # weighted-fair pop groups and budgets requests by this tag;
        # seq is the server's admission order (newest-first preemption)
        self.tenant = "default"
        self.seq = 0


@guarded_by(queue="lock", free="lock")
class _ContinuousServer:
    """Slot-pool serving loop for ``TPUDecoderChat(continuous=True)``.

    A background thread owns a ``pool_init`` state of ``n_slots``
    sequences. Requests enqueue at any time; each loop iteration admits
    waiting requests into free slots (one prefill dispatch per
    admission, bucketed by prompt length), advances every busy slot
    ``chunk_steps`` decode steps in ONE dispatch, and frees slots whose
    stream hit EOS or the request's own ``max_new`` budget. A new
    request therefore waits at most one chunk — not a whole batch
    generation (reference ``HFPipelineChat`` is batch-static,
    llms.py:441).

    Occupancy (``stats["steps"] / stats["slot_steps_total"]``, exported
    via :meth:`occupancy`) is kept high two ways, both default-on via
    ``internals/config.py`` env flags:

    * **chunked prefill** (PATHWAY_TPU_CHUNKED_PREFILL) — prompts longer
      than ``prefill_chunk`` admit piece-wise via
      ``pool_prefill_chunk``, one piece per loop tick interleaved with
      decode chunks, so a long prompt never stalls every active lane
      for a whole-prompt prefill dispatch.
    * **eager refill** (PATHWAY_TPU_EAGER_REFILL) — a lane whose
      DISPATCHED steps already cover its budget frees its slot
      immediately (its remaining tokens drain from the in-flight
      snapshots) instead of ``pipeline_depth`` chunks later at
      drain time — the occupancy gap that kept slots idle a whole
      pipeline's depth per request.
    * **prefill/decode overlap** (PATHWAY_TPU_PREFILL_OVERLAP) — each
      tick dispatches the in-flight lanes' decode chunk FIRST, then
      runs admission host work and prefill dispatches while it
      computes; newcomers join the next chunk boundary, which they
      would have waited for anyway (xLLM-style chunk-boundary
      admission, arXiv:2510.14686).
    * **batched admission** (PATHWAY_TPU_BATCH_ADMIT) — same-bucket
      requests that arrive together admit via one ``pool_admit_batch``
      dispatch (pow2 group sizes to bound jit variants) instead of one
      dispatch per request, so an arrival burst costs O(log n)
      dispatches.
    * **chunk-steps autotune** (PATHWAY_TPU_CHUNK_AUTOTUNE) —
      ``chunk_steps`` adapts to observed arrival rate: queue pressure
      shrinks the chunk (earlier boundaries admit sooner and recycle
      slots sooner); an idle queue grows it back toward the
      constructor value (fewer dispatches per token). Candidates are
      halvings of the constructor value, so the KV-cache slack sizing
      stays valid.
    * **self-speculative decode** (PATHWAY_TPU_SPEC_DECODE, greedy
      servers only) — decode chunks become draft/verify/accept cycles:
      the first ``PATHWAY_TPU_SPEC_DECODE_DRAFT_LAYERS`` layers draft
      ``PATHWAY_TPU_SPEC_DECODE_K`` tokens against a depth-prefix of
      the same KV pool and ONE full-model dispatch verifies all of
      them, emitting 1..k+1 byte-identical greedy tokens per lane per
      weight stream (``pool_decode_spec``). The drain keeps an
      acceptance-rate EMA and latches back to plain chunks when the
      drafts stop paying (< 0.25 after 4 drains).
    * **int8 KV** (PATHWAY_TPU_KV_QUANT=int8) — the slot pool and the
      prefix arena store KV as symmetric int8 + f32 per-token scales
      (~2x slots and cached blocks per HBM byte), dequantized on read
      inside attention.
    * **paged KV** (PATHWAY_TPU_PAGED_KV) — slots stop owning dense
      ``cache_len`` KV rows; KV lives in one global pool of fixed-size
      blocks addressed through a per-slot block table, and admission
      allocates only the blocks a request can actually reach (prompt +
      its own ``max_new`` + pipeline slack) from a host
      ``BlockAllocator``. The prefix cache runs in ADOPTED mode: a
      finished prompt's blocks publish into the radix tree zero-copy
      (pin, not ``kv_extract``) and a hit seeds a newcomer by writing
      the shared ids into its block table copy-on-write — no arena
      copies, so the ``prefix_copy_bytes`` ledger stays at zero.
      Stranded bytes surface as the ``kv_fragmentation`` gauge.
      PATHWAY_TPU_PAGED_KERNEL additionally routes plain decode chunks
      through the Pallas paged-attention kernel
      (``models/paged_attention.py``)."""

    def __init__(self, params, cfg, tokenizer, *, n_slots: int,
                 chunk_steps: int, max_prompt_tokens: int,
                 default_max_new: int, temperature: float, top_k, top_p,
                 seed: int, pipeline_depth: int = 4,
                 chunked_prefill: bool | None = None,
                 prefill_chunk: int | None = None,
                 eager_refill: bool | None = None,
                 prefix_cache: bool | None = None,
                 prefix_cache_mb: float | None = None,
                 prefix_block: int | None = None,
                 spec_decode: bool | None = None,
                 spec_draft_layers: int | None = None,
                 spec_k: int | None = None,
                 kv_quant: str | bool | None = None,
                 paged_kv: bool | None = None,
                 paged_kv_block: int | None = None,
                 paged_kv_blocks: int | None = None,
                 paged_kernel: bool | None = None,
                 flash_prefill: bool | None = None,
                 disagg: bool | None = None,
                 disagg_prefill_budget: int | None = None,
                 tenant_sched: bool | None = None,
                 tenant_budget: int | None = None,
                 tenant_weights: str | None = None,
                 prefix_t2_mb: float | None = None,
                 mesh=None,
                 weight_quant: str = ""):
        import threading
        from collections import deque

        import jax

        from pathway_tpu.internals import config as _config_mod
        from pathway_tpu.internals.config import pathway_config
        from pathway_tpu.models import decoder as decoder_mod
        from pathway_tpu.ops import next_pow2

        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.n_slots = n_slots
        self.chunk_steps = chunk_steps
        self.max_prompt_bucket = next_pow2(max_prompt_tokens, 8)
        # the host loop runs ``pipeline_depth`` chunks AHEAD of the token
        # drain: each chunk's token block starts its device->host copy at
        # dispatch and has depth*cycle_time to land before the host reads
        # it (a read otherwise blocks on the device). A lane
        # may overrun its budget until its tokens drain, so give one
        # chunk of cache slack per in-flight chunk plus the current one.
        self.pipeline_depth = max(0, int(pipeline_depth))
        # self-speculative decode (PATHWAY_TPU_SPEC_DECODE): greedy lanes
        # advance via draft/verify/accept cycles — the first
        # spec_draft_layers layers draft spec_k tokens, one full-model
        # dispatch verifies them all (models/decoder.py:pool_decode_spec).
        # Greedy-only by construction (acceptance compares argmaxes), so
        # sampling servers always take the plain chunk path; a 1-layer
        # model has no shallower draft stack, so it does too.
        want_spec = (
            pathway_config.spec_decode
            if spec_decode is None else bool(spec_decode)
        )
        if spec_decode and cfg.loops > 1:
            # ASKED for: the draft is a depth prefix of the layers, which a
            # stack run several times does not have. Refused by type, as
            # the mechanisms below are; the flag's default resolves to
            # plain chunks for such a stack, as it does for one layer
            decoder_mod.require_single_pass(cfg, "spec_decode")
        self.spec_decode = bool(
            want_spec and float(temperature) == 0.0
            and top_k is None and top_p is None and cfg.layers >= 2
            and cfg.loops == 1
        )
        d = (
            pathway_config.spec_draft_layers
            if spec_draft_layers is None else int(spec_draft_layers)
        )
        if d <= 0:
            d = max(1, cfg.layers // 4)
        self.spec_draft_layers = max(1, min(d, cfg.layers - 1))
        self.spec_k = max(1, (
            pathway_config.spec_k if spec_k is None else int(spec_k)
        ))
        # adaptive fallback: spec decode must never LOSE throughput, so
        # after a few drained dispatches with the acceptance EMA below
        # threshold the server latches back to plain chunks (safe: both
        # paths emit identical greedy tokens, latching changes cost only)
        self._spec_off = False
        self._spec_drains = 0
        self._accept_ema: float | None = None
        # spec registry counters accumulate here between flushes (one
        # registry call per request completion, not six per drain); the
        # loop thread owns it, so no lock
        self._spec_accum: dict = {}
        # int8 KV (PATHWAY_TPU_KV_QUANT): the slot pool + prefix arena
        # store KV as symmetric int8 with per-(layer, slot, head, token)
        # f32 scales, dequantized on read inside attention
        kvq = pathway_config.kv_quant if kv_quant is None else kv_quant
        kvq = "int8" if kvq is True else ("" if kvq in (False, None) else kvq)
        self.kv_quant = _config_mod._parse_kv_quant(str(kvq))
        # a spec dispatch writes up to n_cycles*(spec_k+1) KV columns per
        # lane — bounded by max(chunk_steps, spec_k+1) — so the per-chunk
        # over-budget slack widens to that bound when spec is on
        slack = max(
            chunk_steps, (self.spec_k + 1) if self.spec_decode else 0
        )
        self._slack = slack
        self.cache_len = (
            self.max_prompt_bucket + default_max_new
            + (self.pipeline_depth + 1) * slack
        )
        self.eos_id = getattr(tokenizer, "eos_id", None)
        self.chunked_prefill = (
            pathway_config.chunked_prefill
            if chunked_prefill is None else bool(chunked_prefill)
        )
        self.prefill_chunk = max(8, next_pow2(
            pathway_config.prefill_chunk
            if prefill_chunk is None else int(prefill_chunk), 8,
        ))
        self.eager_refill = (
            pathway_config.eager_refill
            if eager_refill is None else bool(eager_refill)
        )
        # paged KV (PATHWAY_TPU_PAGED_KV): KV lives in a global pool of
        # fixed-size blocks behind a per-slot block table
        # (models/decoder.py paged_pool_init). The block size is a pow2
        # multiple of the prefill chunk so cached prefixes end on piece
        # boundaries; cache_len rounds UP to a whole number of blocks
        # (table rows address whole blocks). The kill switch
        # (PATHWAY_TPU_PAGED_KV=0) keeps the dense pool byte-identical.
        self.paged_kv = bool(
            pathway_config.paged_kv if paged_kv is None else paged_kv
        )
        self.paged_kernel = bool(self.paged_kv and (
            pathway_config.paged_kernel
            if paged_kernel is None else bool(paged_kernel)
        ))
        # flash prefill (PATHWAY_TPU_FLASH_PREFILL): every whole-prompt
        # admit and every chunked-prefill piece runs the tiled
        # online-softmax kernel (models/flash_attention.py) instead of
        # materializing the (T, C) mask-bias score matrix. Kill switch
        # keeps the dense path byte-identical. Construction-time read:
        # the per-server jit caches below key nothing on it — the closure
        # captures the bool, and a rebuilt server re-traces.
        self.flash_prefill = bool(
            pathway_config.flash_prefill
            if flash_prefill is None else flash_prefill
        )
        if self.flash_prefill:
            from pathway_tpu.models import flash_attention as _fa

            _fa.configure_blocks(pathway_config.flash_block_q,
                                 pathway_config.flash_block_k)
        self.paged_block = 0
        self._paged_blocks_override = 0
        self._allocator = None
        self._total_blocks = 0
        # slot -> list of block ids the slot holds references on (its
        # table row, sentinel-padded on device); slot -> reachable tokens
        # (the fragmentation gauge's "needed" numerator, dense too)
        self._slot_blocks: dict[int, list] = {}
        self._slot_cover: dict[int, int] = {}
        self._kv_frag = 0.0
        self._frag_sum = 0.0
        self._frag_n = 0
        if self.paged_kv:
            pb = (
                pathway_config.paged_kv_block
                if paged_kv_block is None else int(paged_kv_block)
            )
            self.paged_block = next_pow2(
                max(pb, self.prefill_chunk), self.prefill_chunk
            )
            self.cache_len = -(-self.cache_len
                               // self.paged_block) * self.paged_block
            self._paged_blocks_override = max(0, (
                pathway_config.paged_kv_blocks
                if paged_kv_blocks is None else int(paged_kv_blocks)
            ))
        # chunk-admission serving knobs (internals/config.py):
        # * batch_admit — same-bucket arrivals prefill in ONE grouped
        #   pool_admit_batch dispatch instead of one dispatch each;
        # * prefill_overlap — the decode chunk dispatches BEFORE admission
        #   work each tick, so newcomer prefill overlaps in-flight decode;
        # * chunk_autotune — decode-chunk steps shrink (halving, floor 4)
        #   against the observed arrival rate / queue pressure so chunk
        #   boundaries (admission + drain points) come sooner under load.
        self.batch_admit = pathway_config.batch_admit
        self.prefill_overlap = pathway_config.prefill_overlap
        self.chunk_autotune = pathway_config.chunk_autotune
        # disaggregated prefill/decode lanes (PATHWAY_TPU_DISAGG):
        # pending prefills form a prefill LANE that dispatches at most
        # disagg_prefill_budget pieces per tick (round-robin) while any
        # slot decodes, so a decode chunk never queues behind a burst of
        # long-prompt prefill pieces. A finished prefill MIGRATES into
        # the decode lane by block handoff — zero-copy on one chip (the
        # blocks stay put; only lane membership flips), kv_block_export/
        # kv_block_import for the cross-device case. Greedy tokens are
        # schedule-invariant, so the flag is a byte-identical kill
        # switch (tests/test_disagg.py).
        self.disagg = bool(
            pathway_config.disagg if disagg is None else disagg
        )
        self._prefill_budget = max(1, int(
            pathway_config.disagg_prefill_budget
            if disagg_prefill_budget is None else disagg_prefill_budget
        ))
        self._prefill_rr = 0  # round-robin cursor over the prefill lane
        self._lane_counts = {"prefill": 0, "decode": 0}
        # multi-tenant weighted-fair admission (PATHWAY_TPU_TENANT_SCHED):
        # the queue stays ONE deque (watermark, deadline sweep and crash
        # recovery unchanged) — the scheduler is a pure pop POLICY over
        # it, plus per-tenant in-flight token budgets whose enforcement
        # escalates from skip to preemption (_maybe_preempt).
        self._tenants = None
        want_tenants = bool(
            pathway_config.tenant_sched
            if tenant_sched is None else tenant_sched
        )
        if want_tenants:
            from pathway_tpu.engine import slo as slo_mod

            self._tenants = slo_mod.TenantScheduler(
                weights=slo_mod.TenantScheduler.parse_weights(
                    pathway_config.tenant_weights
                    if tenant_weights is None else str(tenant_weights)
                ),
                budget_tokens=int(
                    pathway_config.tenant_budget
                    if tenant_budget is None else tenant_budget
                ),
            )
        # preempted requests' parked KV: req -> (block row, admit cover).
        # Paged mode keeps the allocator refs alive so re-admission
        # reuses the computed prompt KV by table edit; classified apart
        # from fragmentation via the kv_parked_bytes gauge.
        self._parked: dict = {}
        self._parked_blocks = 0
        self._admit_seq = 0  # admission order, newest-first preemption
        # id(req) -> (tenant, charged tokens): the credit must match
        # the charge even after EOS/degradation mutate req.max_new
        self._charged: dict[int, tuple[str, int]] = {}
        # prefix KV cache (PATHWAY_TPU_PREFIX_CACHE): admission matches a
        # prompt's longest block-aligned cached prefix in a host radix
        # tree and SEEDS the slot's KV from a device arena instead of
        # re-prefilling it; only the uncached suffix pays prefill. The
        # cached path rides the chunked-prefill piece machinery (a hit
        # admits right-padded so token i sits at cache column i — the
        # arena layout), so it requires chunked prefill; with the flag
        # off the admission path is byte-identical to before.
        import numpy as _np_mod

        self.prefix = None
        self.prefix_block = 0
        want_prefix = (
            pathway_config.prefix_cache
            if prefix_cache is None else bool(prefix_cache)
        )
        if want_prefix and self.chunked_prefill:
            from pathway_tpu.engine.prefix_cache import PrefixCache

            mb = (
                pathway_config.prefix_cache_mb
                if prefix_cache_mb is None else float(prefix_cache_mb)
            )
            blk = (
                pathway_config.prefix_block
                if prefix_block is None else int(prefix_block)
            )
            # block must be a pow2 multiple of the prefill chunk: cached
            # prefixes then end on piece boundaries, so the right-padded
            # suffix never writes past the prompt's pow2 bucket. Paged
            # mode pins it to the POOL block — a cached block there IS a
            # pool block (adopted zero-copy), so the sizes must agree.
            blk = next_pow2(max(blk, self.prefill_chunk), self.prefill_chunk)
            if self.paged_kv:
                blk = self.paged_block
            # int8 KV: each cached head-token costs head_dim int8 bytes
            # plus one f32 scale instead of head_dim full-precision
            # bytes, so the same MB budget holds ~2x the blocks; a latent
            # layer caches one row a token, nothing per head
            block_bytes = blk * decoder_mod.kv_token_bytes(
                cfg, _np_mod.dtype(cfg.dtype).itemsize, bool(self.kv_quant))
            n_blocks = int(mb * (1 << 20) // block_bytes)
            if n_blocks >= 1:
                self.prefix_block = blk
                self._prefix_kwargs = dict(
                    n_blocks=n_blocks, block=blk, block_bytes=block_bytes
                )
                # two-tier cache (PATHWAY_TPU_PREFIX_T2_MB): eviction
                # demotes leaf edges to a host np block store; the
                # export callback device_gets the blocks' KV bytes.
                # Budget 0 is the byte-identical single-tier kill switch
                # (tests/test_prefix_cache.py).
                t2_mb = (
                    pathway_config.prefix_t2_mb
                    if prefix_t2_mb is None else float(prefix_t2_mb)
                )
                t2_blocks = int(t2_mb * (1 << 20) // block_bytes)
                if t2_blocks >= 1:
                    self._prefix_kwargs["tier2_blocks"] = t2_blocks
                    self._prefix_kwargs["export"] = self._export_blocks
                self.prefix = self._make_prefix_cache()
        # request -> radix node whose root-path the request has pinned
        # (released when the request completes)
        self._prefix_nodes: dict = {}
        # tier-2 promotion pipeline: admission-time tier-2 hits stage
        # their host blobs to the device OFF-THREAD on the PR-2 h2d
        # StageWorker; the loop adopts staged blobs into the tree/arena
        # between ticks (_drain_promotions). _t2_pending counts hits not
        # yet adopted, so tests/bench can quiesce (t2_drain).
        self._promote_worker = None
        self._promote_ready: deque = deque()
        self._t2_pending = 0
        self._export_jits: dict = {}
        self._import_jits: dict = {}
        if self.prefix is not None and self.prefix.tier2 is not None:
            from pathway_tpu.engine.async_runtime import StageWorker

            self._promote_worker = StageWorker(
                fn=self._stage_promotion, maxsize=4, name="prefix-t2-h2d"
            )
        # per-block KV device footprint (the kv_parked_bytes gauge's
        # multiplier; paged mode only — dense preemption has no blocks
        # to park)
        self._block_kv_bytes = (
            self.paged_block * decoder_mod.kv_token_bytes(
                cfg, _np_mod.dtype(cfg.dtype).itemsize, bool(self.kv_quant))
            if self.paged_kv else 0
        )
        # autotune candidates: halvings of the constructor's chunk_steps
        # down to 4 — all <= chunk_steps, so the cache-slack sizing above
        # stays valid for every candidate
        cands, c = [], chunk_steps
        while c >= 4:
            cands.append(c)
            c //= 2
        self._step_cands = cands or [chunk_steps]
        self._arrival_ema: float | None = None
        self._last_submit_t: float | None = None
        self._step_wall_ema: float | None = None
        self._last_dispatch_t: float | None = None
        self._last_dispatch_steps = 0
        self._D = decoder_mod
        # serving mesh (PATHWAY_TPU_MESH): resolved ONCE here. Params
        # and the pool COMMIT onto the (data, fsdp, tp) mesh with
        # NamedSharding (Megatron tp over heads/ffn/vocab, fsdp over
        # the remainder, the KV pool's head axis over tp); every jitted
        # pool op below then inherits the layout through GSPMD sharding
        # propagation, and donation carries it across dispatches. Off —
        # or on a 1x1x1 mesh — placement degenerates to single-chip and
        # tokens are byte-identical (tests/test_mesh_serving.py).
        from pathway_tpu.parallel.mesh import serving_mesh_from_flags

        self.mesh = mesh if mesh is not None else serving_mesh_from_flags()
        # already-quantized params arrive from TPUDecoderChat; the string
        # is carried for stats/traces only — the format marker on the
        # pytree itself (``wte_scale``) is what the forward paths read
        self.weight_quant = weight_quant
        if not decoder_mod.gpt2_block(cfg):
            # what this configuration's layers cannot ride yet refuses by
            # TYPE, here, naming the mechanism: never a silent fallback to
            # another path. (Default-off, every one; self-speculative
            # decoding, chunked prefill, batched admission and the prefix
            # cache are written for every layout.)
            for mechanism, on in (
                ("paged_kv", self.paged_kv),
                ("paged_kernel", self.paged_kernel),
                ("flash_prefill", self.flash_prefill),
                ("kv_quant", bool(self.kv_quant)),
                ("weight_quant", bool(weight_quant)),
                ("disagg", self.disagg),
                ("mesh", self.mesh is not None),
            ):
                if on:
                    decoder_mod.require_gpt2_block(cfg, mechanism)
        if self.mesh is not None:
            self.params = decoder_mod.shard_decoder_params(
                self.params, cfg, self.mesh
            )
        self.pool = self._build_pool()
        self.kv_bytes_saved = 0
        if self.kv_quant:
            # ledger the HBM the int8 pool did NOT allocate vs the same
            # pool at full precision (recorded once)
            from pathway_tpu.engine.probes import record_spec

            it = _np_mod.dtype(cfg.dtype).itemsize
            base = sum(
                int(self.pool[c].size) * it
                for c in ("k", "v", "kb", "vb", "arena_k", "arena_v")
                if c in self.pool
            )
            self.kv_bytes_saved = base - decoder_mod.pool_bytes(self.pool)
            record_spec("kv_bytes_saved", self.kv_bytes_saved)
        # HBM ledger: per-component, PER-DEVICE footprint of the pool
        # just built (slot caches / dequant scales / prefix arena).
        # Recorded once here — never on the per-token path — feeding
        # `hbm_bytes{component=,device=}` and the per-device high-water.
        # Single-chip everything lands on device "0", which keeps the
        # component-aggregated gauges byte-identical to the PR-9 ledger.
        from pathway_tpu.engine.probes import record_hbm

        for comp, per_dev in decoder_mod.pool_component_device_bytes(
            self.pool
        ).items():
            for dev, nbytes in per_dev.items():
                record_hbm(comp, nbytes, device=dev)
        # the decoder weights component, re-recorded post-shard so the
        # per-device split reflects the actual mesh placement (TPUDecoder
        # Chat recorded the pre-shard single-device view at device_put)
        for dev, nbytes in decoder_mod.params_device_bytes(
            self.params
        ).items():
            record_hbm("weights.decoder", nbytes, device=dev)
        self._admit_fns: dict = {}
        self._admit_batch_fns: dict = {}
        self._prefill_fns: dict = {}
        self._admit_cached_fns: dict = {}
        self._extract_fns: dict = {}
        # paged-mode jitted table editors (block shapes are static, so
        # each is a singleton): admission seed (table row + cached-column
        # mask) and the free-time row clear back to the sentinel block
        self._paged_seed_jit = None
        self._table_clear_jit = None
        # slot -> (remaining prefill pieces, n_prompt); drained one piece
        # per loop tick so prefill interleaves with decode chunks
        self._pending_prefill: dict[int, tuple] = {}
        # per-slot DISPATCHED decode steps since admission (eager refill)
        self._sent = [0] * n_slots
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        # n_steps -> jitted decode-chunk executable. The pool is donated:
        # the KV caches are the dominant HBM object and the loop is pure
        # state-in/state-out — without donation every chunk would copy the
        # whole pool and double peak memory.
        self._chunk_fns: dict[int, Any] = {}
        # n_cycles -> jitted spec draft/verify/accept executable
        self._spec_fns: dict[int, Any] = {}
        self._key = jax.random.PRNGKey(seed)
        self._ticks = 0
        from pathway_tpu.analysis.runtime import make_lock

        self.queue: deque = deque()
        self.slots: list = [None] * n_slots
        self.free = list(range(n_slots))
        self.lock = make_lock("decode_server.lock")
        self.wake = threading.Event()
        self._stop = False
        self.failed: BaseException | None = None
        # fault tolerance (all flags read ONCE here, so the serving hot
        # path never touches the environment): supervision gates both
        # per-request isolation and bounded loop restarts; deadlines and
        # the queue watermark shed instead of blocking; the degradation
        # ladder follows the SLO watchdog's alert state. Every default
        # keeps the pre-supervision behavior byte-identical
        # (tests/test_chaos.py pins it).
        self._restart_budget = int(pathway_config.serve_restarts)
        self._supervised = self._restart_budget > 0
        self._retry_budget = int(pathway_config.serve_retries)
        self._deadline_s = float(pathway_config.request_deadline_ms) / 1e3
        self._queue_bound = int(pathway_config.serve_queue)
        self._default_max_new = int(default_max_new)
        self._degradation_level = 0
        self._degrade = None
        if pathway_config.degradation:
            from pathway_tpu.engine import slo as slo_mod

            self._degrade = slo_mod.get_degradation_controller()
        from pathway_tpu.engine import chaos as chaos_mod

        self._chaos_admit = chaos_mod.site("decode.admit")
        self._chaos_dispatch = chaos_mod.site("decode.dispatch")
        self.stats = {
            "chunks": 0, "admitted": 0, "steps": 0,
            "slot_steps_total": 0, "prefill_chunks": 0,
            "admit_dispatches": 0, "prefix_hit_tokens": 0,
            "prefix_miss_tokens": 0, "prefix_hit_requests": 0,
            "prefix_requests": 0, "spec_dispatches": 0,
            "spec_cycles": 0, "spec_drafted": 0, "spec_accepted": 0,
            "spec_emitted": 0, "spec_verify_steps": 0,
            "restarts": 0, "request_failures": 0, "request_retries": 0,
            "shed": 0, "leaked_thread": 0, "paged_oom": 0,
            "preemptions": 0, "kv_migrated_blocks": 0,
            "t2_hit_requests": 0, "t2_promoted_blocks": 0,
            "prefix_declined": 0,
        }
        # the device's running totals as last drained, by pool counter
        # ((phase, held|all) assignments; tokens by exit pass)
        self._device_seen: dict = {}
        logger.info(
            "decoder server pool: %d slots x %d columns x %d cache layers "
            "(%d layers x %d passes) = %d bytes",
            n_slots, self.cache_len, cfg.loops * cfg.layers, cfg.layers,
            cfg.loops, decoder_mod.pool_bytes(self.pool))
        # in-flight chunk records, oldest first; an attribute (not a loop
        # local) so the failure sweep can fail eagerly-freed requests
        # whose tokens never drained
        self._inflight: deque = deque()
        # tags this server's request spans in the global trace ring
        self._trace_tag = f"decode:{id(self):x}"
        self._warm_start()
        self.thread = threading.Thread(
            target=self._run_safe, daemon=True, name="pathway:decoder-serve"
        )
        self.thread.start()

    def _warm_start(self) -> None:
        """Compile, before the loop starts, every executable the loop can
        dispatch for this configuration, by running each once over idle
        lanes: one-shot admits for every prompt bucket up to the prefill
        chunk (each batch size batched admission can form), the chunked
        prefill pieces (first, middle, last; the cached path's too), the
        prefix cache's copies, every decode-chunk step count the autotuner
        can pick and every speculative cycle count those map to. Nothing
        then compiles under traffic. The prompt buckets are the powers of
        two up to ``max_prompt_tokens``'s. The pool is rebuilt afterwards:
        the warm-up leaves no trace in it."""
        import time as time_mod

        import jax
        import numpy as np

        t0 = time_mod.perf_counter()
        P = self.prefill_chunk
        buckets, b = [], 8
        while b <= self.max_prompt_bucket:
            buckets.append(b)
            b *= 2
        chunked = [b for b in buckets if self.chunked_prefill and b > P]
        direct = [b for b in buckets if b not in chunked]
        slot0 = np.int32(0)
        for s in direct:
            ids = np.zeros((1, s), np.int32)
            mask = np.zeros((1, s), np.int32)
            mask[0, -1] = 1
            self.pool = self._admit_fn(s)(
                self.params, ids, mask, self.pool, slot0)
            m = 2
            while self.batch_admit and m <= self.n_slots:
                self.pool = self._admit_batch_fn(m, s)(
                    self.params, np.repeat(ids, m, 0), np.repeat(mask, m, 0),
                    self.pool, np.arange(m, dtype=np.int32))
                m *= 2
        if chunked or (self.prefix is not None and buckets
                       and buckets[-1] > self.prefix_block):
            ids = np.zeros((1, P), np.int32)
            mask = np.ones((1, P), np.int32)
            pos = np.arange(P, dtype=np.int32)[None, :]
            n_prompt = np.asarray([P], np.int32)
            variants = [(True, False, False), (False, False, False),
                        (False, True, False)]
            if self.prefix is not None:
                variants.append((False, True, True))
            for first, last, with_col in variants:
                args = (self.params, ids, mask, pos, self.pool, slot0,
                        np.int32(0), n_prompt)
                if with_col:
                    args += (np.int32(0),)
                self.pool = self._prefill_fn(P, first, last, with_col)(*args)
        if self.prefix is not None and not self.paged_kv and buckets:
            B = self.prefix_block
            most = min(buckets[-1] // B, self.prefix.capacity_blocks)
            ring = self._D.pool_ring(self.pool)
            if ring:
                most = min(most, ring // B)
            for n in range(1, most + 1):
                idxs = np.zeros((n,), np.int32)
                self.pool = self._extract_fn(n)(
                    self.pool, slot0, np.int32(0), idxs)
                self.pool = self._admit_cached_fn(n)(self.pool, slot0, idxs)
        idle = np.zeros(self.n_slots, dtype=bool)
        cycles = set()
        for steps in self._step_cands:
            out = self._chunk_fn_for(steps)(
                self.params, self.pool, idle, self._key)
            self.pool = out[0]
            cycles.add(max(1, steps // (self.spec_k + 1)))
        if self.spec_decode:
            for n_cycles in sorted(cycles):
                out = self._spec_fn_for(n_cycles)(
                    self.params, self.pool, idle)
                self.pool = out[0]
        jax.block_until_ready(self.pool)
        self.pool = None        # free it before the fresh one is built
        self.pool = self._build_pool()
        self.warm_seconds = time_mod.perf_counter() - t0

    def recent_traces(self, n: int | None = None) -> list[dict]:
        """Completed per-request spans of THIS server (oldest first),
        from the bounded global trace ring (``PATHWAY_TPU_TRACE_RING``).
        Empty under ``PATHWAY_TPU_METRICS=0``."""
        from pathway_tpu.engine import tracing

        return tracing.recent_traces(server=self._trace_tag, n=n)

    def _build_pool(self):
        """A fresh ``pool_init`` state sized for this server — used at
        construction and again by the supervised restart path (a crash
        mid-dispatch may have invalidated the donated pool buffers).
        Paged mode instead builds ``paged_pool_init`` plus a fresh host
        ``BlockAllocator``; the block count defaults to the dense pool's
        capacity (every slot's full table plus the prefix budget plus
        the sentinel), and ``PATHWAY_TPU_PAGED_KV_BLOCKS`` overrides it
        for oversubscription (allocator raises ``PagedPoolOOM`` when a
        burst doesn't fit — admission parks the request)."""
        if self.paged_kv:
            per_slot = self.cache_len // self.paged_block
            auto = self.n_slots * per_slot + (
                self.prefix.capacity_blocks if self.prefix is not None else 0
            ) + 1
            self._total_blocks = max(2, self._paged_blocks_override or auto)
            self._allocator = self._D.BlockAllocator(self._total_blocks)
            self._slot_blocks = {}
            self._paged_seed_jit = None
            self._table_clear_jit = None
            pool = self._D.paged_pool_init(
                self.params, self.cfg, self.n_slots, self.cache_len,
                n_blocks=self._total_blocks, block=self.paged_block,
                kv_quant=bool(self.kv_quant),
            )
        else:
            pool = self._D.pool_init(
                self.params, self.cfg, self.n_slots, self.cache_len,
                arena_blocks=(
                    self.prefix.capacity_blocks if self.prefix else 0
                ),
                arena_block=self.prefix_block,
                kv_quant=bool(self.kv_quant),
                # a window layer's ring: room past the window for what one
                # dispatch writes ahead of the committed cursor
                window_slack=max(256, self.spec_k + 1),
            )
        # commit the pool onto the serving mesh (head axis over tp) —
        # no-op off-mesh; the supervised restart path lands here too,
        # so a rebuilt pool re-shards identically
        return self._D.shard_pool(pool, self.cfg, self.mesh)

    def _make_prefix_cache(self):
        """The prefix tree for this server: arena-backed normally;
        ADOPTED in paged mode — cached ids are global-pool blocks held
        through the allocator's pin/release refcounts (the lambdas
        late-bind ``self._allocator`` so a supervised pool rebuild swaps
        the allocator under the same tree factory)."""
        from pathway_tpu.engine.prefix_cache import PrefixCache

        kw = dict(self._prefix_kwargs)
        if self.paged_kv:
            kw["pin"] = lambda ids: self._allocator.pin(ids)
            kw["unpin"] = lambda ids: self._allocator.release(ids)
        return PrefixCache(**kw)

    def _paged_seed_fn(self):
        """Jitted paged admission seed: install a slot's block-table row
        and its cached-column mask in one donated table edit
        (``paged_admit_cached`` — COW, no KV bytes move)."""
        if self._paged_seed_jit is None:
            import jax

            D = self._D

            def seed(pool, slot, row, n_cached):
                return D.paged_admit_cached(pool, slot, row, n_cached)

            self._paged_seed_jit = jax.jit(seed, donate_argnums=(0,))
        return self._paged_seed_jit

    def _table_clear_fn(self):
        """Jitted free-time row clear: point every entry of a freed
        slot's table row at the sentinel block BEFORE its blocks return
        to the allocator. Without this, a stale row and a new owner's
        row could reference the same physical block and the
        gather-run-scatter round trip would write both copies back in
        nondeterministic order."""
        if self._table_clear_jit is None:
            import jax
            import jax.numpy as jnp

            D = self._D
            M = self.cache_len // self.paged_block

            def clear(pool, slot):
                return D.paged_table_set(
                    pool, slot, jnp.zeros((M,), jnp.int32)
                )

            self._table_clear_jit = jax.jit(clear, donate_argnums=(0,))
        return self._table_clear_jit

    def _release_slot_kv(self, slot: int) -> None:
        """Host-side KV bookkeeping when a slot frees: drop its
        fragmentation cover and, in paged mode, clear its table row and
        release its block references (blocks a prefix node still pins
        stay resident)."""
        self._slot_cover.pop(slot, None)
        if self._allocator is not None:
            row = self._slot_blocks.pop(slot, None)
            if row:
                import numpy as np

                self.pool = self._table_clear_fn()(
                    self.pool, np.int32(slot)
                )
                self._allocator.release(row)
        self._update_fragmentation()

    def _update_fragmentation(self) -> None:
        """Refresh the ``kv_fragmentation`` gauge: 1 - reachable/allocated
        KV bytes over the active slots. A dense slot always allocates the
        full ``cache_len`` row; a paged slot allocates only its table's
        blocks, so the gauge is the direct HBM-stranding comparison
        (``kv_fragmentation``)."""
        from pathway_tpu.engine.probes import record_kv_fragmentation

        covers = self._slot_cover
        if not covers:
            frag = 0.0
        else:
            needed = sum(covers.values())
            if self.paged_kv:
                alloc = sum(
                    len(self._slot_blocks.get(s, ())) * self.paged_block
                    for s in covers
                )
            else:
                alloc = len(covers) * self.cache_len
            frag = max(0.0, 1.0 - needed / alloc) if alloc else 0.0
            self._frag_sum += frag
            self._frag_n += 1
        self._kv_frag = frag
        record_kv_fragmentation(frag, server=self._trace_tag)

    def kv_fragmentation(self) -> dict:
        """Current and admission-averaged stranded-KV fraction."""
        return {
            "current": float(self._kv_frag),
            "mean": (self._frag_sum / self._frag_n) if self._frag_n else 0.0,
        }

    def _recover_after_crash(self, exc: BaseException) -> None:
        """Reset the server to an admittable state after a loop-scoped
        crash: rebuild the device pool, clear the host slot/prefill/
        in-flight bookkeeping, drop the (now-unbacked) prefix tree, and
        re-queue every interrupted request within its retry budget."""
        from pathway_tpu.engine import probes
        from pathway_tpu.internals.errors import get_global_error_log

        get_global_error_log().log(
            f"decoder serving loop crashed "
            f"({type(exc).__name__}: {exc}); supervised restart"
        )
        probes.REGISTRY.counter_add(
            "serve_restarts", server=self._trace_tag
        )
        victims: list = []
        with self.lock:
            for rec in list(self._inflight):
                victims.extend(r for r in rec[2] if r is not None)
            self._inflight.clear()
            victims.extend(r for r in self.slots if r is not None)
            for i in range(self.n_slots):
                self.slots[i] = None
            self.free = list(range(self.n_slots))
            self.stats["restarts"] += 1
        self._pending_prefill.clear()
        self._sent = [0] * self.n_slots
        self._slot_cover.clear()
        self._slot_blocks.clear()
        # parked rows and staged promotions died with the allocator/
        # pool the rebuild below replaces — drop WITHOUT releasing
        self._parked.clear()
        self._parked_blocks = 0
        self._record_parked()
        self._promote_ready.clear()
        with self.lock:
            self._t2_pending = 0
        self.pool = self._build_pool()
        # the rebuilt pool's prefix arena/allocator is empty: reset the
        # host radix tree to match (prefix_reset also drops the
        # per-request pins). unpin=False — the old tree's block pins
        # died with the allocator _build_pool just replaced, so they
        # must NOT release into the fresh one.
        self.prefix_reset(unpin=False)
        self._update_fragmentation()
        seen: set[int] = set()
        requeue: list = []
        for req in victims:
            if id(req) in seen or req.done.is_set():
                continue
            seen.add(id(req))
            self._tenant_credit(req)  # re-charged at re-admission
            req.retries += 1
            if req.retries <= self._retry_budget:
                # restart re-decodes from the prompt: drop partial output
                req.tokens = []
                req.first_token_at = None
                req.span.event("restart_requeue", attempt=req.retries)
                probes.REGISTRY.counter_add(
                    "requests_isolated", outcome="retried"
                )
                with self.lock:
                    self.stats["request_retries"] += 1
                requeue.append(req)
            else:
                self._fail_request(req, "failed")
        with self.lock:
            for req in reversed(requeue):
                self.queue.appendleft(req)

    def _fail_request(self, req, reason: str) -> None:
        """Terminal failure of ONE request (server keeps serving): the
        text=None sentinel plus a structured reason for the REST layer."""
        from pathway_tpu.engine import probes

        self._discard_parked(req)
        self._tenant_credit(req)
        req.error_reason = reason
        req.text = None
        probes.REGISTRY.counter_add(
            "requests_isolated", outcome="failed"
        )
        with self.lock:
            self.stats["request_failures"] += 1
        req.span.finish(error=True, tokens=len(req.tokens))
        req.done.set()

    def _shed_request(self, req, reason: str) -> None:
        """Admission-control shed (deadline / queue_full / degraded):
        terminal, structured, and counted — REST maps it to 503 +
        Retry-After."""
        from pathway_tpu.engine import probes

        self._discard_parked(req)
        self._tenant_credit(req)
        req.error_reason = f"shed:{reason}"
        req.retry_after = 1.0
        req.text = None
        probes.REGISTRY.counter_add("requests_shed", reason=reason)
        with self.lock:
            self.stats["shed"] += 1
        req.span.finish(error=True, tokens=len(req.tokens))
        req.done.set()

    def _isolate_admission_failure(self, slot: int, req, exc: Exception,
                                   active=None) -> None:
        """Rewind ONE request's admission — slot record, pending prefill
        pieces, prefix pins, lane mask — and re-queue it within its
        retry budget; past the budget it fails alone. The rest of the
        pool keeps serving."""
        from pathway_tpu.internals.errors import get_global_error_log

        self.slots[slot] = None
        self._pending_prefill.pop(slot, None)
        if active is not None:
            active[slot] = False
        self._prefix_release(req)
        self._release_slot_kv(slot)
        self._tenant_credit(req)  # re-charged if the requeue re-admits
        with self.lock:
            self.free.append(int(slot))
        req.retries += 1
        if req.retries <= self._retry_budget:
            from pathway_tpu.engine import probes

            req.span.event("retry", error=type(exc).__name__)
            probes.REGISTRY.counter_add(
                "requests_isolated", outcome="retried"
            )
            with self.lock:
                self.stats["request_retries"] += 1
                self.queue.appendleft(req)
        else:
            get_global_error_log().log(
                f"request failed after {req.retries - 1} retries: "
                f"{type(exc).__name__}: {exc}"
            )
            self._fail_request(req, "failed")

    def _run_safe(self):
        try:
            if self._restart_budget > 0:
                # supervised: a crashed loop recovers and re-enters with
                # exponential backoff, up to the restart budget — then
                # (and only then) the failure latches as before
                from pathway_tpu.internals.udfs.retries import (
                    ExponentialBackoffRetryStrategy,
                )

                def cycle():
                    try:
                        self._loop()
                    except Exception as exc:
                        self._recover_after_crash(exc)
                        raise

                ExponentialBackoffRetryStrategy(
                    max_retries=self._restart_budget, initial_delay=20,
                    backoff_factor=2, jitter_ms=10, max_delay_ms=2000,
                ).invoke_sync(cycle)
            else:
                self._loop()
        except BaseException as exc:  # noqa: BLE001 - never hang waiters
            self.failed = exc
            from pathway_tpu.internals.errors import get_global_error_log

            get_global_error_log().log(
                f"decoder serving loop died: {type(exc).__name__}: {exc}"
            )
        finally:
            # whether the loop died or shutdown() stopped it mid-flight:
            # every request still in a slot or queued completes with the
            # error sentinel — a timeout-less resolve wait must never hang
            with self.lock:
                pending = [r for r in self.slots if r is not None]
                pending.extend(self.queue)
                self.queue.clear()
            # eagerly-freed requests live only in the in-flight snapshots
            # until their tokens drain — sweep those too
            for rec in list(self._inflight):
                pending.extend(r for r in rec[2] if r is not None)
            for req in pending:
                if not req.done.is_set():
                    req.text = None  # error sentinel (UDF rows -> ERROR)
                    req.span.finish(error=True, tokens=len(req.tokens))
                    req.done.set()

    def submit(self, prompt_ids: list, max_new: int, *,
               priority: int = 1,
               tenant: str = "default") -> _PendingCompletion:
        import time as time_mod

        from pathway_tpu.engine import probes, tracing

        req = _PendingCompletion(prompt_ids, max_new)
        req.priority = int(priority)
        req.tenant = str(tenant) or "default"
        req.span = tracing.start_span(
            "decode", server=self._trace_tag,
            prompt_tokens=len(prompt_ids), max_new=max_new,
            tenant=req.tenant,
        )
        req.span.event("submit")
        now = time_mod.perf_counter()
        if self._deadline_s > 0:
            # monotonic, matching the loop's queue sweep clock
            req.deadline = time_mod.monotonic() + self._deadline_s
        shed_reason = None
        with self.lock:
            # checked under the lock: _run_safe drains the queue under it,
            # so a dead server can never strand a late submit
            if self.failed is not None:
                raise RuntimeError(
                    f"decoder serving loop died: {self.failed!r}"
                )
            if self._stop:
                raise RuntimeError("decoder serving loop is shut down")
            if (self._queue_bound > 0
                    and len(self.queue) >= self._queue_bound):
                # over the watermark: shed NOW instead of blocking the
                # submitter or growing the queue past what deadlines
                # could ever drain
                shed_reason = "queue_full"
            elif self._degradation_level >= 3 and req.priority <= 0:
                shed_reason = "degraded"
            else:
                self.queue.append(req)
                # observed arrival rate feeds the chunk-steps autotuner
                if self._last_submit_t is not None:
                    gap = now - self._last_submit_t
                    self._arrival_ema = (
                        gap if self._arrival_ema is None
                        else 0.8 * self._arrival_ema + 0.2 * gap
                    )
                self._last_submit_t = now
        if shed_reason is not None:
            self._shed_request(req, shed_reason)
            return req
        self.wake.set()
        return req

    def occupancy(self) -> float:
        """Active-slot-steps / total-slot-steps across every decode chunk
        dispatched so far: the fraction of the pool's decode compute that
        served live lanes (1.0 = every lane of every chunk was busy)."""
        return self.stats["steps"] / max(self.stats["slot_steps_total"], 1)

    def _admit_fn(self, s: int):
        fn = self._admit_fns.get(s)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg
            fl, msh = self.flash_prefill, self.mesh

            def admit(params_, ids, mask, pool, slot):
                return D.pool_admit(params_, ids, mask, pool, slot, cfgc,
                                    flash=fl, mesh=msh)

            fn = jax.jit(admit, donate_argnums=(3,))
            self._admit_fns[s] = fn
        return fn

    def _admit_batch_fn(self, m: int, s: int):
        fn = self._admit_batch_fns.get((m, s))
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg
            fl, msh = self.flash_prefill, self.mesh

            def admit(params_, ids, mask, pool, slots):
                return D.pool_admit_batch(params_, ids, mask, pool, slots,
                                          cfgc, flash=fl, mesh=msh)

            fn = jax.jit(admit, donate_argnums=(3,))
            self._admit_batch_fns[(m, s)] = fn
        return fn

    def _chunk_fn_for(self, steps: int):
        fn = self._chunk_fns.get(steps)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg
            temp, tk, tp = self._temperature, self._top_k, self._top_p
            pk, msh = self.paged_kernel, self.mesh

            def chunk(params_, pool, active, key):
                pool, toks = D.pool_decode_chunk(
                    params_, pool, active, key, cfgc, steps,
                    temperature=temp, top_k=tk, top_p=tp,
                    paged_kernel=pk, mesh=msh,
                )
                # the device's counters ride out with the tokens (the pool
                # itself is donated to the next dispatch): no extra sync
                return pool, toks, self._device_counts(pool)

            fn = jax.jit(chunk, donate_argnums=(1,))
            self._chunk_fns[steps] = fn
        return fn

    def _spec_fn_for(self, n_cycles: int):
        fn = self._spec_fns.get(n_cycles)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg
            dl, kk = self.spec_draft_layers, self.spec_k

            def spec(params_, pool, active):
                pool, toks, n_emit = D.pool_decode_spec(
                    params_, pool, active, cfgc, n_cycles,
                    draft_layers=dl, n_spec=kk,
                )
                return pool, toks, n_emit, self._device_counts(pool)

            fn = jax.jit(spec, donate_argnums=(1,))
            self._spec_fns[n_cycles] = fn
        return fn

    @staticmethod
    def _device_counts(pool: dict):
        """Copies of the pool's running counters (the routers' assignments,
        a looped stack's tokens by exit pass) for a dispatch to hand out
        beside its tokens; None where the pool keeps neither."""
        out = {k: pool[k] + 0 for k in ("moe_counts", "loop_exits")
               if k in pool}
        return out or None

    def spec_acceptance(self) -> float:
        """Drained draft-token acceptance rate of this server (0.0 before
        any speculative dispatch drained)."""
        d = self.stats["spec_drafted"]
        return self.stats["spec_accepted"] / d if d else 0.0

    def tokens_per_dispatch(self) -> float:
        """Tokens emitted per full-model lane-cycle (the unit one plain
        decode lane-step also costs; 1.0 is the plain-decode baseline)."""
        v = self.stats["spec_verify_steps"]
        # a plain chunk emits exactly one token per lane-step
        return self.stats["spec_emitted"] / v if v else 1.0

    def _pick_steps(self, queue_len: int) -> int:
        """Decode-chunk step count for this tick. Under queue pressure the
        SMALLEST candidate wins: the next chunk boundary is both the next
        admission opportunity and (pipeline_depth chunks on) the next
        drain/slot-release point, so shorter chunks recycle slots into a
        waiting queue sooner. With no queue, pick the largest candidate
        whose wall time still fits inside ~one observed inter-arrival gap
        (a newcomer waits about one gap at most); an idle trace with no
        arrival estimate keeps the full constructor chunk."""
        if not self.chunk_autotune or len(self._step_cands) == 1:
            return self.chunk_steps
        if queue_len > 0:
            return self._step_cands[-1]
        ia, sw = self._arrival_ema, self._step_wall_ema
        if ia is None or sw is None or sw <= 0.0:
            return self._step_cands[0]
        for c in self._step_cands:
            if c * sw <= ia:
                return c
        return self._step_cands[-1]

    def _prefill_fn(self, t: int, first: bool, last: bool,
                    with_col: bool = False):
        key = (t, first, last, with_col)
        fn = self._prefill_fns.get(key)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg
            fl, msh = self.flash_prefill, self.mesh

            if with_col:
                # cached-path final piece: the prompt's last real token
                # may sit mid-piece (right-padded layout), so its column
                # arrives traced
                def piece(params_, ids, mask, pos, pool, slot, start,
                          n_prompt, last_col):
                    return D.pool_prefill_chunk(
                        params_, ids, mask, pos, pool, slot, start,
                        n_prompt, cfgc, first=first, last=last,
                        last_col=last_col, flash=fl, mesh=msh,
                    )
            else:
                def piece(params_, ids, mask, pos, pool, slot, start,
                          n_prompt):
                    return D.pool_prefill_chunk(
                        params_, ids, mask, pos, pool, slot, start,
                        n_prompt, cfgc, first=first, last=last,
                        flash=fl, mesh=msh,
                    )

            fn = jax.jit(piece, donate_argnums=(4,))
            self._prefill_fns[key] = fn
        return fn

    def _admit_cached_fn(self, m: int):
        fn = self._admit_cached_fns.get(m)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg

            def seed(pool, slot, idxs):
                return D.pool_admit_cached(pool, slot, idxs, cfgc)

            fn = jax.jit(seed, donate_argnums=(0,))
            self._admit_cached_fns[m] = fn
        return fn

    def _extract_fn(self, n: int):
        fn = self._extract_fns.get(n)
        if fn is None:
            import jax

            D, cfgc = self._D, self.cfg

            def extract(pool, slot, start, idxs):
                return D.kv_extract(pool, slot, start, idxs, cfgc)

            fn = jax.jit(extract, donate_argnums=(0,))
            self._extract_fns[n] = fn
        return fn

    def _prefix_insert(self, slot: int, req, e: list, base: int,
                       written: int = 0) -> None:
        """Publish ``slot``'s freshly-prefilled full blocks of prompt
        ``e`` into the radix tree + arena. ``base`` is the cache column
        of token 0 (``s - n`` for a left-padded miss admission, 0 for
        the right-padded cached path); ``written`` how many cache columns
        the admission wrote (its padded extent). Moves the request's ref
        to the deepest node so the whole prefix stays pinned while it
        decodes."""
        import numpy as np

        from pathway_tpu.engine import probes, tracing

        ring = self._D.pool_ring(self.pool)
        if ring and written > ring:
            # a window layer's ring has wrapped: the prompt's early rows
            # are gone from it, so its blocks cannot be published whole.
            # Decline by what the server can see; never a wrong hit later
            self.stats["prefix_declined"] += 1
            return
        with tracing.region("pw.decode.prefix_insert", slot=int(slot),
                            tokens=len(e)):
            self._prefix_publish(slot, req, e, base)

    def _prefix_publish(self, slot: int, req, e: list, base: int) -> None:
        import numpy as np

        from pathway_tpu.engine import probes

        if self.paged_kv:
            # zero-copy adoption: the slot's OWN blocks (its table row)
            # become the cached prefix — the tree pins them through the
            # allocator, no kv_extract dispatch, no duplicate HBM bytes.
            # Right-padded paged admission puts block i of the prompt in
            # row entry i, so the row prefix IS the block_ids argument.
            row = self._slot_blocks.get(slot)
            if row is None:
                return
            nfull = min(len(e) // self.prefix_block, len(row))
            node, _first_new, _new = self.prefix.insert(
                e, n_blocks=nfull, block_ids=row
            )
        else:
            node, first_new, new_ids = self.prefix.insert(e)
            if new_ids:
                self.pool = self._extract_fn(len(new_ids))(
                    self.pool, np.int32(slot),
                    np.int32(base + first_new * self.prefix_block),
                    np.asarray(new_ids, np.int32),
                )
                probes.record_device_dispatch("prefix_extract")
        old = self._prefix_nodes.get(req)
        self.prefix.acquire(node)
        if old is not None:
            self.prefix.release(old)
        self._prefix_nodes[req] = node

    def _prefix_release(self, req) -> None:
        node = self._prefix_nodes.pop(req, None)
        if node is not None and self.prefix is not None:
            self.prefix.release(node)

    def prefix_reset(self, *, unpin: bool = True) -> None:
        """Drop every cached prefix and zero the per-server prefix
        counters (warm up the executables, then measure a clean
        trace). Only call while no requests are in flight. In paged
        mode the tree's adopted blocks unpin back into the allocator;
        the supervised restart path passes ``unpin=False`` because its
        pool rebuild already replaced the allocator the old pins lived
        in."""
        if self.prefix is None:
            return
        self._prefix_nodes.clear()
        if self.paged_kv and unpin:
            self.prefix.reset()
        else:
            self.prefix = self._make_prefix_cache()
        for k in ("prefix_hit_tokens", "prefix_miss_tokens",
                  "prefix_hit_requests", "prefix_requests"):
            self.stats[k] = 0
        # drop staged-but-unadopted promotions with the tree they
        # targeted; items still inside the StageWorker drain later and
        # re-match against the fresh tree (stale paths skip harmlessly)
        while self._promote_ready:
            self._promote_ready.popleft()
            with self.lock:
                self._t2_pending -= 1

    # -- tier-2 promotion pipeline ------------------------------------

    def _export_blocks(self, ids: list) -> dict:
        """Tier-2 demote callback (``PrefixCache(export=...)``): gather
        the KV bytes of the given arena/pool blocks and device_get them
        as per-channel host ``np`` blobs in the ``kv_block_export``
        layout. Runs on the loop thread inside eviction — one gather
        dispatch per demoted edge, amortized over the edge's lifetime."""
        import jax
        import numpy as np

        if self._export_jits.get("fn") is None:
            D = self._D

            def export(pool, idxs):
                return D.kv_block_export(pool, idxs)

            self._export_jits["fn"] = jax.jit(export)
        blobs = self._export_jits["fn"](
            self.pool, np.asarray(ids, np.int32)
        )
        return {c: np.asarray(v) for c, v in blobs.items()}

    def _import_blocks_fn(self):
        """Jitted promotion scatter: write staged block blobs into the
        pool/arena at the freshly-allocated ids (pool donated — same
        state-in/state-out discipline as every other pool edit)."""
        if self._import_jits.get("fn") is None:
            import jax

            D = self._D

            def imp(pool, idxs, blobs):
                return D.kv_block_import(pool, idxs, blobs)

            self._import_jits["fn"] = jax.jit(imp, donate_argnums=(0,))
        return self._import_jits["fn"]

    def _schedule_promotion(self, tokens, j: int, keys: list,
                            blobs: dict) -> None:
        """Queue a tier-2 hit's host blobs for async h2d staging on the
        PR-2 StageWorker; the loop adopts them between ticks."""
        with self.lock:
            self._t2_pending += 1
        try:
            self._promote_worker.submit(
                (list(tokens), int(j), list(keys), blobs)
            )
        except Exception:  # noqa: BLE001 - closed worker at shutdown
            with self.lock:
                self._t2_pending -= 1

    def _stage_promotion(self, item) -> None:
        """StageWorker fn (worker thread — must be total): move the
        blobs host->device off the serving thread so the adoption tick
        only pays a table/arena scatter, never a PCIe copy."""
        import time as time_mod

        import jax

        from pathway_tpu.engine import tracing

        tokens, j, keys, blobs = item
        try:
            with tracing.region("pw.decode.h2d", blocks=len(keys)):
                staged = {c: jax.device_put(v) for c, v in blobs.items()}
                for v in staged.values():
                    v.block_until_ready()
            self._promote_ready.append((tokens, j, keys, staged))
        except Exception:  # noqa: BLE001 - drop the hit, keep serving
            with self.lock:
                self._t2_pending -= 1
        self.wake.set()

    def _drain_promotions(self) -> None:
        """Adopt every staged promotion (loop thread, once per tick,
        BEFORE admissions — so a request arriving right behind its
        promotion already sees the tier-1 hit)."""
        if self._promote_worker is None:
            return
        from pathway_tpu.internals.errors import get_global_error_log

        while self._promote_ready:
            tokens, j, keys, staged = self._promote_ready.popleft()
            try:
                self._apply_promotion(tokens, j, keys, staged)
            except Exception as exc:  # noqa: BLE001 - best-effort cache
                get_global_error_log().log(
                    f"tier-2 promotion dropped: "
                    f"{type(exc).__name__}: {exc}"
                )
            finally:
                with self.lock:
                    self._t2_pending -= 1

    def _apply_promotion(self, tokens, j: int, keys: list,
                         staged: dict) -> None:
        """Re-insert a staged tier-2 edge into the radix tree and
        scatter its KV bytes into fresh device blocks. The tree may
        have moved since the admission-time lookup (another request
        prefilled the same head), so re-match and keep only the still-
        missing suffix; a path that diverged entirely is dropped — the
        blobs were popped from tier 2 and promotion owns them."""
        import numpy as np

        from pathway_tpu.engine.probes import record_prefix

        if self.prefix is None:
            return
        B = self.prefix_block
        nb = j + len(keys)
        j2, _ids, _node = self.prefix.match(tokens[: nb * B])
        if j2 != j:
            d = j2 - j
            if d < 0 or d >= len(keys):
                return  # stale: the matched path changed under us
            keys = keys[d:]
            staged = {c: v[d:] for c, v in staged.items()}
            j = j2
            nb = j + len(keys)
        if self.paged_kv:
            try:
                ids = self._allocator.alloc(len(keys))
            except self._D.PagedPoolOOM:
                return  # pool is the scarce tier — decode wins
            _node2, _first, new_ids = self.prefix.insert(
                tokens[: nb * B], n_blocks=nb,
                block_ids=[0] * j + ids,
            )
            if new_ids:
                self.pool = self._import_blocks_fn()(
                    self.pool, np.asarray(new_ids, np.int32),
                    {c: v[: len(new_ids)] for c, v in staged.items()},
                )
            # the tree pinned new_ids (adopting insert): drop our own
            # alloc refs so eviction alone governs their lifetime —
            # and free any tail the tree's budget didn't stretch to
            self._allocator.release(ids)
        else:
            _node2, first_new, new_ids = self.prefix.insert(
                tokens[: nb * B], n_blocks=nb
            )
            if not new_ids:
                return
            d = first_new - j
            if d < 0 or d >= len(keys):
                return
            self.pool = self._import_blocks_fn()(
                self.pool, np.asarray(new_ids, np.int32),
                {c: v[d:d + len(new_ids)] for c, v in staged.items()},
            )
        if new_ids:
            self.stats["t2_promoted_blocks"] += len(new_ids)
            record_prefix("t2_promoted_blocks", len(new_ids))

    def t2_drain(self, timeout: float = 10.0) -> bool:
        """Block until every scheduled tier-2 promotion has been staged
        AND adopted (tests/bench quiesce point); True on success."""
        import time as time_mod

        if self._promote_worker is None:
            return True
        end = time_mod.monotonic() + timeout
        while time_mod.monotonic() < end:
            with self.lock:
                if self._t2_pending <= 0:
                    return True
            self.wake.set()
            time_mod.sleep(0.005)
        return False

    def _t2_probe(self, e: list, n: int, m: int, node) -> None:
        """Admission-time tier-2 lookup past a tier-1 match of ``m``
        blocks. A hit schedules async promotion — THIS request still
        prefills (the blobs are host-side); the NEXT request on the
        same head lands the tier-1 hit."""
        if self.prefix is None or self.prefix.tier2 is None:
            return
        from pathway_tpu.engine.probes import record_prefix

        n_full = (n - 1) // self.prefix_block
        if m >= n_full:
            return
        record_prefix("t2_lookups", 1)
        hit = self.prefix.match_t2(e, n_full, node, m)
        if hit is None:
            return
        keys, blobs = hit
        record_prefix("t2_hits", 1)
        self.stats["t2_hit_requests"] += 1
        self._schedule_promotion(e, m, keys, blobs)

    # -- multi-tenant budgets & preemption ----------------------------

    def _tenant_charge(self, req) -> None:
        """Admission charges the request's full decode budget against
        its tenant; the amount is remembered so the credit matches even
        after EOS/degradation mutate ``req.max_new``."""
        if self._tenants is None:
            return
        amt = int(req.max_new)
        self._tenants.charge(req.tenant, amt)
        self._charged[id(req)] = (req.tenant, amt)

    def _tenant_credit(self, req) -> None:
        if self._tenants is None:
            return
        rec = self._charged.pop(id(req), None)
        if rec is not None:
            self._tenants.credit(rec[0], rec[1])

    def _record_parked(self) -> None:
        """Refresh the ``kv_parked_bytes`` gauge: preempted requests'
        parked blocks are HELD ON PURPOSE, so they are classified apart
        from the fragmentation (stranded-bytes) signal."""
        from pathway_tpu.engine.probes import record_kv_parked

        record_kv_parked(
            self._parked_blocks * self._block_kv_bytes,
            server=self._trace_tag,
        )

    def _discard_parked(self, req) -> None:
        """Release a preempted request's parked blocks (terminal paths:
        fail/shed — the KV will never be re-admitted)."""
        row = self._parked.pop(req, None)
        if row is None:
            return
        self._parked_blocks -= len(row)
        if self._allocator is not None:
            self._allocator.release(row)
        self._record_parked()

    def _preempt_request(self, slot: int, req, active) -> None:
        """Budget preemption: rewind ONE over-budget request's slot via
        the PR-10 isolation machinery, PARK its paged KV blocks (the
        allocator refs stay alive, so re-admission is a table edit plus
        a one-block tail re-prefill — not a full re-prefill), and
        requeue it at the head. Preemption is a scheduling decision,
        not a failure: the request is never shed and never counts
        against its retry budget."""
        import numpy as np

        from pathway_tpu.engine import probes

        req.span.event("preempt", slot=int(slot), tenant=req.tenant)
        self.slots[slot] = None
        self._pending_prefill.pop(slot, None)
        active[slot] = False
        self._sent[slot] = 0
        self._prefix_release(req)
        self._slot_cover.pop(slot, None)
        if self._allocator is not None:
            row = self._slot_blocks.pop(slot, None)
            if row:
                self.pool = self._table_clear_fn()(
                    self.pool, np.int32(slot)
                )
                # refs are KEPT: the blocks park instead of freeing
                self._parked[req] = row
                self._parked_blocks += len(row)
                self._record_parked()
        self._update_fragmentation()
        # null the request out of the in-flight snapshots: tokens from
        # chunks already dispatched must not drain into the rewound
        # stream (re-admission re-decodes them byte-identically)
        for rec in self._inflight:
            snap = rec[2]
            for i, r in enumerate(snap):
                if r is req:
                    snap[i] = None
        req.tokens = []
        req.first_token_at = None
        self._tenant_credit(req)
        probes.REGISTRY.counter_add("preemptions", tenant=req.tenant)
        with self.lock:
            self.stats["preemptions"] += 1
            self.free.append(int(slot))
            self.queue.appendleft(req)

    def _maybe_preempt(self, active) -> None:
        """Escalated budget enforcement: when a queued ELIGIBLE tenant
        would admit but every slot is busy and some tenant is over its
        token budget, preempt that tenant's newest-admitted decode-lane
        request (newest-first keeps the most-finished work running).
        Slots still mid-prefill are never victims — their parked rows
        would hold uncomputed KV."""
        if self._tenants is None or self._tenants.budget_tokens <= 0:
            return
        with self.lock:
            if not self.queue or self.free:
                return
            entries = [(r.tenant, r.max_new) for r in self.queue]
        if self._tenants.select(entries, charge=False) is None:
            return  # every waiter is itself over budget — hold
        victim = None
        for slot, req in enumerate(self.slots):
            if (req is None or req.done.is_set()
                    or slot in self._pending_prefill):
                continue
            if not self._tenants.over_budget(req.tenant):
                continue
            if victim is None or req.seq > self.slots[victim].seq:
                victim = slot
        if victim is not None:
            self._preempt_request(victim, self.slots[victim], active)

    # -- lane / tenant observability ----------------------------------

    def lane_stats(self) -> dict:
        """Per-lane occupancy snapshot: slots mid-prompt (prefill lane)
        vs slots emitting (decode lane)."""
        return dict(self._lane_counts)

    def tenant_depths(self) -> dict:
        """Queued requests per tenant (scrape/panel feed)."""
        with self.lock:
            depth: dict[str, int] = {}
            for r in self.queue:
                depth[r.tenant] = depth.get(r.tenant, 0) + 1
        return depth

    def _admit_one(self, slot: int, req, direct: list,
                   direct_inserts: list) -> None:
        """Admission host work for ONE request — prefix match, cached
        seeding, prompt padding, prefill scheduling. A method (not loop
        body) so supervised serving can isolate a request-scoped fault
        here to this request alone."""
        import numpy as np

        from pathway_tpu.engine import probes
        from pathway_tpu.engine.probes import record_prefix
        from pathway_tpu.ops import next_pow2

        e = req.ids[-self.max_prompt_bucket:]
        n = len(e)
        req.span.event("admit", slot=int(slot))
        if self._degradation_level >= 1:
            # ladder level 1+: clamp the answer budget so slots recycle
            # sooner while the SLO alert is firing
            req.max_new = min(
                req.max_new, max(1, self._default_max_new // 2)
            )
        if self.paged_kv:
            self._admit_one_paged(slot, req, e, n)
            return
        # reachable span for the fragmentation gauge: a dense slot pins
        # the whole cache_len row regardless
        self._slot_cover[slot] = min(
            self.cache_len,
            n + req.max_new + (self.pipeline_depth + 1) * self._slack,
        )
        self._update_fragmentation()
        B = self.prefix_block
        # prefix-cache accounting + match. A hit never reuses the
        # prompt's FINAL (partial or last-full) block: at least
        # one suffix token must run through pool_prefill_chunk to
        # produce the first-token logits.
        m_hit, arena_ids, node = 0, [], None
        if self.prefix is not None and n > B:
            m, arena_ids, node = self.prefix.match(e)
            m_hit = min(m, (n - 1) // B)
            hit_t = m_hit * B
            record_prefix("requests", 1)
            record_prefix("hit_tokens", hit_t)
            record_prefix("miss_tokens", n - hit_t)
            if m_hit:
                record_prefix("hit_requests", 1)
                self.stats["prefix_hit_requests"] += 1
            self.stats["prefix_requests"] += 1
            self.stats["prefix_hit_tokens"] += hit_t
            self.stats["prefix_miss_tokens"] += n - hit_t
            req.span.event(
                "prefix_match", hit_blocks=int(m_hit),
                hit_tokens=int(hit_t), miss_tokens=int(n - hit_t),
            )
            # tier-2 continuation past the tier-1 match (uncapped m:
            # the probe extends from the true matched depth)
            self._t2_probe(e, n, m, node)
        if m_hit >= 1:
            # cache hit: pin the matched path, seed the slot's
            # cache columns [0, m_hit*B) straight from the arena
            # (one copy dispatch, no compute), then prefill only
            # the suffix — RIGHT-padded, so token i sits at cache
            # column i exactly like the arena blocks expect.
            self.prefix.acquire(node)
            self._prefix_nodes[req] = node
            self.pool = self._admit_cached_fn(m_hit)(
                self.pool, np.int32(slot),
                np.asarray(arena_ids[:m_hit], np.int32),
            )
            # the seed COPIES arena blocks into the slot row: those KV
            # bytes now exist twice in HBM until the slot frees. The
            # ledger makes the double-count visible (the paged pool's
            # copy-on-write tables drive it to zero).
            record_prefix("copy_bytes", m_hit * self.prefix.block_bytes)
            n_cached = m_hit * B
            P = self.prefill_chunk
            W = n_cached + -((n_cached - n) // P) * P
            r_ids = np.zeros((1, W), np.int32)
            r_mask = np.zeros((1, W), np.int32)
            r_ids[0, :n] = e
            r_mask[0, :n] = 1
            pos = np.minimum(
                np.arange(W), n - 1
            )[None, :].astype(np.int32)
            n_prompt = np.asarray([n], np.int32)
            pieces = [
                (r_ids[:, o:o + P], r_mask[:, o:o + P],
                 pos[:, o:o + P], o)
                for o in range(n_cached, W, P)
            ]
            # the final piece may end on pad columns: the real
            # last token's in-piece column rides along traced
            # (None when it IS the final column — static path)
            lc = (n - 1) - (W - P)
            meta = {
                "last_col": None if lc == P - 1 else lc,
                "insert": (req, e, 0, n),
            }
            self._pending_prefill[slot] = (pieces, n_prompt, meta)
            self.stats["admitted"] += 1
            return
        ins = (
            (req, e, 0) if self.prefix is not None and n >= B
            else None
        )
        s = max(8, next_pow2(max(len(e), 1), 8))
        ids = np.zeros((1, s), np.int32)
        mask = np.zeros((1, s), np.int32)
        if e:
            ids[0, s - len(e):] = e
            mask[0, s - len(e):] = 1
        else:
            mask[0, -1] = 1
        if ins is not None:
            # left-padded admission: token 0 sits at column s-n
            ins = (req, e, s - n, s)
        if self.chunked_prefill and s > self.prefill_chunk:
            # split into fixed-size pieces, dispatched ONE per
            # loop tick below — the active lanes keep decoding
            # between pieces instead of stalling for the whole
            # prompt's prefill
            pos = np.clip(
                np.cumsum(mask[0]) - 1, 0, None
            )[None, :].astype(np.int32)
            n_prompt = np.asarray([int(mask.sum())], np.int32)
            P = self.prefill_chunk
            # a piece that lies wholly in the left padding has nothing to
            # compute or to write: the first piece with a real token opens
            # the row (`first` clears the slot's stale mask) in its place
            pieces = [
                (ids[:, o:o + P], mask[:, o:o + P], pos[:, o:o + P], o)
                for o in range(0, s, P) if mask[0, o:o + P].any()
            ]
            meta = {"first_off": pieces[0][3],
                    "first_col": s - int(n_prompt[0])}
            if ins is not None:
                meta["insert"] = ins
            self._pending_prefill[slot] = (pieces, n_prompt, meta)
        else:
            direct.append((slot, ids, mask, s))
            if ins is not None:
                direct_inserts.append((slot, ins))
        self.stats["admitted"] += 1

    def _unpark(self, slot: int, req, e: list, n: int,
                row: list) -> bool:
        """Re-admit a preempted request onto its own parked block row:
        the prompt's full blocks still hold their computed KV (the
        refs never dropped), so admission is one table edit plus a
        re-prefill of the final partial block — that last piece is
        what regenerates the first-token logits the rewound stream
        needs. Returns False when the row no longer fits the (possibly
        degradation-clamped) budget."""
        import numpy as np

        B = self.paged_block
        per_slot = self.cache_len // B
        cover = min(
            self.cache_len,
            n + req.max_new + (self.pipeline_depth + 1) * self._slack,
        )
        need = min(per_slot, -(-cover // B))
        if len(row) != need:
            return False
        self._slot_blocks[slot] = row
        self._slot_cover[slot] = cover
        n_cached = ((n - 1) // B) * B
        row_arr = np.zeros((per_slot,), np.int32)
        row_arr[:len(row)] = row
        self.pool = self._paged_seed_fn()(
            self.pool, np.int32(slot), row_arr, np.int32(n_cached)
        )
        req.span.event("unpark", blocks=len(row), cached=int(n_cached))
        P = self.prefill_chunk
        W = n_cached + -((n_cached - n) // P) * P
        r_ids = np.zeros((1, W), np.int32)
        r_mask = np.zeros((1, W), np.int32)
        r_ids[0, :n] = e
        r_mask[0, :n] = 1
        pos = np.minimum(np.arange(W), n - 1)[None, :].astype(np.int32)
        n_prompt = np.asarray([n], np.int32)
        pieces = [
            (r_ids[:, o:o + P], r_mask[:, o:o + P], pos[:, o:o + P], o)
            for o in range(n_cached, W, P)
        ]
        lc = (n - 1) - (W - P)
        meta = {"last_col": None if lc == P - 1 else lc}
        if self.prefix is not None and n >= B:
            meta["insert"] = (req, e, 0, 0)
        self._pending_prefill[slot] = (pieces, n_prompt, meta)
        self.stats["admitted"] += 1
        self._update_fragmentation()
        return True

    def _admit_one_paged(self, slot: int, req, e: list, n: int) -> None:
        """Paged admission: allocate exactly the blocks this request can
        reach, install the slot's block-table row, seed any cached
        prefix by SHARING blocks (copy-on-write pins — no arena copy
        dispatch), and schedule the prompt as right-padded prefill
        pieces. Every paged admission right-pads (token i at cache
        column i): that is the layout invariant that lets a finished
        prompt's blocks publish into the prefix tree zero-copy. On
        ``PagedPoolOOM`` nothing has been written — the request parks
        at the queue head until blocks free up."""
        import numpy as np

        from pathway_tpu.engine.probes import record_prefix

        if not e:
            # degenerate empty prompt: one pad token at column 0 (the
            # dense path's mask-only-last-column admission computes the
            # same single-token attention)
            e, n = [0], 1
        B = self.paged_block
        per_slot = self.cache_len // B
        parked = self._parked.pop(req, None)
        if parked is not None:
            self._parked_blocks -= len(parked)
            self._record_parked()
            if self._unpark(slot, req, e, n, parked):
                return
            # the budget changed under degradation and the row no
            # longer fits the request — fall through to a fresh
            # admission (the parked KV is lost, correctness is not)
            self._allocator.release(parked)
        m_hit, pool_ids, node = 0, [], None
        if self.prefix is not None and n > B:
            m, pool_ids, node = self.prefix.match(e)
            m_hit = min(m, (n - 1) // B)
            hit_t = m_hit * B
            record_prefix("requests", 1)
            record_prefix("hit_tokens", hit_t)
            record_prefix("miss_tokens", n - hit_t)
            if m_hit:
                record_prefix("hit_requests", 1)
                self.stats["prefix_hit_requests"] += 1
            self.stats["prefix_requests"] += 1
            self.stats["prefix_hit_tokens"] += hit_t
            self.stats["prefix_miss_tokens"] += n - hit_t
            req.span.event(
                "prefix_match", hit_blocks=int(m_hit),
                hit_tokens=int(hit_t), miss_tokens=int(n - hit_t),
            )
            self._t2_probe(e, n, m, node)
        # worst-case columns the lane can write: prompt + its own answer
        # budget + one chunk of overrun slack per in-flight chunk (the
        # same bound that sizes the dense cache_len)
        cover = min(
            self.cache_len,
            n + req.max_new + (self.pipeline_depth + 1) * self._slack,
        )
        need = min(per_slot, -(-cover // B))
        try:
            fresh = self._allocator.alloc(need - m_hit)
        except self._D.PagedPoolOOM as oom:
            self.slots[slot] = None
            with self.lock:
                self.free.append(int(slot))
            if need - m_hit > self._total_blocks - 1:
                # can never fit, even against an idle pool
                self._fail_request(req, "paged_oom")
                return
            req.span.event(
                "paged_oom", want=int(oom.want), free=int(oom.free)
            )
            self.stats["paged_oom"] += 1
            with self.lock:
                self.queue.appendleft(req)
            return
        shared = [int(i) for i in pool_ids[:m_hit]]
        if shared:
            # the slot's OWN reference on the shared blocks — balanced
            # by the release in _release_slot_kv, independent of the
            # tree's pin (which the prefix node's refcount protects)
            self._allocator.pin(shared)
        row = shared + fresh
        self._slot_blocks[slot] = row
        self._slot_cover[slot] = cover
        n_cached = m_hit * B
        row_arr = np.zeros((per_slot,), np.int32)
        row_arr[:len(row)] = row
        # one donated table edit installs the row and the cached-column
        # mask (all-zero mask when n_cached == 0); shared KV bytes never
        # move — suffix and decode writes land past the shared run
        self.pool = self._paged_seed_fn()(
            self.pool, np.int32(slot), row_arr, np.int32(n_cached)
        )
        if m_hit:
            self.prefix.acquire(node)
            self._prefix_nodes[req] = node
        P = self.prefill_chunk
        W = n_cached + -((n_cached - n) // P) * P
        r_ids = np.zeros((1, W), np.int32)
        r_mask = np.zeros((1, W), np.int32)
        r_ids[0, :n] = e
        r_mask[0, :n] = 1
        pos = np.minimum(np.arange(W), n - 1)[None, :].astype(np.int32)
        n_prompt = np.asarray([n], np.int32)
        pieces = [
            (r_ids[:, o:o + P], r_mask[:, o:o + P], pos[:, o:o + P], o)
            for o in range(n_cached, W, P)
        ]
        lc = (n - 1) - (W - P)
        meta = {"last_col": None if lc == P - 1 else lc}
        if self.prefix is not None and n >= B:
            meta["insert"] = (req, e, 0, 0)
        self._pending_prefill[slot] = (pieces, n_prompt, meta)
        self.stats["admitted"] += 1
        self._update_fragmentation()

    def _loop_account(self, phase: str, tokens: int) -> None:
        """``loop_passes{phase, pass}``: every pass of a looped stack ran
        for ``tokens`` tokens that were real (host arithmetic, no sync)."""
        if self.cfg.loops > 1 and tokens:
            from pathway_tpu.engine import probes

            probes.record_loop_passes(phase, tokens, self.cfg.loops)

    def _prefill_piece(self, slot: int, active) -> None:
        """Dispatch one pending prefill piece for ``slot`` (a method so
        supervised serving can rewind just this slot on a fault)."""
        import numpy as np

        from pathway_tpu.engine import tracing

        pieces, n_prompt, meta = self._pending_prefill[slot]
        p_ids, p_mask, p_pos, off = pieces.pop(0)
        first = off == (meta.get("first_off", 0) if meta else 0)
        last = not pieces
        lc = meta.get("last_col") if (meta and last) else None
        with tracing.region("pw.decode.prefill", tokens=int(p_ids.shape[1]),
                            piece=int(off // p_ids.shape[1]),
                            passes=self.cfg.loops):
            if lc is None:
                self.pool = self._prefill_fn(p_ids.shape[1], first, last)(
                    self.params, p_ids, p_mask, p_pos, self.pool,
                    np.int32(slot), np.int32(off), n_prompt,
                )
            else:
                self.pool = self._prefill_fn(
                    p_ids.shape[1], first, last, True
                )(
                    self.params, p_ids, p_mask, p_pos, self.pool,
                    np.int32(slot), np.int32(off), n_prompt,
                    np.int32(lc),
                )
        self.stats["prefill_chunks"] += 1
        live = np.flatnonzero(p_mask[0])
        if live.size:
            # how much of the row this piece's attention touched: host
            # arithmetic on the piece's offset and the row's live columns
            from pathway_tpu.engine import probes

            self._loop_account("prefill", int(live.size))
            blocks = self._D.prefill_blocks_visited(
                self.cfg, int(p_ids.shape[1]), self.cache_len,
                self._D.pool_ring(self.pool), int(off),
                int(meta.get("first_col", 0)) if meta else 0,
                int(off) + int(live[-1]), flash=self.flash_prefill)
            if blocks:
                probes.record_prefill_attn_blocks(blocks)
            if blocks.get(("latent", 1)):
                # a latent layer expands exactly the key blocks it visits
                probes.record_latent_rows_expanded(
                    blocks[("latent", 1)] * self._D.chunk_rows(
                        self.cfg, int(p_ids.shape[1]), self.cache_len,
                        np.dtype(self.cfg.dtype).itemsize))
        req_p = self.slots[slot]
        if req_p is not None:
            req_p.span.event(
                "prefill_chunk", offset=int(off),
                width=int(p_ids.shape[1]), last=bool(last),
            )
        if last:
            del self._pending_prefill[slot]
            active[slot] = True
            if self.disagg:
                # lane handoff: the finished prompt's KV migrates from
                # the prefill lane into the decode lane by block-table
                # IDENTITY — zero-copy on one chip (the slot's row is
                # the handoff; kv_block_export/import carry the same
                # blobs for the cross-device fleet case). Counted only
                # under the flag so the kill switch stays stats-clean.
                from pathway_tpu.engine import probes

                nb = (
                    len(self._slot_blocks.get(slot, ()))
                    if self.paged_kv
                    else -(-int(n_prompt[0]) // self.prefill_chunk)
                )
                self.stats["kv_migrated_blocks"] += nb
                probes.REGISTRY.counter_add(
                    "kv_migrated_blocks", nb, server=self._trace_tag
                )
                if req_p is not None:
                    req_p.span.event("migrate", blocks=int(nb))
            if meta and meta.get("insert") is not None:
                req_i, e_i, base_i, wrote_i = meta["insert"]
                self._prefix_insert(slot, req_i, e_i, base_i, wrote_i)

    def _loop(self):
        import time as time_mod

        import jax
        import numpy as np

        from pathway_tpu.engine import probes, tracing
        from pathway_tpu.engine.probes import record_spec, record_spec_many

        active = np.zeros(self.n_slots, dtype=bool)
        inflight = self._inflight

        def dispatch_decode() -> bool:
            """One decode chunk over the active lanes; False if none."""
            if not active.any():
                return False
            if self._chaos_dispatch is not None:
                # loop-scoped fault: every in-flight lane is affected, so
                # recovery is a supervised restart, not per-request
                self._chaos_dispatch.maybe_fail()
            with self.lock:
                qlen = len(self.queue)
            steps = self._pick_steps(qlen)
            # tick-to-tick wall per dispatched step: in steady state the
            # host loop is paced by the device finishing chunks, so this
            # approximates chunk wall time for the autotuner
            now = time_mod.perf_counter()
            if self._last_dispatch_t is not None and self._last_dispatch_steps:
                per = (now - self._last_dispatch_t) / self._last_dispatch_steps
                self._step_wall_ema = (
                    per if self._step_wall_ema is None
                    else 0.7 * self._step_wall_ema + 0.3 * per
                )
            self._last_dispatch_t = now
            self._ticks += 1
            # the device gets its OWN copy of the lane mask: dispatch is
            # asynchronous and may read the host buffer after this call
            # returns (the CPU backend aliases it outright), while eager
            # refill and the drain below flip `active` entries in place —
            # a lane the host frees must still be live in THIS chunk
            lanes = active.copy()
            if (self.spec_decode and not self._spec_off
                    and self._degradation_level < 2):
                # speculative path: a chunk of `steps` plain lane-steps
                # becomes n_cycles draft/verify/accept cycles — each
                # cycle costs ~one full-model stream (the verify) and
                # emits 1..spec_k+1 tokens per lane, so lane budgets
                # and the autotuner account in CYCLES here
                n_cycles = max(1, steps // (self.spec_k + 1))
                self._last_dispatch_steps = n_cycles
                with tracing.region("pw.decode.chunk", steps=n_cycles,
                                    lanes=int(lanes.sum()), spec=1,
                                    passes=self.cfg.loops):
                    self.pool, toks_dev, emit_dev, counts_dev = \
                        self._spec_fn_for(n_cycles)(
                            self.params, self.pool, lanes)
                payload = (toks_dev, emit_dev)
                lane_steps = n_cycles
                self.stats["spec_dispatches"] += 1
                self.stats["spec_cycles"] += n_cycles
            else:
                self._last_dispatch_steps = steps
                key = jax.random.fold_in(self._key, self._ticks)
                with tracing.region("pw.decode.chunk", steps=steps,
                                    lanes=int(lanes.sum()), spec=0,
                                    passes=self.cfg.loops):
                    self.pool, toks_dev, counts_dev = self._chunk_fn_for(
                        steps)(self.params, self.pool, lanes, key)
                payload = toks_dev
                emit_dev = None
                lane_steps = steps
            try:
                # start the device->host token copy NOW: the block
                # lands while the next pipeline_depth chunks compute,
                # so the eventual read does not wait on the device
                toks_dev.copy_to_host_async()
                if emit_dev is not None:
                    emit_dev.copy_to_host_async()
                for dev in (counts_dev or {}).values():
                    dev.copy_to_host_async()
            except Exception:  # noqa: BLE001 - platform-optional
                pass
            self.stats["chunks"] += 1
            self.stats["slot_steps_total"] += self.n_slots * lane_steps
            # refresh the occupancy gauge on every 8th chunk (and the
            # first): the panel/scrape readers poll at human timescales,
            # and a per-chunk gauge write is measurable overhead on the
            # dispatch hot path
            if (self.stats["chunks"] & 7) == 1:
                probes.REGISTRY.gauge_set(
                    "serving_occupancy", self.occupancy(),
                    server=self._trace_tag,
                )
                probes.REGISTRY.gauge_set(
                    "lane_occupancy", float(len(self._pending_prefill)),
                    server=self._trace_tag, lane="prefill",
                )
                probes.REGISTRY.gauge_set(
                    "lane_occupancy", float(active.sum()),
                    server=self._trace_tag, lane="decode",
                )
                if self._tenants is not None:
                    for t, d in self.tenant_depths().items():
                        probes.REGISTRY.gauge_set(
                            "tenant_queue_depth", float(d),
                            server=self._trace_tag, tenant=t,
                        )
            # snapshot WHICH request each lane served: by the time
            # these tokens drain the slot may have been freed and
            # re-admitted to a different request
            inflight.append((payload, lanes, list(self.slots), counts_dev))
            useful0 = self.stats["steps"]
            for slot in np.nonzero(active)[0]:
                req = self.slots[slot]
                if req is None:
                    continue
                # occupancy numerator counts USEFUL slot-steps only:
                # a lane decoding past its budget while its tokens
                # drain is busy but wasted, exactly the idle-by-
                # another-name this metric exists to expose. Spec
                # cycles count conservatively as one step each (a
                # cycle emits AT LEAST one token), so eager refill
                # never frees a lane before its budget is truly
                # covered by dispatched work.
                self.stats["steps"] += min(
                    lane_steps, max(0, req.max_new - self._sent[slot])
                )
                self._sent[slot] += lane_steps
                if self.eager_refill and self._sent[slot] >= req.max_new:
                    # budget exhaustion is host-knowable at DISPATCH
                    # time: no further chunk can add to this lane's
                    # answer, so free the slot NOW — its tokens drain
                    # from the snapshots — instead of pipeline_depth
                    # chunks later. Device stream ordering makes the
                    # next occupant's prefill overwrite safe: it is
                    # enqueued after this chunk.
                    self.slots[slot] = None
                    active[slot] = False
                    self._release_slot_kv(slot)
                    with self.lock:
                        self.free.append(int(slot))
            self._loop_account("decode", self.stats["steps"] - useful0)
            return True

        def admit_direct(direct) -> None:
            """One-shot (non-chunked) admissions. With batch admission,
            same-bucket arrivals group into pow2-sized
            ``pool_admit_batch`` dispatches (slots are distinct by
            construction); otherwise one ``pool_admit`` each."""
            if self.batch_admit and len(direct) > 1:
                by_s: dict[int, list] = {}
                for slot, ids, mask, s in direct:
                    by_s.setdefault(s, []).append((slot, ids, mask))
                for s, grp in by_s.items():
                    o = 0
                    while o < len(grp):
                        m = 1 << ((len(grp) - o).bit_length() - 1)
                        part = grp[o:o + m]
                        o += m
                        if m == 1:
                            slot, ids, mask = part[0]
                            self.pool = self._admit_fn(s)(
                                self.params, ids, mask, self.pool,
                                np.int32(slot),
                            )
                        else:
                            ids = np.concatenate([p[1] for p in part], axis=0)
                            mask = np.concatenate([p[2] for p in part], axis=0)
                            slots = np.asarray([p[0] for p in part], np.int32)
                            self.pool = self._admit_batch_fn(m, s)(
                                self.params, ids, mask, self.pool, slots
                            )
                        self.stats["admit_dispatches"] += 1
                        for p in part:
                            active[p[0]] = True
            else:
                for slot, ids, mask, s in direct:
                    self.pool = self._admit_fn(s)(
                        self.params, ids, mask, self.pool, np.int32(slot)
                    )
                    self.stats["admit_dispatches"] += 1
                    active[slot] = True

        while not self._stop:
            # decode FIRST (PATHWAY_TPU_PREFILL_OVERLAP, default on): the
            # active lanes' next chunk is on the device before any
            # admission work runs, so newcomer tokenized-prompt prep and
            # prefill dispatches OVERLAP the in-flight decode instead of
            # delaying it. Newcomers join the next chunk — they waited one
            # chunk boundary either way; the chunk just starts earlier.
            dispatched = self.prefill_overlap and dispatch_decode()
            if self._degrade is not None:
                # one rate-limited watchdog read per tick; levels are
                # consumed below (clamp / spec gate / shed)
                self._degradation_level = self._degrade.maybe_evaluate()
            # adopt staged tier-2 promotions BEFORE admissions: a
            # request arriving right behind its promotion already
            # lands the tier-1 hit
            self._drain_promotions()
            admissions = []
            shed: list = []
            with self.lock:
                if self._deadline_s > 0.0 and self.queue:
                    # sweep requests whose deadline lapsed while queued:
                    # running them now wastes device time on an answer
                    # the caller already gave up on
                    now_d = time_mod.monotonic()
                    kept = []
                    for r in self.queue:
                        if r.deadline is not None and r.deadline <= now_d:
                            shed.append((r, "deadline"))
                        else:
                            kept.append(r)
                    if shed:
                        self.queue.clear()
                        self.queue.extend(kept)
                now_a = time_mod.monotonic()
                while self.queue and self.free:
                    if self._tenants is not None:
                        # weighted-fair pop (PATHWAY_TPU_TENANT_SCHED):
                        # the queue stays one FIFO deque; the scheduler
                        # only picks WHICH tenant's oldest entry admits
                        # next (None = every waiter is over its token
                        # budget — hold until a slot credits back)
                        entries = [
                            (r.tenant, r.max_new) for r in self.queue
                        ]
                        i = self._tenants.select(entries)
                        if i is None:
                            break
                        req = self.queue[i]
                        del self.queue[i]
                    else:
                        req = self.queue.popleft()
                    if (self._degradation_level >= 3
                            and req.priority <= 0):
                        shed.append((req, "degraded"))
                        continue
                    if (req.deadline is not None
                            and req.deadline <= now_a):
                        # admission-time enforcement: a deadline can
                        # lapse between the sweep above and the pop
                        shed.append((req, "deadline"))
                        continue
                    self._admit_seq += 1
                    req.seq = self._admit_seq
                    self._tenant_charge(req)
                    admissions.append((self.free.pop(), req))
            for req, reason in shed:
                self._shed_request(req, reason)
            direct = []
            direct_inserts = []
            for slot, req in admissions:
                # the slot record goes in FIRST: if the admit dispatch
                # raises, the failure sweep still finds (and fails) this
                # request instead of stranding its waiter
                self.slots[slot] = req
                self._sent[slot] = 0
                try:
                    if self._chaos_admit is not None:
                        # request-scoped fault: only this request's host
                        # bookkeeping is torn, so supervision rewinds the
                        # one slot instead of restarting the loop
                        self._chaos_admit.maybe_fail()
                    with tracing.region("pw.decode.admit", slot=int(slot),
                                        tokens=len(req.ids)):
                        self._admit_one(slot, req, direct, direct_inserts)
                except Exception as exc:  # noqa: BLE001 - isolation gate
                    if not self._supervised:
                        raise
                    self._isolate_admission_failure(slot, req, exc, active)
            admit_direct(direct)
            for slot, _ids_d, mask_d, _s_d in direct:
                self._loop_account("prefill", int(mask_d.sum()))
                req_d = self.slots[slot]
                if req_d is not None:
                    req_d.span.event("prefill", tokens=int(mask_d.sum()))
            for slot, (req_i, e_i, base_i, wrote_i) in direct_inserts:
                # after the admit dispatch: the slot's KV now holds the
                # prompt's blocks — publish the new ones into the arena
                self._prefix_insert(slot, req_i, e_i, base_i, wrote_i)
            pend = list(self._pending_prefill)
            if (self.disagg and active.any()
                    and len(pend) > self._prefill_budget):
                # disaggregated lanes (PATHWAY_TPU_DISAGG): the decode
                # lane owns the dispatch stream — at most
                # prefill_budget prompts advance one piece per tick
                # (round-robin, so every pending prompt progresses),
                # instead of EVERY pending prompt queueing a piece
                # ahead of the next decode chunk. With no active
                # decode lane there is nothing to protect and all
                # prompts advance, same as interleaved. Greedy tokens
                # are schedule-invariant, so the flag never changes a
                # stream — only its timing.
                start = self._prefill_rr % len(pend)
                pend = [
                    pend[(start + k) % len(pend)]
                    for k in range(self._prefill_budget)
                ]
                self._prefill_rr += self._prefill_budget
            for slot in pend:
                try:
                    self._prefill_piece(slot, active)
                except Exception as exc:  # noqa: BLE001 - isolation gate
                    req_p = self.slots[slot]
                    if not self._supervised or req_p is None:
                        raise
                    self._isolate_admission_failure(
                        slot, req_p, exc, active
                    )
            self._maybe_preempt(active)
            self._lane_counts["prefill"] = len(self._pending_prefill)
            self._lane_counts["decode"] = int(active.sum())
            if not dispatched:
                # legacy ordering (kill switch off) — or the pool was
                # empty at the top of the tick and admissions just
                # activated lanes: decode them without an idle hop
                dispatched = dispatch_decode()
            if dispatched:
                if len(inflight) <= self.pipeline_depth:
                    continue
            elif not inflight:
                if self._pending_prefill:
                    continue
                self._spec_flush()  # trailing drains past the last finish
                self.wake.clear()
                self.wake.wait(timeout=0.05)
                continue
            prev = inflight.popleft()
            with tracing.region("pw.decode.drain"):
                self._drain(prev, active)

    def _drain(self, prev, active) -> None:
        """Read one dispatched chunk's tokens (their copy to the host began
        at dispatch) and hand them to the requests its lanes served."""
        import time as time_mod

        import numpy as np

        payload, was_active, snap_slots, counts_dev = prev
        for name, account in (("moe_counts", self._moe_account),
                              ("loop_exits", self._exit_account)):
            if name in (counts_dev or {}):
                account(self._device_delta(name, counts_dev[name]))
        spec_rec = isinstance(payload, tuple)
        if spec_rec:
            # (n_cycles, n_slots, spec_k+1) proposed tokens and the
            # (n_cycles, n_slots) per-cycle accepted counts: a
            # lane's stream is each cycle's first n_emit tokens
            toks = np.asarray(payload[0])
            emit = np.asarray(payload[1])
            lanes = np.nonzero(was_active)[0]
            cyc, kk = toks.shape[0], toks.shape[2] - 1
            n_act = len(lanes)
            drafted = cyc * n_act * kk
            emitted = int(emit[:, lanes].sum()) if n_act else 0
            accepted = emitted - cyc * n_act
            # accumulate locally, flush to the registry at request
            # completions (and loop idle): one registry call per
            # request instead of six per spec drain
            acc = self._spec_accum
            for k, v in (
                ("dispatches", 1), ("verify_steps", cyc * n_act),
                ("draft_steps", drafted), ("drafted", drafted),
                ("accepted", accepted), ("emitted", emitted),
            ):
                acc[k] = acc.get(k, 0) + v
            self.stats["spec_verify_steps"] += cyc * n_act
            self.stats["spec_drafted"] += drafted
            self.stats["spec_accepted"] += accepted
            self.stats["spec_emitted"] += emitted
            if drafted:
                rate = accepted / drafted
                self._accept_ema = (
                    rate if self._accept_ema is None
                    else 0.7 * self._accept_ema + 0.3 * rate
                )
                self._spec_drains += 1
                # below ~1/(k+1) acceptance the drafts are noise:
                # latch back to plain chunks (identical tokens,
                # none of the draft cost)
                if (self._spec_drains >= 4
                        and self._accept_ema < 0.25):
                    self._spec_off = True
        else:
            toks = np.asarray(payload)
        for slot in np.nonzero(was_active)[0]:
            req = snap_slots[slot]
            if req is None or req.done.is_set():
                continue  # freed by an earlier chunk's tail
            if (self._deadline_s > 0.0 and req.deadline is not None
                    and req.deadline <= time_mod.monotonic()):
                # in-flight enforcement: an admitted-then-stalled
                # request can't burn its slot past its deadline —
                # free it NOW instead of decoding an answer the
                # caller already abandoned
                if self.slots[slot] is req:
                    self.slots[slot] = None
                    active[slot] = False
                    self._release_slot_kv(slot)
                    with self.lock:
                        self.free.append(int(slot))
                self._prefix_release(req)
                self._discard_parked(req)
                self._tenant_credit(req)
                self._shed_request(req, "deadline_inflight")
                continue
            if spec_rec:
                stream = [
                    int(t) for c in range(toks.shape[0])
                    for t in toks[c, slot, : emit[c, slot]]
                ]
                req.span.event(
                    "spec_cycles", cycles=int(cyc),
                    emitted=len(stream), accepted=len(stream) - int(cyc),
                )
            else:
                stream = toks[:, slot].tolist()
                req.span.event("decode_chunk", steps=len(stream))
            for t in stream:
                if self.eos_id is not None and t == self.eos_id:
                    req.max_new = 0  # stream closed
                    break
                if not req.tokens:
                    req.first_token_at = time_mod.perf_counter()
                    req.span.event("first_token")
                req.tokens.append(int(t))
                if len(req.tokens) >= req.max_new:
                    break
            if req.max_new == 0 or len(req.tokens) >= req.max_new:
                import time as time_mod

                req.text = self.tokenizer.decode(req.tokens)
                req.finished_at = time_mod.perf_counter()
                # eager refill may have freed (and even re-admitted)
                # this slot chunks ago — only release it if it still
                # belongs to the request we just completed
                if self.slots[slot] is req:
                    self.slots[slot] = None
                    active[slot] = False
                    self._release_slot_kv(slot)
                    with self.lock:
                        self.free.append(int(slot))
                self._prefix_release(req)
                self._tenant_credit(req)
                # flush + finish BEFORE done.set(): a waiter that
                # wakes on done must find the spec counters and the
                # span already recorded
                self._spec_flush()
                req.span.event("drain")
                req.span.event("done")
                req.span.finish(tokens=len(req.tokens))
                req.done.set()

    def _spec_flush(self):
        """Flush locally-accumulated spec counters to the registry.
        Called at request completions and loop idle; when the kill
        switch is off the flush discards (record_spec_many no-ops), so
        disabled-window counts never leak into an enabled scrape."""
        acc = self._spec_accum
        if acc:
            self._spec_accum = {}
            from pathway_tpu.engine.probes import record_spec_many

            record_spec_many(**acc)

    def _device_delta(self, name: str, totals):
        """What one of the pool's running counters (which wrap mod 2**32)
        gained since it was last drained: the difference between two drains
        is small."""
        import numpy as np

        totals = np.asarray(totals).astype("uint32")
        seen = self._device_seen.get(name, totals * 0)
        self._device_seen[name] = totals
        return (totals - seen).astype("int64")      # uint32: wraps right

    def _moe_account(self, delta) -> None:
        """``moe_assignments{held=0|1, phase=}`` from what the device's
        (phase, held | all) totals gained."""
        from pathway_tpu.engine import probes

        for row, phase in enumerate(("prefill", "decode")):
            held, every = int(delta[row, 0]), int(delta[row, 1])
            if every:
                probes.REGISTRY.counter_add(
                    "moe_assignments", held, held=1, phase=phase)
                probes.REGISTRY.counter_add(
                    "moe_assignments", every - held, held=0, phase=phase)

    def _exit_account(self, delta) -> None:
        """``loop_exit_step{step=1..loops}`` from what the device's totals
        of sampled tokens, by the pass the exit rule took their logits
        from, gained."""
        from pathway_tpu.engine import probes

        probes.record_loop_exits(
            {u + 1: int(n) for u, n in enumerate(delta) if n})

    def shutdown(self, timeout: float = 10.0):
        self._stop = True
        self.wake.set()
        t = self.thread
        if t is not None and t.is_alive():
            # join so interpreter teardown never kills the thread mid
            # device call (jax runtime aborts on threads dying inside it)
            t.join(timeout=timeout)
            if t.is_alive():
                # a leaked serving thread is a wedged device call or a
                # stuck lock — record it loudly instead of exiting as if
                # the shutdown were clean
                from pathway_tpu.internals.errors import get_global_error_log

                with self.lock:
                    self.stats["leaked_thread"] += 1
                get_global_error_log().log(
                    f"serving loop thread {t.name!r} still alive "
                    f"{timeout}s after shutdown join"
                )
        # getattr: shutdown must also work on a partially-constructed
        # server (init failure cleanup, bare-object harness tests)
        promote = getattr(self, "_promote_worker", None)
        if promote is not None:
            promote.close()
        # the loop thread is down: every span it will ever write has been
        # written, so drain the flight recorder's buffered JSONL lines
        from pathway_tpu.engine import tracing

        tracing.flush_traces()


@pw.udf
def prompt_chat_single_qa(question: str) -> Json:
    """Wrap a plain question string into a one-message chat (reference
    ``prompt_chat_single_qa``, llms.py:686)."""
    return Json([{"role": "user", "content": question}])
