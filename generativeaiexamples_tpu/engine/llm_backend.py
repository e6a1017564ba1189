"""LLM backend seam: the chains' view of "an LLM".

Mirrors the reference's ``get_llm`` factory (reference:
common/utils.py:265-288, which returns a ChatNVIDIA pointed either at a
local NIM URL or the hosted catalog). Backends:

- ``TPULLMBackend`` — the in-process engine singleton (no HTTP hop);
- ``RemoteLLMBackend`` — any OpenAI-compatible ``/v1/chat/completions``
  endpoint (e.g. our facade in another pod), preserving the
  APP_LLM_SERVERURL env semantics;
- ``EchoLLMBackend`` — deterministic test backend (the injection seam the
  reference lacks, SURVEY §4).
"""
from __future__ import annotations

import json
from typing import Generator, Iterable, List, Optional, Sequence, Tuple

from generativeaiexamples_tpu.utils import faults as faults_mod
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import resilience

logger = get_logger(__name__)

Messages = Sequence[Tuple[str, str]]  # (role, content)


class LLMBackend:
    def stream_chat(
        self,
        messages: Messages,
        temperature: float = 0.2,
        top_p: float = 0.7,
        max_tokens: int = 1024,
        stop: Sequence[str] = (),
        prefix_hint: Optional[str] = None,
        spec_decode: Optional[bool] = None,
        ignore_eos: bool = False,
    ) -> Generator[str, None, None]:
        """``prefix_hint`` names the chain/session this request belongs
        to, feeding the engine's prefix KV cache (advisory — backends
        without one ignore it). ``spec_decode`` is the per-request
        speculative-decoding override (None follows the engine config,
        False opts out); like prefix_hint it is engine-local scheduling
        advice that non-engine backends ignore. ``ignore_eos`` makes a
        stop id an ordinary token: the answer ends at ``max_tokens``."""
        raise NotImplementedError

    def complete(self, messages: Messages, **kwargs) -> str:
        return "".join(self.stream_chat(messages, **kwargs))


class TPULLMBackend(LLMBackend):
    def __init__(self, engine=None):
        from generativeaiexamples_tpu.engine.llm_engine import get_engine

        self._engine = engine or get_engine()

    def stream_chat(self, messages, temperature=0.2, top_p=0.7, max_tokens=1024,
                    stop=(), prefix_hint=None, spec_decode=None, ignore_eos=False):
        from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
        from generativeaiexamples_tpu.engine.tokenizer import render_chat_cached

        faults_mod.fault_point("backend.stream")
        params = SamplingParams(
            temperature=temperature,
            top_p=top_p,
            max_tokens=max_tokens,
            stop=tuple(stop or ()),
            prefix_hint=prefix_hint,
            spec_decode=spec_decode,
            ignore_eos=bool(ignore_eos),
        )
        # Per-request deadline (bound to this thread by the server):
        # the remaining budget becomes the engine stream timeout, so a
        # deadlined request can never park on the token queue past its
        # budget. stream_text submits EAGERLY, so the engine's
        # admission-queue cap (EngineOverloaded) raises here — where
        # the server can still shed with a clean 429.
        deadline = resilience.get_current_deadline()
        timeout = None
        if deadline is not None:
            resilience.raise_if_deadline_expired("backend.stream")
            timeout = max(0.05, deadline.remaining())
        # Cached chat rendering: the static system preamble is tokenized
        # once per chain, not once per request — ids are identical to
        # tokenizer.render_chat.
        ids = render_chat_cached(self._engine.tokenizer, list(messages))
        return self._engine.stream_text(ids, params, timeout=timeout)


class RemoteLLMBackend(LLMBackend):
    """OpenAI-compatible streaming chat client over requests."""

    def __init__(self, server_url: str, model_name: str, timeout: float = 600.0):
        from generativeaiexamples_tpu.utils import normalize_v1_url

        self._url = normalize_v1_url(server_url)
        self._model = model_name
        self._timeout = timeout

    def stream_chat(self, messages, temperature=0.2, top_p=0.7, max_tokens=1024,
                    stop=(), prefix_hint=None, spec_decode=None, ignore_eos=False):
        # prefix_hint/spec_decode are engine-local scheduling advice; the
        # OpenAI wire format has no field for them, so the remote
        # backend drops both.
        import requests

        faults_mod.fault_point("backend.stream")
        payload = {
            "model": self._model,
            "messages": [{"role": r, "content": c} for r, c in messages],
            "temperature": temperature,
            "top_p": top_p,
            "max_tokens": max_tokens,
            "stream": True,
        }
        if stop:
            payload["stop"] = list(stop)
        if ignore_eos:
            payload["ignore_eos"] = True
        deadline = resilience.get_current_deadline()
        timeout = self._timeout
        if deadline is not None:
            timeout = max(0.05, min(timeout, deadline.remaining()))

        def _connect():
            r = requests.post(
                f"{self._url}/chat/completions", json=payload, stream=True,
                timeout=timeout,
            )
            r.raise_for_status()
            return r

        # Retry + breaker cover the CONNECT/handshake only; once bytes
        # stream, a blind replay could re-emit answer text.
        resp = resilience.call_with_resilience(
            "llm_remote", _connect, retry_on=(requests.RequestException,),
            retry_filter=resilience.http_error_is_transient,
        )

        def gen():
            for line in resp.iter_lines(decode_unicode=True):
                if not line or not line.startswith("data: "):
                    continue
                body = line[len("data: "):]
                if body.strip() == "[DONE]":
                    break
                chunk = json.loads(body)
                delta = chunk["choices"][0].get("delta", {}).get("content", "")
                if delta:
                    yield delta

        return gen()


class EchoLLMBackend(LLMBackend):
    """Streams the last user message back word-by-word (tests)."""

    def stream_chat(self, messages, temperature=0.2, top_p=0.7, max_tokens=1024,
                    stop=(), prefix_hint=None, spec_decode=None, ignore_eos=False):
        last_user = next((c for r, c in reversed(list(messages)) if r == "user"), "")

        def gen():
            for word in last_user.split(" ")[:max_tokens]:
                yield word + " "

        return gen()


def resolve_backend(base_url=None, model: str = "local", backend=None) -> LLMBackend:
    """Adapter-facing dispatch: an explicit backend wins, a URL selects
    the OpenAI-compatible client, otherwise the in-process engine — the
    same two paths get_llm chooses between in the reference
    (common/utils.py:265-288). Shared by integrations/ so backend
    construction (auth, timeouts) changes in one place."""
    if backend is not None:
        return backend
    if base_url:
        return RemoteLLMBackend(base_url, model)
    return TPULLMBackend()


_LLM_CACHE: dict = {}


def create_llm(config=None, **overrides) -> LLMBackend:
    """Factory mirroring get_llm (common/utils.py:265-288)."""
    from generativeaiexamples_tpu.config import get_config

    config = config or get_config()
    engine_kind = (overrides.get("model_engine") or config.llm.model_engine or "tpu").lower()
    server_url = overrides.get("server_url", config.llm.server_url)
    model_name = overrides.get("model_name", config.llm.model_name)
    key = (engine_kind, server_url, model_name)
    if key in _LLM_CACHE:
        return _LLM_CACHE[key]
    if engine_kind == "echo":
        backend: LLMBackend = EchoLLMBackend()
    elif server_url and engine_kind in ("openai", "nvidia-ai-endpoints", "remote"):
        backend = RemoteLLMBackend(server_url, model_name)
    elif engine_kind in ("tpu", "local"):
        backend = TPULLMBackend()
    else:
        raise ValueError(f"Unknown llm model_engine {engine_kind!r}")
    _LLM_CACHE[key] = backend
    return backend
