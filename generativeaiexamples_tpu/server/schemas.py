"""Chain-server wire schemas.

Byte-compatible with the reference's pydantic models (reference:
RetrievalAugmentedGeneration/common/server.py:60-141): same field names,
defaults, bounds, bleach sanitization, and JSON shapes — re-declared in
pydantic v2.
"""
from __future__ import annotations

from typing import List, Optional

import bleach
from pydantic import BaseModel, Field, StrictBool, field_validator

MAX_CONTENT_LEN = 131072


class Message(BaseModel):
    """A chat message (reference: server.py:60-77)."""

    role: str = Field(default="user", max_length=256)
    content: str = Field(
        default="I am going to Paris, what should I see?", max_length=MAX_CONTENT_LEN
    )

    @field_validator("role")
    @classmethod
    def validate_role(cls, value: str) -> str:
        value = bleach.clean(value, strip=True)
        if value.lower() not in {"user", "assistant", "system"}:
            raise ValueError("Role must be one of 'user', 'assistant', or 'system'")
        return value.lower()

    @field_validator("content")
    @classmethod
    def sanitize_content(cls, v: str) -> str:
        return bleach.clean(v, strip=True)


class Prompt(BaseModel):
    """The /generate request body (reference: server.py:79-108)."""

    messages: List[Message] = Field(..., max_length=50000)
    use_knowledge_base: bool = Field(...)
    temperature: float = Field(0.2, ge=0.1, le=1.0)
    top_p: float = Field(0.7, ge=0.1, le=1.0)
    # The reference caps an answer at 1024 tokens (server.py:85); a reasoning
    # model answers in thousands. The engine still ends a request at its slot's
    # capacity (max_seq_len - prompt), whatever is asked here.
    max_tokens: int = Field(1024, ge=0, le=32768)
    stop: List[str] = Field(default=[], max_length=256)
    # Additive (non-reference): a stop id is an ordinary token and the
    # answer ends at max_tokens (or its slot's capacity). For byte-level
    # models, where a sampled answer would otherwise end wherever chance
    # draws one of the two stop ids. A boolean and nothing that looks like one.
    ignore_eos: StrictBool = Field(default=False)
    # Additive (non-reference): per-request deadline budget override in
    # milliseconds; the X-Request-Deadline-Ms header wins over this, the
    # resilience.request_deadline_ms config default applies when absent.
    # 0 explicitly disables the deadline (same contract as the header
    # and the config knob).
    deadline_ms: Optional[int] = Field(default=None, ge=0, le=86_400_000)


class ChainResponseChoices(BaseModel):
    """One streamed choice (reference: server.py:110-114)."""

    index: int = Field(default=0, ge=0, le=256)
    message: Message = Field(default=Message(role="assistant", content=""))
    finish_reason: str = Field(default="", max_length=4096)


class ChainResponse(BaseModel):
    """One SSE chunk body (reference: server.py:115-118)."""

    id: str = Field(default="", max_length=100000)
    choices: List[ChainResponseChoices] = Field(default=[], max_length=256)
    # Additive (non-reference): structured resilience warnings, e.g.
    # "retrieval_degraded: ..." when a RAG chain fell back to an
    # LLM-only answer. Serialized only when present (frames keep the
    # reference's exact byte shape otherwise).
    warnings: Optional[List[str]] = Field(default=None, max_length=16)


class DocumentSearch(BaseModel):
    """The /search request body (reference: server.py:120-124)."""

    query: str = Field(default="", max_length=MAX_CONTENT_LEN)
    top_k: int = Field(default=4, ge=0, le=25)


class DocumentChunk(BaseModel):
    """A retrieved chunk (reference: server.py:126-130)."""

    content: str = Field(default="", max_length=MAX_CONTENT_LEN)
    filename: str = Field(default="", max_length=4096)
    score: float = Field(...)


class DocumentSearchResponse(BaseModel):
    """The /search response (reference: server.py:132-134)."""

    chunks: List[DocumentChunk] = Field(..., max_length=256)


class DocumentsResponse(BaseModel):
    """GET /documents response (reference: server.py:136-138)."""

    documents: List[str] = Field(default=[], max_length=1000000)


class HealthResponse(BaseModel):
    """GET /health response (reference: server.py:140-141)."""

    message: str = Field(default="", max_length=4096)
