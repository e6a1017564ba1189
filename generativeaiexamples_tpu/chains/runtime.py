"""Shared chain runtime: the typed equivalent of the reference's factory
module (reference: common/utils.py:147-331) without LangChain/LlamaIndex.

Provides lru-cached singletons for the embedder, LLM backend, vector
stores (one per collection, like the reference's per-deployment
collections), the text splitter, and the retrieval helper with the
1500-token context cap (common/utils.py:97-122 LimitRetrievedNodesLength).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Generator, List, Optional, Sequence, Tuple

from generativeaiexamples_tpu.config import AppConfig, get_config
from generativeaiexamples_tpu.retrieval.store import Chunk, SearchHit, VectorStore, create_vector_store
from generativeaiexamples_tpu.retrieval.splitter import get_text_splitter
from generativeaiexamples_tpu.utils import faults as faults_mod
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils import resilience
from generativeaiexamples_tpu.utils import slo as slo_mod
from generativeaiexamples_tpu.utils.tracing import get_tracer

logger = get_logger(__name__)

_REG = metrics_mod.get_registry()
_M_RETRIEVE = _REG.histogram(
    "genai_chain_retrieve_seconds",
    "End-to-end retrieval pipeline latency (embed + search + fuse + rerank).",
    ("pipeline",),
)
_M_INGEST = _REG.histogram(
    "genai_chain_ingest_seconds",
    "Document ingestion latency (load + split + embed + index).",
)
_M_INGESTED_CHUNKS = _REG.counter(
    "genai_chain_ingested_chunks_total",
    "Chunks indexed through the single write path (index_chunks).",
)
_M_DEGRADED = _REG.counter(
    "genai_chain_degraded_answers_total",
    "RAG requests answered LLM-only because retrieval failed or its "
    "breaker was open, by chain.",
    ("chain",),
)


@dataclasses.dataclass
class DegradedWarning:
    """Structured degradation marker a chain yields BEFORE its fallback
    answer; the server forwards it as a warnings-only SSE frame instead
    of answer text."""

    reason: str
    detail: str = ""

    def __str__(self) -> str:
        return f"{self.reason}: {self.detail}" if self.detail else self.reason


def resilience_enabled(config: Optional[AppConfig] = None) -> bool:
    """Whether chains should degrade gracefully (resilience.enable)."""
    config = config or get_config()
    return resilience.resilience_enabled(config)


def degraded_answer(
    chain: str,
    llm_chain_fn,
    query: str,
    chat_history,
    exc: BaseException,
    **kwargs,
) -> Generator:
    """LLM-only fallback for a RAG chain whose retrieval leg failed:
    yields a DegradedWarning first (structured SSE warning), then the
    plain llm_chain stream — a degraded answer instead of a 500."""
    _M_DEGRADED.labels(chain=chain).inc()
    slo_mod.observe_event("degraded")
    flight_recorder.event(
        "degraded", chain=chain, error=type(exc).__name__
    )
    logger.warning(
        "%s: retrieval unavailable (%s); degrading to LLM-only answer",
        chain, exc,
    )

    def gen():
        yield DegradedWarning(
            reason="retrieval_degraded",
            detail=f"{type(exc).__name__}: {exc}; answering without retrieved context",
        )
        for chunk in llm_chain_fn(query=query, chat_history=chat_history, **kwargs):
            yield chunk

    return gen()

_STORES: Dict[str, VectorStore] = {}
_BM25: Dict[str, object] = {}


# Tokenization caches (per-chain tokenized preamble + encode LRU) live
# with the tokenizer (engine/tokenizer.py) so the engine layer never
# depends on chains; re-exported here as the chain-facing API.
from generativeaiexamples_tpu.engine.tokenizer import (  # noqa: E402
    chat_preamble_ids,
    clear_tokenization_caches,
    encode_cached,
    render_chat_cached,
)


def get_embedder(config: Optional[AppConfig] = None):
    from generativeaiexamples_tpu.engine.embedder import create_embedder

    return create_embedder(config or get_config())


def get_llm(config: Optional[AppConfig] = None, **overrides):
    from generativeaiexamples_tpu.engine.llm_backend import create_llm

    return create_llm(config or get_config(), **overrides)


def get_vector_store(collection: str = "default", config: Optional[AppConfig] = None) -> VectorStore:
    """One store per collection name (reference: vector_db / conv_store)."""
    config = config or get_config()
    if collection not in _STORES:
        ret = config.retriever
        _STORES[collection] = create_vector_store(
            config.vector_store.name,
            dimensions=get_embedder(config).dimensions,
            persist_dir=config.vector_store.persist_dir,
            url=config.vector_store.url,
            collection=collection,
            # ANN engine knobs (in-process TPU store only; the factory
            # drops them for client/server backends)
            ann_mode=(getattr(ret, "ann_mode", "exact") or "exact"),
            ann_capacity=int(getattr(ret, "ann_capacity", 0)),
            ann_max_batch=int(getattr(ret, "ann_max_batch", 8)),
            nlist=config.vector_store.nlist,
            nprobe=config.vector_store.nprobe,
        )
    return _STORES[collection]


def get_bm25_index(collection: str = "default", config: Optional[AppConfig] = None):
    """Per-collection lexical sidecar for the hybrid pipelines
    (reference names them at configuration.py:151-160 with an
    Elasticsearch BM25 leg, docker-compose-vectordb.yaml:100-118)."""
    from generativeaiexamples_tpu.retrieval.bm25 import BM25Index

    config = config or get_config()
    if collection not in _BM25:
        _BM25[collection] = BM25Index(
            persist_dir=config.vector_store.persist_dir, collection=collection
        )
    return _BM25[collection]


def _lexical_enabled(config: AppConfig) -> bool:
    return config.retriever.nr_pipeline in ("hybrid", "ranked_hybrid")


def index_chunks(chunks: Sequence[Chunk], collection: str = "default",
                 config: Optional[AppConfig] = None) -> None:
    """Embed + insert into the vector store, and mirror into the BM25
    sidecar when a hybrid pipeline is configured — the single write
    path chains (and ingest_file) use so the lexical leg never goes
    stale."""
    config = config or get_config()
    tracer = get_tracer()
    with tracer.span("embedder.embed_documents", {"count": len(chunks)}):
        embeddings = get_embedder(config).embed_documents([c.text for c in chunks])
    with tracer.span("vectorstore.add", {"count": len(chunks)}):
        get_vector_store(collection, config).add(chunks, embeddings)
    if _lexical_enabled(config):
        with tracer.span("bm25.add", {"count": len(chunks)}):
            get_bm25_index(collection, config).add(chunks)
    _M_INGESTED_CHUNKS.inc(len(chunks))


def delete_documents(filenames: Sequence[str], collection: str = "default",
                     config: Optional[AppConfig] = None) -> bool:
    """Drop documents from the vector store AND the lexical sidecar —
    deleting from only one would resurface deleted content through the
    other leg's hits. The sidecar delete runs UNCONDITIONALLY (not just
    on hybrid pipelines): a persisted index written under an earlier
    hybrid config must not keep deleted chunks for when the pipeline
    switches back."""
    config = config or get_config()
    ok = get_vector_store(collection, config).delete_sources(filenames)
    get_bm25_index(collection, config).delete_sources(filenames)
    return ok


def reset_runtime() -> None:
    """Testing hook: drop cached stores/backends."""
    from generativeaiexamples_tpu.engine import retrieval_tier as _tier

    # The tier worker holds references into the store/embedder caches —
    # stop it first so no wave dispatches against a half-reset runtime.
    _tier.close_tier()
    _STORES.clear()
    _BM25.clear()
    clear_tokenization_caches()
    resilience.reset_breakers()
    from generativeaiexamples_tpu.engine import embedder as _emb
    from generativeaiexamples_tpu.engine import llm_backend as _llm
    from generativeaiexamples_tpu.engine import reranker as _rr

    # Stop micro-batcher dispatch threads and drop query LRUs before
    # dropping the backend caches — a dangling thread would keep batching
    # against a config the next test already replaced.
    for cache in (_emb._EMBEDDER_CACHE, _rr._RERANKER_CACHE):
        for backend in cache.values():
            close = getattr(backend, "close", None)
            if callable(close):
                close()
            clear = getattr(backend, "clear_query_cache", None)
            if callable(clear):
                clear()
    _emb._EMBEDDER_CACHE.clear()
    _llm._LLM_CACHE.clear()
    _rr._RERANKER_CACHE.clear()
    get_config.cache_clear()


def get_splitter(config: Optional[AppConfig] = None):
    config = config or get_config()
    return get_text_splitter(
        config.text_splitter.chunk_size, config.text_splitter.chunk_overlap
    )


def ingest_file(filepath: str, filename: str, collection: str = "default",
                config: Optional[AppConfig] = None) -> int:
    """Load → split → embed → insert. Returns the number of chunks."""
    from generativeaiexamples_tpu.retrieval.loaders import load_document

    config = config or get_config()
    tracer = get_tracer()
    t0 = time.time()
    with tracer.span("chain.ingest", {"filename": filename, "collection": collection}) as span:
        with tracer.span("loader.load"):
            text = load_document(filepath)
        if not text.strip():
            raise ValueError(f"No text extracted from {filename}")
        chunks = [
            Chunk(text=piece, source=filename)
            for piece in get_splitter(config).split_text(text)
        ]
        span.set_attribute("chunks", len(chunks))
        index_chunks(chunks, collection, config)
    _M_INGEST.observe(time.time() - t0)
    logger.info("Ingested %s: %d chunks into %s", filename, len(chunks), collection)
    return len(chunks)


def resolve_pipeline(config: AppConfig, top_k: int):
    """Resolve the retrieval pipeline plan: ``(pipeline name, lexical
    leg enabled, reranker or None, fetch_k)``. Shared by the
    synchronous path and the retrieval tier so the two can never drift
    on semantics. Pipeline names (reference: configuration.py:151-160):
    "hybrid" = dense + BM25 lexical legs fused by reciprocal rank;
    "ranked_hybrid" = the same fusion feeding the cross-encoder
    reranker; anything else = dense only."""
    pipeline = config.retriever.nr_pipeline
    lexical = _lexical_enabled(config)
    reranker = None
    fetch_k = top_k
    if pipeline == "ranked_hybrid":
        from generativeaiexamples_tpu.engine.reranker import create_reranker

        reranker = create_reranker(config)
    if reranker is not None or lexical:
        fetch_k = top_k * max(1, config.ranking.fetch_factor)
    return pipeline, lexical, reranker, fetch_k


def finish_hits(query: str, hits: List[SearchHit], fetch_k: int, top_k: int,
                lexical: bool, reranker, collection: str,
                config: AppConfig) -> List[SearchHit]:
    """The fuse/rerank tail shared by both retrieval paths: BM25 RRF
    fusion when a hybrid pipeline enables the lexical leg, then the
    cross-encoder rerank (or plain trim) down to ``top_k``."""
    tracer = get_tracer()
    if lexical:
        from generativeaiexamples_tpu.retrieval.bm25 import rrf_fuse

        index = get_bm25_index(collection, config)
        if index.count():
            with tracer.span("bm25.search"):
                lex_hits = index.search(query, fetch_k)
            if lex_hits:
                hits = rrf_fuse([hits, lex_hits])[:fetch_k]
    if reranker is not None and len(hits) > 1:
        from generativeaiexamples_tpu.engine.reranker import rerank_hits

        with tracer.span("reranker.rerank", {"candidates": len(hits)}):
            hits = rerank_hits(reranker, query, hits, top_k)
    else:
        hits = hits[:top_k]
    return hits


def retrieve(
    query: str,
    top_k: Optional[int] = None,
    score_threshold: Optional[float] = None,
    collection: str = "default",
    config: Optional[AppConfig] = None,
) -> List[SearchHit]:
    config = config or get_config()
    top_k = top_k if top_k is not None else config.retriever.top_k
    threshold = (
        score_threshold if score_threshold is not None else config.retriever.score_threshold
    )
    # Resilience seams: the deterministic fault site for "retrieval is
    # down" drills, and the per-request deadline check — a request whose
    # budget is gone must not start an embed+search+rerank pipeline.
    faults_mod.fault_point("retrieval.search")
    resilience.raise_if_deadline_expired("retrieval")
    tracer = get_tracer()
    t0 = time.time()
    pipeline = config.retriever.nr_pipeline
    if (getattr(config.retriever, "backend", "off") or "off").lower() == "tier":
        # Tier path (docs/retrieval_tier.md): the query joins a batched
        # embed→search→rerank wave co-scheduled against generation; the
        # answer is bit-identical to the synchronous pipeline below and
        # charged to the SAME metric/flight families.
        from generativeaiexamples_tpu.engine import retrieval_tier

        with tracer.span(
            "retriever.retrieve_tier", {"top_k": top_k, "collection": collection}
        ) as span:
            hits = retrieval_tier.get_tier(config).retrieve(
                query, top_k, threshold, collection
            )
            span.set_attribute("hits", len(hits))
        _M_RETRIEVE.labels(pipeline=pipeline or "dense").observe(time.time() - t0)
        flight_recorder.event(
            "retrieve", pipeline=pipeline or "dense", hits=len(hits),
            duration_s=round(time.time() - t0, 6),
        )
        return hits
    with tracer.span("retriever.retrieve", {"top_k": top_k, "collection": collection}) as span:
        pipeline, lexical, reranker, fetch_k = resolve_pipeline(config, top_k)
        with tracer.span("embedder.embed_query"):
            q_emb = get_embedder(config).embed_query(query)
        with tracer.span("vectorstore.search"):
            hits = get_vector_store(collection, config).search(q_emb, fetch_k, threshold)
        hits = finish_hits(
            query, hits, fetch_k, top_k, lexical, reranker, collection, config
        )
        span.set_attribute("hits", len(hits))
    _M_RETRIEVE.labels(pipeline=pipeline or "dense").observe(time.time() - t0)
    flight_recorder.event(
        "retrieve", pipeline=pipeline or "dense", hits=len(hits),
        duration_s=round(time.time() - t0, 6),
    )
    return hits


def cap_context(texts: Sequence[str], token_cap: Optional[int] = None,
                config: Optional[AppConfig] = None) -> str:
    """Concatenate retrieved texts under the hard token budget
    (reference: LimitRetrievedNodesLength, common/utils.py:97-122)."""
    config = config or get_config()
    cap = token_cap if token_cap is not None else config.retriever.context_token_cap
    out: List[str] = []
    used = 0
    for text in texts:
        tokens = text.split()
        if used + len(tokens) > cap:
            remaining = cap - used
            if remaining > 0:
                out.append(" ".join(tokens[:remaining]))
            break
        out.append(text)
        used += len(tokens)
    return "\n\n".join(out)


def history_to_messages(chat_history) -> List[Tuple[str, str]]:
    """Normalize server Message objects / dicts / tuples to (role, content)."""
    out: List[Tuple[str, str]] = []
    for m in chat_history or []:
        if isinstance(m, tuple):
            out.append((m[0], m[1]))
        elif isinstance(m, dict):
            out.append((m.get("role", "user"), m.get("content", "")))
        else:
            out.append((getattr(m, "role", "user"), getattr(m, "content", "")))
    return out


def llm_settings(kwargs: dict) -> dict:
    """Extract generation settings the chains forward to the backend
    (temperature/top_p/max_tokens/stop — server.py:270-274), and
    ``ignore_eos`` where a request set it (absent otherwise: a backend
    that never heard of it is called as before)."""
    out = {}
    for key in ("temperature", "top_p", "max_tokens", "stop"):
        if key in kwargs and kwargs[key] is not None:
            out[key] = kwargs[key]
    if kwargs.get("ignore_eos"):
        out["ignore_eos"] = True
    return out
