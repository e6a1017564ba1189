"""Application configuration schema.

Parity with the reference schema (reference: RetrievalAugmentedGeneration/
common/configuration.py:20-258) — same sections, field names, env names and
defaults — plus a TPU-specific ``engine`` section configuring the in-repo
JAX/XLA inference plane that replaces the reference's NIM/TRT-LLM
microservices (docker-compose-nim-ms.yaml).
"""
from __future__ import annotations

from generativeaiexamples_tpu.config.wizard import ConfigWizard, configclass, configfield


@configclass
class VectorStoreConfig(ConfigWizard):
    """Vector store connection (reference: configuration.py:21-47)."""

    name: str = configfield(
        "name",
        default="tpu",  # supports: tpu (in-process TPU matmul index), milvus, pgvector, faiss
        help_txt="The name of vector store",
    )
    # genai-lint: disable=config-knob-drift -- free-form host string (milvus URLs carry a scheme, pgvector host:port does not); the store connector owns the parse
    url: str = configfield(
        "url",
        default="",  # e.g. http://milvus:19530 / pgvector:5432; unused for in-process stores
        help_txt="The host of the machine running Vector Store DB",
    )
    nlist: int = configfield(
        "nlist",
        default=64,  # IVF cluster count
        help_txt="Number of cluster units",
    )
    nprobe: int = configfield(
        "nprobe",
        default=16,  # IVF probe count
        help_txt="Number of units to query",
    )
    persist_dir: str = configfield(
        "persist_dir",
        default="/tmp-data/vectorstore",
        help_txt="Directory where in-process vector stores persist their state",
    )


@configclass
class LLMConfig(ConfigWizard):
    """LLM backend (reference: configuration.py:51-77)."""

    server_url: str = configfield(
        "server_url",
        default="",
        help_txt="The location of the server hosting the LLM; empty means in-process TPU engine.",
    )
    model_name: str = configfield(
        "model_name",
        default="meta-llama/Meta-Llama-3-8B-Instruct",
        help_txt="The name of the hosted model.",
    )
    model_engine: str = configfield(
        "model_engine",
        default="tpu",
        help_txt="LLM backend kind. Allowed values: tpu (in-process JAX engine), "
        "openai (any OpenAI-compatible HTTP endpoint, incl. our /v1 facade), echo (testing).",
    )
    model_name_pandas_ai: str = configfield(
        "model_name_pandas_ai",
        default="meta-llama/Meta-Llama-3-8B-Instruct",
        help_txt="The model used by the structured-data (CSV) agent.",
    )


@configclass
class TextSplitterConfig(ConfigWizard):
    """Text splitter (reference: configuration.py:80-101)."""

    model_name: str = configfield(
        "model_name",
        default="Snowflake/snowflake-arctic-embed-l",
        help_txt="Tokenizer model used for token-based text splitting.",
    )
    chunk_size: int = configfield(
        "chunk_size",
        default=510,
        help_txt="Chunk size (tokens) for text splitting.",
    )
    chunk_overlap: int = configfield(
        "chunk_overlap",
        default=200,
        help_txt="Overlapping token count between adjacent chunks.",
    )


@configclass
class EmbeddingConfig(ConfigWizard):
    """Embedding model (reference: configuration.py:105-130)."""

    model_name: str = configfield(
        "model_name",
        default="snowflake/arctic-embed-l",
        help_txt="The name of the embedding model.",
    )
    model_engine: str = configfield(
        "model_engine",
        default="tpu",
        help_txt="Embedder backend kind. Allowed values: tpu (in-process JAX encoder), "
        "openai (OpenAI-compatible /v1/embeddings endpoint), hash (testing).",
    )
    dimensions: int = configfield(
        "dimensions",
        default=1024,
        help_txt="Embedding dimensionality; used for vector-DB index creation.",
    )
    server_url: str = configfield(
        "server_url",
        default="",
        help_txt="URL of a remote embedding server; empty means in-process TPU engine.",
    )
    # genai-lint: disable=config-knob-drift -- free-form path; empty (random-init) is legal and existence is only checkable where the weights load
    checkpoint_path: str = configfield(
        "checkpoint_path",
        default="",
        help_txt="Path to embedder weights (safetensors dir); empty means "
        "deterministic random-init (testing/benching).",
    )
    query_cache_size: int = configfield(
        "query_cache_size",
        default=256,
        help_txt="LRU entries for embed_query results keyed on "
        "query_prefix + text (repeated questions — eval harness loops, "
        "multi-turn follow-ups — skip the device dispatch entirely). "
        "0 disables the cache.",
    )


@configclass
class RetrieverConfig(ConfigWizard):
    """Retrieval pipeline (reference: configuration.py:134-160)."""

    top_k: int = configfield(
        "top_k",
        default=4,
        help_txt="Number of relevant results to retrieve",
    )
    score_threshold: float = configfield(
        "score_threshold",
        default=0.25,
        help_txt="The minimum confidence score for the retrieved values to be considered",
    )
    nr_url: str = configfield(
        "nr_url",
        default="http://retrieval-ms:8000",
        help_txt="Optional external retriever microservice url",
    )
    nr_pipeline: str = configfield(
        "nr_pipeline",
        default="ranked_hybrid",
        help_txt="Retriever pipeline variant: ranked_hybrid or hybrid",
    )
    context_token_cap: int = configfield(
        "context_token_cap",
        default=1500,
        help_txt="Hard cap on retrieved-context tokens fed to the LLM "
        "(reference: common/utils.py:97-122).",
    )
    backend: str = configfield(
        "backend",
        default="off",  # off = synchronous per-request pipeline
        help_txt="Retrieval execution path: off (synchronous per-request "
        "embed+search+rerank) or tier (batched waves co-scheduled "
        "against generation on the scheduler seam; docs/retrieval_tier.md).",
    )
    tier_queue_depth: int = configfield(
        "tier_queue_depth",
        default=16,  # bounded submit queue (backpressure past this)
        help_txt="Retrieval-tier transfer queue capacity; submitters "
        "stall (counted) when the worker falls behind. 0 auto-sizes.",
    )
    tier_window_ms: int = configfield(
        "tier_window_ms",
        default=20,
        help_txt="Upper bound on how long a retrieval-tier wave yields "
        "to the scheduler policy's retrieval window before dispatching "
        "anyway. 0 dispatches immediately (no co-scheduling yield).",
    )
    ann_mode: str = configfield(
        "ann_mode",
        default="exact",
        help_txt="TPU ANN search mode: exact (full-corpus matmul top-k, "
        "bit-parity pinned) or ivf (centroid-probed approximate search "
        "using vector_store.nlist/nprobe).",
    )
    ann_capacity: int = configfield(
        "ann_capacity",
        default=0,  # 0 = auto pow2 rung (min 1024 rows)
        help_txt="Fixed corpus-capacity floor (rows) for the padded ANN "
        "matrix; 0 auto-sizes to the pow2 rung of the live corpus.",
    )
    ann_max_batch: int = configfield(
        "ann_max_batch",
        default=8,
        help_txt="Largest query-row rung per ANN search dispatch (the "
        "pow2 row ladder the warmup compiles).",
    )


@configclass
class RankingConfig(ConfigWizard):
    """Reranking model for the ranked_hybrid pipeline (reference: the
    NV-Rerank-QA ranking-ms at deploy/compose/docker-compose-nim-ms.yaml:58-84)."""

    model_name: str = configfield(
        "model_name",
        default="arctic-embed-m",
        help_txt="Cross-encoder model preset or HF name for reranking.",
    )
    model_engine: str = configfield(
        "model_engine",
        default="",
        help_txt="Reranker backend: '' (disabled), tpu (in-process JAX "
        "cross-encoder), remote (NIM /v1/ranking API), overlap (lexical, testing).",
    )
    server_url: str = configfield(
        "server_url",
        default="",
        help_txt="URL of a remote ranking microservice (remote engine).",
    )
    # genai-lint: disable=config-knob-drift -- free-form path; empty (random-init) is legal and existence is only checkable where the weights load
    checkpoint_path: str = configfield(
        "checkpoint_path",
        default="",
        help_txt="Path to cross-encoder weights (safetensors dir).",
    )
    fetch_factor: int = configfield(
        "fetch_factor",
        default=4,
        help_txt="ranked_hybrid fetches top_k*fetch_factor candidates "
        "before reranking down to top_k.",
    )


@configclass
class PromptsConfig(ConfigWizard):
    """Prompt templates (reference: configuration.py:164-204)."""

    chat_template: str = configfield(
        "chat_template",
        default=(
            "You are a helpful, respectful and honest assistant."
            "Always answer as helpfully as possible, while being safe."
            "Please ensure that your responses are positive in nature."
        ),
        help_txt="Prompt template for chat.",
    )
    rag_template: str = configfield(
        "rag_template",
        default=(
            "<s>[INST] <<SYS>>"
            "Use the following context to answer the user's question. If you don't know the answer,"
            "just say that you don't know, don't try to make up an answer."
            "<</SYS>>"
            "<s>[INST] Context: {context_str} Question: {query_str} Only return the helpful"
            " answer below and nothing else. Helpful answer:[/INST]"
        ),
        help_txt="Prompt template for rag.",
    )
    multi_turn_rag_template: str = configfield(
        "multi_turn_rag_template",
        default=(
            "You are a document chatbot. Help the user as they ask questions about documents."
            " User message just asked: {input}\n\n"
            " For this, we have retrieved the following potentially-useful info: "
            " Conversation History Retrieved:\n{history}\n\n"
            " Document Retrieved:\n{context}\n\n"
            " Answer only from retrieved data. Make your response conversational."
        ),
        help_txt="Prompt template for multi-turn rag.",
    )


@configclass
class EngineConfig(ConfigWizard):
    """In-process TPU inference engine (new in the TPU build).

    Replaces the reference's external NIM container configuration
    (docker-compose-nim-ms.yaml:2-22, INFERENCE_GPU_COUNT) with mesh/sharding
    parameters for the JAX engine.
    """

    # genai-lint: disable=config-knob-drift -- free-form path; empty (random-init) is legal and existence is only checkable where the weights load
    checkpoint_path: str = configfield(
        "checkpoint_path",
        default="",
        help_txt="Path to model weights (safetensors dir or orbax checkpoint). "
        "Empty means deterministic random-init (testing/benching).",
    )
    # genai-lint: disable=config-knob-drift -- free-form path; empty (byte-level fallback) is legal, checked by the tokenizer loader
    tokenizer_path: str = configfield(
        "tokenizer_path",
        default="",
        help_txt="Path to a HF tokenizer.json; empty falls back to the byte-level tokenizer.",
    )
    tensor_parallelism: int = configfield(
        "tensor_parallelism",
        default=-1,
        help_txt="Size of the model mesh axis; -1 uses all local devices "
        "(TPU analogue of NIM's INFERENCE_GPU_COUNT).",
    )
    dtype: str = configfield(
        "dtype",
        default="bfloat16",
        help_txt="Activation/weight dtype for inference.",
    )
    quantization: str = configfield(
        "quantization",
        default="none",
        help_txt=(
            "Quantization: none, int8 (weight-only, near-exact), or w8a8 "
            "(int8 MXU with per-token activation quant — fastest decode, "
            "approximate)."
        ),
    )
    kv_cache_dtype: str = configfield(
        "kv_cache_dtype",
        default="bfloat16",
        help_txt="KV page-pool storage: bfloat16, int8 (halves cache HBM, "
        "roughly doubling slot capacity; read by the Pallas page-attention "
        "kernel where the geometry allows, by the XLA dequant gather "
        "elsewhere), or int4 (packs two values per byte, halving KV bytes "
        "again; page-granular scales, same exact-operand kernel discipline).",
    )
    max_batch_size: int = configfield(
        "max_batch_size",
        default=8,
        help_txt="Maximum concurrent sequences in the continuous-batching decode loop.",
    )
    max_seq_len: int = configfield(
        "max_seq_len",
        default=8192,
        help_txt="KV-cache sequence capacity per slot (Llama-3 native window).",
    )
    paged_kernel: str = configfield(
        "paged_kernel",
        default="auto",
        help_txt="Ragged Pallas page-attention kernel over the KV page "
        "pool (ops/page_attention.py): 'auto' compiles it "
        "on a single TPU device — or shard_map-wrapped over the model "
        "mesh axis on a TP mesh (heads shard, page tables replicate) — "
        "when ops.page_attention.supports_geometry accepts the "
        "per-shard pool shape (falling back LOUDLY to the XLA dequant "
        "gather otherwise), 'off' forces the gather (A/B tuning), "
        "'interpret' runs the kernel in Pallas interpret mode on any "
        "backend (CPU identity tests; orders of magnitude slower — "
        "never production).",
    )
    page_size: int = configfield(
        "page_size",
        default=128,
        help_txt="Tokens per KV-cache page (docs/paged_kv.md): a "
        "power of two <= 128 dividing prefill_chunk (chunk-aligned "
        "prefix-cache entries must be page-aligned for zero-copy "
        "sharing) and the effective max_seq_len; any other geometry is "
        "refused at start-up.",
    )
    kv_pool_pages: int = configfield(
        "kv_pool_pages",
        default=0,
        help_txt="Device page-pool size (pages). 0 auto-sizes to "
        "one full-capacity strip of pages per decode slot plus one per "
        "prefix-cache entry, plus the reserved scratch page. "
        "Larger pools admit more concurrent mixed-length requests at "
        "the same per-request capacity.",
    )
    prefill_chunk: int = configfield(
        "prefill_chunk",
        default=512,
        help_txt="Prefill chunk (engine tokens): a prompt of one chunk "
        "prefills in one padded dispatch; a longer one runs as repeated "
        "chunk dispatches against its pages, each over the rows that "
        "hold tokens in it at the narrowest width rung that fits them "
        "(powers of four down from prefill_chunk, whole pages), so the "
        "compiled-shape set is bounded (wave sizes x attention windows "
        "at the full width, wave sizes alone at a narrow one) "
        "and NO prompt length can trigger an XLA compile inside a "
        "request (reference analogue: TRT-LLM chunked context). A "
        "multiple of page_size.",
    )
    warmup_prompt_lengths: str = configfield(
        "warmup_prompt_lengths",
        default="",
        help_txt="Non-empty (comma-separated positive ints, e.g. '512'): "
        "the chain-server pre-compiles every serving shape at startup in "
        "a background thread. The values select nothing: the one warm "
        "walk covers every prompt length (every prompt prefills through "
        "the same fixed-shape chunk programs). Without warming, the first "
        "request of each shape stalls for a multi-minute XLA compile of "
        "the serving graph (measured ~5 min for an 8B prefill "
        "mid-serving).",
    )
    prefix_cache_enable: str = configfield(
        "prefix_cache_enable",
        default="auto",
        help_txt="Automatic prefix KV-cache reuse ('auto' or 'off'). In "
        "auto, chunk-aligned prompt prefixes (shared RAG preambles, "
        "multi-turn histories) are indexed in a radix cache whose "
        "entries hold refcounted pool pages; a warm request maps the "
        "cached pages into its page table (zero copy) and "
        "chunk-prefills only the uncached suffix; 'off' restores the "
        "exact unaugmented admission path (docs/prefix_cache.md).",
    )
    prefix_cache_slots: int = configfield(
        "prefix_cache_slots",
        default=4,
        help_txt="Reserved HBM cache slots (each max_seq_len rows, same "
        "layout as a batch slot) holding cached prefixes, refcounted and "
        "LRU-evicted. Each slot costs the same KV memory as one decode "
        "slot; 0 disables the prefix cache.",
    )
    spec_decode_enable: str = configfield(
        "spec_decode_enable",
        default="off",
        help_txt="Prompt-lookup speculative decoding ('on' or 'off'). In "
        "on, greedy (temperature=0) rows draft up to spec_draft_len "
        "tokens per step by matching the tail of their generated "
        "sequence against their own prompt+output buffer, and one "
        "compiled verify dispatch scores every draft position, "
        "accepting the longest greedy-matching prefix — multiplying "
        "tokens-per-dispatch on copy-heavy RAG/multi-turn traffic. "
        "Greedy output stays token-identical to 'off'; temperature>0 "
        "rows fall back to normal single-token decode inside the same "
        "dispatch. 'off' "
        "restores the exact unaugmented decode path "
        "(docs/spec_decode.md).",
    )
    spec_pipeline_enable: str = configfield(
        "spec_pipeline_enable",
        default="on",
        help_txt="Pipelined spec-verify dispatch ('on' or 'off'), "
        "resolved once at engine init. In 'on' (with a runahead-capable "
        "proposer, i.e. 'lookup'), the dispatch thread leaves each "
        "verify in flight, drafts the next round from an optimistic "
        "full-acceptance context while the device works, and lands the "
        "result at the next dispatch — confirming the runahead draft "
        "or rolling it back. Streams stay token-identical either way "
        "(drafts only steer acceptance, never emission); 'off' "
        "restores the exact synchronous spec dispatch path "
        "(docs/spec_decode.md).",
    )
    spec_draft_len: int = configfield(
        "spec_draft_len",
        default=8,
        help_txt="Max draft tokens per slot per verify dispatch (K). The "
        "verify step scores K+1 positions per row, so activation "
        "footprint scales with K+1; acceptance beyond ~8 is rare "
        "outside long verbatim copies.",
    )
    spec_ngram_max: int = configfield(
        "spec_ngram_max",
        default=3,
        help_txt="Longest tail n-gram the prompt-lookup proposer tries "
        "to match (it falls back n-1 .. 1). Longer n-grams draft more "
        "precisely but match less often.",
    )
    # --- spec_draft_model section: the resident draft model -----------
    spec_proposer: str = configfield(
        "spec_proposer",
        default="lookup",
        help_txt="Draft source for speculative decoding: 'lookup' (the "
        "prompt-lookup n-gram proposer — the exact prior spec path, "
        "greedy rows only), 'draft_model' (a resident small Llama "
        "drafting K tokens for the whole decode wave in one batched "
        "dispatch — generalizes speculation to normal, non-copy-heavy "
        "chat/RAG traffic, sampled rows included), or 'combined' "
        "(lookup first, draft model where the n-gram scan finds "
        "nothing). Draft-model modes require spec_draft_model or "
        "spec_draft_checkpoint_path (docs/spec_decode.md).",
    )
    spec_draft_model: str = configfield(
        "spec_draft_model",
        default="",
        help_txt="Named models/llama.py preset for the resident draft "
        "model (e.g. 'llama3-1b-proxy' drafting for an 8B/70B target). "
        "The draft shares the target's tokenizer/vocab and window; its "
        "weights+KV ride the same mesh. Required (or "
        "spec_draft_checkpoint_path) when spec_proposer is "
        "'draft_model' or 'combined'.",
    )
    spec_draft_checkpoint_path: str = configfield(
        "spec_draft_checkpoint_path",
        default="",
        help_txt="Checkpoint for the resident draft model (safetensors "
        "dir with config.json). Empty means deterministic random-init "
        "draft weights — fine for benching the dispatch mechanics, "
        "useless for real acceptance (the bench records the regime as "
        "provenance).",
    )
    spec_draft_model_len: int = configfield(
        "spec_draft_model_len",
        default=0,
        help_txt="Draft width K for the draft-model proposers; 0 "
        "inherits spec_draft_len. One effective K "
        "(engine/spec_decode.py effective_draft_len) feeds the verify "
        "program width, the draft program's step count, AND the paged "
        "admission funding slack, so a draft can never propose past "
        "its funded page reservation.",
    )
    spec_draft_kv_dtype: str = configfield(
        "spec_draft_kv_dtype",
        default="bfloat16",
        help_txt="Draft-model KV cache storage: bfloat16 or int8 "
        "(halves the draft cache's HBM; the draft keeps a private "
        "per-slot cache of its own, not pages of the target's pool).",
    )
    prefill_wave_tokens: int = configfield(
        "prefill_wave_tokens",
        default=16384,
        help_txt="Cap on rows x prefill_chunk of one prefill dispatch: a "
        "wave holds prefill_wave_tokens / prefill_chunk rows, so the "
        "compiled extend's activation footprint stays bounded (a 16 x "
        "2560-token unrolled 8B prefill needs >17 GB HBM and cannot "
        "compile on one v5e chip).",
    )
    model_config_name: str = configfield(
        "model_config_name",
        default="llama3-8b",
        help_txt="Named architecture preset (see models/llama.py PRESETS) used when "
        "checkpoint_path has no config.json.",
    )
    decode_runahead: int = configfield(
        "decode_runahead",
        default=4,
        help_txt="Decode blocks dispatched ahead of host readback. Hides "
        "device->host readback latency; bounds "
        "wasted steps after a sequence stops at decode_runahead * "
        "decode_block.",
    )
    decode_block: int = configfield(
        "decode_block",
        default=8,
        help_txt="Decode steps fused into one dispatch (lax.scan); one "
        "device->host readback returns a [block, batch] token slab. Amortizes "
        "per-dispatch launch and readback cost; 1 disables blocking for lowest per-token "
        "latency.",
    )
    stream_timeout_s: float = configfield(
        "stream_timeout_s",
        default=600.0,
        help_txt="Default stall deadline (seconds) for a consumer "
        "waiting on the next generated token (stream_text/iter_ids "
        "without an explicit timeout; per-request deadlines override "
        "it). Was a hardcoded 600 s before the resilience layer.",
    )
    quiesce_timeout_s: float = configfield(
        "quiesce_timeout_s",
        default=600.0,
        help_txt="How long warmup paths wait for live decode to drain "
        "before dispatching donated-buffer warm programs (previously a "
        "hardcoded 600 s).",
    )
    drain_timeout_s: float = configfield(
        "drain_timeout_s",
        default=30.0,
        help_txt="Budget (seconds) for POST /internal/drain to park "
        "the dispatch loop at a block boundary and checkpoint every "
        "in-flight request to the snapshot spool. Past the deadline, "
        "still-live requests are preempted replay-only (prompt + "
        "pinned seed, no KV payload) so nothing is ever lost, just "
        "recomputed. Also bounds a restore's wait for the dispatch "
        "loop to pick it up.",
    )
    snapshot_spool_dir: str = configfield(
        "snapshot_spool_dir",
        default="/tmp/genai_snapshots",
        help_txt="Directory receiving one provenance-stamped JSON "
        "document per preempted request (engine/request_snapshot.py). "
        "Restore refuses documents whose engine config fingerprint "
        "differs from the serving engine's.",
    )
    snapshot_spool_max: int = configfield(
        "snapshot_spool_max",
        default=64,
        help_txt="Maximum snapshot documents kept in the spool; the "
        "oldest is evicted when a drain would exceed it (the anomaly "
        "black box's bundle-dir discipline). Must be >= 1.",
    )
    max_queued_requests: int = configfield(
        "max_queued_requests",
        default=0,
        help_txt="Admission-queue depth cap: submit() raises a typed "
        "EngineOverloaded once this many requests await slots, instead "
        "of growing the queue without bound. 0 (default) keeps the "
        "unbounded prior behavior (the chain-server's "
        "resilience.engine_queue_cap sheds at the HTTP layer either "
        "way). When set, must be >= max_batch_size so warmup's full "
        "admission waves fit.",
    )
    watchdog_stall_s: float = configfield(
        "watchdog_stall_s",
        default=300.0,
        help_txt="Dispatch-loop watchdog threshold (seconds): with work "
        "outstanding and no dispatch-loop progress for this long, the "
        "engine flips the genai_engine_wedged gauge and the readiness "
        "probe to unready (it recovers automatically if the loop "
        "resumes). 0 disables the watchdog.",
    )
    scheduler_policy: str = configfield(
        "scheduler_policy",
        default="unified",
        help_txt="Engine scheduler policy (engine/scheduler/, "
        "docs/scheduler.md): 'unified' (default — admission, wave "
        "formation, and decode share one dispatch thread, reproducing "
        "the exact pre-scheduler dispatch order token-identically) or "
        "'disagg' (prefill/decode disaggregation: a dedicated prefill "
        "tier worker forms and prefills admission waves and streams "
        "finished KV pages to the decode tier through a bounded "
        "transfer queue, so long-prompt prefills stop stealing decode "
        "dispatch slots; pages are the handoff unit).",
    )
    handoff_queue_depth: int = configfield(
        "handoff_queue_depth",
        default=0,
        help_txt="Bound on the prefill→decode transfer queue under "
        "scheduler_policy='disagg' (requests; a full queue stalls the "
        "prefill tier BEFORE its next wave — decode-tier consumption "
        "paces the pipeline, counted by "
        "genai_engine_handoff_stall_seconds). 0 auto-sizes to "
        "2 x max_batch_size.",
    )
    spec_draft_min_acceptance: float = configfield(
        "spec_draft_min_acceptance",
        default=0.0,
        help_txt="Draft-aware scheduling: when the rolling draft-token "
        "acceptance ratio across recent verify rounds drops below this, "
        "the scheduler policy skips the resident-draft dispatch for the "
        "wave (genai_engine_spec_draft_skips_total counts; periodic "
        "probe rounds keep re-measuring so a recovered workload resumes "
        "drafting). In [0, 1); 0 (default) disables the gate. Only "
        "draft-model proposers gate — prompt-lookup drafts are "
        "host-side scans and effectively free.",
    )
    spec_adaptive_k: str = configfield(
        "spec_adaptive_k",
        default="off",
        help_txt="Acceptance-adaptive draft width ('on' or 'off'). In "
        "'on', each spec round picks its draft width K from a fixed "
        "halving ladder (effective K down to spec_adaptive_k_min) "
        "driven by the scheduler's rolling acceptance ratio: full "
        "width while acceptance holds above spec_adaptive_k_threshold "
        "(or while evidence is thin), shrunk rungs while it collapses, "
        "with periodic full-width probe rounds so a recovered workload "
        "re-expands. Verify executables stay a closed warmed set (one "
        "per rung — warmup walks the ladder); page funding stays at "
        "the configured max K, so shrinking never under-funds "
        "(docs/spec_decode.md).",
    )
    spec_adaptive_k_min: int = configfield(
        "spec_adaptive_k_min",
        default=1,
        help_txt="Floor of the adaptive-K ladder (>= 1, <= the "
        "effective draft length). The ladder is halvings of the "
        "effective K clamped to this floor; 1 keeps single-token "
        "drafting alive even under fully collapsed acceptance.",
    )
    spec_adaptive_k_threshold: float = configfield(
        "spec_adaptive_k_threshold",
        default=0.5,
        help_txt="Acceptance ratio at or above which adaptive-K stays "
        "at full width, in (0, 1]. Below it, the next round's K shrinks "
        "toward ratio x K_max (never below spec_adaptive_k_min). While "
        "acceptance never dips below this threshold, streams are "
        "token-identical to fixed-K.",
    )


@configclass
class ResilienceConfig(ConfigWizard):
    """End-to-end resilience knobs (new in the TPU build): request
    deadlines, admission control/load shedding, dependency retry +
    circuit breaking, and the deterministic fault-injection harness.
    Validation lives in utils/resilience.py:validate_config (pure host)
    and runs at chain-server startup."""

    enable: str = configfield(
        "enable",
        default="on",
        help_txt="Resilience layer master switch ('on' or 'off'). 'off' "
        "restores the exact pre-resilience request path: no deadlines, "
        "no admission control, no retry/breaker wrapping, and the "
        "chains' original failure behavior.",
    )
    request_deadline_ms: int = configfield(
        "request_deadline_ms",
        default=600000,
        help_txt="Default per-request deadline budget (milliseconds) for "
        "/generate, overridable per request by the X-Request-Deadline-Ms "
        "header or the body's deadline_ms field. Propagated into the "
        "chains and the engine stream timeout. 0 disables the default "
        "deadline.",
    )
    max_active_streams: int = configfield(
        "max_active_streams",
        default=64,
        help_txt="Admission control: /generate requests are shed with "
        "429 + Retry-After once this many SSE streams are in flight. "
        "0 disables the cap.",
    )
    engine_queue_cap: int = configfield(
        "engine_queue_cap",
        default=64,
        help_txt="Admission control: /generate requests are shed with "
        "429 + Retry-After while the in-process engine's pending queue "
        "is at or above this depth. 0 disables the check.",
    )
    shed_retry_after_s: float = configfield(
        "shed_retry_after_s",
        default=1.0,
        help_txt="Retry-After header value (seconds) on shed (429) "
        "responses.",
    )
    retry_max_attempts: int = configfield(
        "retry_max_attempts",
        default=3,
        help_txt="Max attempts per guarded dependency call (Milvus "
        "search, remote embedder/reranker/LLM). 1 disables retries.",
    )
    retry_base_delay_ms: int = configfield(
        "retry_base_delay_ms",
        default=50,
        help_txt="First retry backoff delay (milliseconds); doubles per "
        "attempt up to retry_max_delay_ms.",
    )
    retry_max_delay_ms: int = configfield(
        "retry_max_delay_ms",
        default=2000,
        help_txt="Backoff delay ceiling (milliseconds).",
    )
    retry_jitter: float = configfield(
        "retry_jitter",
        default=0.5,
        help_txt="Symmetric multiplicative jitter fraction applied to "
        "each backoff delay (0 disables jitter; must be in [0, 1]).",
    )
    breaker_failure_threshold: int = configfield(
        "breaker_failure_threshold",
        default=5,
        help_txt="Consecutive failures that trip a dependency's circuit "
        "breaker open (per-dependency: milvus, embedder, reranker, "
        "llm_remote, bm25, native_store).",
    )
    breaker_recovery_s: float = configfield(
        "breaker_recovery_s",
        default=30.0,
        help_txt="Seconds an open breaker waits before letting one "
        "half-open probe through.",
    )
    faults: str = configfield(
        "faults",
        default="",
        help_txt="Deterministic fault-injection spec applied at server "
        "startup (same grammar as the GENAI_FAULTS env var): "
        "'site:mode[=value]@at[xcount]' entries joined with ';' — e.g. "
        "'retrieval.search:error@1x0'. Empty disables. See "
        "docs/resilience.md.",
    )


@configclass
class BatchingConfig(ConfigWizard):
    """Cross-request dynamic micro-batching for the TPU retrieval
    side-models (embedder + reranker) — docs/retrieval_batching.md.
    Under concurrency, per-request batch-of-1 embed/rerank dispatches
    coalesce into shared device batches with decode-aware dispatch;
    results are bit-identical to the synchronous path. Validation lives
    in engine/batcher.py:validate_config (pure host) and runs at
    chain-server startup."""

    enable: str = configfield(
        "enable",
        default="on",
        help_txt="Retrieval micro-batcher master switch ('on' or 'off'). "
        "'off' keeps TPUEmbedder/TPUReranker on their direct synchronous "
        "dispatch path (no batcher thread, legacy sleep-based decode "
        "throttle for bulk ingestion).",
    )
    max_wait_ms: float = configfield(
        "max_wait_ms",
        default=4.0,
        help_txt="Batch-formation window (milliseconds): a batch "
        "dispatches when it reaches the model's max batch rows or this "
        "much time passes since its oldest item, whichever first. "
        "Per-request resilience deadlines cap the window further.",
    )
    max_batch_embed: int = configfield(
        "max_batch_embed",
        default=32,
        help_txt="Max rows per coalesced embedder device dispatch.",
    )
    max_batch_rerank: int = configfield(
        "max_batch_rerank",
        default=16,
        help_txt="Max (query, passage) pairs per coalesced reranker "
        "device dispatch.",
    )
    ingest_decode_yield_ms: float = configfield(
        "ingest_decode_yield_ms",
        default=50.0,
        help_txt="How long (milliseconds) the bulk-ingestion embed lane "
        "waits for an ingest window from the co-located LLM engine's "
        "scheduler policy before each batch (decode-idle under "
        "'unified', prefill-tier-idle under 'disagg'; "
        "docs/scheduler.md). Bounds how much ingestion defers to token "
        "latency; 0 disables the gate. The interactive query lane "
        "never yields.",
    )


@configclass
class ObservabilityConfig(ConfigWizard):
    """Flight recorder + slow-request capture (new in the TPU build):
    a bounded ring of per-request lifecycle timelines
    (utils/flight_recorder.py) served at ``GET /internal/requests`` and
    ``GET /internal/requests/{id}``, with automatic export of requests
    that cross the slow thresholds. Validation lives in
    utils/flight_recorder.py:validate_config and runs at server
    startup."""

    flight_recorder_enable: str = configfield(
        "flight_recorder_enable",
        default="on",
        help_txt="Per-request flight recorder master switch ('on' or "
        "'off'). 'off' reduces every recording call site to one boolean "
        "read — the /internal/requests endpoints then serve empty "
        "views.",
    )
    flight_recorder_capacity: int = configfield(
        "flight_recorder_capacity",
        default=256,
        help_txt="Completed request timelines kept in the in-memory "
        "ring for GET /internal/requests; eviction always drops whole "
        "timelines, oldest first.",
    )
    slow_request_ttft_ms: float = configfield(
        "slow_request_ttft_ms",
        default=0.0,
        help_txt="Slow-request capture trigger: a finished request "
        "whose TTFT is at or above this many milliseconds exports its "
        "full timeline (JSONL when slow_capture_path is set, plus span "
        "events when tracing is active). 0 disables the TTFT trigger.",
    )
    slow_request_total_ms: float = configfield(
        "slow_request_total_ms",
        default=0.0,
        help_txt="Slow-request capture trigger on total request "
        "latency (milliseconds). 0 disables the total-latency trigger.",
    )
    slow_capture_path: str = configfield(
        "slow_capture_path",
        default="",
        help_txt="File path receiving one JSONL line per slow-request "
        "capture (full timeline). Empty keeps captures in-memory only "
        "(still retrievable via GET /internal/requests/{id}).",
    )
    dispatch_timeline_enable: str = configfield(
        "dispatch_timeline_enable",
        default="on",
        help_txt="Engine dispatch-timeline ring master switch ('on' or "
        "'off'; engine/dispatch_timeline.py, served at GET "
        "/internal/timeline). The engine resolves the switch ONCE at "
        "init, so 'off' restores the exact prior dispatch path; the "
        "GENAI_DISPATCH_TIMELINE env kill switch overrides 'on'. "
        "Validation lives in dispatch_timeline.validate_config.",
    )
    dispatch_timeline_capacity: int = configfield(
        "dispatch_timeline_capacity",
        default=4096,
        help_txt="Dispatch spans kept in the in-memory timeline ring; "
        "eviction always drops a whole span window (64 spans) at once, "
        "oldest first, and the capacity rounds up to a whole window.",
    )


@configclass
class BlackboxConfig(ConfigWizard):
    """Anomaly black box (utils/blackbox.py, docs/observability.md): a
    config-gated trigger registry that snapshots a bounded,
    rate-limited on-disk debug bundle — flight timelines, metrics
    exposition, SLO/utilization snapshots, provenance, log tail — the
    moment an SLO breach streak, wedged dispatch loop,
    page-backpressure storm, shed spike, or breaker-open actually
    happens; served at ``GET /internal/debug/bundles``. Validation
    lives in utils/blackbox.py:validate_config and runs at server
    startup. ``GENAI_BLACKBOX=off`` is the process kill switch."""

    enable: str = configfield(
        "enable",
        default="on",
        help_txt="Black-box master switch ('on' or 'off'). 'off' "
        "reduces every trigger notification to one boolean read; the "
        "GENAI_BLACKBOX env kill switch overrides 'on'.",
    )
    dir: str = configfield(
        "dir",
        default="/tmp/genai_blackbox",
        help_txt="Directory receiving one JSON bundle file per "
        "capture. Bounded at max_bundles (oldest evicted).",
    )
    max_bundles: int = configfield(
        "max_bundles",
        default=8,
        help_txt="Maximum bundle files kept on disk; the oldest is "
        "evicted when a new capture would exceed it.",
    )
    min_interval_s: float = configfield(
        "min_interval_s",
        default=60.0,
        help_txt="Global capture rate limit (seconds): at most one "
        "bundle per interval regardless of how many triggers fire "
        "(an incident storm yields one bundle, not a disk storm). "
        "0 disables the rate limit.",
    )
    slo_breach_streak: int = configfield(
        "slo_breach_streak",
        default=3,
        help_txt="Consecutive SLO evaluations with all_met=false (and "
        "at least one sampled objective) before the slo_breach trigger "
        "captures. 0 disarms the trigger.",
    )
    shed_spike: int = configfield(
        "shed_spike",
        default=20,
        help_txt="Admission sheds within 60 s before the shed_spike "
        "trigger captures. 0 disarms the trigger.",
    )
    page_backpressure_storm: int = configfield(
        "page_backpressure_storm",
        default=10,
        help_txt="Paged-KV funding give-ups within 60 s before the "
        "page_backpressure trigger captures. 0 disarms the trigger.",
    )
    replica_death_storm: int = configfield(
        "replica_death_storm",
        default=3,
        help_txt="Router-observed passive replica failures (health "
        "note_failure events) within 60 s before the replica_death "
        "trigger captures a bundle — a kill/preemption storm is "
        "exactly the moment the stitched state matters. 0 disarms "
        "the trigger.",
    )


@configclass
class SLOConfig(ConfigWizard):
    """Service-level objectives evaluated in-process over sliding
    windows (utils/slo.py): exposed as genai_slo_* attainment gauges
    and ``GET /internal/slo``. A target of 0 disables that objective.
    Validation lives in utils/slo.py:validate_config and runs at server
    startup."""

    enable: str = configfield(
        "enable",
        default="on",
        help_txt="SLO evaluation master switch ('on' or 'off'). 'off' "
        "disables every objective — observations become no-ops and "
        "/internal/slo reports an empty objective set.",
    )
    window_s: float = configfield(
        "window_s",
        default=300.0,
        help_txt="Sliding-window length (seconds) every objective is "
        "evaluated over.",
    )
    ttft_p95_ms: float = configfield(
        "ttft_p95_ms",
        default=30000.0,
        help_txt="Objective: engine submit -> first token p95 at or "
        "under this many milliseconds. 0 disables.",
    )
    inter_token_p95_ms: float = configfield(
        "inter_token_p95_ms",
        default=1000.0,
        help_txt="Objective: per-token emission interval p95 at or "
        "under this many milliseconds (decode slabs arrive in blocks, "
        "so the distribution includes the block cadence). 0 disables.",
    )
    shed_rate_max: float = configfield(
        "shed_rate_max",
        default=0.05,
        help_txt="Objective: fraction of /generate requests shed with "
        "429 at or under this rate over the window. 0 disables.",
    )
    degraded_rate_max: float = configfield(
        "degraded_rate_max",
        default=0.05,
        help_txt="Objective: fraction of RAG answers served degraded "
        "(LLM-only fallback) at or under this rate over the window. "
        "0 disables.",
    )
    router_proxy_overhead_p95_ms: float = configfield(
        "router_proxy_overhead_p95_ms",
        default=50.0,
        help_txt="Router-process objective (never evaluated in the "
        "engine/chain servers): router-added latency per proxied "
        "request p95 at or under this many milliseconds. 0 disables.",
    )
    router_failover_rate_max: float = configfield(
        "router_failover_rate_max",
        default=0.05,
        help_txt="Router-process objective: fraction of proxied "
        "requests that required a sibling failover retry at or under "
        "this rate over the window. 0 disables.",
    )


@configclass
class RouterConfig(ConfigWizard):
    """Cache-aware multi-replica routing tier (docs/router.md): a
    standalone reverse proxy fronting N chain-server/engine replicas
    with prefix-affinity placement, tenant fairness, and health-driven
    failover. Validation lives in router/app.py:validate_config and
    runs at router startup."""

    replicas: str = configfield(
        "replicas",
        default="",
        help_txt="Comma-separated replica base URLs the router fronts "
        "(e.g. 'http://replica-a:8081,http://replica-b:8081'). Replica "
        "ids r0, r1, ... are assigned in list order (drain endpoint, "
        "metric labels).",
    )
    policy: str = configfield(
        "policy",
        default="affinity",
        help_txt="Placement policy: 'affinity' (consistent-hash ring on "
        "the request's prefix key — conversation first message / "
        "repeated question text — with bounded-load spill) or "
        "'round_robin' (blind baseline, the bench A/B control). "
        "Switchable at runtime via POST /internal/policy.",
    )
    ring_vnodes: int = configfield(
        "ring_vnodes",
        default=64,
        help_txt="Virtual ring points per replica; more points smooth "
        "the key distribution at slightly higher placement cost.",
    )
    load_bound: float = configfield(
        "load_bound",
        default=1.25,
        help_txt="Bounded-load factor c: a replica is spill-saturated "
        "once its router-side inflight exceeds c * (total inflight / "
        "placeable replicas). 0 disables inflight-based spill.",
    )
    spill_queue_depth: int = configfield(
        "spill_queue_depth",
        default=8,
        help_txt="Spill past a replica whose last-observed engine "
        "admission-queue depth (X-GenAI-Queue-Depth shed headers, "
        "health polls) is at or above this. 0 disables depth-based "
        "spill.",
    )
    failover_retry: str = configfield(
        "failover_retry",
        default="on",
        help_txt="Master switch for re-placing a failed /generate on "
        "ring siblings ('on' or 'off'). 'off' forces a single attempt "
        "regardless of retry_budget. Mid-stream deaths re-place with "
        "the forwarded-character offset bridged (snapshot restore or "
        "replay), so the client stream continues instead of closing.",
    )
    retry_budget: int = configfield(
        "retry_budget",
        default=1,
        help_txt="Sibling re-placements allowed per request (attempts "
        "= 1 + budget). When the budget is spent the LAST upstream "
        "error passes through to the client and "
        "genai_router_retry_budget_exhausted_total counts it. The "
        "previous retry-once hardcode is the budget=1 default; 0 "
        "disables failover for pre-stream errors too.",
    )
    health_interval_s: float = configfield(
        "health_interval_s",
        default=2.0,
        help_txt="Health-poller period (seconds) for each replica's "
        "/internal/ready (readiness + wedged) probe.",
    )
    health_fail_threshold: int = configfield(
        "health_fail_threshold",
        default=2,
        help_txt="Consecutive failed probes (or proxy-observed "
        "failures) before a replica leaves placement.",
    )
    health_ok_threshold: int = configfield(
        "health_ok_threshold",
        default=2,
        help_txt="Consecutive good probes before an unhealthy replica "
        "re-enters placement.",
    )
    health_slo_gate: str = configfield(
        "health_slo_gate",
        default="off",
        help_txt="Also fail a replica's probe while its /internal/slo "
        "reports all_met=false ('on' or 'off'). Off by default: SLO "
        "flap under load spikes would amplify the spike onto the "
        "survivors.",
    )
    tenants: str = configfield(
        "tenants",
        default="",
        help_txt="Per-tenant quota spec: "
        "'name:rate=QPS,burst=N,inflight=N,weight=W,keys=k1|k2' "
        "entries joined with ';'. The 'default' entry's limits apply "
        "to unknown tenant ids (each under its own account). Empty "
        "disables tenant admission control.",
    )
    max_inflight: int = configfield(
        "max_inflight",
        default=0,
        help_txt="Router-wide inflight cap used for weighted "
        "fair-share shedding: below it every tenant runs unthrottled; "
        "at it, tenants holding at least their weight share are shed "
        "first. 0 disables fair-share shedding.",
    )
    connect_timeout_s: float = configfield(
        "connect_timeout_s",
        default=10.0,
        help_txt="Upstream TCP connect timeout (seconds) per proxied "
        "request.",
    )
    read_timeout_s: float = configfield(
        "read_timeout_s",
        default=600.0,
        help_txt="Upstream per-read (inter-chunk) timeout (seconds) "
        "for proxied streams.",
    )


@configclass
class AppConfig(ConfigWizard):
    """Root application configuration (reference: configuration.py:208-258)."""

    vector_store: VectorStoreConfig = configfield(
        "vector_store",
        env=False,
        help_txt="The configuration of the vector db connection.",
        default_factory=VectorStoreConfig,
    )
    llm: LLMConfig = configfield(
        "llm",
        env=False,
        help_txt="The configuration for the server hosting the Large Language Models.",
        default_factory=LLMConfig,
    )
    text_splitter: TextSplitterConfig = configfield(
        "text_splitter",
        env=False,
        help_txt="The configuration for text splitter.",
        default_factory=TextSplitterConfig,
    )
    embeddings: EmbeddingConfig = configfield(
        "embeddings",
        env=False,
        help_txt="The configuration of embedding model.",
        default_factory=EmbeddingConfig,
    )
    retriever: RetrieverConfig = configfield(
        "retriever",
        env=False,
        help_txt="The configuration of the retriever pipeline.",
        default_factory=RetrieverConfig,
    )
    ranking: RankingConfig = configfield(
        "ranking",
        env=False,
        help_txt="The configuration of the reranking model.",
        default_factory=RankingConfig,
    )
    prompts: PromptsConfig = configfield(
        "prompts",
        env=False,
        help_txt="Prompt templates for chat and rag.",
        default_factory=PromptsConfig,
    )
    engine: EngineConfig = configfield(
        "engine",
        env=False,
        help_txt="The in-process TPU inference engine.",
        default_factory=EngineConfig,
    )
    resilience: ResilienceConfig = configfield(
        "resilience",
        env=False,
        help_txt="Deadlines, admission control, retry/circuit breaking "
        "and fault injection.",
        default_factory=ResilienceConfig,
    )
    batching: BatchingConfig = configfield(
        "batching",
        env=False,
        help_txt="Cross-request micro-batching for the retrieval "
        "side-models (embedder + reranker).",
        default_factory=BatchingConfig,
    )
    observability: ObservabilityConfig = configfield(
        "observability",
        env=False,
        help_txt="Per-request flight recorder and slow-request capture.",
        default_factory=ObservabilityConfig,
    )
    blackbox: BlackboxConfig = configfield(
        "blackbox",
        env=False,
        help_txt="Anomaly black box: incident-triggered debug-bundle "
        "capture.",
        default_factory=BlackboxConfig,
    )
    slo: SLOConfig = configfield(
        "slo",
        env=False,
        help_txt="Service-level objectives evaluated over sliding "
        "windows (genai_slo_* gauges + GET /internal/slo).",
        default_factory=SLOConfig,
    )
    router: RouterConfig = configfield(
        "router",
        env=False,
        help_txt="Multi-replica routing tier: placement, tenant "
        "fairness, health/drain, failover.",
        default_factory=RouterConfig,
    )
