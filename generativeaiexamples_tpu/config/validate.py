"""Startup validation for the reference config sections and the
engine-core knobs.

The resilience/batching/SLO/blackbox/flight-recorder/router sections
have always validated at startup (each module owns its own
``validate_config``); the reference sections (vector_store, llm,
embeddings, retriever, ranking, text_splitter, prompts) and the
engine-core knobs never did — a typo'd ``APP_ENGINE_DTYPE`` surfaced
as a mid-boot JAX error minutes into weight loading, and a bad
``model_engine`` fell back silently. genai_lint's config-knob-drift
rule now requires every schema knob to be touched by a validator;
this module is where the previously-unvalidated ones live. Pure host
(no engine/device imports), so tier-1 covers it without a server.

Called from the chain-server's ``create_app`` next to the other
validators; the engine sections that llm_engine validates at build
time (kv layout, spec ladder — engine/kv_pages.py and
engine/spec_decode.py) are NOT duplicated here.
"""
from __future__ import annotations

_ON_OFF = ("on", "off")
_LLM_ENGINES = ("tpu", "local", "openai", "nvidia-ai-endpoints", "remote", "echo")
_EMBED_ENGINES = ("", "tpu", "openai", "nvidia-ai-endpoints", "remote", "hash")
_RANKING_ENGINES = ("", "tpu", "remote", "overlap")
_RETRIEVER_PIPELINES = ("ranked_hybrid", "hybrid")
_RETRIEVER_BACKENDS = ("off", "tier")
_ANN_MODES = ("exact", "ivf")
_ENGINE_DTYPES = ("bfloat16", "float32", "float16")
_QUANTIZATIONS = ("none", "int8", "w8a8")
_KV_DTYPES = ("bfloat16", "int8", "int4")
_SPEC_PROPOSERS = ("lookup", "draft_model", "combined")


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise ValueError(message)


def validate_config(cfg) -> None:
    """Validate the reference sections + engine-core knobs (pure host;
    chain-server startup). Raises ValueError with the knob's dotted
    name, same phrasing as the sibling validators."""
    vs = cfg.vector_store
    _require(bool(vs.name.strip()),
             "vector_store.name must not be empty")
    _require(vs.nlist > 0, f"vector_store.nlist must be > 0, got {vs.nlist}")
    _require(vs.nprobe > 0,
             f"vector_store.nprobe must be > 0, got {vs.nprobe}")
    _require(bool(vs.persist_dir.strip()),
             "vector_store.persist_dir must not be empty")

    llm = cfg.llm
    engine_kind = (llm.model_engine or "tpu").lower()
    _require(engine_kind in _LLM_ENGINES,
             f"llm.model_engine must be one of {_LLM_ENGINES}, "
             f"got {llm.model_engine!r}")
    _require(bool(llm.model_name.strip()), "llm.model_name must not be empty")
    _require(bool(llm.model_name_pandas_ai.strip()),
             "llm.model_name_pandas_ai must not be empty")
    if engine_kind in ("openai", "nvidia-ai-endpoints", "remote"):
        _require(bool(llm.server_url),
                 f"llm.model_engine={engine_kind!r} requires llm.server_url "
                 f"(APP_LLM_SERVERURL)")

    ts = cfg.text_splitter
    _require(bool(ts.model_name.strip()),
             "text_splitter.model_name must not be empty")
    _require(ts.chunk_size > 0,
             f"text_splitter.chunk_size must be > 0, got {ts.chunk_size}")
    _require(0 <= ts.chunk_overlap < ts.chunk_size,
             f"text_splitter.chunk_overlap must be in [0, chunk_size), "
             f"got {ts.chunk_overlap} (chunk_size {ts.chunk_size})")

    emb = cfg.embeddings
    _require((emb.model_engine or "").lower() in _EMBED_ENGINES,
             f"embeddings.model_engine must be one of {_EMBED_ENGINES}, "
             f"got {emb.model_engine!r}")
    _require(bool(emb.model_name.strip()),
             "embeddings.model_name must not be empty")
    _require(emb.dimensions > 0,
             f"embeddings.dimensions must be > 0, got {emb.dimensions}")
    _require(emb.query_cache_size >= 0,
             f"embeddings.query_cache_size must be >= 0 (0 disables), "
             f"got {emb.query_cache_size}")
    if (emb.model_engine or "").lower() in ("openai", "nvidia-ai-endpoints",
                                            "remote"):
        _require(bool(emb.server_url),
                 f"embeddings.model_engine={emb.model_engine!r} requires "
                 f"embeddings.server_url (APP_EMBEDDINGS_SERVERURL)")

    ret = cfg.retriever
    _require(ret.top_k > 0, f"retriever.top_k must be > 0, got {ret.top_k}")
    _require(0.0 <= ret.score_threshold <= 1.0,
             f"retriever.score_threshold must be in [0, 1], "
             f"got {ret.score_threshold}")
    _require(ret.nr_pipeline in _RETRIEVER_PIPELINES,
             f"retriever.nr_pipeline must be one of {_RETRIEVER_PIPELINES}, "
             f"got {ret.nr_pipeline!r}")
    _require(ret.context_token_cap >= 0,
             f"retriever.context_token_cap must be >= 0 (0 disables), "
             f"got {ret.context_token_cap}")
    if ret.nr_url:
        _require("://" in ret.nr_url,
                 f"retriever.nr_url must carry a scheme "
                 f"(http://host:port), got {ret.nr_url!r}")
    _require((ret.backend or "off").lower() in _RETRIEVER_BACKENDS,
             f"retriever.backend must be one of {_RETRIEVER_BACKENDS}, "
             f"got {ret.backend!r}")
    _require(ret.tier_queue_depth >= 0,
             f"retriever.tier_queue_depth must be >= 0 (0 auto-sizes), "
             f"got {ret.tier_queue_depth}")
    _require(ret.tier_window_ms >= 0,
             f"retriever.tier_window_ms must be >= 0 (0 disables the "
             f"co-scheduling yield), got {ret.tier_window_ms}")
    _require((ret.ann_mode or "exact").lower() in _ANN_MODES,
             f"retriever.ann_mode must be one of {_ANN_MODES}, "
             f"got {ret.ann_mode!r}")
    _require(ret.ann_capacity >= 0,
             f"retriever.ann_capacity must be >= 0 (0 auto-sizes), "
             f"got {ret.ann_capacity}")
    _require(ret.ann_max_batch >= 1,
             f"retriever.ann_max_batch must be >= 1, got {ret.ann_max_batch}")
    if (ret.backend or "off").lower() == "tier":
        _require((cfg.vector_store.name or "tpu").lower() in ("tpu", "memory"),
                 f"retriever.backend=tier requires the in-process TPU "
                 f"vector store (vector_store.name=tpu), got "
                 f"vector_store.name={cfg.vector_store.name!r}")

    rk = cfg.ranking
    _require((rk.model_engine or "").lower() in _RANKING_ENGINES,
             f"ranking.model_engine must be one of {_RANKING_ENGINES} "
             f"('' disables), got {rk.model_engine!r}")
    _require(bool(rk.model_name.strip()),
             "ranking.model_name must not be empty")
    _require(rk.fetch_factor >= 1,
             f"ranking.fetch_factor must be >= 1, got {rk.fetch_factor}")
    if (rk.model_engine or "").lower() == "remote":
        _require(bool(rk.server_url),
                 "ranking.model_engine=remote requires ranking.server_url "
                 "(APP_RANKING_SERVERURL)")

    pr = cfg.prompts
    _require(bool(pr.chat_template.strip()),
             "prompts.chat_template must not be empty")
    _require(bool(pr.rag_template.strip()),
             "prompts.rag_template must not be empty")
    _require(bool(pr.multi_turn_rag_template.strip()),
             "prompts.multi_turn_rag_template must not be empty")

    e = cfg.engine
    _require(e.tensor_parallelism == -1 or e.tensor_parallelism > 0,
             f"engine.tensor_parallelism must be -1 (all devices) or > 0, "
             f"got {e.tensor_parallelism}")
    _require(e.dtype in _ENGINE_DTYPES,
             f"engine.dtype must be one of {_ENGINE_DTYPES}, got {e.dtype!r}")
    _require(e.quantization in _QUANTIZATIONS,
             f"engine.quantization must be one of {_QUANTIZATIONS}, "
             f"got {e.quantization!r}")
    _require(e.kv_cache_dtype in _KV_DTYPES,
             f"engine.kv_cache_dtype must be one of {_KV_DTYPES}, "
             f"got {e.kv_cache_dtype!r}")
    _require(e.max_batch_size > 0,
             f"engine.max_batch_size must be > 0, got {e.max_batch_size}")
    _require(e.max_seq_len > 0,
             f"engine.max_seq_len must be > 0, got {e.max_seq_len}")
    _require(bool(e.model_config_name.strip()),
             "engine.model_config_name must not be empty")
    for part in (e.warmup_prompt_lengths or "").split(","):
        part = part.strip()
        _require(part == "" or (part.isdigit() and int(part) > 0),
                 f"engine.warmup_prompt_lengths must be comma-separated "
                 f"positive ints, got {e.warmup_prompt_lengths!r}")
    _require(e.prefix_cache_enable in ("auto", "off"),
             f"engine.prefix_cache_enable must be auto|off, "
             f"got {e.prefix_cache_enable!r}")
    _require(e.prefix_cache_slots >= 0,
             f"engine.prefix_cache_slots must be >= 0 (0 disables), "
             f"got {e.prefix_cache_slots}")
    _require(e.spec_pipeline_enable in ("on", "off"),
             f"engine.spec_pipeline_enable must be on|off, "
             f"got {e.spec_pipeline_enable!r}")
    _require(e.spec_proposer in _SPEC_PROPOSERS,
             f"engine.spec_proposer must be one of {_SPEC_PROPOSERS}, "
             f"got {e.spec_proposer!r}")
    if e.spec_decode_enable == "on" and e.spec_proposer != "lookup":
        _require(bool(e.spec_draft_model or e.spec_draft_checkpoint_path),
                 f"engine.spec_proposer={e.spec_proposer!r} requires "
                 f"engine.spec_draft_model or "
                 f"engine.spec_draft_checkpoint_path")
    _require(e.scheduler_policy in ("unified", "disagg"),
             f"engine.scheduler_policy must be unified|disagg, "
             f"got {e.scheduler_policy!r}")
    _require(e.handoff_queue_depth >= 0,
             f"engine.handoff_queue_depth must be >= 0 (0 auto-sizes), "
             f"got {e.handoff_queue_depth}")
    _require(0.0 <= e.spec_draft_min_acceptance < 1.0,
             f"engine.spec_draft_min_acceptance must be in [0, 1) "
             f"(0 disables), got {e.spec_draft_min_acceptance}")
    _require(e.spec_adaptive_k in ("on", "off"),
             f"engine.spec_adaptive_k must be on|off, "
             f"got {e.spec_adaptive_k!r}")
    _require(e.spec_adaptive_k_min >= 1,
             f"engine.spec_adaptive_k_min must be >= 1, "
             f"got {e.spec_adaptive_k_min}")
    _require(0.0 < e.spec_adaptive_k_threshold <= 1.0,
             f"engine.spec_adaptive_k_threshold must be in (0, 1], "
             f"got {e.spec_adaptive_k_threshold}")
    _require(e.prefill_wave_tokens > 0,
             f"engine.prefill_wave_tokens must be > 0, "
             f"got {e.prefill_wave_tokens}")
    _require(e.decode_runahead >= 1,
             f"engine.decode_runahead must be >= 1, got {e.decode_runahead}")
    _require(e.decode_block >= 1,
             f"engine.decode_block must be >= 1, got {e.decode_block}")
    _require(e.stream_timeout_s > 0,
             f"engine.stream_timeout_s must be > 0, "
             f"got {e.stream_timeout_s}")
    _require(e.quiesce_timeout_s > 0,
             f"engine.quiesce_timeout_s must be > 0, "
             f"got {e.quiesce_timeout_s}")
    _require(e.drain_timeout_s > 0,
             f"engine.drain_timeout_s must be > 0, "
             f"got {e.drain_timeout_s}")
    _require(bool(e.snapshot_spool_dir),
             "engine.snapshot_spool_dir must be a non-empty path (the "
             "drain workflow spools preempted requests there)")
    _require(e.snapshot_spool_max >= 1,
             f"engine.snapshot_spool_max must be >= 1, "
             f"got {e.snapshot_spool_max}")
    _require(
        e.max_queued_requests == 0
        or e.max_queued_requests >= e.max_batch_size,
        f"engine.max_queued_requests must be 0 (unbounded) or >= "
        f"max_batch_size so warmup's full admission waves fit, got "
        f"{e.max_queued_requests} (max_batch_size {e.max_batch_size})",
    )
    _require(e.watchdog_stall_s >= 0,
             f"engine.watchdog_stall_s must be >= 0 (0 disables), "
             f"got {e.watchdog_stall_s}")
