"""Named loadgen profiles.

A profile bundles a workload spec with the server environment its
``--launch-server`` mode boots, so a whole measured run is one
command:

- ``cpu_smoke`` — the deterministic CI profile: tiny debug model on
  CPU, hash embedder, compressed think times, a few dozen requests.
  Two runs with the same seed produce identical schedules and
  identical request outcome sets (pinned by tests/test_loadgen_e2e.py);
  it exists to keep the harness itself honest, not to measure
  hardware.
- ``full`` — the hardware profile: the bench e2e serving config
  (llama3-8b int8) under a realistic mix — closed-loop chat sessions
  with think time, an open-loop RAG Poisson ramp, an ingestion storm,
  and a disconnect fraction. Numbers from this profile feed
  LOADGEN_BASELINE.json and the regression gate.

``APP_*`` values here only apply when the runner launches the server
itself; against an already-running deployment the profile's spec still
applies but the environment is the deployment's own.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from tools.loadgen.workload import ScenarioSpec, WorkloadSpec


@dataclasses.dataclass(frozen=True)
class Profile:
    name: str
    spec: WorkloadSpec
    server_env: Dict[str, str]
    scrape_interval_s: float = 0.5
    ready_timeout_s: float = 600.0


_CPU_SMOKE_SPEC = WorkloadSpec(
    name="cpu_smoke",
    seed=1234,
    scenarios=(
        # Ingestion leads: the query scenarios start after the corpus
        # exists, so every request takes the full retrieval + engine
        # path in BOTH runs (a cold store would answer early requests
        # with the canned no-documents message and no engine submit,
        # making run 1's phase-join set smaller than run 2's).
        ScenarioSpec(
            name="ingest_storm",
            kind="ingest",
            docs=2,
            doc_kb=2,
        ),
        ScenarioSpec(
            name="chat",
            kind="sessions",
            start_s=0.8,
            sessions=3,
            turns=2,
            think_time_s=0.05,
            use_knowledge_base=True,
            max_tokens=8,
        ),
        ScenarioSpec(
            name="rag_burst",
            kind="poisson",
            start_s=0.8,
            rate_qps=4.0,
            duration_s=2.0,
            ramp_s=1.0,
            use_knowledge_base=True,
            max_tokens=8,
            abort_fraction=0.25,
            abort_after_frames=1,
        ),
        # Spec-decode coverage: extra decode-heavy sessions riding the
        # profile's spec-on engine (resident draft model, see
        # _CPU_SMOKE_ENV). The chain default temperature (0.2) drafts
        # under the draft-model proposer — normal traffic, not a
        # copy-heavy special case — so the summary's gated `spec`
        # block (tokens_per_dispatch / acceptance_ratio / draft share)
        # measures the production path and the perf gate covers spec
        # from day one.
        ScenarioSpec(
            name="spec_chat",
            kind="sessions",
            start_s=1.0,
            sessions=2,
            turns=2,
            think_time_s=0.05,
            use_knowledge_base=True,
            max_tokens=12,
        ),
    ),
)

_CPU_SMOKE_ENV = {
    "EXAMPLE_NAME": "developer_rag",
    # Tracing ON (memory exporter: no console spew, no network) — the
    # flight recorder stamps records with the incoming traceparent's
    # trace id only when tracing is enabled, and that trace id is the
    # loadgen's phase-attribution join key.
    "ENABLE_TRACING": "1",
    "TRACE_EXPORTER": "memory",
    "APP_LLM_MODELENGINE": "tpu",
    "APP_EMBEDDINGS_MODELENGINE": "hash",
    "APP_VECTORSTORE_NAME": "tpu",
    "APP_RETRIEVER_SCORETHRESHOLD": "0",
    "APP_ENGINE_MODELCONFIGNAME": "debug",
    "APP_ENGINE_MAXBATCHSIZE": "4",
    "APP_ENGINE_MAXSEQLEN": "128",
    "APP_ENGINE_PREFILLCHUNK": "16",
    # The default 128-token page cannot tile this profile's 16-token
    # prefill chunk (start-up would refuse it): shrink the page. The
    # pool is gather-served on CPU, and the summary carries the
    # paged_attn dispatch split.
    "APP_ENGINE_PAGESIZE": "16",
    "APP_ENGINE_DECODEBLOCK": "4",
    "APP_ENGINE_TENSORPARALLELISM": "1",
    # Speculative decoding ON with the resident draft model: the smoke
    # profile exercises the draft-dispatch path end to end (draft
    # prefill at admission, batched draft + verify per round) and the
    # summary's gated `spec` block keeps it measured. The draft shares
    # the target's "debug" preset (random-init twins — acceptance is
    # the mechanical ceiling, which is exactly what a determinism smoke
    # wants to pin); spec_draft_len stays at its default K.
    "APP_ENGINE_SPECDECODEENABLE": "on",
    "APP_ENGINE_SPECPROPOSER": "draft_model",
    "APP_ENGINE_SPECDRAFTMODEL": "debug",
    # Warm every serving shape (chunk set + wave rungs + decode windows
    # + prefix-cache copy programs) BEFORE /internal/ready: measured
    # traffic must never pay an XLA compile, or adjacent same-seed runs
    # differ by whole seconds wherever a first-seen shape lands.
    "APP_ENGINE_WARMUPPROMPTLENGTHS": "16",
    "JAX_PLATFORMS": "cpu",
    "LOGLEVEL": "WARNING",
}

# P/D-disaggregation acceptance workload (docs/scheduler.md): the mix
# is the tension disagg exists to resolve — an open-loop storm of
# long-RAG prefills (retrieval-context prompts filling the debug
# window: ~8 chunk dispatches each) arriving independently of decode
# progress, concurrent with short closed-loop agentic chat whose
# inter-token cadence is exactly what prefill waves steal under the
# unified policy. Runs against the cpu_smoke engine with
# scheduler_policy=disagg (two tiers on the single CPU device sharing
# one page pool — the zero-copy same-host handoff path); the summary's
# gated `disagg` block (handoffs, pages, stall times, recompute==0)
# and compiles.hot_path_total==0 are the acceptance assertions
# (tests/test_scheduler_disagg.py runs this profile as the CI leg).
_MIXED_PHASE_SPEC = WorkloadSpec(
    name="mixed_phase",
    seed=5150,
    scenarios=(
        ScenarioSpec(
            name="ingest_seed",
            kind="ingest",
            docs=3,
            doc_kb=4,
        ),
        ScenarioSpec(
            name="rag_storm",
            kind="poisson",
            start_s=0.8,
            rate_qps=5.0,
            duration_s=2.5,
            ramp_s=0.5,
            use_knowledge_base=True,
            max_tokens=8,
        ),
        ScenarioSpec(
            name="agentic_chat",
            kind="sessions",
            start_s=0.8,
            sessions=3,
            turns=3,
            think_time_s=0.05,
            use_knowledge_base=False,
            max_tokens=10,
        ),
    ),
)

# The cpu_smoke engine split into two tiers: same debug model, same
# paged layout (16-token pages), the prefill tier worker feeding the
# decode tier through the transfer queue. Spec decode stays ON from
# the base env, so the draft-under-disagg dispatch interleaving
# (prefill-tier draft admission vs decode-tier proposals) is exercised
# and warmed per tier — warmup covers the shared program set, and the
# hot-path gate proves no tier compiles mid-serving.
_MIXED_PHASE_ENV = dict(
    _CPU_SMOKE_ENV,
    APP_ENGINE_SCHEDULERPOLICY="disagg",
)

# Retrieval-tier acceptance workload (docs/retrieval_tier.md): a high
# search:generate ratio — an open-loop /search storm several times the
# generate rate, riding a seeded corpus, with a small RAG trickle so
# decode traffic runs CONCURRENTLY with the tier's waves (the
# co-scheduling seam the tier exists for, not an idle-engine
# microbenchmark). Runs against the cpu_smoke engine with
# retriever.backend=tier; the summary's gated `retrieval_tier` block
# (dispatches, queries, queries_per_dispatch, stall times) and
# compiles.hot_path_total==0 are the acceptance assertions — every
# post-warmup search must hit a pre-compiled pow2 (rows, k) rung
# (tests/test_retrieval_tier_e2e.py runs this profile as the CI leg).
_RETRIEVAL_HEAVY_SPEC = WorkloadSpec(
    name="retrieval_heavy",
    seed=8086,
    scenarios=(
        ScenarioSpec(
            name="ingest_seed",
            kind="ingest",
            docs=3,
            doc_kb=4,
        ),
        ScenarioSpec(
            name="search_storm",
            kind="search",
            start_s=0.8,
            rate_qps=6.0,
            duration_s=2.5,
            ramp_s=0.5,
        ),
        ScenarioSpec(
            name="rag_trickle",
            kind="poisson",
            start_s=1.0,
            rate_qps=1.0,
            duration_s=2.0,
            use_knowledge_base=True,
            max_tokens=8,
        ),
    ),
)

# The cpu_smoke engine with the retrieval tier on: /search and chain
# retrieval route through the batched ANN wave path instead of the
# synchronous per-request store search. Everything else (debug model,
# paged KV, spec decode, warmup shapes) stays the base profile, so a
# tier-vs-off comparison isolates the backend flip.
_RETRIEVAL_HEAVY_ENV = dict(
    _CPU_SMOKE_ENV,
    APP_RETRIEVER_BACKEND="tier",
)

_FULL_SPEC = WorkloadSpec(
    name="full",
    seed=20260803,
    scenarios=(
        ScenarioSpec(
            name="chat",
            kind="sessions",
            sessions=8,
            turns=4,
            think_time_s=4.0,
            use_knowledge_base=True,
            max_tokens=128,
        ),
        ScenarioSpec(
            name="rag_poisson",
            kind="poisson",
            rate_qps=1.0,
            ramp_s=20.0,
            duration_s=120.0,
            use_knowledge_base=True,
            max_tokens=128,
            abort_fraction=0.05,
            abort_after_frames=8,
        ),
        ScenarioSpec(
            name="ingest_storm",
            kind="ingest",
            start_s=30.0,
            docs=6,
            doc_kb=64,
        ),
    ),
)

_FULL_ENV = {
    "EXAMPLE_NAME": "developer_rag",
    "ENABLE_TRACING": "1",
    "TRACE_EXPORTER": "memory",
    "APP_LLM_MODELENGINE": "tpu",
    "APP_VECTORSTORE_NAME": "tpu",
    "APP_RETRIEVER_SCORETHRESHOLD": "0",
    "APP_ENGINE_MODELCONFIGNAME": "llama3-8b",
    "APP_ENGINE_QUANTIZATION": "int8",
    "APP_ENGINE_KVCACHEDTYPE": "int8",
    "APP_ENGINE_MAXBATCHSIZE": "16",
    "APP_ENGINE_MAXSEQLEN": "4096",
    # 128-token pages tile both the chunk and the window; the pool is
    # read by the ragged Pallas kernel on a single-chip host (the
    # gather on TP meshes).
    "APP_ENGINE_PREFILLCHUNK": "512",
    "APP_ENGINE_WARMUPPROMPTLENGTHS": "2048,2560,3072",
    "LOGLEVEL": "WARNING",
}

# Fleet A/B profile (tools/loadgen/fleet.py, docs/router.md): the mix
# is deliberately affinity-SENSITIVE — multi-turn sessions whose later
# turns only hit the prefix cache when they land on the replica that
# served the earlier turns, plus a small repeated-question pool whose
# cached full-prompt entries co-locate under consistent hashing.
# Round-robin placement scatters both, which is exactly the
# degradation the bench measures. No abort fraction: client
# disconnects would alias with the failover counters the fleet record
# reports. The prefix cache is sized for the mix's working set
# (sessions + question pool): at the debug default of 4 slots the
# measurement inverts — LRU thrash, not placement, dominates, and
# affinity CONCENTRATING a session's entries on one replica thrashes
# harder than round-robin accidentally spreading them.
_FLEET_SMOKE_ENV = dict(
    _CPU_SMOKE_ENV,
    # The fleet A/B isolates PLACEMENT effects on the prefix cache;
    # spec-on (inherited from cpu_smoke's env) would slow the
    # co-located replicas' decode and convert same-question repeats
    # into same-wave misses via queue buildup — charging placement for
    # speculation. Spec keeps its own gated coverage in cpu_smoke.
    APP_ENGINE_SPECDECODEENABLE="off",
    # ... and no resident draft either: a draft proposer builds its
    # runtime even with speculation off, and cpu_smoke's `debug` draft
    # (a 128-token window) cannot mirror debug-1k's 1024 positions.
    APP_ENGINE_SPECPROPOSER="lookup",
    APP_ENGINE_PREFIXCACHESLOTS="16",
    # A prefix-cache "hit" counts at >= one chunk of shared prefix, and
    # EVERY request of a chain shares its ~226-token preamble — at
    # cpu_smoke's 16-token chunk the preamble alone (14 chunks) matches
    # on any warm replica under ANY policy, so binary hit rate cannot
    # see placement at all. A 256-token chunk puts the smallest
    # cacheable prefix past the preamble: a hit then requires the
    # session's own earlier turns or the question's own cached full
    # prompt — i.e. exactly the within-key reuse placement preserves
    # and round-robin scatters.
    APP_ENGINE_PREFILLCHUNK="256",
    APP_ENGINE_WARMUPPROMPTLENGTHS="256",
    # Headroom over the deepest session turn (~650 byte-tokenizer ids):
    # the debug model's 128-token window would tail-TRUNCATE every
    # prompt, shifting the whole token sequence per turn and destroying
    # all prefix structure — the A/B would measure truncation, not
    # placement. debug-1k is debug's dims with a 1024-token window (the
    # engine clamps max_seq_len to the MODEL's window, so raising the
    # engine knob alone would silently do nothing).
    APP_ENGINE_MODELCONFIGNAME="debug-1k",
    APP_ENGINE_MAXSEQLEN="1024",
    # The A/B isolates PLACEMENT: bounded-load spill stays on in the
    # production defaults (and is pinned deterministically by
    # tests/test_router.py), but here every debug replica shares one
    # host's cores, so router-side inflight skew reflects host
    # contention, not replica capacity — spurious spill would charge
    # placement for scheduling noise.
    APP_ROUTER_LOADBOUND="0",
    APP_ROUTER_SPILLQUEUEDEPTH="0",
)
_FLEET_SMOKE_SPEC = WorkloadSpec(
    name="fleet_smoke",
    seed=97531,
    scenarios=(
        ScenarioSpec(
            name="ingest_seed",
            kind="ingest",
            docs=2,
            doc_kb=2,
        ),
        # kb=False: a turn's prompt literally EXTENDS the previous
        # turn's (preamble + growing history), so session reuse is
        # within-key — the reuse placement can actually preserve. With
        # kb on, retrieval injects the current question's context ahead
        # of the history and most reuse becomes CROSS-key (different
        # questions sharing retrieved chunks), which no content-keyed
        # placement can co-locate — that component is measured by the
        # rag_repeat scenario's repeated identical questions instead.
        # Offered load stays comfortably under one debug engine's
        # capacity: a same-question repeat only HITS if the first
        # occurrence's prefill finished before the repeat is admitted
        # (insert is post-prefill), so queue buildup converts real
        # reuse into same-wave misses — and the co-located fleet
        # passes, sharing one host's cores, queue more than the single
        # pass, which would charge placement for host contention.
        ScenarioSpec(
            name="chat",
            kind="sessions",
            start_s=0.8,
            sessions=6,
            turns=4,
            think_time_s=0.4,
            # A wide pool: each session's opening question (= its
            # placement key AND its radix-cache root) is almost surely
            # unique, so sessions spread over the ring instead of
            # colliding on one replica.
            question_pool=64,
            use_knowledge_base=False,
            max_tokens=8,
        ),
        ScenarioSpec(
            name="rag_repeat",
            kind="poisson",
            start_s=0.8,
            rate_qps=1.5,
            duration_s=6.0,
            question_pool=4,
            use_knowledge_base=True,
            max_tokens=8,
        ),
    ),
)

# Kill-replica chaos workload (tools/loadgen/chaos.py,
# docs/resilience.md): steady traffic long enough for the injector to
# drain one replica mid-decode (live-request checkpoint → sibling
# restore) and SIGKILL the other (mid-stream death → sibling replay),
# with full recovery between events. max_tokens spans several decode
# blocks so a drain's block-boundary capture lands mid-decode (a
# snapshot with emitted tokens — the restorable kind), and NO abort
# fraction: client disconnects would alias with the failover and
# requests_lost accounting the chaos gate exists to pin.
_CHAOS_SMOKE_SPEC = WorkloadSpec(
    name="chaos_smoke",
    seed=31337,
    scenarios=(
        ScenarioSpec(
            name="ingest_seed",
            kind="ingest",
            docs=2,
            doc_kb=2,
        ),
        # Open loop: arrivals keep coming regardless of the chaos the
        # injector causes — exactly the traffic that must not be lost.
        ScenarioSpec(
            name="steady_rag",
            kind="poisson",
            start_s=0.8,
            rate_qps=1.5,
            duration_s=30.0,
            use_knowledge_base=True,
            max_tokens=12,
        ),
        # Closed loop: long multi-turn sessions whose later turns ride
        # through both chaos events (a session's stream is the thing
        # mid-stream bridging protects).
        ScenarioSpec(
            name="chat",
            kind="sessions",
            start_s=0.8,
            sessions=3,
            turns=6,
            think_time_s=1.0,
            question_pool=16,
            use_knowledge_base=False,
            max_tokens=12,
        ),
    ),
)

_CHAOS_SMOKE_ENV = dict(
    _CPU_SMOKE_ENV,
    # The chaos gate measures the preemption machinery, not placement
    # or speculation: spec decode keeps its gated coverage in cpu_smoke
    # (and the kill/restore token-identity matrix covers spec-on
    # restores); here it would only add draft-pipeline settle time to
    # every drain. Load-bound spill off for the same reason as
    # fleet_smoke — co-located replicas share one host's cores, so
    # inflight skew is host contention, and spurious spill would alias
    # with the failover counters the chaos block reports.
    APP_ENGINE_SPECDECODEENABLE="off",
    APP_ROUTER_LOADBOUND="0",
    APP_ROUTER_SPILLQUEUEDEPTH="0",
)

# int4 paged-KV + adaptive-K acceptance leg (docs/paged_kv.md,
# docs/spec_decode.md): the exact cpu_smoke workload against the same
# debug engine with the KV pool packed two-values-per-byte
# (kv_cache_dtype=int4 — paged layout, gather-served on CPU) and
# acceptance-adaptive draft width on. The assertions are the shared
# gates: compiles.hot_path_total==0 (the int4 pool and the adaptive-K
# ladder both resolve to pre-warmed executables — warmup walks every
# (window, K) rung), and the spec block's gated effective_k_mean (the
# random-init debug twins accept at the mechanical ceiling, so K must
# hold at the configured max — adaptive K silently collapsing fails).
_INT4_SMOKE_ENV = dict(
    _CPU_SMOKE_ENV,
    APP_ENGINE_KVCACHEDTYPE="int4",
    APP_ENGINE_SPECADAPTIVEK="on",
)

PROFILES: Dict[str, Profile] = {
    "cpu_smoke": Profile(
        name="cpu_smoke",
        spec=_CPU_SMOKE_SPEC,
        server_env=_CPU_SMOKE_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
    "mixed_phase": Profile(
        name="mixed_phase",
        spec=_MIXED_PHASE_SPEC,
        server_env=_MIXED_PHASE_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
    "retrieval_heavy": Profile(
        name="retrieval_heavy",
        spec=_RETRIEVAL_HEAVY_SPEC,
        server_env=_RETRIEVAL_HEAVY_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
    "full": Profile(
        name="full",
        spec=_FULL_SPEC,
        server_env=_FULL_ENV,
        scrape_interval_s=1.0,
        ready_timeout_s=1800.0,
    ),
    "fleet_smoke": Profile(
        name="fleet_smoke",
        spec=_FLEET_SMOKE_SPEC,
        server_env=_FLEET_SMOKE_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
    "chaos_smoke": Profile(
        name="chaos_smoke",
        spec=_CHAOS_SMOKE_SPEC,
        server_env=_CHAOS_SMOKE_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
    "int4_smoke": Profile(
        name="int4_smoke",
        spec=_CPU_SMOKE_SPEC,
        server_env=_INT4_SMOKE_ENV,
        scrape_interval_s=0.2,
        ready_timeout_s=600.0,
    ),
}
