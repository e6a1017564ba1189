"""Model families: the one door through which the engine reaches a model.

``resolve(model_config_name)`` returns ``(family, model_config)``. A
family is a small record of functions over an OPAQUE cache pytree — the
engine's paged step programs (``engine/llm_engine.py``
``_build_steps``) call these and nothing of a model module:

- ``config_type``: the class of the family's configuration objects
  (what ``family_of`` tells families apart by);
- ``init_params(cfg, seed, dtype)``: seeded random weights on the host;
- ``place_params(params)``: from what ``init_params`` (or a checkpoint)
  gives, already on the device, to the layout the walks read (the
  identity by default; ``llama`` splits its stacked leaves per layer);
- ``init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype,
  quantized, packed, head_sharded)``: the cache pytree (page pools and,
  for a fixed-state family, the per-slot arrays beside them;
  ``head_sharded`` says the engine will shard the pools' heads over a
  mesh, which decides how a quantised pool stores its scale planes,
  ``models/llama.py`` ``kv_scale_plane_shape``);
- ``prefill_paged(params, cfg, caches, tokens, lengths, slots, tables,
  page_size, **paths) -> (logits [N, V], caches)``: a REFERENCE walk, a
  whole prompt in one program. No engine program calls it (every prompt
  is served through ``extend_paged`` from offset zero); it is the walk a
  chunk at offset zero is held to by the tests and by two adapters of
  the benchmark (docs/model_registry.md);
- ``extend_paged(params, cfg, caches, tokens, offsets, valid, slots,
  tables, window, page_size, **paths) -> (hidden [N, D], caches)``;
- ``decode_paged(params, cfg, caches, tokens, positions, live, tables,
  window, page_size, **paths) -> (logits [B, V], caches)``;
- ``extend_packed(params, cfg, caches, tokens [T], starts, counts,
  offsets, slots [R each], tables, page_size, *, seg, windows,
  window_index, n_rows, **paths) -> (hidden [R, D], caches)`` or None:
  the OPTIONAL chunk walk over a packed token axis (a wave's live tokens
  row after row, ``models/llama.py`` ``extend_layers_packed``). A family
  that registers one is sent its prefill waves packed, on one ladder of
  token counts (``engine/scheduler/shapes.py`` ``packed_rungs``); a family
  that registers none keeps ``[rows, width]`` dispatches of
  ``extend_paged`` (docs/model_registry.md);
- ``verify_paged(...)`` or None (no speculative verify program);
- ``head(params, cfg, hidden [N, D], **paths) -> logits [N, V]``;
- the memory plan: ``serving_memory_bytes``, ``count_logical_params``,
  ``paged_kv_shape`` (the geometry of what IS paged: layers, KV heads
  and head size of the pools, query heads of the page kernel's read;
  ``bytes_per_token`` where K and V are not two rows of that size each)
  and ``fixed_state_bytes_per_slot``.

``paths`` are the kernel paths the engine resolved (``use_flash``,
``quant_kernel``, ``tp``, ``page_kernel``, and whatever the family's own
``resolve_kernels`` named); a family takes what it knows and ignores
the rest.

``resolve_kernels(cfg, kind)`` names the kernels a family brings beside
the engine's (``kind`` is ``'compiled'`` on one TPU device,
``'interpret'`` where the engine's ``paged_kernel`` says so, else None):
``{path name: kind}``, handed to every walk as keywords and printed on
the engine's ``resolved kernel paths:`` line.

``extend_reads_window=False`` says the family's extend walk follows each
row's own context (a loop over its pages), so the engine builds one
extend program a chunk width instead of one a window rung.

``stat_names`` / ``read_stats(caches)``: small int32 counts a family's
walks leave in the cache pytree (pairs routed to held experts, tokens a
selection kept). The decode and extend programs hand them back with the
tokens, and the engine writes them into the dispatch's span under these
names (and into the counters of ``engine/llm_engine.py`` ``_STAT_COUNTERS``
that carry the same names).

**What the engine refuses follows what a family DECLARES**
(``engine/llm_engine.py`` ``_validate_family``, the table in
docs/model_registry.md), one declaration a refusal:

- ``fixed_state``: a slot holds state that is NOT pages (a recurrent
  state, a window ring). Such a family gets one row a prefill wave and
  no request snapshot, and prefix-cache reuse only if it names
  ``state_row_keys``: the leaves of its cache that hold one row a slot,
  which a prefix entry then carries beside its pages (a store row saved
  between two chunks of an admission, copied back on a hit:
  docs/prefix_cache.md). A family WITHOUT fixed state has pages only:
  the prefix store shares them by refcount and a wave holds as many rows
  as ``prefill_wave_tokens`` allows, whatever the pools hold.
- ``verify_paged``: None, and speculative decoding is refused.
- ``weight_formats`` / ``kv_formats``: the ``quantization`` and
  ``kv_cache_dtype`` values, beside plain weights and a bfloat16 pool,
  that the family's walks read. Whatever is not named is refused.
- ``sharded``: the walks take a tensor-parallel mesh; False refuses one.
- ``snapshot_pages``: the pools are the per-layer ``{"k", "v"}`` pages a
  request snapshot's payload carries; False refuses ``drain`` /
  ``restore_snapshot`` where they are taken.

``span_fields`` are the constant counts a family adds to the
dispatch-timeline spans.

``llama`` registers through the same door: its presets dict is
``llama.PRESETS`` itself (so a preset written there at run time, as the
benchmark's Mistral adapter does, resolves), its walks are
``models/llama.py``'s, untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class PagedKVShape:
    """What the page pools hold and the page kernel reads."""

    num_layers: int
    num_kv_heads: int
    head_dim: int
    num_heads: int
    # paged bytes a cached token costs, where that is not K and V rows
    # of ``num_kv_heads * head_dim`` each a layer (a latent row read as
    # both; K and V rows plus a page's summary)
    bytes_per_token: Optional[int] = None
    # the pools are head-less latent rows that ops/latent_attention.py
    # reads (its own rule of pages a step), not K and V pages
    latent: bool = False


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    presets: Dict[str, Any]
    config_type: type
    fixed_state: bool
    init_params: Callable[..., Any]
    init_paged_cache: Callable[..., Any]
    prefill_paged: Callable[..., Tuple[Any, Any]]
    extend_paged: Callable[..., Tuple[Any, Any]]
    decode_paged: Callable[..., Tuple[Any, Any]]
    verify_paged: Optional[Callable[..., Tuple[Any, Any]]]
    head: Callable[..., Any]
    serving_memory_bytes: Callable[..., Dict[str, int]]
    count_logical_params: Callable[[Any], int]
    paged_kv_shape: Callable[[Any], PagedKVShape]
    fixed_state_bytes_per_slot: Callable[..., int] = lambda cfg, kv_bytes=2: 0
    span_fields: Callable[[Any], Dict[str, int]] = lambda cfg: {}
    place_params: Callable[[Any], Any] = lambda params: params
    resolve_kernels: Callable[[Any, Optional[str]], Dict[str, Optional[str]]] = lambda cfg, kind: {}
    stat_names: Tuple[str, ...] = ()
    # False: the extend walk follows each row's own context whatever
    # ``window`` says, so the engine names ONE window (capacity) and builds
    # one extend program a width, not one a power-of-two window rung
    extend_reads_window: bool = True
    read_stats: Optional[Callable[[Any], Any]] = None
    # the chunk walk over a packed token axis; None: waves go out as
    # [rows, width] rectangles through ``extend_paged``
    extend_packed: Optional[Callable[..., Tuple[Any, Any]]] = None
    # a fixed-state family the prefix store can carry: the top-level keys
    # of the cache pytree whose every leaf holds ONE ROW A SLOT, first
    # axis. ``init_paged_cache`` is then asked for the decode slots plus
    # ``prefix_cache_slots`` rows, the walks touch the rows their
    # ``slots`` name (decode: the first ``B``), and the engine saves and
    # restores a prefix's state by copying one row of these leaves to
    # another. Empty: the store is refused (engine/prefix_cache.py)
    state_row_keys: Tuple[str, ...] = ()
    # what the walks READ beside plain weights and a bfloat16 pool: values
    # of ``quantization`` and of ``kv_cache_dtype``. Unnamed: refused
    weight_formats: Tuple[str, ...] = ()
    kv_formats: Tuple[str, ...] = ()
    # the walks run over a tensor-parallel mesh (``tp=``, sharded pools)
    sharded: bool = False
    # the pools are per-layer {"k", "v"} pages: what a request snapshot carries
    snapshot_pages: bool = False


_FAMILIES: Dict[str, ModelFamily] = {}


def register_family(family: ModelFamily) -> None:
    _FAMILIES[family.name] = family


def families() -> Dict[str, ModelFamily]:
    _load_builtin()
    return dict(_FAMILIES)


def register_preset(family: str, name: str, cfg: Any) -> None:
    """Add (or replace) a named configuration of a family, e.g. from a
    configuration file's published sizes."""
    families()[family].presets[name] = cfg


def resolve(name: str) -> Tuple[ModelFamily, Any]:
    """The family and configuration ``model_config_name`` names."""
    fams = families()
    for fam in fams.values():
        if name in fam.presets:
            return fam, fam.presets[name]
    known = sorted(n for f in fams.values() for n in f.presets)
    raise KeyError(f"unknown model_config_name {name!r}; known: {known}")


def family_of(cfg: Any) -> ModelFamily:
    """The family a configuration OBJECT belongs to (a checkpoint's
    config never passed through ``resolve``)."""
    for fam in families().values():
        if isinstance(cfg, fam.config_type):
            return fam
    raise KeyError(f"no model family owns a configuration of type {type(cfg).__name__}")


# --------------------------------------------------------------------- //
# The built-in families


def _llama_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import llama

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False,
                         head_sharded=False):
        del num_slots  # every layer's state is pages
        return llama.init_kv_pool(cfg, pool_pages, page_size, dtype, quantized=quantized, packed=packed,
                                  head_sharded=head_sharded)

    def prefill_paged(params, cfg, caches, tokens, lengths, slots, tables, page_size, *,
                      use_flash=None, quant_kernel=None, tp=None, **_):
        # one fresh-K/V forward (prefill_layers never touches a cache),
        # then one pool scatter per layer via the page tables
        logits, kvs = llama.prefill_layers(
            params, cfg, tokens, lengths, use_flash=use_flash, quant_kernel=quant_kernel, tp=tp,
        )
        return logits, llama.write_prefill_pages(caches, kvs, tables[slots], page_size)

    def extend_paged(params, cfg, caches, tokens, offsets, valid, slots, tables, window, page_size, *,
                     quant_kernel=None, tp=None, page_kernel=None, **_):
        return llama.extend_layers_paged(
            params, cfg, tokens, offsets, valid, slots, tables, caches, window, page_size,
            quant_kernel=quant_kernel, tp=tp, page_kernel=page_kernel,
        )

    def extend_packed(params, cfg, caches, tokens, starts, counts, offsets, slots, tables, page_size, *,
                      seg, windows, window_index=0, n_rows=None,
                      quant_kernel=None, tp=None, page_kernel=None, **_):
        return llama.extend_layers_packed(
            params, cfg, tokens, starts, counts, offsets, slots, tables, caches, page_size,
            seg=seg, windows=windows, window_index=window_index, n_rows=n_rows,
            quant_kernel=quant_kernel, tp=tp, page_kernel=page_kernel,
        )

    def decode_paged(params, cfg, caches, tokens, positions, live, tables, window, page_size, *,
                     quant_kernel=None, tp=None, page_kernel=None, **_):
        return llama.decode_layers_paged(
            params, cfg, tokens, positions, live, tables, caches, window=window, page_size=page_size,
            quant_kernel=quant_kernel, tp=tp, page_kernel=page_kernel,
        )

    def verify_paged(params, cfg, caches, tokens, offsets, valid, slots, tables, window, page_size, *,
                     quant_kernel=None, tp=None, page_kernel=None, **_):
        return llama.verify_layers_paged(
            params, cfg, tokens, offsets, valid, slots, tables, caches, window, page_size,
            quant_kernel=quant_kernel, tp=tp, page_kernel=page_kernel,
        )

    def head(params, cfg, hidden, *, quant_kernel=None, tp=None, **_):
        return llama._head(params, hidden[:, None, :], cfg, quant_kernel, tp=tp)[:, 0, :]

    return ModelFamily(
        name="llama", presets=llama.PRESETS, config_type=llama.LlamaConfig, fixed_state=False,
        init_params=llama.init_params_fast, init_paged_cache=init_paged_cache,
        # consumes params (pops stacked leaves as they split), so each
        # stacked buffer frees at once: peak HBM stays ~1x weights, which
        # is what lets 8B-int8 fit a 16 GB chip
        place_params=llama.consume_split_params_layers,
        prefill_paged=prefill_paged, extend_paged=extend_paged, decode_paged=decode_paged,
        verify_paged=verify_paged, head=head, extend_packed=extend_packed,
        serving_memory_bytes=llama.serving_memory_bytes,
        count_logical_params=llama.count_logical_params,
        paged_kv_shape=lambda cfg: PagedKVShape(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads),
        weight_formats=("int8", "w8a8"), kv_formats=("int8", "int4"), sharded=True, snapshot_pages=True,
    )


def _phi4flash_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import phi4flash as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("phi4flash keeps its one paged layer and its fixed state in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="phi4flash", presets=m.PRESETS, config_type=m.Phi4FlashConfig, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # ONE paged layer, in the pair layout the page kernel reads
        paged_kv_shape=lambda cfg: PagedKVShape(1, cfg.pair_kv_heads, cfg.pair_dim, cfg.num_heads),
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        span_fields=lambda cfg: {
            "kv_readers": 1 + len(cfg.layers_of("cross")),
            "window_layers": len(cfg.layers_of("window")),
            "window": cfg.sliding_window,
            # the layers past the shared-KV layer see a chunk's last position only
            "last_position_only": 1,
        },
    )


def _glm5next_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import glm5next as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("glm5next keeps its latent pool and its fixed state in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="glm5next", presets=m.PRESETS, config_type=m.Glm5NextConfig, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # ONE head-less latent row a token and sparse-attention layer,
        # read as key and value by every query head
        paged_kv_shape=lambda cfg: PagedKVShape(
            len(cfg.layers_of("dsa")), 1, cfg.kv_lora_rank, cfg.num_heads,
            bytes_per_token=m.kv_bytes_per_token(cfg), latent=True),
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind, "delta_step": kind},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _gigachat35_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import gigachat35 as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("gigachat35 keeps its latent pool and its fixed state in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="gigachat35", presets=m.PRESETS, config_type=m.GigaChat35Config, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # ONE head-less row a token and latent-attention layer, the padded
        # [c | k_rope] the pool allocates; every query head reads it as key
        # and its first kv_lora_rank columns as value
        paged_kv_shape=lambda cfg: PagedKVShape(
            len(cfg.layers_of("mla")), 1, cfg.latent_row, cfg.num_heads,
            bytes_per_token=m.kv_bytes_per_token(cfg), latent=True),
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        # the chunk walk's latent read where the widths tile the chip (ops/latent_attention.py)
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind, "delta_step": kind,
                                           "latent_chunk": m.latent_chunk_kind(cfg, kind)},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _afmoe_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import afmoe as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("afmoe keeps its paged full layers and its window rings in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="afmoe", presets=m.PRESETS, config_type=m.AfmoeConfig, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # the full layers alone are paged, plain GQA in head-major pages
        paged_kv_shape=lambda cfg: PagedKVShape(
            len(cfg.layers_of("full")), cfg.num_kv_heads, cfg.head_dim, cfg.num_heads),
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        # no "window_layers": window_tokens_read is a step stat here (counted on the device)
        span_fields=lambda cfg: {"kv_readers": len(cfg.layers_of("full"))},
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _solaropen2_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import solaropen2 as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("solaropen2 keeps its paged softmax layers and its fixed state in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="solaropen2", presets=m.PRESETS, config_type=m.SolarOpen2Config, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # the softmax layers alone are paged, plain GQA in head-major pages
        paged_kv_shape=lambda cfg: PagedKVShape(
            len(cfg.layers_of("full")), cfg.num_kv_heads, cfg.head_dim, cfg.num_heads),
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        span_fields=lambda cfg: {"kv_readers": len(cfg.layers_of("full"))},
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind, "delta_step": kind},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
        # KDA's state and the convolution's tail: what a prefix entry carries
        state_row_keys=m.STATE_ROW_KEYS,
    )


def _kimik2_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import kimik2 as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("kimik2 keeps its latent pools in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        # pages only: no layer keeps state beside the latent pools, so the
        # prefix store shares a prompt's pages by refcount (no state row)
        name="kimik2", presets=m.PRESETS, config_type=m.KimiK2Config, fixed_state=False,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # ONE head-less row a token and LAYER, the padded [c | k_rope] the
        # pools allocate; every query head reads it as key and its first
        # kv_lora_rank columns as value
        paged_kv_shape=lambda cfg: PagedKVShape(
            cfg.num_layers, 1, cfg.latent_row, cfg.num_heads, bytes_per_token=m.kv_bytes_per_token(cfg),
            latent=True),
        span_fields=lambda cfg: {"latent_layers": cfg.num_layers},
        # the chunk walk's latent read where the widths tile the chip (ops/latent_attention.py)
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind, "latent_chunk": m.latent_chunk_kind(cfg, kind)},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _minimaxm3_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import minimaxm3 as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("minimaxm3 keeps its pages and their summaries in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        # pages only: K and V rows and a summary a page, all indexed by
        # physical page, so the prefix store shares them by refcount
        name="minimaxm3", presets=m.PRESETS, config_type=m.MiniMaxM3Config, fixed_state=False,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # plain GQA in head-major pages in EVERY layer, and one [Hkv, Dh]
        # summary row a page beside them (what bytes_per_token adds)
        paged_kv_shape=lambda cfg: PagedKVShape(
            cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads,
            bytes_per_token=m.kv_bytes_per_token(cfg)),
        span_fields=lambda cfg: {"kv_readers": cfg.num_layers, "msa_pages_a_read": cfg.pages_a_read},
        # the decode step's read of the SELECTED pages (ops/page_attention.py selected_page_attention) and the
        # chunk walk's masked read of the live ones (ops/selected_chunk_read.py), each where the widths tile
        resolve_kernels=lambda cfg, kind: {"grouped_matmul": kind, "selected_read": m.selected_read_kind(cfg, kind),
                                           "selected_chunk": m.selected_chunk_kind(cfg, kind)},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _evabyte_family() -> ModelFamily:
    from generativeaiexamples_tpu.models import evabyte as m

    def init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype, quantized=False, packed=False, **_):
        if quantized or packed:
            raise ValueError("evabyte keeps its window buffers and its summary pages in bfloat16")
        return m.init_paged_cache(cfg, pool_pages, page_size, num_slots, dtype)

    return ModelFamily(
        name="evabyte", presets=m.PRESETS, config_type=m.EvaByteConfig, fixed_state=True,
        init_params=m.init_params_fast, init_paged_cache=init_paged_cache,
        prefill_paged=m.prefill_paged, extend_paged=m.extend_paged, decode_paged=m.decode_paged,
        verify_paged=None, head=lambda params, cfg, hidden, **_: m.head(params, cfg, hidden),
        serving_memory_bytes=m.serving_memory_bytes, count_logical_params=m.count_logical_params,
        # what is paged is a K and a V SUMMARY row a chunk of tokens, every layer: a page of ``page_size``
        # tokens is ``page_size / chunk_size`` rows, which ``bytes_per_token`` says and the head sizes do not
        paged_kv_shape=lambda cfg: PagedKVShape(
            cfg.num_layers, cfg.num_heads, cfg.head_dim, cfg.num_heads, bytes_per_token=m.kv_bytes_per_token(cfg)),
        # the open window's exact K and V, restarted at every window boundary
        fixed_state_bytes_per_slot=m.fixed_state_bytes_per_slot,
        span_fields=lambda cfg: {"eva_layers": cfg.num_layers, "eva_window": cfg.window_size,
                                 "eva_chunk": cfg.chunk_size},
        # the decode step's one-softmax read of buffer and pages (ops/eva_read.py)
        resolve_kernels=lambda cfg, kind: {"eva_read": m.eva_read_kind(cfg, kind)},
        stat_names=m.STAT_NAMES, read_stats=m.read_stats, extend_reads_window=False,
    )


def _load_builtin() -> None:
    if not _FAMILIES:
        register_family(_llama_family())
        register_family(_phi4flash_family())
        register_family(_glm5next_family())
        register_family(_gigachat35_family())
        register_family(_afmoe_family())
        register_family(_solaropen2_family())
        register_family(_kimik2_family())
        register_family(_minimaxm3_family())
        register_family(_evabyte_family())
