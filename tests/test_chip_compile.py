"""The main path's Pallas kernels compile for the real chip, at
Llama-3-8B widths, without a chip.

The TPU's compiler is installed on CPU-only machines and compiles for a
device that is DESCRIBED, not attached (``v5e:2x2``). Interpret-mode
tests cannot see what it refuses: a slice off the tiling grid, a kernel
over its fast-memory budget, a program that cannot be partitioned. Each
test asserts the kernel is really in the program (``tpu_custom_call``).
A compile that passes is not a chip run — ``chip_smoke.py`` is.

Only one process may load libtpu, and it keeps it until exit, so the
topology is described INSIDE a module-scoped fixture of this one file:
never at import, in a ``skipif``, in ``parametrize`` or in conftest.py
(xdist workers would collect different tests and run none), and every
compile happens in this process (a child could not load the library).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import (
    delta_rule,
    flash_attention,
    grouped_matmul,
    int8_matmul,
    latent_attention,
    page_attention,
)

# Llama-3-8B (models/llama.py LlamaConfig defaults)
HIDDEN, MLP, VOCAB = 4096, 14336, 128256
HQ, HKV, DH = 32, 8, 128
PAGE = 128
# pages of a row a grid step of the decode-side latent reads walks at every cell's shapes (PR 51)
LATENT_PAGES_A_STEP = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pack_shapes(sharding, K, F):
    """A packed int8 weight as ops/quant.py lays it out (F padded to the
    kernel's block) plus its logical-F scale row."""
    f_pad = -(-F // int8_matmul.F_BLK) * int8_matmul.F_BLK
    return (
        jax.ShapeDtypeStruct((K, f_pad), jnp.int8, sharding=sharding),
        jax.ShapeDtypeStruct((1, F), jnp.float32, sharding=sharding),
    )


@pytest.mark.parametrize(
    "M,K,F",
    [
        (16, HIDDEN, HIDDEN + 2 * HKV * DH),  # fused QKV
        (16, HIDDEN, 2 * MLP),  # fused gate|up
        (64, MLP, HIDDEN),  # down
        (16, HIDDEN, VOCAB),  # lm_head (F padded to the block)
    ],
)
def test_int8_matmul_compiles(one_chip, no_persistent_cache, M, K, F):
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    q, scale = _pack_shapes(one_chip, K, F)
    text = _compiled_text(int8_matmul.int8_matmul, x, q, scale)
    assert "tpu_custom_call" in text


def test_int8_w8a8_matmul_compiles(one_chip, no_persistent_cache):
    x = jax.ShapeDtypeStruct((16, MLP), jnp.bfloat16, sharding=one_chip)
    q, scale = _pack_shapes(one_chip, MLP, HIDDEN)
    text = _compiled_text(int8_matmul.int8_w8a8_matmul, x, q, scale)
    assert "tpu_custom_call" in text


def _paged_args(sharding, T, kv_dtype, head_spec=None, B=16, pages_per_row=32):
    """Shapes of one paged-attention read. ``head_spec`` shards the head
    axes for the TP variant."""
    def s(shape, dtype, spec=None):
        sh = sharding(spec) if callable(sharding) else sharding
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    n_pages = B * pages_per_row + 1
    quantized = kv_dtype == jnp.int8
    h = head_spec
    args = [
        s((B, T, HQ, DH), jnp.bfloat16, P(None, None, h, None)),
        s((n_pages, PAGE, HKV, DH), kv_dtype, P(None, None, h, None)),
        s((n_pages, PAGE, HKV, DH), kv_dtype, P(None, None, h, None)),
        s((B, pages_per_row), jnp.int32, P()),
        s((B,), jnp.int32, P()),
    ]
    if quantized:
        # as init_kv_pool stores them: lane-dense on one chip, [P, page, Hkv] where the heads are sharded
        plane = (n_pages,) + llama.kv_scale_plane_shape(PAGE, HKV, head_sharded=h is not None)
        args += [s(plane, jnp.float32, P(None, None, h))] * 2
    return args


@pytest.mark.parametrize(
    "T,kv_dtype",
    [(1, jnp.int8), (1, jnp.bfloat16), (5, jnp.int8)],
    ids=["decode-int8", "decode-bf16", "verify5-int8"],
)
def test_paged_attention_compiles(one_chip, no_persistent_cache, T, kv_dtype):
    assert page_attention.supports_geometry(
        PAGE, DH, HQ, HKV, T,
        kv_dtype="int8" if kv_dtype == jnp.int8 else "bfloat16",
    )
    text = _compiled_text(
        page_attention.paged_attention, *_paged_args(one_chip, T, kv_dtype)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "T,heads,kv_dtype,head_major,n",
    [
        (1, (32, 8), jnp.int8, False, 2),  # Mistral's int8 token-major pool, scale planes lane-dense [P, 8, 128]
        (16, (32, 8), jnp.int8, False, 1),  # ... its folded extend read: 512 score rows a page
        (1, (32, 8), "int8-head-sharded", False, 1),  # ... scale planes [P, 128, 8], as a head-sharded pool keeps them
        (1, (40, 10), jnp.bfloat16, True, 2),  # Phi-4-flash's pair layout
        (1, (32, 4), jnp.bfloat16, True, 2),  # Trinity-Mini's full layer
        (1, (64, 8), jnp.bfloat16, True, 2),  # Solar-Open2's one softmax layer
    ],
    ids=["mistral-int8", "mistral-int8-fold16", "mistral-int8-token-major-scales", "phi4flash-pairs", "trinity-32-4", "solaropen2-64-8"],
)
def test_grouped_paged_attention_compiles_at_the_served_geometries(
    one_chip, no_persistent_cache, T, heads, kv_dtype, head_major, n
):
    """Several pages of a row a grid step: every pool operand is passed
    once a place of the group, at the pages a step the rule gives each
    served geometry (and at four, which the tests walk)."""
    hq, hkv = heads
    B, pages_per_row = 16, 32
    n_pages = B * pages_per_row + 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    head_sharded = kv_dtype == "int8-head-sharded"
    kv_dtype = jnp.int8 if head_sharded else kv_dtype
    pool = s((n_pages, hkv, PAGE, DH) if head_major else (n_pages, PAGE, hkv, DH), kv_dtype)
    scales = []
    if kv_dtype == jnp.int8:
        plane = llama.kv_scale_plane_shape(PAGE, hkv, head_sharded)
        assert plane == ((PAGE, hkv) if head_sharded else (PAGE * hkv // 128, 128))
        scales = [s((n_pages,) + plane, jnp.float32)] * 2
    assert page_attention.pages_per_step(pool, *scales[:1], query_len=T) == n

    for pages in (None, 4):
        def fn(q, k, v, tables, pos, *sc):
            return page_attention.paged_attention(
                q, k, v, tables, pos, *sc, head_major=head_major, group=pages
            )

        text = _compiled_text(
            fn, s((B, T, hq, DH), jnp.bfloat16), pool, pool,
            s((B, pages_per_row), jnp.int32), s((B,), jnp.int32), *scales,
        )
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("T", [512, 2048])
def test_flash_attention_compiles(one_chip, no_persistent_cache, T):
    def s(heads):
        return jax.ShapeDtypeStruct((1, T, heads, DH), jnp.bfloat16, sharding=one_chip)

    text = _compiled_text(flash_attention.flash_attention_causal, s(HQ), s(HKV), s(HKV))
    assert "tpu_custom_call" in text


# the decode-side latent reads at the three cells' shapes: (rows, table pages, row columns, value columns, bias)
LATENT_DECODE_CELLS = {
    "gigachat35": (64, 64, 640, 512, False),
    "kimik25": (32, 192, 640, 512, False),
    "glm53flash": (64, 64, 512, 512, True),
}


@pytest.mark.parametrize("cell", sorted(LATENT_DECODE_CELLS))
def test_dense_latent_attention_compiles_with_a_key_wider_than_the_value(one_chip, no_persistent_cache, cell):
    """The decode-side latent reads at the published widths, the pages a
    grid step that ``latent_pages_per_step`` names for each cell's shapes
    (every place of a step its own block operand): models/gigachat35.py
    and models/kimik2.py, 64 heads against rows of [c 512 | k_rope 64]
    padded to 640 columns, the value the first 512 (a slice on a lane
    tile); models/glm5next.py, rows of 512 read whole under a bias that
    is one block a step."""
    B, pages, W, R, biased = LATENT_DECODE_CELLS[cell]
    H = 64
    assert page_attention.supports_geometry(PAGE, W, H, 1) and not page_attention.supports_geometry(PAGE, 576, H, 1)
    n = latent_attention.latent_pages_per_step(PAGE, W, jnp.bfloat16, pages)
    assert n == LATENT_PAGES_A_STEP and pages % n == 0

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def read(q, pool, tables, positions, *bias):
        if bias:
            return latent_attention.latent_attention(q, pool, *bias, tables, positions, scale=0.0625)
        return latent_attention.dense_latent_attention(q, pool, tables, positions, value_dim=R, scale=0.1053)

    compiled = jax.jit(read).lower(
        s((B, H, W), jnp.bfloat16), s((B * pages + 1, PAGE, W), jnp.bfloat16), s((B, pages), jnp.int32),
        s((B,), jnp.int32), *([s((B, pages * PAGE), jnp.float32)] if biased else [])).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and ("latent_attention" if biased else "latent_attention_dense") in text
    # the work list and its intermediates, nothing of the pool's size (0.67 GB), beside the arguments
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("rows, T", [(1, 512), (1, 128), (4, 512)], ids=["chunk-512", "chunk-128", "four-rows"])
def test_latent_chunk_read_compiles_at_the_published_widths(one_chip, no_persistent_cache, rows, T):
    """The chunk walk's latent read of models/kimik2.py and
    models/gigachat35.py alone (ops/latent_attention.py
    ``latent_chunk_read``): 64 heads of 128 | 64 against a latent of 512
    in rows of 640 columns, a table of 192 pages a row (``max_seq_len``
    24,576), eight pages and four heads a grid step, a run-time count of
    steps on the grid's second axis, 64 MB of VMEM allowed."""
    H, dn, dr, Dv, R, W, pages = 64, 128, 64, 128, 512, 640, 192
    assert latent_attention.chunk_read_supported(dn, dr, Dv, R, W, T, PAGE)
    assert latent_attention.chunk_block_pages(PAGE, pages) == 8 and latent_attention.chunk_heads_per_step(H) == 4

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def read(q_nope, q_rope, pool, tables, positions, n_tokens, wuk, wuv):
        return latent_attention.latent_chunk_read(q_nope, q_rope, pool, tables, positions, n_tokens, wuk, wuv, scale=0.1351)

    bf16 = jnp.bfloat16
    compiled = jax.jit(read).lower(
        s((rows, H, T, dn), bf16), s((rows, H, T, dr), bf16), s((6145, PAGE, W), bf16), s((rows, pages), jnp.int32),
        s((rows, T), jnp.int32), s((rows,), jnp.int32), s((H, dn, R), bf16), s((H, R, Dv), bf16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_chunk_read" in text
    # beside the arguments: the queries laid [q_nope | q_rope | 0] a head, and nothing of a score's size
    assert compiled.memory_analysis().temp_size_in_bytes < rows * H * T * (dn + 128) * 2 + (4 << 20)


@pytest.mark.parametrize("tokens,E", [(64, 16), (512, 16), (32, 12)],
                         ids=["decode-16-row-tiles", "chunk-64-row-tiles", "kimi-decode-12-held"])
def test_grouped_matmul_compiles_at_7168_wide_experts(one_chip, no_persistent_cache, tokens, E):
    """``row_tile`` and the column blocks were chosen at 4096 x 2048
    experts; the same kernels at 7168 x 2048, 16 held, 8 pairs a token
    (and Kimi-K2.5's decode step: 32 rows, 12 held). The grid's tile axis
    is a run-time bound (PR 55): Mosaic must take it with the tile's
    expert prefetched beside it."""
    D, F, k = 7168, 2048, 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def mlp(x, local, gates, w_gu, w_d):
        return grouped_matmul.grouped_mlp(x, local, gates, w_gu, w_d, limit=10.0, kernel="compiled")

    text = _compiled_text(mlp, s((tokens, D), jnp.bfloat16), s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
                          s((E, D, 2 * F), jnp.bfloat16), s((E, F, D), jnp.bfloat16))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("tokens", [64, 512], ids=["decode-160-tiles-of-16", "chunk-192-tiles-of-64"])
def test_grouped_matmul_compiles_at_128_fine_grained_experts(one_chip, no_persistent_cache, tokens):
    """models/afmoe.py's shape: ALL 128 experts of 2048 x 1024 held, 8
    pairs a token, no clamp (``limit`` = inf): 128 groups, up to 160
    tiles of 16 rows a decode step, 192 of 64 a chunk."""
    D, F, E, k = 2048, 1024, 128, 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def mlp(x, local, gates, w_gu, w_d):
        return grouped_matmul.grouped_mlp(x, local, gates, w_gu, w_d, limit=float("inf"), kernel="compiled")

    text = _compiled_text(mlp, s((tokens, D), jnp.bfloat16), s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
                          s((E, D, 2 * F), jnp.bfloat16), s((E, F, D), jnp.bfloat16))
    assert text.count("tpu_custom_call") >= 2


def _delta_rule_shapes(sharding, key_heads, per_channel, layers=None):
    """The delta-rule step's operands at the two expert cells' shapes:
    64 slots x 64 value heads x [128, 128] float32 a layer."""
    N, H, D = 64, 64, 128

    def s(*shape, dtype=jnp.float32):
        shape = shape if layers is None else (layers,) + shape
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (s(N, H, D, D), s(N, key_heads, D), s(N, key_heads, D), s(N, H, D), s(N, H),
            s(N, H, D) if per_channel else s(N, H), jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=sharding))


def _delta_rule_step(S, q, k, v, beta, g, live):
    if g.ndim == 2:  # one decay a head (Gated DeltaNet): handed over broadcast
        g = jnp.broadcast_to(g[..., None], g.shape + S.shape[2:3])
    return delta_rule.delta_rule_step(S, q, k, v, beta, g, live)


@pytest.mark.parametrize("key_heads,per_channel", [(64, True), (32, False)], ids=["kda-64-key-heads", "gdn-32-key-heads"])
def test_delta_rule_step_compiles_at_the_cells_shapes(one_chip, no_persistent_cache, key_heads, per_channel):
    """models/glm5next.py's shape (per-channel decay, a key head a value
    head) and models/gigachat35.py's (one decay a head broadcast, a key
    head feeding two value heads through the block index map)."""
    compiled = jax.jit(_delta_rule_step, donate_argnums=0).lower(
        *_delta_rule_shapes(one_chip, key_heads, per_channel)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    state = 64 * 64 * 128 * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state and mem.temp_size_in_bytes < state // 8  # in place: no second state


def test_delta_rule_decode_block_keeps_four_layers_of_state_in_place(one_chip, no_persistent_cache):
    """The decode program's side of it: a ``lax.scan`` of two steps (the
    expert cells' ``decode_block``) over four layers of donated state,
    each advanced by the kernel: every layer's state is aliased from the
    program's argument to its result, and no state-sized buffer appears
    among the temporaries (1.07 GB of state on a chip that has 4 GB free)."""
    layers, state = 4, 64 * 64 * 128 * 128 * 4

    def block(states, q, k, v, beta, g, live):
        def body(states, _):
            outs = [_delta_rule_step(S, q[l], k[l], v[l], beta[l], g[l], live) for l, S in enumerate(states)]
            return [S for _, S in outs], sum(o for o, _ in outs)

        return jax.lax.scan(body, states, None, length=2)

    S, *rest, live = _delta_rule_shapes(one_chip, 32, False, layers=layers)
    one = jax.ShapeDtypeStruct(S.shape[1:], S.dtype, sharding=one_chip)
    compiled = jax.jit(block, donate_argnums=0).lower([one] * layers, *rest, live).compile()
    assert compiled.as_text().count("tpu_custom_call") >= layers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layers * state and mem.temp_size_in_bytes < state // 8


def _sampler_args(sharding, V, B=64):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding(shape))

    return (s((B, V), jnp.float32), s((B, 2), jnp.uint32), s((B,), jnp.float32),
            s((B,), jnp.float32), s((B,), jnp.bool_))


def _sort_widths(text):
    import re

    return [int(w) for w in re.findall(r"= \(?[a-z0-9]+\[\d+,(\d+)\]\S* (?:[^=]*)sort\(", text)]


@pytest.mark.parametrize("V", [19360, 32768, 200064])
def test_sampler_never_sorts_the_vocabulary(one_chip, no_persistent_cache, V):
    """models/sampling.py at the cells' vocabularies, 64 rows: the chip's
    compiler sorts nothing wider than the second rung's group maxima and
    candidates (a lax.top_k whose result is sliced again is sorted WHOLE
    unless a barrier keeps XLA from merging the slices: 18.7 ms a step
    at 200,064 against 1.9, my chip runs, PR 36), and the first rung
    reads the vocabulary in place: no operation writes a second copy."""
    from generativeaiexamples_tpu.models import sampling

    text = _compiled_text(sampling.sample_tokens, *_sampler_args(lambda shape: one_chip, V))
    groups = sampling.top_k_groups(V, sampling.NUCLEUS_TOP_K)
    assert groups == (128, 16)
    widths = _sort_widths(text)
    assert widths and max(widths) <= max(-(-V // 128), sampling.NUCLEUS_TOP_K * 16), widths
    if V % 128 == 0:
        assert not _vocabulary_copies(text, 64 * V)


def _vocabulary_copies(text, elements):
    """Instructions that write an array as large as the logits and are a
    copy by opcode or by the fusion's name (``copy_bitcast_fusion``)."""
    import math
    import re

    out = []
    for name, dims, opcode in re.findall(r"^\s*(?:ROOT )?%(\S+) = f32\[([\d,]+)\]\S* ([a-z-]+)\(", text, re.M):
        if math.prod(int(d) for d in dims.split(",")) == elements and (
            opcode in ("copy", "reshape", "transpose")
            or opcode == "fusion" and name.startswith(("copy", "transpose"))
        ):  # (copy-start / copy-done of the logits is a prefetch of this test's own parameter)
            out.append(name)
    return out


def test_sampler_compiles_with_the_vocabulary_sharded_over_four_chips(topo, no_persistent_cache):
    import numpy as np

    from generativeaiexamples_tpu.models import sampling
    from generativeaiexamples_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
    )

    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(1, 1, 1, 4),
        (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS),
    )

    def sharding(shape):
        return NamedSharding(mesh, P(None, MODEL_AXIS) if shape[-1] == 128256 else P())

    text = _compiled_text(sampling.sample_tokens, *_sampler_args(sharding, VOCAB, B=16))
    assert max(_sort_widths(text)) < VOCAB // 4


def test_paged_attention_tp_compiles_over_four_chips(topo, no_persistent_cache):
    """The shard_map head-sharded page kernel over a 4-device ``model``
    mesh: kernel tiles on every device, no gather of the pool."""
    import numpy as np

    from generativeaiexamples_tpu.parallel import tp_kernels
    from generativeaiexamples_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
    )

    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(1, 1, 1, 4),
        (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS),
    )
    tp = tp_kernels.TPContext(mesh, 4, interpret=False)
    args = _paged_args(
        lambda spec: NamedSharding(mesh, spec), 1, jnp.int8, head_spec=MODEL_AXIS
    )

    def fn(q, k, v, tables, pos, ks, vs):
        return tp_kernels.paged_attention_tp(
            q, k, v, tables, pos, ks, vs, tp=tp, interpret=False
        )

    # the list is built over the whole pool's geometry and walked by
    # every device over its own two KV heads (an int8 page keeps its own step)
    assert page_attention.pages_per_step(args[1], args[5]) == 1
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


# --------------------------------------------------------------------------- #
# Solar-Open2 (models/solaropen2.py): experts of width 1280, a state that
# holds the prefix store's rows behind the slots', and the whole step
# programs at the configuration's shapes


@pytest.mark.parametrize("tokens", [64, 512], ids=["decode-72-tiles-of-16", "chunk-104-tiles-of-64"])
def test_grouped_matmul_compiles_at_40_experts_of_width_1280(one_chip, no_persistent_cache, tokens):
    """1280 is ten lane tiles: the gate/up block is 256 columns (512 does
    not divide the width; the whole width twice over would be 42 MB of
    VMEM), the down block 1024 of 4096."""
    D, F, E, k = 4096, 1280, 40, 8
    assert grouped_matmul._col_block(F, 512) == 256 and grouped_matmul._col_block(D, 1024) == 1024

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def mlp(x, local, gates, w_gu, w_d):
        return grouped_matmul.grouped_mlp(x, local, gates, w_gu, w_d, limit=float("inf"), kernel="compiled")

    text = _compiled_text(mlp, s((tokens, D), jnp.bfloat16), s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
                          s((E, D, 2 * F), jnp.bfloat16), s((E, F, D), jnp.bfloat16))
    assert text.count("tpu_custom_call") >= 2


def test_delta_rule_step_walks_the_slots_of_a_state_that_holds_store_rows_too(one_chip, no_persistent_cache):
    """64 slots' rows of a [64 + 96, 64, 128, 128] float32 state advance
    in place: the whole array is aliased from argument to result and no
    state-sized temporary appears (the 96 store rows are never fetched)."""
    S, q, k, v, beta, g, live = _delta_rule_shapes(one_chip, 64, True)
    whole = jax.ShapeDtypeStruct((160,) + S.shape[1:], S.dtype, sharding=one_chip)
    compiled = jax.jit(_delta_rule_step, donate_argnums=0).lower(whole, q, k, v, beta, g, live).compile()
    assert "tpu_custom_call" in compiled.as_text()
    state = 160 * 64 * 128 * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state and mem.temp_size_in_bytes < state // 20


@pytest.fixture(scope="module")
def solaropen2_programs(one_chip):
    """The family's walks at the benchmark configuration's shapes, on
    shapes alone (``jax.eval_shape``): (config, params, cache, family)."""
    import functools
    import json

    from generativeaiexamples_tpu.models import registry
    from perfbench import arch

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs", "solar-open2-250b-ep8-bf16.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    arch.load(cfg).register(cfg)
    family, mc = registry.resolve(cfg["name"])
    eng = cfg["engine"]
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(lambda: family.init_params(mc, 0, jnp.bfloat16)))
    rows = eng["max_batch_size"] + eng["prefix_cache_slots"]
    cache = on_chip(jax.eval_shape(functools.partial(
        family.init_paged_cache, mc, eng["kv_pool_pages"], eng["page_size"], rows, jnp.bfloat16)))
    return cfg, mc, family, params, cache


def test_solaropen2_step_programs_compile_at_the_configurations_shapes(one_chip, no_persistent_cache, solaropen2_programs):
    """Decode (a scan of ``decode_block`` steps over 64 rows) and the two
    extend widths (one row of 512 and of 128 tokens) for the described
    chip: every kernel in the program, the cache in place, and what the
    program holds beside its arguments far under the 4 GB the plan leaves."""
    cfg, mc, family, params, cache = solaropen2_programs
    eng = cfg["engine"]
    page, B, seq = eng["page_size"], eng["max_batch_size"], eng["max_seq_len"]
    kernels = family.resolve_kernels(mc, "compiled")
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    tables = i32(B, seq // page)

    def decode(params, caches, tokens, positions, live, tables):
        def body(carry, _):
            tokens, positions, caches = carry
            logits, caches = family.decode_paged(params, mc, caches, tokens, positions, live, tables, seq, page,
                                                 page_kernel="compiled", **kernels)
            return (jnp.argmax(logits, -1).astype(jnp.int32), positions + 1, caches), tokens
        return jax.lax.scan(body, (tokens, positions, caches), None, length=eng["decode_block"])

    def extend(params, caches, tokens, offsets, valid, slots, tables):
        return family.extend_paged(params, mc, caches, tokens, offsets, valid, slots, tables, seq, page, **kernels)

    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert cache_bytes == pytest.approx(cfg["memory_plan"]["fixed_state_bytes"] + cfg["memory_plan"]["page_pool_bytes"], rel=1e-4)
    live = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(params, cache, i32(B), i32(B), live, tables).compile()
    mem = compiled.memory_analysis()
    # grouped matmul x2 and a mixer's kernel (page attention or the delta rule's step) a layer
    assert compiled.as_text().count("tpu_custom_call") >= 3 * len(mc.layers)
    assert mem.alias_size_in_bytes >= cache_bytes - 1024 and mem.temp_size_in_bytes < 0.5e9  # (the few counts are written anew)
    for width in (eng["prefill_chunk"], 128):
        compiled = jax.jit(extend, donate_argnums=(1,)).lower(
            params, cache, i32(1, width), i32(1), i32(1), i32(1), tables).compile()
        mem = compiled.memory_analysis()
        assert compiled.as_text().count("tpu_custom_call") >= 2 * len(mc.layers)
        assert mem.alias_size_in_bytes >= cache_bytes - 1024 and mem.temp_size_in_bytes < 1.0e9


def test_the_prefix_state_copy_moves_one_row_in_place(one_chip, no_persistent_cache, solaropen2_programs):
    """The engine's save / restore program (engine/llm_engine.py
    ``prefix_state_copy``) over the configuration's cache: the whole
    cache aliased, no temporary to speak of."""
    _, _, family, _, cache = solaropen2_programs

    def copy(caches, rows):
        new = dict(caches)
        for key in family.state_row_keys:
            new[key] = jax.tree.map(lambda leaf: leaf.at[rows[1]].set(leaf[rows[0]]), caches[key])
        return new, rows + 0

    rows = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    mem = jax.jit(copy, donate_argnums=(0,)).lower(cache, rows).compile().memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes and mem.temp_size_in_bytes < 64 * 1024 * 1024


# --------------------------------------------------------------------------- #
# Kimi-K2.5 (models/kimik2.py): five latent pools a step and nothing a slot,
# 12 experts of 2048, a table of 192 pages a row (max_seq_len 24,576)


@pytest.fixture(scope="module")
def kimik2_programs(one_chip):
    """The family's walks at the benchmark configuration's shapes, on
    shapes alone (``jax.eval_shape``): (config, model config, family, params, cache)."""
    import functools
    import json

    from generativeaiexamples_tpu.models import registry
    from perfbench import arch

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs", "kimi-k2.5-ep32-bf16.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    arch.load(cfg).register(cfg)
    family, mc = registry.resolve(cfg["name"])
    eng = cfg["engine"]
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(lambda: family.init_params(mc, 0, jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(functools.partial(
        family.init_paged_cache, mc, eng["kv_pool_pages"], eng["page_size"], eng["max_batch_size"], jnp.bfloat16)))
    return cfg, mc, family, params, cache


@pytest.mark.parametrize("program", ["decode", "extend-512", "extend-128", "extend-512-four-rows"])
def test_kimik2_step_programs_compile_at_the_configurations_shapes(one_chip, no_persistent_cache, kimik2_programs, program):
    """Decode (a scan of ``decode_block`` steps over 32 rows: five latent
    reads and four grouped products a step) and the extend widths (one
    row of 512 and of 128 tokens; four rows, what a wider
    ``prefill_wave_tokens`` would send a pages-only family) for the
    described chip: every kernel in the program, the five pools in place,
    and what the program holds beside its arguments far under the 3 GB
    the plan leaves."""
    cfg, mc, family, params, cache = kimik2_programs
    eng = cfg["engine"]
    page, B, seq = eng["page_size"], eng["max_batch_size"], eng["max_seq_len"]
    kernels = family.resolve_kernels(mc, "compiled")
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    tables = i32(B, seq // page)
    assert seq // page == 192 and len(cache["lat"]) == 5 and set(cache) == {"lat", "stats"}
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert cache_bytes == pytest.approx(cfg["memory_plan"]["page_pool_bytes"], rel=1e-6)

    def decode(params, caches, tokens, positions, live, tables):
        def body(carry, _):
            tokens, positions, caches = carry
            logits, caches = family.decode_paged(params, mc, caches, tokens, positions, live, tables, seq, page,
                                                 page_kernel="compiled", **kernels)
            return (jnp.argmax(logits, -1).astype(jnp.int32), positions + 1, caches), tokens
        return jax.lax.scan(body, (tokens, positions, caches), None, length=eng["decode_block"])

    def extend(params, caches, tokens, offsets, valid, slots, tables):
        return family.extend_paged(params, mc, caches, tokens, offsets, valid, slots, tables, seq, page, **kernels)

    if program == "decode":
        live = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(params, cache, i32(B), i32(B), live, tables).compile()
        calls, temp_limit = 5 + 2 * 4, 0.5e9  # the latent read a layer, gate|up and down an expert layer
    else:
        rows = 4 if program.endswith("four-rows") else 1
        width = 128 if program == "extend-128" else eng["prefill_chunk"]
        compiled = jax.jit(extend, donate_argnums=(1,)).lower(
            params, cache, i32(rows, width), i32(rows), i32(rows), i32(rows), tables).compile()
        # gate|up and down an expert layer, and the chunk's latent read a layer (ops/latent_attention.py
        # ``latent_chunk_read``: keys, values and scores expanded in VMEM). The 0.33 GB a row of
        # temporaries are not the read's: they stay
        calls, temp_limit = 2 * 4 + 5, 1.0e9 * rows
        assert kernels["latent_chunk"] == "compiled"
    mem = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") >= calls
    assert mem.alias_size_in_bytes >= cache_bytes - 1024 and mem.temp_size_in_bytes < temp_limit


# --------------------------------------------------------------------------- #
# GigaChat3.5 (models/gigachat35.py): one latent layer among four Gated DeltaNet
# layers; the chunk walk's read of it is the same kernel at the same widths


def test_gigachat35_extend_program_holds_the_latent_chunk_read(one_chip, no_persistent_cache):
    """One row of 512 tokens at the benchmark configuration's shapes (a
    table of 64 pages a row): gate|up and down of the four expert layers
    and the ONE latent layer's chunk read, the cache in place."""
    import functools
    import json

    from generativeaiexamples_tpu.models import registry
    from perfbench import arch

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs", "gigachat3.5-432b-a28b-ep16-bf16.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    arch.load(cfg).register(cfg)
    family, mc = registry.resolve(cfg["name"])
    eng = cfg["engine"]
    page, B, seq = eng["page_size"], eng["max_batch_size"], eng["max_seq_len"]
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(lambda: family.init_params(mc, 0, jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(functools.partial(
        family.init_paged_cache, mc, eng["kv_pool_pages"], page, B, jnp.bfloat16)))
    kernels = family.resolve_kernels(mc, "compiled")
    assert kernels["latent_chunk"] == "compiled" and len(cache["lat"]) == 1
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731

    def extend(params, caches, tokens, offsets, valid, slots, tables):
        return family.extend_paged(params, mc, caches, tokens, offsets, valid, slots, tables, seq, page, **kernels)

    compiled = jax.jit(extend, donate_argnums=(1,)).lower(
        params, cache, i32(1, eng["prefill_chunk"]), i32(1), i32(1), i32(1), i32(B, seq // page)).compile()
    text = compiled.as_text()
    experts = sum(mlp == "sparse" for _, mlp in mc.layers)
    assert text.count("tpu_custom_call") >= 2 * experts + 1 and "latent_chunk_read" in text
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes - 1024 and mem.temp_size_in_bytes < 1.0e9


# --------------------------------------------------------------------------- #
# MiniMax-M3 (models/minimaxm3.py): the selected-page read (ops/page_attention.py
# selected_page_attention) at 64/4 heads of 128 over head-major pages, a table of
# 288 pages a row (max_seq_len 36,864), 16 experts of 3072 under swigluoai


@pytest.mark.parametrize("group", [1, 10])
def test_selected_page_read_compiles_at_the_published_widths(one_chip, no_persistent_cache, group, monkeypatch):
    """The kernel alone, for the described chip: 16 rows x 4 KV heads x 19
    selected pages, ``group`` strips a step, a dynamic number of steps."""
    from generativeaiexamples_tpu.ops import page_attention

    B, Hq, Hkv, Dh, page, P, Pmax, K = 16, 64, 4, 128, 128, 3329, 288, 19
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    assert page_attention.supports_selected(page, Dh, Hq, Hkv)

    monkeypatch.setattr(page_attention, "SELECTED_PAGES_A_STEP", group)

    def read(q, k, v, tables, positions, pages, valid):
        work = page_attention.selected_work_list(tables, pages, valid)
        return page_attention.selected_page_attention(q, k, v, positions, work)

    compiled = jax.jit(read).lower(
        s((B, Hq, Dh), jnp.bfloat16), s((P, Hkv, page, Dh), jnp.bfloat16), s((P, Hkv, page, Dh), jnp.bfloat16),
        s((B, Pmax), jnp.int32), s((B,), jnp.int32), s((B, Hkv, K), jnp.int32), s((B, Hkv, K), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows, T", [(1, 512), (1, 128), (2, 512)], ids=["chunk-512", "chunk-128", "two-rows"])
def test_selected_chunk_read_compiles_at_the_published_widths(one_chip, no_persistent_cache, rows, T):
    """The chunk walk's block-sparse read alone (ops/selected_chunk_read.py):
    the extend shapes of the cell, one row x 512 and x 128 queries of 64/4
    heads of 128, 19 places a query, a table of 288 pages, a run-time count
    of (row, tile, block) items on the grid's second axis."""
    from generativeaiexamples_tpu.models import minimaxm3
    from generativeaiexamples_tpu.ops import selected_chunk_read as scr

    Hq, Hkv, Dh, page, P, Pmax, K = 64, 4, 128, 128, 3457, 288, 19
    assert minimaxm3.selected_chunk_kind(minimaxm3.PRESETS["minimax-m3-ep8"], "compiled", T) == "compiled"
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def read(q, k, v, tables, positions, n_tokens, pages, valid):
        work = scr.chunk_work_list(tables, positions, n_tokens, page, P)
        src, _ = scr.chunk_live_steps(work, pages, valid)
        return scr.selected_chunk_read(q, k, v, positions, pages, valid, work, src)

    compiled = jax.jit(read).lower(
        s((rows, T, Hq, Dh), jnp.bfloat16), s((P, Hkv, page, Dh), jnp.bfloat16), s((P, Hkv, page, Dh), jnp.bfloat16),
        s((rows, Pmax), jnp.int32), s((rows, T), jnp.int32), s((rows,), jnp.int32),
        s((rows, T, Hkv, K), jnp.int32), s((rows, T, Hkv, K), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "selected_chunk_read" in text
    # beside the arguments: the queries regrouped a KV head and the padded page numbers, nothing of a score's size
    assert compiled.memory_analysis().temp_size_in_bytes < rows * T * (Hq * Dh * 2 + Hkv * 128 * 4) + (4 << 20)


def test_eva_decode_read_compiles_at_the_published_widths(one_chip, no_persistent_cache):
    """The decode step's one-softmax read of buffer and pages alone
    (ops/eva_read.py), for the described chip, at the cell's shapes: 24
    rows of 32 heads of 128 over buffers of 2,048 rows and a pool of
    3,073 pages of 8 summary rows (two halves of the heads a 32-bit
    word), a table of 160 pages a row, a dynamic number of steps."""
    from generativeaiexamples_tpu.ops import eva_read

    B, H, Dh, W, page, rows, P, Pmax = 24, 32, 128, 2048, 128, 8, 3073, 160
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    assert eva_read.supports(H, Dh, W)

    def read(q, wk, wv, sk, sv, tables, positions):
        work = eva_read.work_list(tables, positions, W, page)
        return eva_read.eva_decode_read(q, wk, wv, sk, sv, work, window=W)

    compiled = jax.jit(read).lower(
        s((B, H, Dh), jnp.bfloat16), s((B, W, H * Dh), jnp.bfloat16), s((B, W, H * Dh), jnp.bfloat16),
        s((P, rows, H * Dh // 2), jnp.uint32), s((P, rows, H * Dh // 2), jnp.uint32),
        s((B, Pmax), jnp.int32), s((B,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows, T", [(1, 512), (1, 128), (2, 2048)], ids=["chunk-512", "chunk-128", "two-rows-a-window"])
def test_eva_chunk_read_compiles_at_the_published_widths(one_chip, no_persistent_cache, rows, T):
    """The chunk walk's one-softmax read of buffer and pages alone
    (ops/eva_read.py ``eva_chunk_read``), for the described chip, at the
    cell's shapes: one row x 512 queries (and a tail's 128, and the
    reference walk's whole window) of 32 heads of 128, 24 buffers of
    2,048 rows, 3,073 pages of 8 summary rows, a table of 160 pages, a
    run-time count of steps."""
    from generativeaiexamples_tpu.models import evabyte
    from generativeaiexamples_tpu.ops import eva_read

    H, Dh, W, page, slots, P, Pmax = 32, 128, 2048, 128, 24, 3073, 160
    cfg = evabyte.PRESETS["evabyte-6.5b-pp4"]
    assert evabyte.chunk_read_in_kernel(cfg, evabyte.eva_read_kind(cfg, "compiled"), T, page)
    assert not evabyte.chunk_read_in_kernel(cfg, "compiled", 64, page)  # a chunk of no whole lane tiles: _attend
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def read(q, wk, wv, sk, sv, tables, slot, offsets, valid):
        work = eva_read.chunk_work_list(tables, slot, offsets, valid, T, W, page, slots, P)
        return eva_read.eva_chunk_read(q, wk, wv, sk, sv, work, num_heads=H)

    compiled = jax.jit(read).lower(
        s((rows, T, H * Dh), jnp.bfloat16), s((slots, W, H * Dh), jnp.bfloat16), s((slots, W, H * Dh), jnp.bfloat16),
        s((P, 8, H * Dh // 2), jnp.uint32), s((P, 8, H * Dh // 2), jnp.uint32), s((slots, Pmax), jnp.int32),
        s((rows,), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "eva_chunk_read" in text
    # beside the arguments: the work list and its index arithmetic, nothing of a score's size ([32, T, keys] float32)
    assert compiled.memory_analysis().temp_size_in_bytes < (4 << 20)
