"""Pipeline parallelism over the ``pipe`` mesh axis (virtual 8-dev CPU mesh).

Reference capability matched: NeMo's pipeline_model_parallel in the
fine-tuning notebooks (SURVEY §2.6) — here as a GPipe schedule in
shard_map, verified numerically against the unpipelined forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.parallel.mesh import create_mesh
from generativeaiexamples_tpu.parallel.pipeline import (
    merge_stages,
    pipelined_decoder_forward,
    shard_stages,
    split_stages,
)

CFG = llama.PRESETS["debug"]  # 2 layers


def test_split_merge_roundtrip():
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    staged = split_stages(params["layers"], 2)
    assert staged["wq"].shape[0] == 2
    assert staged["wq"].shape[1] == CFG.num_layers // 2
    merged = merge_stages(staged)
    np.testing.assert_array_equal(np.asarray(merged["wq"]), np.asarray(params["layers"]["wq"]))

    with pytest.raises(ValueError, match="not divisible"):
        split_stages(params["layers"], 3)


def test_pipelined_forward_matches_reference():
    mesh = create_mesh(tensor_parallelism=1, pipe_parallelism=2, data_parallelism=1)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, T = 4, 8
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, CFG.vocab_size, (B, T)), jnp.int32
    )
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    expected, _ = llama.forward(params, CFG, tokens, positions)

    staged = shard_stages(split_stages(params["layers"], 2), mesh)
    got = pipelined_decoder_forward(
        params, CFG, tokens, mesh, n_stages=2, n_microbatches=2, staged_layers=staged
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-4, rtol=2e-4)


def test_pipelined_forward_under_jit_and_grad():
    mesh = create_mesh(tensor_parallelism=1, pipe_parallelism=2)
    params = llama.init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    B, T = 2, 8
    tokens = jnp.ones((B, T), jnp.int32)

    def loss_fn(params):
        logits = pipelined_decoder_forward(
            params, CFG, tokens, mesh, n_stages=2, n_microbatches=2
        )
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss))
    # gradients flow through ppermute into every stage's layers
    gnorm = float(jnp.abs(grads["layers"]["wq"]).sum())
    assert gnorm > 0


def test_mesh_with_pipe_axis_composes_with_tp():
    mesh = create_mesh(tensor_parallelism=2, pipe_parallelism=2, data_parallelism=2)
    assert mesh.shape == {"pipe": 2, "data": 2, "seq": 1, "model": 2}

    with pytest.raises(ValueError, match="not divisible"):
        create_mesh(tensor_parallelism=-1, pipe_parallelism=3)
