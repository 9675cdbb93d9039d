"""Pipeline-parallel tests on the virtual CPU mesh (conftest.py): GPipe
schedule correctness vs the sequential oracle, gradient equivalence
(reverse pipeline via jax.grad), and end-to-end training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.pipeline import (pipeline_apply,
                                                  sequential_apply,
                                                  stack_stage_params)


def _block(params, x):
    return jnp.tanh(x @ params["W"] + params["b"])


def _stages(S=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    return stack_stage_params([
        {"W": jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.4),
         "b": jnp.asarray(rng.randn(D).astype(np.float32) * 0.1)}
        for _ in range(S)])


def test_pipeline_forward_matches_sequential():
    S, D, B = 4, 8, 16
    mesh = make_mesh({"pipe": S}, jax.devices()[:S])
    params = _stages(S, D)
    x = jnp.asarray(np.random.RandomState(1).randn(B, D)
                    .astype(np.float32))
    want = sequential_apply(_block, params, x)
    got = jax.jit(lambda p, x: pipeline_apply(_block, p, x, mesh))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # more microbatches than stages also works
    got8 = jax.jit(lambda p, x: pipeline_apply(
        _block, p, x, mesh, num_microbatches=8))(params, x)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential():
    """jax.grad through the scan/ppermute IS the reverse pipeline
    schedule — gradients must equal the sequential model's."""
    S, D, B = 4, 8, 8
    mesh = make_mesh({"pipe": S}, jax.devices()[:S])
    params = _stages(S, D, seed=2)
    x = jnp.asarray(np.random.RandomState(3).randn(B, D)
                    .astype(np.float32))
    y = jnp.asarray(np.random.RandomState(4).randn(B, D)
                    .astype(np.float32))

    def loss_pipe(p):
        out = pipeline_apply(_block, p, x, mesh)
        return jnp.mean((out - y) ** 2)

    def loss_seq(p):
        out = sequential_apply(_block, p, x)
        return jnp.mean((out - y) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        g_pipe, g_seq)


def test_pipeline_training_decreases_loss():
    S, D, B = 4, 6, 24
    mesh = make_mesh({"pipe": S}, jax.devices()[:S])
    params = _stages(S, D, seed=5)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    y = jnp.asarray(rng.randn(B, D).astype(np.float32) * 0.3)

    @jax.jit
    def step(p):
        def loss_fn(pp):
            out = pipeline_apply(_block, pp, x, mesh,
                                 num_microbatches=6)
            return jnp.mean((out - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.2 * b, p, g), loss

    params, first = step(params)
    for _ in range(30):
        params, loss = step(params)
    assert float(loss) < float(first) * 0.7


def test_pipeline_batch_divisibility_error():
    S = 4
    mesh = make_mesh({"pipe": S}, jax.devices()[:S])
    params = _stages(S, 4)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply(_block, params,
                       jnp.zeros((10, 4)), mesh, num_microbatches=4)
