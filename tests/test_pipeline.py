"""Pipeline parallelism: the microbatch schedule must be numerically
IDENTICAL to running the stages sequentially (same params, same data) —
forward loss, gradients (via one training step), and learning."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from paddlebox_tpu.parallel.pipeline import (
    PIPE_AXIS,
    PipelineTrainer,
    init_pipeline_params,
    pipeline_forward_loss,
    reference_forward_loss,
)

P_STAGES, M, MB, D_IN, WIDTH = 4, 8, 16, 10, 32


def _mesh():
    return Mesh(np.array(jax.devices()[:P_STAGES]), (PIPE_AXIS,))


def _data(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(M, MB, D_IN)).astype(np.float32)
    y = (x.mean(-1) > 0).astype(np.float32)  # learnable signal
    mask = np.ones((M, MB), np.float32)
    mask[-1, MB // 2 :] = 0.0  # ragged tail microbatch
    return x, y, mask


def test_forward_matches_sequential():
    mesh = _mesh()
    params = init_pipeline_params(
        jax.random.PRNGKey(0), D_IN, WIDTH, 2, P_STAGES
    )
    x, y, mask = _data()

    from jax.sharding import NamedSharding, PartitionSpec as PS

    piped = jax.jit(
        jax.shard_map(
            lambda p, a, b, c: pipeline_forward_loss(
                jax.tree.map(lambda l: l[0], p), a, b, c
            )[None],
            mesh=mesh,
            in_specs=(PS(PIPE_AXIS), PS(), PS(), PS()),
            out_specs=PS(PIPE_AXIS),
        )
    )
    p_shard = jax.device_put(params, NamedSharding(mesh, PS(PIPE_AXIS)))
    got = np.asarray(piped(p_shard, x, y, mask))
    want = float(reference_forward_loss(params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask)))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # every stage returns the psummed loss: all equal
    assert np.allclose(got, got[0])


def test_train_step_matches_sequential_grads():
    """One pipelined SGD step == one sequential SGD step on the same
    stacked params (grads flow correctly through scan + ppermute).

    SGD, deliberately: the update is LINEAR in the gradient, so the
    comparison is a direct gradient-equivalence check.  Adam's first
    step normalizes (update ≈ lr·g/|g|), which amplifies reduction-order
    float noise at near-zero-gradient coordinates into O(lr)
    differences — that flakiness was measured to live exclusively at
    |grad| < 3e-5 coords and says nothing about the pipeline's grads."""
    import optax

    mesh = _mesh()
    params = init_pipeline_params(
        jax.random.PRNGKey(1), D_IN, WIDTH, 2, P_STAGES
    )
    x, y, mask = _data(1)

    tr = PipelineTrainer(mesh, D_IN, WIDTH, 2, params=params,
                         optimizer=optax.sgd(1e-2))
    tr.train_step(x, y, mask)
    from paddlebox_tpu.parallel.multiprocess import local_view

    got = jax.tree.map(lambda l: local_view(l), tr.params)

    # sequential oracle
    opt = optax.sgd(1e-2)
    o0 = opt.init(params)
    loss, grads = jax.value_and_grad(reference_forward_loss)(
        params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask)
    )
    upd, _ = opt.update(grads, o0, params)
    want = optax.apply_updates(params, upd)

    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=2e-4, atol=1e-6,
            err_msg=k,
        )


def test_pipeline_learns():
    mesh = _mesh()
    tr = PipelineTrainer(mesh, D_IN, WIDTH, 2, lr=5e-3, seed=3)
    x, y, mask = _data(3)
    losses = [tr.train_step(x, y, mask) for _ in range(30)]
    assert losses[-1] < losses[0] - 0.05, (losses[0], losses[-1])
