"""Expert parallelism: the sharded expert mix must equal the serial MMoE
expert computation, forward and backward."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.parallel.expert import (
    EXPERT_AXIS,
    expert_parallel_forward,
    serial_expert_forward,
)

P_DEV, E, B, D_IN, D_HID = 4, 8, 16, 10, 12


def _mesh():
    return Mesh(np.array(jax.devices()[:P_DEV]), (EXPERT_AXIS,))


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(E, D_IN, D_HID)).astype(np.float32) * 0.3
    b = rng.normal(size=(E, D_HID)).astype(np.float32) * 0.1
    x = rng.normal(size=(B, D_IN)).astype(np.float32)
    logits = rng.normal(size=(B, E)).astype(np.float32)
    gates = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=1))
    return w, b, x, gates


def _sharded_fn(mesh):
    return jax.jit(
        jax.shard_map(
            expert_parallel_forward,
            mesh=mesh,
            in_specs=(P(EXPERT_AXIS), P(EXPERT_AXIS), P(),
                      P(None, EXPERT_AXIS)),
            out_specs=P(),
        )
    )


def test_forward_matches_serial():
    mesh = _mesh()
    w, b, x, gates = _inputs()
    want = np.asarray(serial_expert_forward(*map(jnp.asarray, (w, b, x, gates))))
    got = np.asarray(_sharded_fn(mesh)(w, b, x, gates))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_gradients_match_serial():
    mesh = _mesh()
    w, b, x, gates = _inputs(1)
    tgt = np.random.default_rng(7).normal(size=(B, D_HID)).astype(np.float32)

    def loss_serial(w_, b_):
        return jnp.mean((serial_expert_forward(w_, b_, x, gates) - tgt) ** 2)

    want = jax.grad(loss_serial, argnums=(0, 1))(
        jnp.asarray(w), jnp.asarray(b)
    )

    def loss_sharded(w_, b_):
        body = jax.shard_map(
            expert_parallel_forward,
            mesh=mesh,
            in_specs=(P(EXPERT_AXIS), P(EXPERT_AXIS), P(),
                      P(None, EXPERT_AXIS)),
            out_specs=P(),
        )
        return jnp.mean((body(w_, b_, x, gates) - tgt) ** 2)

    got = jax.jit(jax.grad(loss_sharded, argnums=(0, 1)))(w, b)
    for g, wref in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wref), rtol=1e-4, atol=1e-7
        )
