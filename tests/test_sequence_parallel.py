"""Sequence parallelism: ring + Ulysses attention must match full attention
on the gathered sequence, forward AND backward, causal and not."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.parallel.sequence import (
    SEQ_AXIS,
    full_attention,
    ring_attention,
    ulysses_attention,
)

P_DEV, B, T_LOCAL, H, D = 4, 2, 8, 4, 8
T = P_DEV * T_LOCAL


def _mesh():
    return Mesh(np.array(jax.devices()[:P_DEV]), (SEQ_AXIS,))


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.normal(size=(B, T, H, D)).astype(np.float32) * 0.5
        for _ in range(3)
    ]


def _sharded(mesh, fn, causal):
    spec = P(None, SEQ_AXIS)  # shard the T axis

    return jax.jit(
        jax.shard_map(
            functools.partial(fn, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
    )


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize(
    "fn", [ring_attention, ulysses_attention], ids=["ring", "ulysses"]
)
def test_matches_full_attention(fn, causal):
    mesh = _mesh()
    q, k, v = _qkv()
    want = np.asarray(full_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal
    ))
    got = np.asarray(_sharded(mesh, fn, causal)(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize(
    "fn", [ring_attention, ulysses_attention], ids=["ring", "ulysses"]
)
def test_gradients_match_full_attention(fn, causal):
    mesh = _mesh()
    q, k, v = _qkv(1)
    tgt = np.asarray(
        np.random.default_rng(9).normal(size=(B, T, H, D)), np.float32
    )

    def loss_full(q_, k_, v_):
        return jnp.mean(
            (full_attention(q_, k_, v_, causal=causal) - tgt) ** 2
        )

    want = jax.grad(loss_full, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )

    spec = P(None, SEQ_AXIS)

    def loss_sharded(q_, k_, v_):
        body = jax.shard_map(
            functools.partial(fn, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        return jnp.mean((body(q_, k_, v_) - tgt) ** 2)

    got = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-4, atol=1e-6
        )


def test_ulysses_rejects_indivisible_heads():
    mesh = _mesh()
    rng = np.random.default_rng(0)
    bad = [rng.normal(size=(B, T, 6, D)).astype(np.float32) for _ in range(3)]
    with pytest.raises(ValueError, match="divisible"):
        _sharded(mesh, ulysses_attention, False)(*bad)
