"""Device mesh + multi-host bootstrap.

TPU-native replacement for the reference's communication bootstrap zoo —
NCCL-id TCP rendezvous (operators/collective/gen_nccl_id_op_helper.cc), MPI
cluster membership inside libbox_ps (box_wrapper.h:415,537), and Gloo
HDFS/HTTP KV rendezvous (fleet/gloo_wrapper.h:136-150).  On TPU all of it
collapses into the JAX coordination service (`jax.distributed.initialize`)
plus one `jax.sharding.Mesh` whose single "data" axis carries data
parallelism AND the key-sharded sparse table; collectives ride ICI inside a
slice and DCN across slices with no further configuration (SURVEY.md §2.10).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bootstrap (reference: MPICluster::Ins / gen_nccl_id TCP
    rendezvous): join the JAX coordination service at the address the
    launcher provides (arguments, or ``PBOX_COORDINATOR_ADDRESS`` /
    ``PBOX_NUM_PROCESSES`` / ``PBOX_PROCESS_ID``).  Must run before any
    backend-initializing JAX call.

    With no coordinates at all this is a single-process run and a no-op.
    JAX's own cluster auto-detection is deliberately not tried there: on a
    TPU host it asks the cloud metadata server, and a one-host machine
    with no network (the chip tool's) dies in that request instead of
    learning that it is alone."""
    import os

    if jax.distributed.is_initialized():
        return
    if coordinator_address is None:
        coordinator_address = os.environ.get("PBOX_COORDINATOR_ADDRESS")
    if num_processes is None and "PBOX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["PBOX_NUM_PROCESSES"])
    if process_id is None and "PBOX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["PBOX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = DATA_AXIS,
) -> Mesh:
    """One-axis mesh over the job's devices.

    CTR sparse-PS training is data-parallel with a key-sharded table; both
    map onto a single mesh axis (the reference's one NCCL ring,
    collective_helper.h:63).  Model-parallel axes are not needed for this
    workload (SURVEY.md §5.7).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def make_composed_mesh(
    n_data: int,
    n_inner: int,
    inner_axis: str,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """2-D (data x inner) mesh for composed parallelism: the sparse table +
    batch shard over ``data`` exactly as on a 1-D mesh, while a model axis
    (``expert``/``seq``) splits the dense compute inside each data shard.
    Device layout is data-major, so each data shard's inner group is an
    ICI-adjacent block.  MultiChipTrainer binds only ``data`` manually
    (axis_names) and the model's inner shard_map (``expert_mesh="inherit"``
    etc.) binds the inner axis inside the same jitted step.

    Any ``n_data >= 2`` composes (odd totals simply leave the remaining
    devices out of the mesh).  ``n_data == 1`` is rejected: XLA's SPMD
    partitioner RET_CHECKs on a 1-sized *manual* data axis nested with an
    auto inner axis ("Cross-partition allreduce must be in (partial) manual
    partitioning mode", spmd_partitioner.cc:3497) — and that shape IS the
    single-chip trainer with a model-parallel mesh, which the Trainer +
    explicit ``expert_mesh``/``seq_mesh`` path already serves without the
    sharded-table machinery."""
    if devices is None:
        devices = jax.devices()
    if n_data < 2:
        raise ValueError(
            "make_composed_mesh needs a data axis of >= 2 (a 1-sized manual "
            "data axis trips an XLA partial-manual partitioner RET_CHECK "
            "when nested with an auto inner axis); for one data shard use "
            "the single-chip Trainer with an explicit model mesh "
            "(MMoE(expert_mesh=make_mesh(...)) / LongSeqCtrDnn(seq_mesh=...))"
        )
    need = n_data * n_inner
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(n_data, n_inner)
    return Mesh(arr, (DATA_AXIS, inner_axis))


def inherit_shard_map(body, *, in_specs, out_specs, axis_name: str):
    """``jax.shard_map`` of ``body`` over ``axis_name`` of the CONTEXT mesh
    (the composed mode: call inside an outer shard_map that is manual over
    the data axis only), differentiable from inside that outer body.

    jax 0.9 cannot transpose a nested shard_map there: the residuals it
    saves vary over the outer axis, the transposed call names that axis in
    its specs and the lowering rejects it, and ``check_vma=False`` erases
    the outer axis from the cotangent types instead.  So the backward pass
    is a second explicit shard_map over the body's own vjp (forward
    recomputed from the inputs), whose specs name ``axis_name`` only.
    Inputs that are not floating point get no cotangent."""
    kw = dict(axis_names={axis_name})
    fwd_map = jax.shard_map(body, in_specs=in_specs, out_specs=out_specs, **kw)

    @jax.custom_vjp
    def mapped(*args):
        return fwd_map(*args)

    def bwd(args, ct):
        diff = tuple(
            i for i, a in enumerate(args)
            if all(jnp.issubdtype(l.dtype, jnp.inexact)
                   for l in jax.tree.leaves(a))
        )

        def body_vjp(args, ct):
            def of_diff(*dargs):
                full = list(args)
                for i, d in zip(diff, dargs):
                    full[i] = d
                return body(*full)

            return jax.vjp(of_diff, *(args[i] for i in diff))[1](ct)

        # runs while the caller's step is being traced, once per trace
        bwd_map = jax.shard_map(
            body_vjp, in_specs=(tuple(in_specs), out_specs),
            out_specs=tuple(in_specs[i] for i in diff), **kw,
        )
        cts = bwd_map(args, ct)
        out = [None] * len(args)
        for i, c in zip(diff, cts):
            out[i] = c
        return tuple(out)

    mapped.defvjp(lambda *args: (fwd_map(*args), args), bwd)
    return mapped


def data_axis_size(mesh: Mesh) -> int:
    """Size of the data axis (== total devices on a 1-D mesh)."""
    return int(mesh.shape[DATA_AXIS])
