"""Expert parallelism: expert banks sharded over an ``expert`` mesh axis.

Two kinds of gating live here.

**Dense gating** (MMoE, models/mmoe.py): every instance consumes every
expert with a softmax weight.  The TPU-native EP layout is
collective-light:

  * each device owns E/P experts (the expert bank's leading axis sharded
    over the mesh);
  * the batch is replicated across the axis; every device runs ITS experts
    on the full batch (one vmapped matmul — MXU-dense);
  * the gate matrix is sharded along its expert axis by SPEC (each device
    receives exactly its experts' columns — no in-body axis_index, which
    keeps the body legal inside an OUTER shard_map for composed
    data x expert meshes);
  * outputs are weighted by the local gate columns and psummed: one
    [B, D_out] all-reduce per mix, vs all-gathering E expert outputs.

**Token routing** (``route_tokens``, ``routed_experts``): every token
scores all E experts (softmax, or sigmoid with a selection bias), takes
its k best and renormalises their weights; a device is told which
experts ``lo..hi`` it holds and computes, for the tokens routed to them,
their part of the sum.  What the absent experts would add is left out:
the parts of all the shares add up to the whole layer
(tests/test_decoder_lm.py), and the sum over the shares is the layer's
all-to-all / psum, which a one-share run does without.  No token is ever
dropped and there is no capacity factor: the shapes are static at the worst
case (every token may pick any held expert).

This is the ``parallel/`` family's fifth axis (dp, sparse-MP, pp, sp, ep);
like the others it reduces to the serial computation at P=1.  Reference
anchor: MMoE user programs on the BoxPS trainer (SURVEY.md §2.11); the
reference has no expert-parallel engine — its MoE models replicate experts
per GPU — so this is a capability the TPU design adds, not ports.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EXPERT_AXIS = "expert"


def mix_local_experts(
    h: jax.Array,  # [E_local, B, D] this device's expert outputs
    gates_local: jax.Array,  # [B, E_local] or [T, B, E_local] gate columns
    axis_name: str = EXPERT_AXIS,
) -> jax.Array:
    """The EP mixing layout, shared by every consumer (call INSIDE
    shard_map): weight the local expert outputs by THIS device's gate
    columns (sharded in by spec ``P(..., EXPERT_AXIS)``), psum.
    Returns [B, D] (2-D gates) or [T, B, D] (stacked per-task gates) —
    fully reduced, identical on every device."""
    if gates_local.ndim == 2:
        local = jnp.einsum("ebo,be->bo", h, gates_local)
    else:
        local = jnp.einsum("ebo,tbe->tbo", h, gates_local)
    return jax.lax.psum(local, axis_name)


def expert_parallel_forward(
    expert_w: jax.Array,  # [E_local, D_in, D_hid] this device's experts
    expert_b: jax.Array,  # [E_local, D_hid]
    x: jax.Array,  # [B, D_in] replicated batch
    gates_local: jax.Array,  # [B, E_local] this device's gate columns
    axis_name: str = EXPERT_AXIS,
) -> jax.Array:
    """Gate-weighted sum of single-layer ReLU expert outputs (call INSIDE
    shard_map over ``axis_name``; shard gates with ``P(None, EXPERT_AXIS)``).
    Returns [B, D_hid], fully reduced."""
    # local experts on the full batch: [E_local, B, D_hid]
    h = jax.nn.relu(
        jnp.einsum("bi,eio->ebo", x, expert_w) + expert_b[:, None, :]
    )
    return mix_local_experts(h, gates_local, axis_name)


def expert_parallel_mlp_mix(
    stacked_layers: list,  # [{"w": [E_local, d_i, d_o], "b": [E_local, d_o]}]
    x: jax.Array,  # [B, D_in] replicated batch
    gates_local: jax.Array,  # [T, B, E_local] stacked per-task gate columns
    axis_name: str = EXPERT_AXIS,
) -> jax.Array:
    """Multi-layer expert bank with mlp() semantics (ReLU between layers,
    last layer linear, expert outputs upcast to f32 BEFORE the gate mixing
    — the same cast policy as models/layers.mlp, so a compute-dtype bank
    mixes identically to the serial path).  Call INSIDE shard_map; shard
    gates with ``P(None, None, EXPERT_AXIS)``.
    Returns [T, B, D_out] f32, fully reduced."""
    e_local = stacked_layers[0]["w"].shape[0]
    h = jnp.broadcast_to(x, (e_local, *x.shape))  # [E_local, B, D_in]
    for li, layer in enumerate(stacked_layers):
        h = jnp.einsum("ebi,eio->ebo", h, layer["w"]) + layer["b"][:, None, :]
        if li < len(stacked_layers) - 1:
            h = jax.nn.relu(h)
    h = h.astype(jnp.float32)
    return mix_local_experts(h, gates_local.astype(jnp.float32), axis_name)


def serial_expert_forward(
    expert_w: jax.Array,  # [E, D_in, D_hid]
    expert_b: jax.Array,  # [E, D_hid]
    x: jax.Array,
    gates: jax.Array,
) -> jax.Array:
    """Single-device reference semantics (the MMoE expert mix)."""
    h = jax.nn.relu(
        jnp.einsum("bi,eio->ebo", x, expert_w) + expert_b[:, None, :]
    )
    return jnp.einsum("ebo,be->bo", h, gates)


# ------------------------------------------------------------ token routing
def route_tokens(x: jax.Array, router_w: jax.Array, k: int,
                 score: str = "softmax",
                 select_bias: jax.Array | None = None,
                 scale: float = 1.0) -> tuple:
    """Router over ALL experts: a score of ``x @ router_w`` (float32) per
    expert, the k best, their scores renormalised to sum 1 over those k
    whether their experts are held here or not, times ``scale``.

    ``score``: ``"softmax"`` over the experts, or ``"sigmoid"`` of each
    logit alone (its k scores are divided by their sum + 1e-20).
    ``select_bias`` [E] is added to the scores for the CHOICE only (a
    bias that balances the experts' load without an auxiliary loss): the
    weights are the unbiased scores of the chosen, so the bias has no
    gradient.  x: [N, D]; router_w: [D, E].  Returns (weights [N, k]
    float32, expert ids [N, k] int32)."""
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    # eps, bias and scale enter the program only where they are given:
    # the defaults trace the operations they always did
    if score == "softmax":
        scores, eps = jax.nn.softmax(logits, axis=-1), 0.0
    elif score == "sigmoid":
        scores, eps = jax.nn.sigmoid(logits), 1e-20
    else:
        raise ValueError(f"unknown router score {score!r}")
    if select_bias is None:
        top_w, top_e = jax.lax.top_k(scores, k)
    else:
        _, top_e = jax.lax.top_k(scores + select_bias, k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    total = top_w.sum(axis=-1, keepdims=True)
    if eps:
        total = total + eps
    top_w = top_w / total
    if scale != 1.0:
        top_w = top_w * scale
    return top_w, top_e.astype(jnp.int32)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """One expert on every row: down(silu(gate x) * up x), float32 sums
    (what ``routed_experts`` writes out per held expert; a shared expert
    or a dense feed-forward is this alone).  x: [N, D]; w_gate, w_up:
    [D, F]; w_down: [F, D]."""
    h = jax.nn.silu(
        jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    ) * jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot(h.astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


def routed_experts(
    x: jax.Array,  # [N, D] tokens
    top_w: jax.Array,  # [N, k] renormalised weights (route_tokens)
    top_e: jax.Array,  # [N, k] expert ids over all E (-1: routed nowhere)
    w_gate: jax.Array,  # [E_held, D, F] this share's experts lo..hi-1
    w_up: jax.Array,  # [E_held, D, F]
    w_down: jax.Array,  # [E_held, F, D]
    lo: int,
) -> tuple:
    """This share's part of the routed layer: for every token, the sum over
    its choices that fall on a held expert e of ``w_e * down_e(silu(gate_e
    x) * up_e x)``; choices on absent experts add nothing.  Returns (y [N,
    D] float32, load [E_held] int32: the tokens each held expert got).

    Drop-free by static shapes at the worst case: every held expert runs
    over all N tokens, with weight zero where a token did not choose it, so
    any routing -- all tokens on one expert -- costs the same and loses
    nothing.  That is E_held / (k * E_held / E) times the products of the
    pairs really routed here (8x at 8 of 64 held, 8 a token; 21.3x at 8 of
    128 held, 6 a token), all of them dense MXU work.  Measured on one v5e
    chip at N = 16,384, D = 2,304, F = 896 (PERF.md section 6, PR 27),
    forward and backward: this form 40.5 ms; tokens sorted by expert with
    ``jax.lax.ragged_dot`` 115 ms (236 ms when every choice lands here);
    the same with the Pallas grouped product (megablox ``gmm``) 90 ms --
    the sorts, the gathers there and back and the worst-case buffers cost
    more than the products they save.  At 8 of
    128 held, 6 a token (N = 16,384, D = 2,048, F = 768; PERF.md section
    6, PR 31) the form computes 21.3x the routed pairs' products at even
    routing, 15.6x as the traced window routed, and takes 38.4 ms a layer
    of a 1,190 ms step (forward, rematerialised forward and backward): no
    grouped form was timed there.  At 8 of 256 held, 8 a token (N = 8,192,
    D = 2,304, F = 1,024; PERF.md section 6, PR 39) it is 32x, the worst
    ratio yet, and 26.6 ms a layer of an 835 ms step.  At 8 of 128 held, 8
    a token, under the block-diffusion objective, whose every sequence goes
    through the layers twice (N = 16,384 positions of 2 x 4,096 tokens in
    two streams, D = 2,048, F = 768; PERF.md section 6, PR 42), it is 16x
    and 38.0 ms a layer of a 1,112 ms step.  A grouped kernel that gathers
    its own rows is the next step, judged on all four ratios."""
    held = w_gate.shape[0]
    e_loc = jnp.where((top_e >= lo) & (top_e < lo + held), top_e - lo, held)
    load = jnp.bincount(e_loc.reshape(-1), length=held + 1)[:held]
    y = jnp.zeros((x.shape[0], w_down.shape[-1]), jnp.float32)
    for i in range(held):
        w = jnp.where(e_loc == i, top_w, 0.0).sum(axis=-1)
        h = jax.nn.silu(
            jnp.dot(x, w_gate[i], preferred_element_type=jnp.float32)
        ) * jnp.dot(x, w_up[i], preferred_element_type=jnp.float32)
        y = y + w[:, None] * jnp.dot(
            h.astype(x.dtype), w_down[i], preferred_element_type=jnp.float32)
    return y, load
