"""A decoder language model as a pass-loop training job.

A language model is a pass loop like every other model here: the token
ids are the feasigns of ONE sparse slot, the input embedding IS the sparse
table (rows ``[show, click, hidden floats]`` + ``g2sum``: sparse adagrad
and the show/click counters as for every model), the decoder, the final
norm and the output head are the dense tower under the trainer's dense
optimizer.  ``BoxPSDataset`` -> ``table.begin_pass(census)`` ->
``Trainer.train_from_dataset`` -> ``end_pass`` run it unchanged.

What it asks of the system beyond a CTR tower, each read from the model
object (no flag, no config key):

  * ``loss(params, rows, batch)`` -- the model half of the step.  The
    trainers take it where a model defines it (train/step_loss.py) in
    place of ``apply`` -> sigmoid cross-entropy: here a softmax
    cross-entropy at every position against the NEXT token's class.
  * ``vocab_keys`` -- the tokenizer's vocabulary as sorted feasigns, fixed
    at construction.  The feed then ships ``key_class`` [K], each
    occurrence's rank in it (data/feed.py ``key_classes``): the device
    never sees 64-bit keys, and the head's classes are those ranks.
  * ``uses_seq_pos`` -- the slot's keys in file order (``seq_pos`` [B, T]).
  * ``step_counters`` -- named sums that ride the donated metric state and
    are published as telemetry counters at the pass's read-back.

The layer is assembled from the model's description, for x [B, T, H] (no
biases; ``n`` = RMSNorm, eps ``rms_eps``, learned scale):

    x += op_l(n1 x)          layer_types[l]: one of five operator kinds
    x += ffn_l(n2 x)         mlp_types[l]: dense, or sparse (+ shared)

``layer_types[l]`` is ``"sliding_attention"`` (causal, a window of
``window`` keys, plain rotary code), ``"full_attention"`` (causal, YaRN
rotary code) -- both ``o(attn(rope(q), rope(k), v))`` with grouped queries,
``n_heads`` query heads over ``n_kv_heads`` key-value heads, and where
``qk_norm`` a learned RMSNorm over the ``head_dim`` floats of every query
and key head before the rotary code -- ``"latent_attention"`` (causal;
``latent`` gives its widths): the keys and values of all heads are
projected up from ONE normed latent of ``kv_rank`` floats a token, a head's
query and key are ``qk_nope`` such floats beside ``qk_rope`` floats that
carry the rotary code (plain, adjacent pairs where ``interleaved``), the
turned key slice is one per token shared by all heads, and the value head
is ``v_dim`` wide (parallel/sequence.py, blockwise: no [T, T] tensor); with
``"rotary": False`` in ``latent`` the layer has no positional code at all
and the ``qk_rope`` slice stays, unturned --, ``"conv"``, a gated short
convolution:
``[b, c, u] = split3(h W_in)``, ``z = b * u``, a causal depthwise
convolution of ``conv_kernel`` taps over time (one weight a channel and
tap, zeros before the first token: ``y_t = sum_j w_j z_{t-K+1+j}``), then
``(c * y) W_out``; written as ``conv_kernel`` shifted multiply-adds, so no
[B, T, K, H] tensor exists -- or ``"kda"`` (``kda`` gives its sizes), the
one kind that carries a state along the sequence, a gated delta rule with
a decay a channel.  With h = n1 x, ``n_heads`` heads of ``head_dim`` = d:

    q~, k~, v~ = h W_q, h W_k, h W_v, each through a causal depthwise
        convolution of ``conv_kernel`` taps (as above) and SiLU
    q_t = l2norm(q~_t) / sqrt(d), k_t = l2norm(k~_t), v_t = v~_t  (a head)
    g_t = -exp(A_log) * softplus((h W_fa) W_fb + dt_bias)   <= 0, a channel
    beta_t = sigmoid(h W_beta)                               one a head
    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t              S [d, d] (key x value) a head, S_0 = 0
    op = (rmsnorm_d(o_t) * sigmoid((h W_ga) W_gb)) W_o

computed in chunks (``gated_delta_chunked``): inside a chunk the pairs'
decays ``exp(G_i - G_j)`` as they stand, one unit-lower-triangular solve a
chunk, and a walk that carries S from chunk to chunk -- one recurrence in
two forms, told apart by what the code can observe (``_kda_form``).  On a
TPU, for heads one lane tile (128) wide, the Pallas kernels of
parallel/kda_kernel.py: a pair's decays live in a register while its sums
are formed, the cumulative sum and the solve go with them, and the states
lie in VMEM while the chunks are walked, forward and backward; the backward
keeps the cumulative sum, the pairs' sums, the solve's result and one state
a block of 4 chunks.  Everywhere else -- every
other backend, and the kernels' oracle -- ``jax.numpy``: the decays of a
group of chunks through memory under ``lax.map``, a two-level ``lax.scan``,
both rematerialised, the backward keeping one state a group of chunks.
``kda.form`` counts which form a traced call took.

``mlp_types[l]`` is ``"dense"`` (one SwiGLU of ``dense_width``) or
``"sparse"``: a router over all ``n_experts`` (``router_score`` softmax or
sigmoid, with ``router_bias`` a per-expert selection bias that is a leaf
of the tree, enters the choice only and so is never updated), the
``n_experts_per_tok`` best renormalised and scaled by ``router_scale``,
SwiGLU experts of width ``expert_width``, of which this share holds
``experts_held = (lo, hi)`` and computes their part of the sum
(parallel/expert.py ``routed_experts``: drop-free, what absent experts
would add is left out), plus, where ``shared_width`` > 0, the experts every
share computes alike as one unweighted SwiGLU of that width.  The head
scores the ``len(vocab_keys)`` classes held here; the loss is the mean over
positions t < T-1 with a next token of the softmax cross-entropy against
that token's class, in token chunks so the [tokens, classes] logits are
never whole in memory.  Each layer is rematerialised in the backward pass
(``jax.checkpoint``, one per layer).  What a layer keeps is its input and,
where its attention came as the flash-form kernel (a TPU), the kernel's
output and log-sum-exp, which the kernel's forward rule names and
``kernel_residuals`` of parallel/sequence.py makes the checkpoint's policy:
the forward kernel runs once a step, everything else of the layer twice.
Elsewhere no policy is named and the input is all a layer keeps.

That loss is the default ``objective``, ``"next_token"``.  The other is
``"block_diffusion"`` (``diffusion`` gives ``block_len`` L, ``eps`` and
``noise_seed``): denoising over blocks, the objective a block-diffusion
model is trained or adapted from an autoregressive one with.  A sequence
x0 of T tokens gets a noise level t in [0, 1] from its instance's FIRST
DENSE FEATURE (``batch["dense"][:, 0]`` = t - 0.5 on a grid of 1/1000: a
corpus whose preprocessing wrote each instance's level there), p = eps +
(1 - eps) t, and each position is masked with probability p, the draw a
pure function of (``noise_seed``, the level, the sequence's first class):
the same instance draws the same mask in every pass.  A masked position's
input is the learned ``mask_embed`` [hidden], a dense leaf (the table's
rows are pulled by the batch's keys, and the mask token is no instance's
key); the layers run ONCE over 2T positions, the noised stream then the
clean one, both at rotary positions 0 .. T-1, every attention layer under
the block mask of parallel/sequence.py (``full_attention``'s third mask
word), block of i = i // L:

    a noised query sees the noised keys of its own block, both directions,
        and the clean keys of the blocks before it;
    a clean query sees the clean keys of its own block and of the blocks
        before it, and no noised key.

The head scores the noised stream only, position i against token x0_i
ITSELF (no shift: generation fills a block's masked positions in place),
and the loss is sum over masked positions of CE / p, over B * T.  Only
``full_attention`` layers have a two-stream form: a description with
another operator kind under this objective is refused by name.

A description may also say that the stack runs more than once: ``loops`` =
R applies the L layers R times over the SAME leaves (a looped language
model: a leaf's gradient is the sum over its R uses), with ``norm_f`` at
the end of every round, its output the next round's input; ``sandwich``
puts a second norm on each operator's output, inside the residual.  With
the third ``objective``, ``"looped_exit"`` (``exit`` gives ``beta``), the
head scores every round and a learned gate says where to stop:

    x = E[tokens]
    for r = 1 .. R:                              the same leaves every round
        for l = 1 .. L:
            x = x + n1b_l(op_l(n1_l x))          sandwich: without it, as
            x = x + n2b_l(ffn_l(n2_l x))         above, no n1b, no n2b
        h_r = norm_f(x);  x = h_r                feeds round r + 1
        z_r = h_r head^T                         logits of round r
        lam_r = sigmoid(h_r . w_g + b_g)         exit gate, a position
    p_1 = lam_1,  p_r = lam_r prod_{j<r} (1 - lam_j)  (r < R),
    p_R = prod_{j<R} (1 - lam_j)                 lam_R is not read
    loss = mean over the positions i with a next token of
           sum_r p_r(i) CE(z_r(i), next token) - beta H(p(i)),
           H(p) = -sum_r p_r ln p_r

the expected loss under the exit distribution less ``beta`` times its
entropy (a uniform prior over the rounds); ``exit_gate`` (``w`` [hidden],
``b`` [1]) is a dense leaf.  ``loops`` > 1 under ``next_token`` scores the
last round alone, which is what ``looped_exit`` gives when the gate never
stops early.  The rounds are ONE traced body (``_rounds``: a ``lax.scan``
whose body is the L rematerialised layers, so the compiler sees the layers
once and the backward pass keeps R x L layer boundaries), scopes
``round_norm`` and ``exit_gate`` beside the layers' and ``lm_head``; the R
head passes are one chunked pass over R x tokens.  Such a description adds
to ``step_counters``: ``loop.layer_passes`` (positions x rounds x layers)
and, under the objective, ``loop.exit_mass_<r>`` (the sum over the scored
positions of p_r) for r = 1 .. R and ``loop.exit_entropy`` (of H(p)).
Refused by name: ``loops`` > 1 under ``block_diffusion`` and with
``"conv"`` or ``"kda"`` layers (a state carried across the rounds is not
written), ``sandwich`` with an operator kind other than the two plain
attentions or with a sparse feed-forward, ``exit`` without its objective and the objective without a
second round.  Generation with an exit at a threshold of the cumulative
mass, and the gate trained alone, are not here (ROADMAP.md A4).  A
description whose ``mlp_types`` are all ``"dense"`` leaves the experts'
three sizes out; one with a sparse layer is refused without them.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.parallel.expert import (
    route_tokens,
    routed_experts,
    swiglu,
)
from paddlebox_tpu.parallel.sequence import (
    apply_rotary,
    full_attention,
    kernel_residuals,
    rotary_tables,
)
from paddlebox_tpu.telemetry import metrics as _tm

_log = logging.getLogger(__name__)
_FORM = _tm.counter(
    "kda.form", "traced calls of gated_delta_chunked by the form they took "
    "(kernel: the Pallas kernels of parallel/kda_kernel.py, on a TPU; "
    "chunks: the jax.numpy form, everywhere else and for shapes the kernels "
    "do not take)")

SLIDING, FULL, LATENT, CONV, KDA = (
    "sliding_attention", "full_attention", "latent_attention", "conv", "kda")
DENSE, SPARSE = "dense", "sparse"
LATENT_KEYS = ("kv_rank", "qk_nope", "qk_rope", "v_dim")
# "rotary": False = no positional code; else "interleaved" says which pairs
LATENT_OPTIONAL = ("interleaved", "rotary")
KDA_KEYS = ("n_heads", "head_dim", "conv_kernel", "gate_rank")
NEXT_TOKEN, BLOCK_DIFFUSION, LOOPED_EXIT = (
    "next_token", "block_diffusion", "looped_exit")
DIFFUSION_KEYS = ("block_len", "eps", "noise_seed")
EXIT_KEYS = ("beta",)
NOISE_GRID = 1000  # the dense feature's grid: a noise level is n / 1000
# gated_delta_chunked's two sizes, read on the chip (PERF.md section 6, PR
# 39): the positions of a chunk, and how many (query, key, channel) decays
# of the chunks' pairs are alive at once
KDA_CHUNK = 32
KDA_PAIR_ELEMS = 1 << 23


def _described(what: str, given, required: tuple, optional: tuple = ()):
    """Required keys present, unknown keys refused."""
    given = set(given or ())
    missing, unknown = set(required) - given, given - set(required) - set(
        optional)
    if missing or unknown:
        raise ValueError(
            f"{what} layers need {what}={required} (optional {optional}): "
            f"missing {sorted(missing)}, unknown {sorted(unknown)}")


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def causal_taps(u: jax.Array, w: jax.Array) -> jax.Array:
    """A causal depthwise convolution over time as shifted multiply-adds:
    u [B, T, ...], w [K, ...] -> y_t = sum_j w_j u_{t-K+1+j}, zeros before
    the first token (tap j reads position t-K+1+j); no [B, T, K, ...]
    tensor."""
    T, K = u.shape[1], w.shape[0]
    z = jnp.pad(u, ((0, 0), (K - 1, 0)) + ((0, 0),) * (u.ndim - 2))
    return sum(w[j] * z[:, j:j + T] for j in range(K))


@jax.custom_vjp
def _take_once(rows_pad, pos):
    """``rows_pad[pos]`` where no position but the padding row (the last)
    occurs twice in ``pos``: the cotangent is gathered back through the
    inverse map, where autodiff of ``take`` would scatter-add wide rows."""
    return jnp.take(rows_pad, pos, axis=0)


def _take_once_bwd(res, g):
    pos, n_rows = res
    flat = g.reshape(-1, g.shape[-1])
    n = flat.shape[0]
    # inverse map: row -> which entry of ``pos`` read it (n = none)
    inv = jnp.full((n_rows,), n, jnp.int32).at[pos.reshape(-1)].set(
        jnp.arange(n, dtype=jnp.int32))
    inv = inv.at[n_rows - 1].set(n)  # the padding row takes no gradient
    flat = jnp.concatenate([flat, jnp.zeros((1, flat.shape[1]), g.dtype)])
    return jnp.take(flat, inv, axis=0), None


_take_once.defvjp(
    lambda rows_pad, pos: (jnp.take(rows_pad, pos, axis=0),
                           (pos, rows_pad.shape[0])),
    _take_once_bwd)


def _kda_form(q, k, v, g, beta, chunk: int) -> tuple:
    """("kernel", its ``Spec``) where the Pallas kernels run the chunk
    recurrence, ("chunks", why not) where the ``jax.numpy`` form does: the
    kernels are a TPU's and take float32 or bfloat16 operands, heads one
    lane tile (128) wide, heads and a chunk of whole sublane tiles."""
    if jax.default_backend() != "tpu":
        return "chunks", "not a TPU"
    dtypes = {jnp.dtype(a.dtype) for a in (q, k, v, g, beta)}
    if not dtypes <= {jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}:
        return "chunks", f"dtypes {sorted(map(str, dtypes))}"
    from paddlebox_tpu.parallel import kda_kernel
    spec, why = kda_kernel.spec_for(
        q.shape[1], q.shape[2], q.shape[3], v.shape[3], chunk)
    return ("chunks", why) if spec is None else ("kernel", spec)


def gated_delta_chunked(q, k, v, g, beta, chunk: int) -> jax.Array:
    """The gated delta rule with a decay a channel, ``chunk`` positions at
    a time.  q, k, g [B, T, nh, dk] (g <= 0: the decay's logarithm), v
    [B, T, nh, dv], beta [B, T, nh]; returns o [B, T, nh, dv] of

        S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                          S [dk, dv] a head, S_0 = 0

    With G_t the sum of g from the chunk's start through t and S_0 the
    state that enters the chunk:

        A_ij = beta_i ((k_i * exp(G_i - G_j)) . k_j)                  j < i
        u    = (I + A)^-1 (beta * (v - (k * exp G) S_0))
        o_i  = (q_i * exp G_i)^T S_0
               + sum over j <= i of ((q_i * exp(G_i - G_j)) . k_j) u_j
        S_C  = diag(exp G_C) S_0 + sum over j of (k_j * exp(G_C - G_j)) u_j^T

    Every exponent is <= 0 as written and stays so: the pairs' decays are
    taken in the difference form, exp(G_i - G_j) for every (i, j <= i,
    channel) of a chunk, never as (k_i exp G_i)(k_j exp -G_j), whose second
    factor overflows float32 once a chunk's decays multiply to under 1e-38
    (PERF.md section 6, PR 39).  That is [chunk, chunk, dk] decays a chunk
    and head, so the chunk is short, and the chunks go ``n`` at a time
    (``KDA_PAIR_ELEMS``) through three steps: the pairs' sums (``lax.map``,
    each group rematerialised: its decays are alive only while it is
    computed, forward and backward); ONE unit-lower-triangular solve for
    all chunks, against [beta k exp G | beta v], since u = u0 - w S_0 is
    linear in the state; and the ``lax.scan`` that carries S, four products
    a chunk, in groups whose body is rematerialised, so that the backward
    pass keeps one state a group and one a chunk of the group it is in.

    That is the ``jax.numpy`` form: every backend's but the TPU's, and the
    oracle of the other.  On a TPU, for float operands with heads one lane
    tile (128) wide, heads and a chunk of whole sublane tiles
    (``_kda_form``), the same recurrence runs as the Pallas kernels of
    parallel/kda_kernel.py, two each way and nothing between them: a pair's
    decays live in a register while its sums are formed, the cumulative sum
    and the solve (by substitution) go with them, and the states lie in VMEM
    while the chunks are walked; q, k, v and g are read where they are.
    Kept for the backward are G, the pairs' sums, the solve's result and
    one state a block of 4 chunks (no decay, no state a chunk), and
    ``KDA_PAIR_ELEMS`` and the checkpoints below play no part.
    ``kda.form`` counts which form a traced call took."""
    form, found = _kda_form(q, k, v, g, beta, chunk)
    _FORM.inc(form=form)
    if form == "kernel":
        from paddlebox_tpu.parallel import kda_kernel
        return kda_kernel.gated_delta(q, k, v, g, beta, found)
    _log.debug("gated_delta_chunked in chunks: %s", found)
    B, T, nh, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    N = -(-T // C)
    limit = max(1, KDA_PAIR_ELEMS // (B * nh * C * C * dk))
    n = max(d for d in range(1, min(limit, N) + 1) if N % d == 0)

    def grouped(a):  # [B, T, nh, ...] -> [N / n, n, B, nh, C, ...]
        # padding: k = v = beta = 0 and g = 0 leave the state as it is
        a = jnp.pad(a, ((0, 0), (0, N * C - T)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, N // n, n, C, *a.shape[2:])
        return a.transpose(1, 2, 0, 4, 3, *range(5, a.ndim))

    q, k, v, g, beta = (grouped(a) for a in (q, k, v, g, beta))
    lower = jnp.tril(jnp.ones((C, C), bool))

    @jax.checkpoint
    def pairs(x):
        q, k, g, beta = x
        G = jnp.cumsum(g, axis=-2)
        decay = jnp.exp(jnp.where(
            lower[:, :, None], G[..., :, None, :] - G[..., None, :, :],
            -jnp.inf))  # [.., i, j, dk]: exp(G_i - G_j), 0 where j > i
        kd = k[..., None, :, :] * decay
        A = beta[..., None] * (k[..., :, None, :] * kd).sum(-1)
        P = (q[..., :, None, :] * kd).sum(-1)
        return jnp.where(lower.T, 0.0, A), P, G  # A: j < i; P: j <= i

    A, P, G = jax.lax.map(pairs, (q, k, g, beta))
    eG = jnp.exp(G)
    sol = jax.lax.linalg.triangular_solve(
        A, beta[..., None] * jnp.concatenate([k * eG, v], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u0 = sol[..., :dk], sol[..., dk:]

    def chunk_step(S, x):  # one chunk of all heads: [B, nh, C, .]
        qG, P, w, u0, k_out, g_out = x
        u = u0 - w @ S
        o = qG @ S + P @ u
        return g_out[..., None] * S + jnp.swapaxes(k_out, -1, -2) @ u, o

    @jax.checkpoint
    def group_step(S, x):
        return jax.lax.scan(chunk_step, S, x)

    _, o = jax.lax.scan(
        group_step, jnp.zeros((B, nh, dk, dv), jnp.float32),
        (q * eG, P, w, u0, k * jnp.exp(G[..., -1:, :] - G),
         eG[..., -1, :]))
    # [N / n, n, B, nh, C, dv] -> [B, T, nh, dv]
    return o.transpose(2, 0, 1, 4, 3, 5).reshape(B, N * C, nh, dv)[:, :T]


def exit_distribution(gates: jax.Array) -> tuple:
    """Where a looped stack would stop: gates [R, ...] the rounds' gate
    logits, lam_r = sigmoid(gates[r]) the chance of stopping at round r
    having come that far.  Returns p [R, ...], p_r = lam_r * prod over
    j < r of (1 - lam_j) and the last round what is left (its own gate is
    not read), and the entropy -sum_r p_r ln p_r [...]; in logarithms, so a
    saturated gate gives 0 and no NaN."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates[:-1]), axis=0)
    zero = jnp.zeros_like(gates[:1])
    logp = (jnp.concatenate([zero, stay])
            + jnp.concatenate([jax.nn.log_sigmoid(gates[:-1]), zero]))
    p = jnp.exp(logp)
    return p, -(p * logp).sum(axis=0)


def _ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def _log_of_uniform_1_16(key, shape):
    """log A, A uniform in [1, 16): with the bias below, what the gated
    delta rule's family seeds its decays with, weak to strong."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _softplus_inverse_of_log_uniform(key, shape):
    """The bias whose softplus is log-uniform in [1e-3, 1e-1)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class DecoderMoeLM:
    """Decoder whose layers are assembled from a description -- window,
    full or latent attention, a gated short convolution or a gated delta
    rule with a carried state; a dense feed-forward or token-routed experts
    with or without shared ones; the stack run once or ``loops`` times over
    the same leaves -- trained through the pass loop on next-token
    prediction, ``objective="block_diffusion"``, on denoising over blocks
    or, ``"looped_exit"``, on every round's prediction weighted by a
    learned exit distribution (the module's docstring)."""

    uses_seq_pos = True
    n_sparse_slots = 1
    # sums over a pass's steps (and layers), published at the read-back:
    # positions with a target; token-expert pairs computed here and
    # n_experts_per_tok x tokens; the largest held expert's tokens beside
    # the held experts' mean; a description with "kda" layers adds
    # ``kda.tokens``, the positions that went through such an operator, one
    # with the block-diffusion objective ``diffusion.positions``, the
    # positions that went through the layers (both streams; there
    # ``trainer.tokens`` counts the masked positions, the ones scored), one
    # whose stack runs more than once ``loop.layer_passes`` and, under the
    # looped-exit objective, the rounds' exit masses and the exit entropy
    step_counters = ("trainer.tokens", "moe.pairs_local", "moe.pairs_routed",
                     "moe.expert_load_max", "moe.expert_load_mean")

    def __init__(
        self,
        emb_width: int,  # pulled row width (cvm_offset + hidden)
        vocab_keys,  # sorted uint64 feasigns: the classes of the head
        max_seq_len: int,
        n_heads: int,
        n_kv_heads: int,
        head_dim: int,
        layer_types: Sequence[str],
        window: int,
        n_experts: int = 0,  # the three sizes of a "sparse" layer's experts
        n_experts_per_tok: int = 0,
        expert_width: int = 0,
        experts_held: Optional[tuple] = None,  # (lo, hi); None = all
        rope_theta: float = 10000.0,
        yarn: Optional[dict] = None,  # rotary_tables' keys, full layers
        rms_eps: float = 1e-6,
        cvm_offset: int = 2,
        block_q: int = 256,
        loss_chunk: int = 2048,
        mlp_types: Optional[Sequence[str]] = None,  # None = all sparse
        dense_width: int = 0,  # the SwiGLU of a "dense" layer
        shared_width: int = 0,  # shared experts as one SwiGLU; 0 = none
        router_score: str = "softmax",  # or "sigmoid"
        router_bias: bool = False,  # a selection-bias leaf, never updated
        router_scale: float = 1.0,
        latent: Optional[dict] = None,  # LATENT_KEYS, for latent layers
        qk_norm: bool = False,  # a learned norm on every query and key head
        conv_kernel: int = 0,  # taps of a "conv" layer's convolution
        kda: Optional[dict] = None,  # KDA_KEYS, for "kda" layers
        objective: str = NEXT_TOKEN,  # "block_diffusion", "looped_exit"
        diffusion: Optional[dict] = None,  # DIFFUSION_KEYS, for the second
        loops: int = 1,  # how many times the stack runs, over the same leaves
        sandwich: bool = False,  # a norm after each operator too
        exit: Optional[dict] = None,  # EXIT_KEYS, for "looped_exit"
    ):
        vocab_keys = np.asarray(vocab_keys, dtype=np.uint64)
        if vocab_keys.ndim != 1 or not np.all(vocab_keys[1:] > vocab_keys[:-1]):
            raise ValueError("vocab_keys must be sorted, distinct feasigns")
        kinds = (SLIDING, FULL, LATENT, CONV, KDA)
        bad = [t for t in layer_types if t not in kinds]
        if bad:
            raise ValueError(
                f"unknown layer types {sorted(set(bad))}: the kinds are "
                f"{kinds}")
        if CONV in layer_types and conv_kernel <= 0:
            raise ValueError("a conv layer needs conv_kernel")
        mlp_types = tuple(mlp_types or (SPARSE,) * len(layer_types))
        bad = [t for t in mlp_types if t not in (DENSE, SPARSE)]
        if bad or len(mlp_types) != len(layer_types):
            raise ValueError(
                f"mlp_types {mlp_types} for {len(layer_types)} layers")
        if DENSE in mlp_types and dense_width <= 0:
            raise ValueError("a dense layer needs dense_width")
        if SPARSE in mlp_types:
            missing = [name for name, size in (
                ("n_experts", n_experts),
                ("n_experts_per_tok", n_experts_per_tok),
                ("expert_width", expert_width)) if size <= 0]
            if missing:
                raise ValueError(f"a sparse layer needs {missing}")
        if LATENT in layer_types:
            _described("latent", latent, LATENT_KEYS, LATENT_OPTIONAL)
            if latent.get("rotary", True) and "interleaved" not in latent:
                raise ValueError("a rotary code needs latent['interleaved']")
        if KDA in layer_types:
            _described("kda", kda, KDA_KEYS)
        if router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router score {router_score!r}")
        if objective not in (NEXT_TOKEN, BLOCK_DIFFUSION, LOOPED_EXIT):
            raise ValueError(f"unknown objective {objective!r}")
        if loops < 1:
            raise ValueError(f"the stack runs {loops} times")
        if objective == BLOCK_DIFFUSION:
            _described("diffusion", diffusion, DIFFUSION_KEYS)
            other = sorted(set(layer_types) - {FULL})
            if other:
                raise ValueError(
                    f"the block_diffusion objective runs {FULL} layers "
                    f"under the block mask over two streams; the two-stream "
                    f"form of {other} is not written")
            if diffusion["block_len"] < 1 or block_q % diffusion["block_len"]:
                raise ValueError(
                    f"block_q {block_q} is no multiple of the block length "
                    f"{diffusion['block_len']}")
            if loops > 1:
                raise ValueError(
                    "the block_diffusion objective runs its two streams "
                    f"through the stack once; loops={loops} under it is not "
                    "written")
        elif diffusion is not None:
            raise ValueError("diffusion describes the block_diffusion "
                             "objective only")
        carried = sorted({CONV, KDA} & set(layer_types))
        if loops > 1 and carried:
            raise ValueError(
                f"loops={loops} with {carried} layers: a state carried "
                "across the rounds is not written")
        if objective == LOOPED_EXIT:
            _described("exit", exit, EXIT_KEYS)
            if loops < 2:
                raise ValueError(
                    "the looped_exit objective weighs the rounds of a stack "
                    f"that runs more than once; loops={loops}")
        elif exit is not None:
            raise ValueError("exit describes the looped_exit objective only")
        unnormed = sorted((set(layer_types) - {SLIDING, FULL})
                          | (set(mlp_types) - {DENSE}))
        if sandwich and unnormed:
            raise ValueError(
                f"the sandwich form of {unnormed} layers is not written")
        if n_heads % n_kv_heads:
            raise ValueError(
                f"{n_heads} query heads over {n_kv_heads} key-value heads")
        lo, hi = experts_held or (0, n_experts)
        if SPARSE in mlp_types and not 0 <= lo < hi <= n_experts:
            raise ValueError(f"experts_held {(lo, hi)} of {n_experts}")
        self.vocab_keys = vocab_keys
        self.n_classes = int(vocab_keys.shape[0])
        self.hidden = emb_width - cvm_offset
        if self.hidden <= 0:
            raise ValueError("emb_width leaves no embedding columns")
        self.emb_width, self.cvm_offset = emb_width, cvm_offset
        self.max_seq_len = max_seq_len
        self.n_heads, self.n_kv_heads = n_heads, n_kv_heads
        self.head_dim = head_dim
        self.layer_types = tuple(layer_types)
        self.mlp_types = mlp_types
        self.dense_width, self.shared_width = dense_width, shared_width
        self.router_score, self.router_bias = router_score, router_bias
        self.router_scale = float(router_scale)
        self.latent = latent
        self.qk_norm, self.conv_kernel = qk_norm, conv_kernel
        self.kda = kda
        if KDA in layer_types:  # one more sum a step, for such a description
            self.step_counters = self.step_counters + ("kda.tokens",)
        self.objective, self.diffusion = objective, diffusion
        if objective == BLOCK_DIFFUSION:
            self.step_counters = self.step_counters + ("diffusion.positions",)
        self.loops, self.sandwich, self.exit = loops, sandwich, exit
        if loops > 1:
            self.step_counters = self.step_counters + ("loop.layer_passes",)
        if objective == LOOPED_EXIT:
            self.step_counters = self.step_counters + tuple(
                f"loop.exit_mass_{r}" for r in range(1, loops + 1)) + (
                    "loop.exit_entropy",)
        self.window = window
        self.n_experts, self.top_k = n_experts, n_experts_per_tok
        self.expert_width = expert_width
        self.experts_held = (lo, hi)
        self.rope_theta, self.yarn = rope_theta, yarn
        self.rms_eps = rms_eps
        self.block_q, self.loss_chunk = block_q, loss_chunk

    # -- params ------------------------------------------------------------ #
    def _layer_weights(self, attn_kind: str, mlp_kind: str) -> list:
        """(name, shape, what a standard normal draw is divided by -- or
        another draw, a function of key and shape) of one layer's seeded
        leaves, in the order their keys are drawn."""
        H, F = self.hidden, self.expert_width
        held = self.experts_held[1] - self.experts_held[0]

        def w(name, *shape, fan_in):
            return name, shape, np.sqrt(fan_in)

        if attn_kind == LATENT:
            z, nh = self.latent, self.n_heads
            out = [
                w("wq", H, nh * (z["qk_nope"] + z["qk_rope"]), fan_in=H),
                w("wkv_a", H, z["kv_rank"] + z["qk_rope"], fan_in=H),
                w("wkv_b", z["kv_rank"], nh * (z["qk_nope"] + z["v_dim"]),
                  fan_in=z["kv_rank"]),
                w("wo", nh * z["v_dim"], H, fan_in=nh * z["v_dim"]),
            ]
        elif attn_kind == CONV:
            K = self.conv_kernel
            out = [w("conv_in", H, 3 * H, fan_in=H),
                   w("conv_w", K, H, fan_in=K),
                   w("conv_out", H, H, fan_in=H)]
        elif attn_kind == KDA:
            z = self.kda
            nh, K, R = z["n_heads"], z["conv_kernel"], z["gate_rank"]
            W = nh * z["head_dim"]
            out = [w("kda_q", H, W, fan_in=H), w("kda_k", H, W, fan_in=H),
                   w("kda_v", H, W, fan_in=H),
                   w("kda_conv_q", K, W, fan_in=K),
                   w("kda_conv_k", K, W, fan_in=K),
                   w("kda_conv_v", K, W, fan_in=K),
                   w("kda_fa", H, R, fan_in=H), w("kda_fb", R, W, fan_in=R),
                   ("kda_A_log", (nh,), _log_of_uniform_1_16),
                   ("kda_dt_bias", (W,), _softplus_inverse_of_log_uniform),
                   w("kda_beta", H, nh, fan_in=H),
                   w("kda_ga", H, R, fan_in=H), w("kda_gb", R, W, fan_in=R),
                   ("kda_o_norm", (z["head_dim"],), _ones),
                   w("kda_o", W, H, fan_in=W)]
        else:
            hq = self.n_heads * self.head_dim
            hkv = self.n_kv_heads * self.head_dim
            out = [w("wq", H, hq, fan_in=H), w("wk", H, hkv, fan_in=H),
                   w("wv", H, hkv, fan_in=H), w("wo", hq, H, fan_in=hq)]
        if mlp_kind == DENSE:
            D = self.dense_width
            return out + [w("mlp_gate", H, D, fan_in=H),
                          w("mlp_up", H, D, fan_in=H),
                          w("mlp_down", D, H, fan_in=D)]
        out.append(w("router", H, self.n_experts, fan_in=H))
        if self.router_bias:  # wide enough to change some choices
            out.append(("router_bias", (self.n_experts,), 10.0))
        out += [w("w_gate", held, H, F, fan_in=H),
                w("w_up", held, H, F, fan_in=H),
                w("w_down", held, F, H, fan_in=F)]
        if self.shared_width:
            S = self.shared_width
            out += [w("shared_gate", H, S, fan_in=H),
                    w("shared_up", H, S, fan_in=H),
                    w("shared_down", S, H, fan_in=S)]
        return out

    def init(self, key: jax.Array) -> dict:
        """Normal weights scaled by 1/sqrt(fan-in), norm scales 1 (a "kda"
        layer's decays as ``_layer_weights`` names them); under the
        block-diffusion objective ``mask_embed`` too, a normal draw at a
        table row's scale, and under the looped-exit objective
        ``exit_gate`` (``w`` normal / sqrt(hidden), ``b`` 0: the gate's
        logit is a standard normal draw a position, no round preferred),
        each from a key of its own so that every other leaf is what it is
        without the objective."""
        H = self.hidden
        layers = []
        for lk, attn_kind, mlp_kind in zip(
                jax.random.split(key, len(self.layer_types) + 1)[1:],
                self.layer_types, self.mlp_types):
            weights = self._layer_weights(attn_kind, mlp_kind)
            lp = {"n1": jnp.ones((H,), jnp.float32),
                  "n2": jnp.ones((H,), jnp.float32)}
            if self.sandwich:
                lp["n1b"] = jnp.ones((H,), jnp.float32)
                lp["n2b"] = jnp.ones((H,), jnp.float32)
            if attn_kind == LATENT:
                lp["n_kv"] = jnp.ones((self.latent["kv_rank"],), jnp.float32)
            elif attn_kind not in (CONV, KDA) and self.qk_norm:
                lp["q_norm"] = jnp.ones((self.head_dim,), jnp.float32)
                lp["k_norm"] = jnp.ones((self.head_dim,), jnp.float32)
            for k, (name, shape, how) in zip(
                    jax.random.split(lk, len(weights)), weights):
                lp[name] = how(k, shape) if callable(how) else (
                    jax.random.normal(k, shape, jnp.float32) / how)
            layers.append(lp)
        params = {
            "layers": layers,
            "norm_f": jnp.ones((H,), jnp.float32),
            "head": jax.random.normal(
                jax.random.split(key)[0], (self.n_classes, H), jnp.float32
            ) / np.sqrt(H),
        }
        if self.objective == BLOCK_DIFFUSION:
            params["mask_embed"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, len(layers) + 1), (H,), jnp.float32)
        if self.objective == LOOPED_EXIT:
            params["exit_gate"] = {
                "w": jax.random.normal(
                    jax.random.fold_in(key, len(layers) + 2), (H,),
                    jnp.float32) / np.sqrt(H),
                "b": jnp.zeros((1,), jnp.float32)}
        return params

    # -- forward ----------------------------------------------------------- #
    def _attend(self, lp: dict, x: jax.Array, kind: str) -> jax.Array:
        """x + the layer's attention over n1(x).  Under the block-diffusion
        objective x is [B, 2T, H], the noised stream then the clean one:
        both at rotary positions 0 .. T-1, under the block mask."""
        B, T, H = x.shape
        if kind == LATENT:
            return self._attend_latent(lp, x)
        sliding = kind == SLIDING
        streams = self.objective == BLOCK_DIFFUSION
        with jax.named_scope(
                "attn_block_diffusion" if streams
                else "attn_window" if sliding else "attn_full"):
            h = rms_norm(x, lp["n1"], self.rms_eps)
            shape = (B, T, -1, self.head_dim)
            q = (h @ lp["wq"]).reshape(shape)
            k = (h @ lp["wk"]).reshape(shape)
            v = (h @ lp["wv"]).reshape(shape)
            if self.qk_norm:
                q = rms_norm(q, lp["q_norm"], self.rms_eps)
                k = rms_norm(k, lp["k_norm"], self.rms_eps)
            cos, sin = rotary_tables(
                jnp.arange(T) % (T // 2) if streams else jnp.arange(T),
                self.head_dim, self.rope_theta,
                None if sliding else self.yarn)
            mask = ({"block_diffusion": self.diffusion["block_len"]}
                    if streams else
                    {"causal": True,
                     "window": self.window if sliding else None})
            a = full_attention(
                apply_rotary(q, cos, sin), apply_rotary(k, cos, sin), v,
                block_q=self.block_q, **mask)
            y = a.reshape(B, T, -1) @ lp["wo"]
            if self.sandwich:
                y = rms_norm(y, lp["n1b"], self.rms_eps)
            return x + y

    def _attend_latent(self, lp: dict, x: jax.Array) -> jax.Array:
        B, T, H = x.shape
        z, nh = self.latent, self.n_heads
        rank, nope, rope = z["kv_rank"], z["qk_nope"], z["qk_rope"]
        with jax.named_scope("attn_latent"):
            h = rms_norm(x, lp["n1"], self.rms_eps)
            q = (h @ lp["wq"]).reshape(B, T, nh, nope + rope)
            kv_a = h @ lp["wkv_a"]  # [B, T, rank + rope]
            kv = (rms_norm(kv_a[..., :rank], lp["n_kv"], self.rms_eps)
                  @ lp["wkv_b"]).reshape(B, T, nh, nope + z["v_dim"])
            if z.get("rotary", True):
                cos, sin = rotary_tables(
                    jnp.arange(T), rope, self.rope_theta,
                    interleaved=z["interleaved"])
                q_pe = apply_rotary(q[..., nope:], cos, sin,
                                    z["interleaved"])
                # the turned key slice: one per token, shared by every head
                k_pe = apply_rotary(kv_a[:, :, None, rank:], cos, sin,
                                    z["interleaved"])
                q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            else:  # no positional code: the slice stays, unturned
                k_pe = kv_a[:, :, None, rank:]
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, nh, rope))],
                axis=-1)
            a = full_attention(q, k, kv[..., nope:], causal=True,
                               block_q=self.block_q)
            return x + a.reshape(B, T, -1) @ lp["wo"]

    def _conv_mix(self, lp: dict, x: jax.Array) -> jax.Array:
        """x + the layer's gated short convolution over n1(x)."""
        with jax.named_scope("conv_mixer"):
            h = rms_norm(x, lp["n1"], self.rms_eps)
            b, c, u = jnp.split(h @ lp["conv_in"], 3, axis=-1)
            y = causal_taps(b * u, lp["conv_w"])
            return x + (c * y) @ lp["conv_out"]

    def _kda_mix(self, lp: dict, x: jax.Array) -> jax.Array:
        """x + the layer's gated delta rule over n1(x) (the module's
        docstring has the equations)."""
        B, T, H = x.shape
        z = self.kda
        nh, hd = z["n_heads"], z["head_dim"]

        def heads(a):
            return a.reshape(B, T, nh, hd)

        def conv(u, w):
            # the taps over the heads' shape: reshaped after them, the chain
            # ends in a bitcast of the compiler's, and a fusion rooted in one
            # has no name for a trace to find its scope by (PERF.md 6, PR 39)
            return jax.nn.silu(causal_taps(heads(u), w.reshape(-1, nh, hd)))

        def l2norm(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        with jax.named_scope("kda_mixer"):
            h = rms_norm(x, lp["n1"], self.rms_eps)
            q = l2norm(conv(h @ lp["kda_q"], lp["kda_conv_q"])) * hd ** -0.5
            k = l2norm(conv(h @ lp["kda_k"], lp["kda_conv_k"]))
            v = conv(h @ lp["kda_v"], lp["kda_conv_v"])
            g = -jnp.exp(lp["kda_A_log"])[:, None] * jax.nn.softplus(heads(
                (h @ lp["kda_fa"]) @ lp["kda_fb"] + lp["kda_dt_bias"]))
            beta = jax.nn.sigmoid(h @ lp["kda_beta"])
            with jax.named_scope("kda_scan"):
                o = gated_delta_chunked(q, k, v, g, beta, KDA_CHUNK)
            gate = jax.nn.sigmoid(heads((h @ lp["kda_ga"]) @ lp["kda_gb"]))
            o = rms_norm(o, lp["kda_o_norm"], self.rms_eps) * gate
            return x + o.reshape(B, T, -1) @ lp["kda_o"]

    def _layer(self, lp: dict, x: jax.Array, valid: jax.Array, kinds: tuple):
        """One decoder layer of ``kinds`` = (operator, feed-forward);
        ``valid`` [B, T] marks the positions that hold a token (the others
        are routed to no expert).  Returns (x, [pairs held here, largest
        held expert's tokens]), zeros for a dense layer."""
        B, T, H = x.shape
        if kinds[0] == CONV:
            x = self._conv_mix(lp, x)
        elif kinds[0] == KDA:
            x = self._kda_mix(lp, x)
        else:
            x = self._attend(lp, x, kinds[0])
        h = rms_norm(x, lp["n2"], self.rms_eps).reshape(B * T, H)
        if kinds[1] == DENSE:
            with jax.named_scope("dense_mlp"):
                y = swiglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
                if self.sandwich:
                    y = rms_norm(y, lp["n2b"], self.rms_eps)
            return x + y.reshape(B, T, H), jnp.zeros((2,), jnp.float32)
        with jax.named_scope("router"):
            top_w, top_e = route_tokens(
                h, lp["router"], self.top_k, self.router_score,
                lp.get("router_bias"), self.router_scale)
            top_e = jnp.where(valid.reshape(-1, 1), top_e, -1)
        with jax.named_scope("experts"):
            y, load = routed_experts(
                h, top_w, top_e, lp["w_gate"], lp["w_up"], lp["w_down"],
                self.experts_held[0])
        if self.shared_width:
            with jax.named_scope("shared_experts"):
                y = y + swiglu(h, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"])
        return x + y.reshape(B, T, H), jnp.stack(
            [load.sum(), load.max()]).astype(jnp.float32)

    def _token_losses(self, params: dict, x: jax.Array, target: jax.Array,
                      normed: bool = False):
        """Softmax cross-entropy of every token against ``target`` (class
        ids; anything where there is none), [N], in chunks of
        ``loss_chunk`` tokens: one chunk's [chunk, classes] logits are all
        that is held, forward and (rematerialised) backward.  ``normed``:
        x has been through ``norm_f`` (a stack that runs more than once
        applies it at the end of every round)."""
        N, H = x.shape
        chunk = min(self.loss_chunk, N)
        pad = -N % chunk
        x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, H)
        target = jnp.pad(target, (0, pad)).reshape(-1, chunk)

        @jax.checkpoint
        def one(args):
            xc, tc = args
            if not normed:
                xc = rms_norm(xc, params["norm_f"], self.rms_eps)
            logits = jnp.dot(xc, params["head"].T,
                             preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(
                logits, jnp.clip(tc, 0, self.n_classes - 1)[:, None], axis=1)
            return jax.nn.logsumexp(logits, axis=-1) - picked[:, 0]

        return jax.lax.map(one, (x, target)).reshape(-1)[:N]

    def loss(self, params: dict, rows: jax.Array, batch: dict):
        """The model half of the training step (train/step_loss.py):
        ``rows`` [K, emb_width] the pulled occurrence rows, ``batch`` the
        step's device feed, of which it reads ``seq_pos`` [B, T],
        ``key_class`` [K] and, under the block-diffusion objective,
        ``dense`` [B, >= 1] (each instance's noise level less a half).

        Returns (loss, preds [B], counts): the mean next-token
        cross-entropy over the positions that have a next token -- or the
        denoising loss, sum over the masked positions of CE / p over B * T,
        or the looped-exit loss, the mean over those positions of the
        rounds' cross-entropies weighted by the exit distribution less beta
        times its entropy --; a number in (0, 1] a sequence -- exp(-its
        mean loss), the geometric mean of the probability it gave its
        scored tokens, 1 where it has none -- so that the trainers' AUC and
        metric state keep their shapes (the AUC of such numbers against
        ``click`` means nothing; the loss is the metric); and
        ``step_counters``' values for this step."""
        seq_pos = batch["seq_pos"]
        B, T = seq_pos.shape
        K = rows.shape[0]
        if T != self.max_seq_len:
            raise ValueError(
                f"seq_pos width {T} != model max_seq_len {self.max_seq_len}")
        with jax.named_scope("embed"):
            # padding positions (== K) read the appended zero row
            rows_pad = jnp.concatenate(
                [rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
            x = _take_once(rows_pad, seq_pos)[..., self.cvm_offset:]
            cls = jnp.take(
                jnp.concatenate([batch["key_class"],
                                 jnp.full((1,), -1, jnp.int32)]), seq_pos)
        streams = self.objective == BLOCK_DIFFUSION
        if streams:
            x, target, weight = self._noised(params, x, cls, seq_pos < K,
                                             batch["dense"])
        else:  # position t is scored against the class at t + 1
            target = jnp.concatenate(
                [cls[:, 1:], jnp.full((B, 1), -1, jnp.int32)], axis=1)
        scored = (target >= 0).astype(jnp.float32)
        valid = seq_pos < K
        if streams:
            valid = jnp.concatenate([valid, valid], axis=1)
        exits = self.objective == LOOPED_EXIT
        if self.loops > 1:
            x, rounds, gates, moe = self._rounds(params, x, valid)
        else:
            x, moe = self._stack(params, x, valid)
        with jax.named_scope("lm_head"):
            if exits:  # every round's state against the same targets
                R = self.loops
                ce = self._token_losses(
                    params, rounds.reshape(R * B * T, -1),
                    jnp.tile(target.reshape(-1), R), normed=True)
                ce = ce.reshape(R, B, T)
            else:  # the noised stream's positions where there are two
                ce = self._token_losses(
                    params, x[:, :T].reshape(B * T, -1), target.reshape(-1),
                    normed=self.loops > 1)
                ce = ce.reshape(B, T) * scored
        n_scored = scored.sum()
        if exits:
            with jax.named_scope("exit_gate"):
                p, entropy = exit_distribution(gates)
                ce = (p * ce).sum(axis=0) * scored
                entropy = entropy * scored
                loss = (ce.sum() - self.exit["beta"] * entropy.sum()
                        ) / jnp.maximum(n_scored, 1.0)
        elif streams:
            loss = (ce * weight[:, None]).sum() / (B * T)
        else:
            loss = ce.sum() / jnp.maximum(n_scored, 1.0)
        preds = jnp.exp(-ce.sum(axis=1) / jnp.maximum(scored.sum(axis=1), 1.0))
        held = max(self.experts_held[1] - self.experts_held[0], 1)
        n_tokens = valid.sum().astype(jnp.float32)
        counts = [n_scored, moe[0],
                  n_tokens * self.top_k * (
                      self.mlp_types.count(SPARSE) * self.loops),
                  moe[1], moe[0] / held]
        if KDA in self.layer_types:
            counts.append(n_tokens * self.layer_types.count(KDA))
        if streams:
            counts.append(n_tokens)
        if self.loops > 1:
            counts.append(n_tokens * self.loops * len(self.layer_types))
        if exits:
            counts += list((p * scored).sum(axis=(1, 2))) + [entropy.sum()]
        return loss, preds, jnp.stack(counts)

    def _stack(self, params: dict, x: jax.Array, valid: jax.Array) -> tuple:
        """The layers once, each rematerialised in the backward pass: a
        layer keeps its input and, where its attention came as the kernel,
        the kernel's output and log-sum-exp (``kernel_residuals``: on a TPU
        the forward kernel runs once a step; elsewhere no policy is named).
        Returns (x, the two sums of ``_layer`` over the layers)."""
        moe = jnp.zeros((2,), jnp.float32)
        keep = kernel_residuals()
        for lp, kinds in zip(params["layers"],
                             zip(self.layer_types, self.mlp_types)):
            x, m = jax.checkpoint(self._layer, static_argnums=(3,),
                                  policy=keep)(lp, x, valid, kinds)
            moe = moe + m
        return x, moe

    def _rounds(self, params: dict, x: jax.Array, valid: jax.Array) -> tuple:
        """The stack ``loops`` times over the same leaves, as ONE traced
        body (a ``lax.scan`` over the rounds: the compiler sees the layers
        once, a leaf's gradient is the sum over its uses, and the backward
        pass keeps a boundary a layer and round, stacked over the rounds --
        with it, on a TPU, an attention kernel's output and log-sum-exp a
        layer and round): ``_stack``, then
        ``norm_f`` at the end of every round, its output the next round's
        input.  Returns the last round's normed state [B, T, H]; under the
        looped-exit objective every round's normed state [R, B, T, H] and
        gate logit [R, B, T] (else None, None); and the two sums of
        ``_layer`` over layers and rounds."""
        exits = self.objective == LOOPED_EXIT

        def one_round(x, _):
            x, moe = self._stack(params, x, valid)
            with jax.named_scope("round_norm"):
                h = rms_norm(x, params["norm_f"], self.rms_eps)
            if not exits:
                return h, (moe, None, None)
            with jax.named_scope("exit_gate"):
                gate = h @ params["exit_gate"]["w"] + params["exit_gate"]["b"]
            return h, (moe, h, gate)

        x, (moe, rounds, gates) = jax.lax.scan(
            one_round, x, None, length=self.loops)
        return x, rounds, gates, moe.sum(axis=0)

    def _noised(self, params: dict, x0: jax.Array, cls: jax.Array,
                valid: jax.Array, dense: jax.Array) -> tuple:
        """The block-diffusion objective's inputs from the clean ones: x0
        [B, T, H] the tokens' embeddings, cls [B, T] their classes, valid
        [B, T], dense [B, >= 1] the feed's dense features.  Returns the
        layers' input [B, 2T, H] (noised stream, then clean), the noised
        stream's targets [B, T] (the token's own class where it is masked,
        -1 elsewhere) and each sequence's loss weight 1 / p."""
        z = self.diffusion
        T = x0.shape[1]
        with jax.named_scope("noise"):
            # the level is an integer n of the dense feature's grid, and the
            # draw a function of (seed, n, first class): the instance's own
            n = jnp.round(NOISE_GRID * dense[:, 0]).astype(jnp.int32) + (
                NOISE_GRID // 2)
            p = z["eps"] + (1.0 - z["eps"]) * (
                n.astype(jnp.float32) / NOISE_GRID)
            seed = jax.random.PRNGKey(z["noise_seed"])
            u = jax.vmap(lambda n_b, c: jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(seed, n_b), c), (T,))
            )(n, cls[:, 0])
            masked = (u < p[:, None]) & valid
            xt = jnp.where(masked[..., None], params["mask_embed"], x0)
            return (jnp.concatenate([xt, x0], axis=1),
                    jnp.where(masked, cls, -1), 1.0 / p)
