"""``full_attention``'s blockwise form as one flash-form Pallas kernel (TPU).

The strips of parallel/sequence.py write a strip's scores [B, Hkv, G, bq, S]
to HBM and read them back for every pass of the softmax, for the second
product and once more when the strip is rematerialised.  Here the scores of
one [block_q, block_kv] tile live in VMEM only: a grid over batch,
key-value heads, query blocks and the key blocks a query block may see, a
running maximum and sum a query row (the online-softmax recursion), and the
output and the log-sum-exp a row the only things written.  The backward is
two kernels that recompute a tile's probabilities from q, k and the
log-sum-exp: one over query blocks for dq (it also writes delta = sum(do *
o) a row, formed a block at a time), one over key blocks for dk and dv (its
tiles transposed, keys on the rows, so every product streams its long side
through the MXU).

Nothing is copied around a call.  q, k and v are read where the projections
wrote them, [B, T, H * D] (the caller's [B, T, H, D] seen flat): a block is
``block`` positions by the columns of the heads one grid step works on --
the query heads of as many key-value heads as make every block's last
dimension whole lane tiles (one at D 128, two at D 192 / Dv 128 and at D
64) -- and a head is a static slice of lanes inside the kernel.  The output
and the three gradients are written the same way, in the caller's dtype.
What a layer keeps of its attention for the backward is therefore q, k and
v as they were given, the output as it was returned and the log-sum-exp
([B, H, T] float32); never a probability, and never a second copy.

Of those five only the output and the log-sum-exp are the kernel's own to
make, so the forward rule names them (``ATTN_OUT``, ``ATTN_LSE``:
``checkpoint_name``).  A caller that rematerialises a whole layer
(``jax.checkpoint``) and says nothing keeps the layer's input alone and runs
the forward kernel a second time to have them again; one whose policy saves
the two names (parallel/sequence.py ``kernel_residuals``) keeps them beside
the input, remakes q, k and v -- projections, norms, rotary codes -- and
runs the forward kernel once.  ``attn.kept`` counts the traced calls whose
forward rule named them.

The mask is one of the three descriptions of ``full_attention`` -- ``causal``,
``window``, ``block_diffusion`` -- and is never passed: from the description
and the block sizes a table is made at trace time that lists, for every
query block, the key blocks that hold a visible pair and whether the mask's
edge crosses each (``_visits``).  The grid runs over the table: a key block
wholly outside the mask is neither fetched nor computed, a tile wholly
inside it applies no mask, and only a tile the edge crosses builds its
``iota`` mask.  The query heads of one key-value head go against its block
of keys together (one fetch of K and V for the group; K and V are never
repeated in memory).

Precision is the configurations': the operands of every product are rounded
to bfloat16 inside the kernels (q, k, v, the probabilities, and in the
backward the cotangents), products accumulate in float32, and maximum, sum,
log-sum-exp and the output's accumulator stay float32 -- what float32
operands at the TPU's default matmul precision are in the strips.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.telemetry import metrics as _tm

CAUSAL, WINDOW, BLOCK_DIFFUSION = "causal", "window", "block_diffusion"
# what the forward rule names for a rematerialising caller's policy: the
# two residuals of the backward that only the kernel can make
ATTN_OUT, ATTN_LSE = "attn_out", "attn_lse"

_KEPT = _tm.counter(
    "attn.kept", "traced calls of the flash-form kernel whose forward rule "
    "named its output and log-sum-exp (ATTN_OUT, ATTN_LSE) for the "
    "backward, by the mask (causal, window, block_diffusion): under a "
    "layer's checkpoint whose policy saves the two names the forward "
    "kernel runs once a step, not twice")

# a masked score: finite, so a row whose first tile shows it nothing reads
# exp(0) there and not exp(-inf - -inf); every row sees itself under each
# described mask, and the first visible score scales that tile away
_MASKED = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024  # of the v5e's 128 MiB


class Spec(NamedTuple):
    """What is static of one call: the mask's description (``kind`` and
    its number: the window, or the diffusion block's length), the tile,
    the key-value heads one grid step works on, the dtype the products'
    operands are rounded to, and whether Pallas interprets the kernels
    (the CPU's tests)."""
    kind: str
    n: Optional[int]
    block_q: int
    block_kv: int
    heads: int = 1
    operands: str = "bfloat16"
    interpret: bool = False


def heads_a_step(hkv: int, d: int, dv: int) -> int:
    """The key-value heads whose columns make one block: the fewest whose
    key and value columns (and with them the query's and the output's) are
    whole lane tiles -- one at D 128, two at D 192 / Dv 128 or D 64 --, or
    every head (a block as wide as the array is always allowed)."""
    return next((n for n in range(1, hkv) if hkv % n == 0
                 and n * d % _LANES == 0 and n * dv % _LANES == 0), hkv)


def blocks_for(t_q: int, t_k: int, g: int, d: int, dv: int,
               hkv: int) -> Optional[tuple]:
    """(block_q, block_kv, heads) for q [.., t_q, hkv * g heads, d] against
    k [.., t_k, hkv, d] and v [.., t_k, hkv, dv], or None where no block
    divides the lengths.  Read on the chip (PERF.md section 6, PR 43): a
    block of keys is the longest of 1,024 / 512 / 256 / 128 that divides --
    the running maximum and sum are a column a query row, as dear an update
    as a tile 128 keys wide, so a wide tile carries them best -- and the g
    heads' query rows against it are as many as keep the block of queries
    at what 2,048 rows of 128 hold (4,096 rows of 64, 1,024 of 192)."""
    rows = 2048 * _LANES // max(d, dv)
    block_q = next((b for b in (1024, 512, 256, 128)
                    if b * g <= max(rows, 128 * g) and t_q % b == 0), None)
    block_kv = next((b for b in (1024, 512, 256, 128) if t_k % b == 0), None)
    if block_q is None or block_kv is None:
        return None
    return block_q, block_kv, heads_a_step(hkv, d, dv)


def _row_intervals(kind: str, n: Optional[int], t_q: int) -> tuple:
    """The keys each query row sees, as two intervals [lo, hi) a row (the
    second empty but under the block mask)."""
    i = np.arange(t_q)
    zero = np.zeros_like(i)
    if kind == BLOCK_DIFFUSION:
        t = t_q // 2
        clean = i >= t
        b0 = (i - t * clean) // n * n  # the row's block starts here
        # a noised row: its own noised block, the clean blocks before it;
        # a clean row: the clean keys through its own block
        return (np.where(clean, t, b0), np.where(clean, t + b0 + n, b0 + n),
                np.where(clean, 0, t), np.where(clean, 0, t + b0))
    lo = np.maximum(i - n + 1, 0) if kind == WINDOW else zero
    return lo, i + 1, zero, zero


def _tile_kinds(spec: Spec, t_q: int, t_k: int) -> np.ndarray:
    """[t_q / block_q, t_k / block_kv]: 0 a tile with no visible pair, 1 one
    the mask's edge crosses, 2 one wholly inside the mask."""
    bq, bkv = spec.block_q, spec.block_kv
    lo1, hi1, lo2, hi2 = _row_intervals(spec.kind, spec.n, t_q)
    c0 = np.arange(0, t_k, bkv)[None, :]

    def seen(lo, hi):
        return np.clip(np.minimum(hi[:, None], c0 + bkv)
                       - np.maximum(lo[:, None], c0), 0, None)

    n = (seen(lo1, hi1) + seen(lo2, hi2)).reshape(
        t_q // bq, bq, -1).sum(axis=1)
    return np.where(n == 0, 0, np.where(n == bq * bkv, 2, 1)).astype(np.int32)


def _visits(kinds: np.ndarray) -> tuple:
    """For each row of ``kinds`` the columns to visit, in order, and each
    one's kind, both [rows * steps] int32 (``steps`` the most any row
    visits).  A row with fewer repeats its last column under kind 0: the
    grid step fetches nothing new and computes nothing."""
    rows = kinds.shape[0]
    steps = max(int((kinds > 0).sum(axis=1).max()), 1)
    index = np.zeros((rows, steps), np.int32)
    kind = np.zeros((rows, steps), np.int32)
    for r in range(rows):
        cols = np.nonzero(kinds[r])[0]
        index[r, :len(cols)] = cols
        index[r, len(cols):] = cols[-1] if len(cols) else 0
        kind[r, :len(cols)] = kinds[r, cols]
    return jnp.asarray(index.ravel()), jnp.asarray(kind.ravel()), steps


def _visible(spec: Spec, t_q: int, q_pos, k_pos):
    """The mask of one tile from its positions (int32, broadcastable)."""
    if spec.kind == CAUSAL:
        return k_pos <= q_pos
    if spec.kind == WINDOW:
        return (k_pos <= q_pos) & (q_pos - k_pos < spec.n)
    t, n = t_q // 2, spec.n
    q_clean, k_clean = q_pos >= t, k_pos >= t
    b0 = jnp.where(q_clean, q_pos - t, q_pos) // n * n
    kj = jnp.where(k_clean, k_pos - t, k_pos)
    own = (kj >= b0) & (kj < b0 + n)
    return ((k_clean & ((kj < b0) | (q_clean & own)))
            | (~k_clean & ~q_clean & own))


def _positions(start, n: int, axis: int):
    shape = (n, 1) if axis == 0 else (1, n)
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _tile_seen(spec: Spec, t_q: int, qb, kb, q_axis: int):
    """The mask of the tile of query block ``qb`` and key block ``kb``,
    queries along ``q_axis``."""
    return _visible(
        spec, t_q, _positions(qb * spec.block_q, spec.block_q, q_axis),
        _positions(kb * spec.block_kv, spec.block_kv, 1 - q_axis))


def _on_tile(kind, tile) -> None:
    """Run ``tile(masked)`` as the step's kind says: not at all (0), under
    the ``iota`` mask (1), bare (2)."""
    pl.when(kind == 1)(functools.partial(tile, True))
    pl.when(kind == 2)(functools.partial(tile, False))


def _scale(d: int) -> float:
    return 1.0 / float(np.sqrt(d))


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _as_row(col):
    """[n, 1] -> [n] (a column of sublanes laid along the lanes)."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, _LANES)).T[0]


def _lanes(n: int, width: int) -> slice:
    """Head ``n`` of a block [positions, heads * width]: its lanes."""
    return slice(n * width, (n + 1) * width)


class _Shape(NamedTuple):
    """One call's sizes: ``hp`` key-value heads a grid step, ``g`` query
    heads each, head widths ``d`` / ``dv``, and the products' dtype."""
    hp: int
    g: int
    d: int
    dv: int
    operands: jnp.dtype


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(index_ref, kind_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                q_s, acc, m_ref, l_ref, *, spec: Spec, sh: _Shape, t_q: int,
                steps: int, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)
    at = i * steps + j

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        for n in range(sh.hp * sh.g):  # a block's queries, rounded once
            q_s[n] = q_ref[:, _lanes(n, sh.d)].astype(sh.operands)

    def tile(masked: bool):
        if masked:
            seen = _tile_seen(spec, t_q, i, index_ref[at], 0)
        for a in range(sh.hp):
            k = k_ref[:, _lanes(a, sh.d)].astype(sh.operands)
            v = v_ref[:, _lanes(a, sh.dv)].astype(sh.operands)
            for n in range(a * sh.g, (a + 1) * sh.g):
                # one step of the online softmax a head
                s = _dot(q_s[n], k, _NT) * scale
                if masked:
                    s = jnp.where(seen, s, _MASKED)
                m_prev = m_ref[n]
                m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - m_next)
                l_ref[n] = alpha * l_ref[n] + p.sum(axis=1, keepdims=True)
                acc[n] = alpha * acc[n] + _dot(p.astype(v.dtype), v)
                m_ref[n] = m_next

    _on_tile(kind_ref[at], tile)

    @pl.when(j == steps - 1)
    def _():
        for n in range(sh.hp * sh.g):
            l = l_ref[n]
            o_ref[:, _lanes(n, sh.dv)] = (acc[n] / l).astype(o_ref.dtype)
            lse_ref[n] = _as_row(m_ref[n] + jnp.log(l))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _query_major(steps: int) -> tuple:
    """Index maps of a grid (batch, head group, query block, visit): the
    query block's columns, the visited key block's, a head group's rows of
    [.., heads, T]."""
    def q_at(b_, h, i, j, index, kind):
        return b_, i, h

    def k_at(b_, h, i, j, index, kind):
        return b_, index[i * steps + j], h

    def row_at(b_, h, i, j, index, kind):
        return b_, h, 0, i

    return q_at, k_at, row_at


def _forward(q, k, v, spec: Spec, sh: _Shape):
    """q [B, T, H * D], k [B, Tk, Hkv * D], v [B, Tk, Hkv * Dv] ->
    out [B, T, H * Dv] in q's dtype, lse [B, Hkv / hp, hp * G, T] float32
    (a row of T a query head, the heads in q's order)."""
    b, t_q, t_k = q.shape[0], q.shape[1], k.shape[1]
    hp, g, d, dv = sh.hp, sh.g, sh.d, sh.dv
    groups = k.shape[2] // (hp * d)
    bq, bkv = spec.block_q, spec.block_kv
    index, kind, steps = _visits(_tile_kinds(spec, t_q, t_k))
    q_at, k_at, row_at = _query_major(steps)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec, sh=sh, t_q=t_q,
                          steps=steps, scale=_scale(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups, t_q // bq, steps),
            in_specs=[
                pl.BlockSpec((None, bq, hp * g * d), q_at),
                pl.BlockSpec((None, bkv, hp * d), k_at),
                pl.BlockSpec((None, bkv, hp * dv), k_at),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, hp * g * dv), q_at),
                pl.BlockSpec((None, None, hp * g, bq), row_at),
            ],
            scratch_shapes=[pltpu.VMEM((hp * g, bq, d), sh.operands),
                            pltpu.VMEM((hp * g, bq, dv), jnp.float32),
                            pltpu.VMEM((hp * g, bq, 1), jnp.float32),
                            pltpu.VMEM((hp * g, bq, 1), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, t_q, groups * hp * g * dv),
                                        q.dtype),
                   jax.ShapeDtypeStruct((b, groups, hp * g, t_q),
                                        jnp.float32)],
        compiler_params=_params(),
        interpret=spec.interpret,
    )(index, kind, q, k, v)


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #
def _dq_kernel(index_ref, kind_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
               lse_ref, dq_ref, delta_ref, q_s, do_s, acc, lse_col,
               delta_col, *, spec: Spec, sh: _Shape, t_q: int, steps: int,
               scale: float):
    i, j = pl.program_id(2), pl.program_id(3)
    at = i * steps + j

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        for n in range(sh.hp * sh.g):
            q_s[n] = q_ref[:, _lanes(n, sh.d)].astype(sh.operands)
            do = do_ref[:, _lanes(n, sh.dv)].astype(jnp.float32)
            delta = (do * o_ref[:, _lanes(n, sh.dv)].astype(jnp.float32)).sum(
                axis=1, keepdims=True)
            do_s[n] = do.astype(sh.operands)
            delta_col[n] = delta
            delta_ref[n] = _as_row(delta)
            lse_col[n] = lse_ref[n][:, None]

    def tile(masked: bool):
        if masked:
            seen = _tile_seen(spec, t_q, i, index_ref[at], 0)
        for a in range(sh.hp):
            k = k_ref[:, _lanes(a, sh.d)].astype(sh.operands)
            v = v_ref[:, _lanes(a, sh.dv)].astype(sh.operands)
            for n in range(a * sh.g, (a + 1) * sh.g):
                s = _dot(q_s[n], k, _NT) * scale
                if masked:
                    s = jnp.where(seen, s, _MASKED)
                p = jnp.exp(s - lse_col[n])
                ds = p * (_dot(do_s[n], v, _NT) - delta_col[n]) * scale
                acc[n] += _dot(ds.astype(k.dtype), k)

    _on_tile(kind_ref[at], tile)

    @pl.when(j == steps - 1)
    def _():
        for n in range(sh.hp * sh.g):
            dq_ref[:, _lanes(n, sh.d)] = acc[n].astype(dq_ref.dtype)


def _dkv_kernel(index_ref, kind_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, k_s, v_s, dk_acc, dv_acc, *,
                spec: Spec, sh: _Shape, t_q: int, steps: int, scale: float):
    """One block of keys against the query blocks that see it, the tile
    transposed: keys on the rows, so log-sum-exp and delta are rows along
    the lanes as they are stored."""
    c, j = pl.program_id(2), pl.program_id(3)
    at = c * steps + j

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        for a in range(sh.hp):  # the block's keys and values, rounded once
            k_s[a] = k_ref[:, _lanes(a, sh.d)].astype(sh.operands)
            v_s[a] = v_ref[:, _lanes(a, sh.dv)].astype(sh.operands)

    def tile(masked: bool):
        if masked:
            seen = _tile_seen(spec, t_q, index_ref[at], c, 1)
        for a in range(sh.hp):
            k, v = k_s[a], v_s[a]
            for n in range(a * sh.g, (a + 1) * sh.g):
                q = q_ref[:, _lanes(n, sh.d)].astype(sh.operands)
                do = do_ref[:, _lanes(n, sh.dv)].astype(sh.operands)
                s = _dot(k, q, _NT) * scale  # [keys, queries]
                if masked:
                    s = jnp.where(seen, s, _MASKED)
                p = jnp.exp(s - lse_ref[n][None, :])
                dv_acc[a] += _dot(p.astype(do.dtype), do)
                ds = p * (_dot(v, do, _NT) - delta_ref[n][None, :]) * scale
                dk_acc[a] += _dot(ds.astype(q.dtype), q)

    _on_tile(kind_ref[at], tile)

    @pl.when(j == steps - 1)
    def _():
        for a in range(sh.hp):
            dk_ref[:, _lanes(a, sh.d)] = dk_acc[a].astype(dk_ref.dtype)
            dv_ref[:, _lanes(a, sh.dv)] = dv_acc[a].astype(dv_ref.dtype)


def _backward(q, k, v, out, do, lse, spec: Spec, sh: _Shape):
    """dq, dk, dv, each where and as its operand is, from the forward's
    operands, its output, the output's cotangent ``do`` [B, T, H * Dv] and
    ``lse``."""
    b, t_q, t_k = q.shape[0], q.shape[1], k.shape[1]
    hp, g, d, dv = sh.hp, sh.g, sh.d, sh.dv
    groups = k.shape[2] // (hp * d)
    bq, bkv = spec.block_q, spec.block_kv
    kinds = _tile_kinds(spec, t_q, t_k)
    static = dict(spec=spec, sh=sh, t_q=t_q, scale=_scale(d))
    wide = {"q": (None, bq, hp * g * d), "o": (None, bq, hp * g * dv),
            "k": (None, bkv, hp * d), "v": (None, bkv, hp * dv),
            "row": (None, None, hp * g, bq)}

    # dq and delta: a query block against the key blocks it sees
    index, kind, steps = _visits(kinds)
    q_at, k_at, row_at = _query_major(steps)

    dq, delta = pl.pallas_call(
        functools.partial(_dq_kernel, steps=steps, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups, t_q // bq, steps),
            in_specs=[pl.BlockSpec(wide["q"], q_at),
                      pl.BlockSpec(wide["k"], k_at),
                      pl.BlockSpec(wide["v"], k_at),
                      pl.BlockSpec(wide["o"], q_at),
                      pl.BlockSpec(wide["o"], q_at),
                      pl.BlockSpec(wide["row"], row_at)],
            out_specs=[pl.BlockSpec(wide["q"], q_at),
                       pl.BlockSpec(wide["row"], row_at)],
            scratch_shapes=[pltpu.VMEM((hp * g, bq, d), sh.operands),
                            pltpu.VMEM((hp * g, bq, dv), sh.operands),
                            pltpu.VMEM((hp * g, bq, d), jnp.float32),
                            pltpu.VMEM((hp * g, bq, 1), jnp.float32),
                            pltpu.VMEM((hp * g, bq, 1), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        compiler_params=_params(),
        interpret=spec.interpret,
    )(index, kind, q, k, v, out, do, lse)

    # dk, dv: a key block against the query blocks that see it
    index_t, kind_t, steps_t = _visits(kinds.T)

    def q_of(b_, h, c, j, index, kind):
        return b_, index[c * steps_t + j], h

    def k_of(b_, h, c, j, index, kind):
        return b_, c, h

    def row_of(b_, h, c, j, index, kind):
        return b_, h, 0, index[c * steps_t + j]

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, steps=steps_t, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups, t_k // bkv, steps_t),
            in_specs=[pl.BlockSpec(wide["q"], q_of),
                      pl.BlockSpec(wide["k"], k_of),
                      pl.BlockSpec(wide["v"], k_of),
                      pl.BlockSpec(wide["o"], q_of),
                      pl.BlockSpec(wide["row"], row_of),
                      pl.BlockSpec(wide["row"], row_of)],
            out_specs=[pl.BlockSpec(wide["k"], k_of),
                       pl.BlockSpec(wide["v"], k_of)],
            scratch_shapes=[pltpu.VMEM((hp, bkv, d), sh.operands),
                            pltpu.VMEM((hp, bkv, dv), sh.operands),
                            pltpu.VMEM((hp, bkv, d), jnp.float32),
                            pltpu.VMEM((hp, bkv, dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(),
        interpret=spec.interpret,
    )(index_t, kind_t, q, k, v, do, lse, delta)
    return dq, dk, dv_


# --------------------------------------------------------------------------- #
# the call
# --------------------------------------------------------------------------- #
def _flat(x):
    """[B, T, H, D] seen as [B, T, H * D]: the same memory."""
    return x.reshape(*x.shape[:2], -1)


def _shape_of(q, k, v, spec: Spec) -> _Shape:
    hkv = k.shape[2]
    if hkv % spec.heads:
        raise ValueError(
            f"{hkv} key-value heads in groups of {spec.heads} a grid step")
    return _Shape(spec.heads, q.shape[2] // hkv, q.shape[3], v.shape[3],
                  jnp.dtype(spec.operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, spec: Spec):
    """q [B, T, H, D], k [B, Tk, Hkv, D], v [B, Tk, Hkv, Dv] -> [B, T, H,
    Dv] in q's dtype, under the mask ``spec`` describes."""
    return _kernel(q, k, v, spec)[0].reshape(*q.shape[:3], v.shape[3])


def _kernel(q, k, v, spec: Spec):
    """(the output as the kernel writes it, [B, T, H * Dv], and lse)."""
    return _forward(_flat(q), _flat(k), _flat(v), spec,
                    _shape_of(q, k, v, spec))


def _flash_fwd(q, k, v, spec: Spec):
    out, lse = _kernel(q, k, v, spec)
    # named: what a rematerialised layer may keep in place of a second run
    # of the kernel (identities unless a checkpoint's policy asks for them).
    # The output is named as the kernel wrote it, flat: kept as [B, T, H,
    # Dv] it would lie tiled by heads, a relayout of the whole array each
    # way between the kernels (3-5 ms a layer at 268 MB: PERF.md section 6,
    # PR 48)
    _KEPT.inc(mask=spec.kind)
    out, lse = checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    out = out.reshape(*q.shape[:3], v.shape[3])
    # kept for the backward: the operands as given, the output as
    # returned, a row's log-sum-exp -- no copy of any of them
    return out, (q, k, v, out, lse)


def _flash_bwd(spec: Spec, kept, do):
    q, k, v, out, lse = kept
    dq, dk, dv = _backward(_flat(q), _flat(k), _flat(v), _flat(out),
                           _flat(do), lse, spec,
                           _shape_of(q, k, v, spec))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
