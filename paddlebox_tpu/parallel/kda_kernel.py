"""The gated delta rule's chunk recurrence as Pallas kernels (TPU).

``models/decoder_lm.py gated_delta_chunked`` in ``jax.numpy`` writes the
pairs' decays ``exp(G_i - G_j)``, [chunk, chunk, dk] floats a chunk and
head, to HBM and reads them back, and walks the chunks in a ``lax.scan``
whose every step reads and writes the [dk, dv] state of every head through
HBM.  Here both stay on the chip, forward and backward, in two kernels
each way, and nothing else runs between them: q, k, v, g are read where
the projections wrote them, [B, T, nh, d] seen as [B, T * nh, d] (the same
memory: a row is one position of one head, eight heads of a position one
tile), and every array the kernels hand each other or the caller has that
layout.

  * ``pairs`` -- a grid over (batch, block of chunks); inside it a chunk
    and a GROUP OF HEADS at a time, the heads on the sublanes: a register
    is one position of eight heads (a value here two registers, sixteen
    heads, whose work interleaves), so a pair (i, j < i) of positions is
    one value of decays ``exp(G_i - G_j)`` -- only the pairs below the
    diagonal are ever formed, every exponent <= 0 as it stands -- and its
    two sums over the channel (one lane reduction a register each,
    float32: no product here goes through the MXU) are R_ij = (k_i *
    decay) . k_j and P_ij = (q_i * decay) . k_j.  The pairs go in blocks
    of 8 x 8 positions, straight-line code inside a block and loops over
    the blocks.  The in-chunk cumulative sum G of g is formed first
    (float32 adds, position by position) and the unit-lower-triangular
    solve is done in the same loops, by forward substitution, since row
    i's R_ij arrive in the order it needs them:

        X_i = beta_i ([k_i e^G_i | v_i] - sum over j < i of R_ij X_j)

    so X = (I + diag(beta) R)^-1 diag(beta) [k e^G | v] = [w | u0].
    Written: G, R, P ([.., chunk]) and X.  The backward recomputes the
    decays from q, k, G, does the solve's adjoint by back substitution
    from the kept R and X, and writes dq, dk, dv, dg (its cumulative sum
    undone) and dbeta; nothing of [chunk, chunk, dk] is ever kept or
    written.
  * ``scan`` -- a grid over (batch, block of chunks), the second
    sequential; the state S of every head, float32 and transposed ([dv,
    dk]: a chunk's decay multiplies along the lanes), is a VMEM scratch,
    zero at the first block.  A chunk of one head is gathered from the
    rows of its positions (a strided tile: eight positions of one head;
    four heads a turn, all their reads before any of their writes so that
    their products interleave),
    ``q e^G``, ``k e^(G_C - G)`` and ``e^(G_C)`` are formed from q, k and G
    as the walk goes, and the four products a chunk and head run with
    operands rounded to ``operands`` (bfloat16: what float32 operands at
    the TPU's default matmul precision are) and float32 accumulation.  It
    writes o and the state that entered each block ([B, blocks, nh, dv,
    dk]: one state a block of chunks, all the backward keeps of the walk).
    The backward walks the blocks in reverse carrying dS in VMEM: a grid
    step recomputes its block's states from the kept one into scratch,
    then walks the block's chunks backwards and writes dq, dk, dG, dP and
    dX.

What a layer keeps of the operator for its backward is therefore q, k, v,
g, beta as given, G, R, P, X, the output and one state a block of chunks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES, _SUBLANES = 128, 8
_VMEM_LIMIT = 100 * 1024 * 1024  # of the v5e's 128 MiB
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


class Spec(NamedTuple):
    """What is static of one call: the positions of a chunk, the chunks a
    grid step works on, the heads whose walks one stretch of straight-line
    code interleaves, the dtype the walk's products' operands are rounded
    to, and whether Pallas interprets the kernels (the CPU's tests)."""
    chunk: int
    chunks: int
    heads: int
    operands: str = "bfloat16"
    interpret: bool = False


def spec_for(t: int, nh: int, dk: int, dv: int, chunk: int):
    """The ``Spec`` for q [.., t, nh, dk] and v [.., t, nh, dv] in chunks of
    ``chunk`` positions, or the reason there is none: the kernels take
    heads one lane tile (128) wide, in whole sublane tiles, and a chunk of
    whole sublane tiles that fills blocks of 128 positions (4 chunks of 32
    a grid step; a sequence of fewer is one block).  Read from the
    compiler's schedule for a v5e (PERF.md section 6, PR 45): 4 heads a
    turn of the walk."""
    if not dk == dv == _LANES:  # a strided tile is eight rows of 128
        return None, f"head widths {dk}, {dv} are not one lane tile"
    if nh % _SUBLANES:
        return None, f"{nh} heads are no whole sublane tiles"
    if chunk % _SUBLANES or chunk > _LANES:
        return None, (f"a chunk of {chunk} is no whole sublane tiles, or "
                      f"more than {_LANES} positions")
    c = min(chunk, -(-t // _SUBLANES) * _SUBLANES)
    n = -(-t // c)
    per = max(1, _LANES // c)  # a block's positions are beta's lanes
    if n > per and c * per % _LANES:
        return None, f"chunks of {c} do not fill blocks of {_LANES} positions"
    return Spec(c, min(n, per), 4), ""


def _params(sequential: bool):
    return pltpu.CompilerParams(
        dimension_semantics=(
            "parallel", "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """One pass over the operands as they are, float32 sums, whatever
    ``jax.default_matmul_precision`` the caller traces under (Mosaic takes
    no ``highest`` product of bfloat16 operands)."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _rowsum(x):
    """[8, n] -> [8, 1]: one lane reduction."""
    return jnp.sum(x, axis=1, keepdims=True)


# --------------------------------------------------------------------------- #
# the pairs' sums and the solve, eight heads a register
# --------------------------------------------------------------------------- #
def _column(tile, lane, at):
    """Column ``at`` of ``tile`` [8, n] as [8, 1] (``lane`` its iota)."""
    return _rowsum(jnp.where(lane == at, tile, 0.0))


class _Sizes(NamedTuple):
    """A chunk's positions, the chunks a block, the heads, the heads a
    register group (the rows of one value: 16 where the heads allow, two
    registers whose work interleaves, else 8) and the head's width."""
    C: int
    nb: int
    nh: int
    H: int
    dk: int


def _each_chunk_and_group(z: _Sizes, body) -> None:
    """``body(c, hg, at)`` for every chunk of the block and every group of
    ``H`` heads: ``at(i)`` is the tile of position i of the chunk, the
    group's rows of [positions * nh, .]."""
    def group(n, _):
        c, hg = n // (z.nh // z.H), n % (z.nh // z.H)
        first = c * z.C * z.nh + hg * z.H

        def at(i: int):
            return pl.ds(pl.multiple_of(first + i * z.nh, z.H), z.H)

        body(c, hg, at)

    jax.lax.fori_loop(0, z.nb * (z.nh // z.H), group, None)


class _BetaTile(NamedTuple):
    """Where chunk c's positions lie in beta's block [nh, positions]: the
    group's heads' rows, a window of lanes, the chunk's first lane in
    it."""
    heads: object
    lanes: object
    first: object
    iota: jax.Array


def _beta_tile(c, hg, z: _Sizes) -> _BetaTile:
    width = min(z.nb * z.C, _LANES)
    start = pl.multiple_of(c * z.C // width * width, width)
    return _BetaTile(
        pl.ds(pl.multiple_of(hg * z.H, z.H), z.H),
        pl.ds(start, width), c * z.C - start,
        jax.lax.broadcasted_iota(jnp.int32, (z.H, width), 1))


def _slot(i, rows: int):
    """Tile i of a scratch of tiles of ``rows`` rows."""
    return pl.ds(pl.multiple_of(i * rows, rows), rows)


def _pairs_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, G_ref, R_ref, P_ref,
                  w_ref, u_ref, acc, *, z: _Sizes):
    C = z.C
    blocks = C // _SUBLANES  # the pairs go in blocks of 8 x 8 positions
    lane = jax.lax.broadcasted_iota(jnp.int32, (z.H, _LANES), 1)

    def sums(ii: int) -> list:  # row ii of the block: R, P, w and u in acc
        return [_slot(of * _SUBLANES + ii, z.H) for of in range(4)]

    def body(c, hg, at):
        bt = _beta_tile(c, hg, z)
        beta = beta_ref[bt.heads, bt.lanes]
        G_i = None
        for i in range(C):
            g_i = g_ref[at(i), :]
            G_i = g_i if i == 0 else G_i + g_i
            G_ref[at(i), :] = G_i

        def block(i0, j0, first: int = 0) -> None:
            """Rows i0 .. i0 + 7 against columns j0 .. j0 + 7; where the
            two are one block (``first`` = 1: j < i) a row is whole when
            its turn ends, and the rows after it read its X."""
            for ii in range(_SUBLANES):
                i = i0 + ii
                q_i, k_i = q_ref[at(i), :], k_ref[at(i), :]
                G_i = G_ref[at(i), :]
                R, P, w, u = (acc[at_, :] for at_ in sums(ii))
                for jj in range(ii if first else _SUBLANES):
                    j = j0 + jj
                    kd = k_ref[at(j), :] * jnp.exp(
                        G_i - G_ref[at(j), :])
                    r = _rowsum(k_i * kd)
                    R = jnp.where(lane == j, r, R)
                    P = jnp.where(lane == j, _rowsum(q_i * kd), P)
                    w = w - r * w_ref[at(j), :]
                    u = u - r * u_ref[at(j), :]
                if first:
                    b_i = _column(beta, bt.iota, bt.first + i)
                    w_ref[at(i), :] = b_i * w
                    u_ref[at(i), :] = b_i * u
                    R_ref[at(i), :] = R
                    P_ref[at(i), :] = P
                else:
                    for at_, x in zip(sums(ii), (R, P, w, u)):
                        acc[at_, :] = x

        def rows_of(ib, _):
            i0 = ib * _SUBLANES
            for ii, (R, P, w, u) in enumerate(sums(ii) for ii in range(
                    _SUBLANES)):
                i = i0 + ii
                k_i, G_i = k_ref[at(i), :], G_ref[at(i), :]
                acc[R, :] = jnp.zeros((z.H, _LANES), jnp.float32)
                acc[P, :] = jnp.where(
                    lane == i, _rowsum(q_ref[at(i), :] * k_i), 0.0)
                acc[w, :] = k_i * jnp.exp(G_i)
                acc[u, :] = v_ref[at(i), :]
            jax.lax.fori_loop(
                0, ib, lambda jb, _: block(i0, jb * _SUBLANES), None)
            block(i0, i0, 1)

        jax.lax.fori_loop(0, blocks, rows_of, None)

    _each_chunk_and_group(z, body)


def _pairs_bwd_kernel(q_ref, k_ref, v_ref, beta_ref, G_ref, R_ref, w_ref,
                      u_ref, dq2_ref, dk2_ref, dG_ref, dP_ref, dw_ref, du_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dZw, dZu, dkk,
                      bR, acc, *, z: _Sizes):
    """The cotangents of q, k, v, g and beta of one block of chunks from
    the walk's: those of G, P and X, and its own of q and k (dq2, dk2).
    With b = beta, Y_i = [k_i e^G_i | v_i], X = (I + b R)^-1 b Y and D_ij =
    exp(G_i - G_j), j < i:

        dZ_j = dX_j - sum over i > j of b_i R_ij dZ_i     back substitution
        t_ij = dZ_i . X_j    a_ij = -b_i t_ij (R's)       p_ij = dP_ij
        db_i = dZ_i . Y_i - sum_j R_ij t_ij
        dq_i = sum_j p_ij k_j D_ij       dkq_i = sum_j a_ij k_j D_ij
        dkk_j = sum_i (a_ij k_i + p_ij q_i) D_ij
        dG += q dq + k dkq - k dkk + b dZ_w k e^G         (+1 at the query's
                                        row of D, -1 at the key's)
        dq += p_ii k_i + dq2     dk = dkq + dkk + p_ii q_i + b dZ_w e^G + dk2
        dv = b dZ_u         dg_i = sum over i' >= i of dG_i'
    """
    C, dk = z.C, z.dk
    blocks = C // _SUBLANES  # the pairs go in blocks of 8 x 8 positions
    lane = jax.lax.broadcasted_iota(jnp.int32, (z.H, _LANES), 1)

    slot = functools.partial(_slot, rows=z.H)

    def sums(ii: int) -> list:  # row ii of the block: dq, dkq and T in acc
        return [slot(of * _SUBLANES + ii) for of in range(3)]

    def body(c, hg, at):
        bt = _beta_tile(c, hg, z)
        beta = beta_ref[bt.heads, bt.lanes]

        def b_of(i):
            return _column(beta, bt.iota, bt.first + i)

        for i in range(C):
            bR[slot(i), :] = b_of(i) * R_ref[at(i), :]
            dkk[slot(i), :] = jnp.zeros((z.H, dk), jnp.float32)
            dZw[slot(i), :] = dw_ref[at(i), :]
            dZu[slot(i), :] = du_ref[at(i), :]

        # back substitution, from the last rows up: row j takes its share
        # of every dZ_i, i > j, which are whole by then
        def owed(j, rows: dict) -> None:
            dZw_j, dZu_j = dZw[slot(j), :], dZu[slot(j), :]
            for bR_i, dZw_i, dZu_i in rows.values():
                bR_ij = _column(bR_i, lane, j)
                dZw_j = dZw_j - bR_ij * dZw_i
                dZu_j = dZu_j - bR_ij * dZu_i
            dZw[slot(j), :] = dZw_j
            dZu[slot(j), :] = dZu_j

        def rows_at(i0, first: int = 0) -> dict:
            return {ii: (bR[slot(i0 + ii), :], dZw[slot(i0 + ii), :],
                         dZu[slot(i0 + ii), :])
                    for ii in range(first, _SUBLANES)}

        def substitute(n, _):
            j0 = (blocks - 1 - n) * _SUBLANES

            def from_rows(ib, _):
                rows = rows_at(ib * _SUBLANES)
                for jj in range(_SUBLANES):
                    owed(j0 + jj, rows)

            jax.lax.fori_loop(blocks - n, blocks, from_rows, None)
            for jj in reversed(range(_SUBLANES - 1)):
                owed(j0 + jj, rows_at(j0, jj + 1))

        jax.lax.fori_loop(0, blocks, substitute, None)

        # the pairs, a block of 8 rows i at a time from the last up; the
        # rows' sums over j wait in ``acc`` between the blocks of columns
        def block(i0, j0, first: int = 0) -> None:
            """Rows i0 .. i0 + 7 against columns j0 .. j0 + 7 (j < i where
            the two are one block: ``first`` = 1)."""
            dkk_j = [dkk[slot(j0 + jj), :] for jj in range(_SUBLANES)]
            for ii in range(first, _SUBLANES):
                i = i0 + ii
                b_i = b_of(i)
                q_i, k_i = q_ref[at(i), :], k_ref[at(i), :]
                G_i, dP_i = G_ref[at(i), :], dP_ref[at(i), :]
                dZw_i, dZu_i = dZw[slot(i), :], dZu[slot(i), :]
                dq, dkq, T = (acc[at_, :] for at_ in sums(ii))
                for jj in range(ii if first else _SUBLANES):
                    j = j0 + jj
                    t = _rowsum(dZw_i * w_ref[at(j), :]
                                + dZu_i * u_ref[at(j), :])
                    T = jnp.where(lane == j, t, T)
                    a, p = -b_i * t, _column(dP_i, lane, j)
                    D = jnp.exp(G_i - G_ref[at(j), :])
                    kd = k_ref[at(j), :] * D
                    dkq = dkq + a * kd
                    dq = dq + p * kd
                    dkk_j[jj] = dkk_j[jj] + (a * k_i + p * q_i) * D
                for at_, x in zip(sums(ii), (dq, dkq, T)):
                    acc[at_, :] = x
            for jj in range(_SUBLANES):
                dkk[slot(j0 + jj), :] = dkk_j[jj]

        def whole(i, ii: int, dg, db):
            """Row i is whole: every pair whose key it is came before."""
            b_i = b_of(i)
            q_i, k_i = q_ref[at(i), :], k_ref[at(i), :]
            dZw_i, dZu_i = dZw[slot(i), :], dZu[slot(i), :]
            dq, dkq, T = (acc[at_, :] for at_ in sums(ii))
            eG = jnp.exp(G_ref[at(i), :])
            dkk_i, dY_w = dkk[slot(i), :], b_i * dZw_i
            p_ii = _column(dP_ref[at(i), :], lane, i)
            Y_w, Y_u = k_i * eG, v_ref[at(i), :]
            db_i = _rowsum(dZw_i * Y_w + dZu_i * Y_u) - _rowsum(
                R_ref[at(i), :] * T)
            dq_ref[at(i), :] = dq + p_ii * k_i + dq2_ref[at(i), :]
            dk_ref[at(i), :] = (dkq + dkk_i + p_ii * q_i + dY_w * eG
                                + dk2_ref[at(i), :])
            dv_ref[at(i), :] = b_i * dZu_i
            dg = dg + (dG_ref[at(i), :] + q_i * dq + k_i * (dkq - dkk_i)
                       + dY_w * Y_w)
            dg_ref[at(i), :] = dg
            return dg, jnp.where(bt.iota == bt.first + i, db_i, db)

        def rows_of(n, carry):
            i0 = (blocks - 1 - n) * _SUBLANES
            for ii in range(3 * _SUBLANES):
                acc[slot(ii), :] = jnp.zeros((z.H, dk), jnp.float32)

            jax.lax.fori_loop(
                0, blocks - 1 - n,
                lambda jb, _: block(i0, jb * _SUBLANES), None)
            block(i0, i0, 1)
            for ii in reversed(range(_SUBLANES)):
                carry = whole(i0 + ii, ii, *carry)
            return carry

        # a block's first chunk of a window of lanes starts its dbeta
        _, db = jax.lax.fori_loop(0, blocks, rows_of, (
            jnp.zeros((z.H, dk), jnp.float32),
            jnp.where(bt.first == 0, 0.0, dbeta_ref[bt.heads, bt.lanes])))
        dbeta_ref[bt.heads, bt.lanes] = db

    _each_chunk_and_group(z, body)


def _pairs_specs(q, beta, spec: Spec) -> tuple:
    """Grid and the kinds of block of the pairs' kernels: a block of
    chunks of [B, T * nh, width], and of beta [B, nh, T]."""
    nh = beta.shape[1]
    z = _Sizes(spec.chunk, spec.chunks, nh,
               next(h for h in (16, 8) if nh % h == 0),
               q.shape[2])
    rows = z.nb * z.C

    def wide(width):
        return pl.BlockSpec((None, rows * nh, width),
                            lambda b_, n: (b_, n, 0))

    heads = pl.BlockSpec((None, nh, rows), lambda b_, n: (b_, 0, n))
    return (q.shape[0], beta.shape[2] // rows), wide, heads, z


@functools.partial(jax.jit, static_argnames="spec")
def pairs(q, k, v, g, beta, spec: Spec):
    """q, k, g, v [B, T * nh, d] (row t * nh + h), beta [B, nh, T], T whole
    blocks of chunks -> G [B, T * nh, d], R, P [B, T * nh, 128] (a
    position's row against the chunk's key positions in its first ``chunk``
    lanes), w and u0 [B, T * nh, d], all float32."""
    d = q.shape[2]
    grid, wide, heads, z = _pairs_specs(q, beta, spec)

    def out(width):
        return jax.ShapeDtypeStruct((*q.shape[:2], width), jnp.float32)

    return pl.pallas_call(
        functools.partial(_pairs_kernel, z=z), grid=grid,
        in_specs=[wide(d), wide(d), wide(d), wide(d), heads],
        out_specs=[wide(d), wide(_LANES), wide(_LANES), wide(d), wide(d)],
        out_shape=[out(d), out(_LANES), out(_LANES), out(d), out(d)],
        scratch_shapes=[pltpu.VMEM((4 * _SUBLANES * z.H, d),
                                   jnp.float32)],
        compiler_params=_params(False), interpret=spec.interpret,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames="spec")
def _pairs_bwd(q, k, v, g, beta, G, R, w, u, walks, spec: Spec):
    """dq, dk, dv, dg, dbeta from ``pairs``' operands and results and the
    walk's six cotangents (of q, k, G, P, w, u0)."""
    d = q.shape[2]
    grid, wide, heads, z = _pairs_specs(q, beta, spec)
    return pl.pallas_call(
        functools.partial(_pairs_bwd_kernel, z=z), grid=grid,
        in_specs=[wide(d), wide(d), wide(d), heads, wide(d), wide(_LANES),
                  wide(d), wide(d), wide(d), wide(d), wide(d), wide(_LANES),
                  wide(d), wide(d)],
        out_specs=[wide(d), wide(d), wide(d), wide(d), heads],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((z.C * z.H, d), jnp.float32)] * 4 + [
            pltpu.VMEM((3 * _SUBLANES * z.H, d), jnp.float32)],
        compiler_params=_params(False), interpret=spec.interpret,
    )(q, k, v, beta, G, R, w, u, *walks)


# --------------------------------------------------------------------------- #
# the walk over the chunks, a head at a time
# --------------------------------------------------------------------------- #
class _Walk(NamedTuple):
    C: int
    nb: int
    nh: int
    hb: int
    d: int
    op: jnp.dtype


def _tiles(w: _Walk, c, h) -> list:
    """The strided tiles of chunk c of head h in [positions * nh, .]: eight
    positions each, a row every nh."""
    first = c * w.C * w.nh + h
    return [pl.ds(first + t * _SUBLANES * w.nh, _SUBLANES, stride=w.nh)
            for t in range(w.C // _SUBLANES)]


def _gather(ref, tiles: list):
    return jnp.concatenate([ref[t, :] for t in tiles], axis=0)


def _scatter(ref, tiles: list, x) -> None:
    for n, t in enumerate(tiles):
        ref[t, :] = x[n * _SUBLANES:(n + 1) * _SUBLANES]


class _Chunk(NamedTuple):
    """One chunk of one head as the walk reads it (float32 but the
    products' operands): ``eG`` = e^G, ``e_out`` = e^(G_C - G), ``g_out`` =
    e^(G_C) [1, d]; qG = q e^G and k_out = k e^(G_C - G)."""
    eG: jax.Array
    e_out: jax.Array
    g_out: jax.Array
    qG: jax.Array
    k_out: jax.Array
    P: jax.Array
    w: jax.Array
    u0: jax.Array


def _read_chunk(q_ref, k_ref, G_ref, P_ref, w_ref, u_ref, tiles: list,
                w: _Walk) -> _Chunk:
    q, k, G = (_gather(r, tiles) for r in (q_ref, k_ref, G_ref))
    G_C = G[w.C - 1:]
    eG, e_out = jnp.exp(G), jnp.exp(G_C - G)
    return _Chunk(eG, e_out, jnp.exp(G_C), q * eG, k * e_out,
                  _gather(P_ref, tiles)[:, :w.C], _gather(w_ref, tiles),
                  _gather(u_ref, tiles))


def _step(x: _Chunk, ST, op):
    """One chunk of the recurrence from the state ``ST`` [dv, dk] that
    enters it: (u, o, the state that leaves it)."""
    S_op = ST.astype(op)
    u = x.u0 - _dot(x.w.astype(op), S_op, _NT)
    o = _dot(x.qG.astype(op), S_op, _NT) + _dot(x.P.astype(op), u.astype(op))
    return u, o, ST * x.g_out + _dot(u.astype(op), x.k_out.astype(op), _TN)


def _each_head(w: _Walk, read, write) -> None:
    """``write(h, read(h))`` for every head, ``hb`` heads a turn of the
    loop: every head's reads come before any head's writes, so that nothing
    orders one head's work after another's and their products interleave."""
    def group(n, _):
        heads = [n * w.hb + h for h in range(w.hb)]
        for h, x in zip(heads, [read(h) for h in heads]):
            write(h, x)

    jax.lax.fori_loop(0, w.nh // w.hb, group, None)


def _scan_kernel(q_ref, k_ref, G_ref, P_ref, w_ref, u_ref, o_ref, kept_ref,
                 S, *, w: _Walk):
    @pl.when(pl.program_id(1) == 0)
    def _():
        S[...] = jnp.zeros(S.shape, jnp.float32)

    kept_ref[...] = S[...]  # the states that enter this block of chunks

    def chunk(c, _):
        def read(h):
            x = _read_chunk(q_ref, k_ref, G_ref, P_ref, w_ref, u_ref,
                            _tiles(w, c, h), w)
            return _step(x, S[h], w.op)

        def write(h, done):
            _, o, S[h] = done
            _scatter(o_ref, _tiles(w, c, h), o)

        _each_head(w, read, write)

    jax.lax.fori_loop(0, w.nb, chunk, None)


def _scan_bwd_kernel(q_ref, k_ref, G_ref, P_ref, w_ref, u_ref, kept_ref,
                     do_ref, dq_ref, dk_ref, dG_ref, dP_ref, dw_ref, du_ref,
                     states, us, dS, *, w: _Walk):
    """One block of chunks of the reverse walk, ``dS`` [dv, dk] a head
    carried from the block after it.  With ST the state that enters a
    chunk and dS the cotangent of the one that leaves it:

        dqG = do ST      dP = do u^T     du = P^T do + k_out dS^T
        dk_out = u dS    dw = -du ST     du0 = du
        dg_out = sum over dv of ST dS
        dST = dS g_out + do^T qG - du^T w
    """
    op, C = w.op, w.C

    @pl.when(pl.program_id(1) == 0)
    def _():
        dS[...] = jnp.zeros(dS.shape, jnp.float32)

    states[0] = kept_ref[...]
    read = functools.partial(_read_chunk, q_ref, k_ref, G_ref, P_ref, w_ref,
                             u_ref, w=w)
    rest = jnp.zeros((_LANES - C, w.d), op)  # dP's lanes past the chunk

    def forward(c, _):  # the block's states and u, once more
        def write(h, done):
            us[c, h], _, states[c + 1, h] = done

        _each_head(w, lambda h: _step(
            read(_tiles(w, c, h)), states[c, h], op), write)

    jax.lax.fori_loop(0, w.nb, forward, None)

    def backward(i, _):
        c = w.nb - 1 - i

        def work(h):
            tiles = _tiles(w, c, h)
            x = read(tiles)
            ST, u, dS_h = states[c, h], us[c, h], dS[h]
            S_op, dS_op = ST.astype(op), dS_h.astype(op)
            do = _gather(do_ref, tiles)
            do_op, u_op = do.astype(op), u.astype(op)
            du = (_dot(x.P.astype(op), do_op, _TN)
                  + _dot(x.k_out.astype(op), dS_op, _NT))
            du_op = du.astype(op)
            dqG = _dot(do_op, S_op)
            dk_out = _dot(u_op, dS_op)
            # back through qG = q e^G, k_out = k e^(G_C - G), g_out = e^G_C
            pull = dk_out * x.k_out
            dG_C = (pull.sum(axis=0, keepdims=True)
                    + (ST * dS_h).sum(axis=0, keepdims=True) * x.g_out)
            last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
            return (
                dqG * x.eG, dk_out * x.e_out,
                dqG * x.qG - pull + jnp.where(last, dG_C, 0.0),
                _dot(do_op, jnp.concatenate([u_op, rest], axis=0), _NT),
                -_dot(du_op, S_op), du,
            ), (dS_h * x.g_out + _dot(do_op, x.qG.astype(op), _TN)
                - _dot(du_op, x.w.astype(op), _TN))

        def write(h, done):
            outs, dS[h] = done
            for ref, x in zip((dq_ref, dk_ref, dG_ref, dP_ref, dw_ref,
                               du_ref), outs):
                _scatter(ref, _tiles(w, c, h), x)

        _each_head(w, work, write)

    jax.lax.fori_loop(0, w.nb, backward, None)


def _scan_specs(q, nh: int, spec: Spec, reverse: bool) -> tuple:
    """Grid and block kinds of the walk's kernels: a block of chunks of [B,
    T * nh, width]; the kept states [B, blocks, nh, d, d]."""
    d = q.shape[2]
    rows = spec.chunks * spec.chunk
    blocks = q.shape[1] // (rows * nh)

    def at(n):
        return blocks - 1 - n if reverse else n

    def wide(width):
        return pl.BlockSpec((None, rows * nh, width),
                            lambda b_, n: (b_, at(n), 0))

    kept = pl.BlockSpec((None, None, nh, d, d),
                        lambda b_, n: (b_, at(n), 0, 0, 0))
    walk = _Walk(spec.chunk, spec.chunks, nh, min(spec.heads, nh), d,
                 jnp.dtype(spec.operands))
    return (q.shape[0], blocks), wide, kept, walk


@functools.partial(jax.jit, static_argnames=("nh", "spec"))
def scan(q, k, G, P, w_, u, nh: int, spec: Spec):
    """q, k, G, w, u0 [B, T * nh, d], P [B, T * nh, 128] -> o [B, T * nh,
    d] float32, the state zero at position 0 and carried from chunk to
    chunk, and the states that entered each block of chunks [B, blocks, nh,
    d, d]."""
    grid, wide, kept, w = _scan_specs(q, nh, spec, False)
    d = w.d
    return pl.pallas_call(
        functools.partial(_scan_kernel, w=w), grid=grid,
        in_specs=[wide(d), wide(d), wide(d), wide(_LANES), wide(d), wide(d)],
        out_specs=[wide(d), kept],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct((q.shape[0], grid[1], nh, d, d),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nh, d, d), jnp.float32)],
        compiler_params=_params(True), interpret=spec.interpret,
    )(q, k, G, P, w_, u)


@functools.partial(jax.jit, static_argnames=("nh", "spec"))
def _scan_bwd(q, k, G, P, w_, u, states, do, nh: int, spec: Spec):
    """The cotangents of ``scan``'s six operands from o's."""
    grid, wide, kept, w = _scan_specs(q, nh, spec, True)
    d = w.d
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, w=w), grid=grid,
        in_specs=[wide(d), wide(d), wide(d), wide(_LANES), wide(d), wide(d),
                  kept, wide(d)],
        out_specs=[wide(d), wide(d), wide(d), wide(_LANES), wide(d), wide(d)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)
                   for x in (q, k, G, P, w_, u)],
        scratch_shapes=[pltpu.VMEM((w.nb + 1, nh, d, d), jnp.float32),
                        pltpu.VMEM((w.nb, nh, w.C, d), jnp.float32),
                        pltpu.VMEM((nh, d, d), jnp.float32)],
        compiler_params=_params(True), interpret=spec.interpret,
    )(q, k, G, P, w_, u, states, do)


# --------------------------------------------------------------------------- #
# the call
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _operator(q, k, v, g, beta, spec: Spec):
    """q, k, v, g [B, T * nh, d] float32, beta [B, nh, T] -> o [B, T * nh,
    d]: the two forward kernels, and for the backward the two backward
    ones, the walk's first."""
    return _operator_fwd(q, k, v, g, beta, spec)[0]


def _operator_fwd(q, k, v, g, beta, spec: Spec):
    G, R, P, w, u = pairs(q, k, v, g, beta, spec)
    o, states = scan(q, k, G, P, w, u, beta.shape[1], spec)
    return o, (q, k, v, g, beta, G, R, P, w, u, states)


def _operator_bwd(spec: Spec, kept, do):
    q, k, v, g, beta, G, R, P, w, u, states = kept
    walks = _scan_bwd(q, k, G, P, w, u, states, do, beta.shape[1], spec)
    return tuple(_pairs_bwd(q, k, v, g, beta, G, R, w, u, walks, spec))


_operator.defvjp(_operator_fwd, _operator_bwd)


def gated_delta(q, k, v, g, beta, spec: Spec) -> jax.Array:
    """``gated_delta_chunked`` (models/decoder_lm.py has the equations) as
    the kernels above: q, k, g, v [B, T, nh, d], beta [B, T, nh] -> o [B,
    T, nh, d] float32."""
    B, T, nh, d = q.shape
    pad = -T % (spec.chunk * spec.chunks)
    # padding: k = v = beta = 0 and g = 0 leave the state as it is
    q, k, v, g, beta = (
        jnp.pad(a.astype(jnp.float32),
                ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))  # a tile is eight float32 rows

    def rows(a):  # [B, T, nh, d] seen as [B, T * nh, d]: the same memory
        return a.reshape(B, (T + pad) * nh, d)

    o = _operator(rows(q), rows(k), rows(v), rows(g),
                  jnp.swapaxes(beta, 1, 2), spec)
    return o.reshape(B, T + pad, nh, d)[:, :T]
