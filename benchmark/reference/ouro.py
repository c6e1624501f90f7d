"""Ouro-2.6B's looped decoder, plainly, as one pipeline stage of 8 of its
48 layers with the whole vocabulary: the reference of the ``ouro`` model
name.

From the model's published ``config.json`` (``model_type`` ouro; the
configuration's file holds it whole; ``cfg`` below is that file) and, for
what the config does not state, the looped language model's paper
("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741,
section 3) and the code published beside the config -- neither is on this
machine, so each such point is listed under ``assumed`` in the
configuration's file.  ``n`` = RMSNorm, eps ``rms_norm_eps`` 1e-6, learned
scale; no biases but the gate's.  R = ``total_ut_steps``, L =
``num_hidden_layers``; the L layers are ONE stack applied R times over the
same weights:

    x = the rows of the tokens                        [T, hidden]
    for r = 1 .. R:                              the same leaves every round
        for l = 1 .. L:
            h = n1_l(x);  q, k, v = h Wq, h Wk, h Wv   (16 heads of 128, as
            many key-value heads: no grouping)
            q, k <- rotary on the whole head, theta 1,000,000, no scaling,
            dimension i paired with i + 64 (rotate-half)
            x = x + n1b_l(softmax(mask(q k^T / sqrt(128))) v Wo)
                                                   mask: key j <= query i
            x = x + n2b_l(Wdown(silu(Wgate n2_l(x)) * Wup n2_l(x)))
        h_r = n_f(x);  x = h_r          the normed state feeds round r + 1
        z_r = h_r Whead^T               logits of round r, vocab_size classes
        lam_r = sigmoid(h_r . w_g + b_g)         the exit gate, a position
    p_1 = lam_1,  p_r = lam_r prod_{j<r} (1 - lam_j)  (1 < r < R),
    p_R = prod_{j<R} (1 - lam_j)              a distribution over the rounds
    loss = mean over the positions i that have a next token of
           sum_r p_r(i) CE(z_r(i), next token's class) - beta H(p(i)),
           H(p) = -sum_r p_r ln p_r,  beta = cfg["exit"]["beta"]

A sandwich: a norm before AND after each operator, inside the residual.
``lam_R`` is computed by no one: the last round takes what is left.  The
class of a token is its key's rank among the table's sorted keys
(``key_rank[inv]`` of the next occurrence).

Departures from the published code, each a matter of form: the exit
distribution is taken in logarithms (``log_sigmoid``), so a saturated gate
gives an entropy of 0 and not 0 * -inf; the stage-I objective alone
(``assumed.objective``); no key-value cache, no generation, and
``early_exit_threshold`` is generation's and unread.

Written to fit beside the four copies of 512 M parameters a step holds
(common.make_step donates its state: parameters, Adam's two moments and
the gradient, 16 bytes a parameter): one sequence at a time (``lax.map``),
every layer APPLICATION rematerialised (``jax.checkpoint``: R x L
boundaries of [T, hidden] are kept), attention one head at a time
(``lax.map`` over the heads, each rematerialised: one [T, T] block of
scores alive) and each block of ``LOGIT_ROWS`` rows of logits
rematerialised, R times over.  The rounds are one ``lax.scan`` over L
Python layers: written as R Python rounds the compiled step is a
compile-cache entry of 330 MB (1.46 GB of code on the chip, 15.3 GB in all
by the compiler's count for a described v5e, 163 s to compile) where the
chip tool's cache may hold 192 MiB, so every process of the cell would
compile both arms anew and leave the cache empty for the next; as a scan
it is 80 MB, 0.39 GB of code, 12.0 GB in all and 86 s.  None of it
changes a number: tests/test_decoder_looped.py holds this file against a
form with none of it -- R Python rounds, every sequence at once, whole
logits -- at the small size.  Every product goes through ``ops``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_ROWS = 1024


def sizes(cfg: dict) -> dict:
    return {
        "H": cfg["hidden_size"], "nq": cfg["num_attention_heads"],
        "nkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "L": cfg["num_hidden_layers"], "R": cfg["total_ut_steps"],
        "T": cfg["feed"]["max_seq_len"],
    }


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``) for this
    description: normal weights scaled by 1/sqrt(fan-in), norm scales 1,
    the gate's weight normal / sqrt(hidden) and its bias 0."""
    z = sizes(cfg)
    H, F, hq, hkv = z["H"], z["F"], z["nq"] * z["d"], z["nkv"] * z["d"]

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    def one():
        return jnp.ones((H,), jnp.float32)

    keys = jax.random.split(key, z["L"] + 1)
    layers = []
    for lk in keys[1:]:
        ks = jax.random.split(lk, 7)
        layers.append({
            "n1": one(), "n1b": one(), "n2": one(), "n2b": one(),
            "wq": w(ks[0], H, hq, fan_in=H),
            "wk": w(ks[1], H, hkv, fan_in=H),
            "wv": w(ks[2], H, hkv, fan_in=H),
            "wo": w(ks[3], hq, H, fan_in=hq),
            "mlp_gate": w(ks[4], H, F, fan_in=H),
            "mlp_up": w(ks[5], H, F, fan_in=H),
            "mlp_down": w(ks[6], F, H, fan_in=F),
        })
    return {"layers": layers, "norm_f": one(),
            "head": w(keys[0], z["V"], H, fan_in=H),
            "exit_gate": {
                "w": w(jax.random.fold_in(key, z["L"] + 2), H, fan_in=H),
                "b": jnp.zeros((1,), jnp.float32)}}


def rotary(cfg: dict, T: int) -> tuple:
    """cos, sin [T, head_dim]: angle(t, i) = t * theta ** (-2i / head_dim)
    for i < head_dim / 2, laid out twice (dimension i turns with
    i + head_dim / 2)."""
    if cfg["rope_scaling"] is not None:
        raise ValueError("the reference has no scaled rotary code")
    d = cfg["head_dim"]
    inv = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def turn(x, cos, sin):
    """x [T, heads, head_dim] turned by rotate-half."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(cfg: dict, ops, lp: dict, h):
    """Causal attention of one sequence, h [T, hidden], a head at a time."""
    z = sizes(cfg)
    T, nq, nkv, d = h.shape[0], z["nq"], z["nkv"], z["d"]
    cos, sin = rotary(cfg, T)
    q = turn(ops.dot(h, lp["wq"]).reshape(T, nq, d), cos, sin)
    k = turn(ops.dot(h, lp["wk"]).reshape(T, nkv, d), cos, sin)
    v = ops.dot(h, lp["wv"]).reshape(T, nkv, d)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))  # [heads, T, d]

    @jax.checkpoint
    def head(i):  # query head i on the key-value head it reads: [T, d]
        kv = i // (nq // nkv)
        s = ops.einsum("qd,kd->qk", q[i], k[kv]) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", p, v[kv])

    out = jax.lax.map(head, jnp.arange(nq))
    return ops.dot(out.transpose(1, 0, 2).reshape(T, nq * d), lp["wo"])


def layer(cfg: dict, ops, lp: dict, x):
    """One application of one layer: the sandwich around each operator."""
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, ops, lp, rms_norm(x, lp["n1"], eps))
    x = x + rms_norm(a, lp["n1b"], eps)
    h = rms_norm(x, lp["n2"], eps)
    y = ops.dot(jax.nn.silu(ops.dot(h, lp["mlp_gate"]))
                * ops.dot(h, lp["mlp_up"]), lp["mlp_down"])
    return x + rms_norm(y, lp["n2b"], eps)


def exit_distribution(gates):
    """gates [R, T] -> (p [R, T], entropy [T]), in logarithms: ln p_r =
    ln lam_r + sum_{j<r} ln(1 - lam_j), the last round what is left."""
    R = gates.shape[0]
    logp, stay = [], jnp.zeros_like(gates[0])
    for r in range(R - 1):
        logp.append(stay + jax.nn.log_sigmoid(gates[r]))
        stay = stay + jax.nn.log_sigmoid(-gates[r])
    logp = jnp.stack(logp + [stay])
    p = jnp.exp(logp)
    return p, -(p * logp).sum(axis=0)


def sequence_sums(cfg: dict, ops, params: dict, x, target):
    """x [T, hidden]; target [T]: the next token's class, -1 where none.
    Returns, summed over the scored positions: the expected
    cross-entropy, the entropy of the exit distribution, the mass of each
    round [R], and how many they are."""
    z = sizes(cfg)
    eps, T, R = cfg["rms_norm_eps"], x.shape[0], z["R"]
    scored = target >= 0
    rows = math.gcd(T, LOGIT_ROWS)

    @jax.checkpoint
    def block(args):  # LOGIT_ROWS rows of one round's logits at a time
        hb, tb = args
        logp = jax.nn.log_softmax(ops.dot(hb, params["head"].T), axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.maximum(tb, 0)[:, None], axis=1)[:, 0]

    def one_round(x, _):
        for lp in params["layers"]:
            x = jax.checkpoint(lambda lp, x: layer(cfg, ops, lp, x))(lp, x)
        x = rms_norm(x, params["norm_f"], eps)  # h_r, and round r + 1's input
        ce = jax.lax.map(block, (x.reshape(T // rows, rows, -1),
                                 target.reshape(T // rows, rows))).reshape(T)
        gate = ops.dot(x, params["exit_gate"]["w"]) + params["exit_gate"]["b"]
        return x, (ce, gate)

    _, (ces, gates) = jax.lax.scan(one_round, x, None, length=R)
    p, entropy = exit_distribution(gates)
    return ((p * ces).sum(axis=0) * scored).sum(), (
        entropy * scored).sum(), (p * scored).sum(axis=1), scored.sum()


def loss_and_sums(cfg: dict, ops, params: dict, rows_occ, batch: dict):
    """The loss, and the sums the program's ``loop.*`` counters hold:
    ``exit_mass`` [R], ``exit_entropy``, ``scored``."""
    z = sizes(cfg)
    B, T = batch["B"], z["T"]
    # the occurrence (instance i, position p < T) is position p of
    # sequence i; padding goes to the overflow row
    taken = (batch["pos"] < T) & (batch["mask"] > 0)
    at = jnp.where(taken, batch["ins"] * T + batch["pos"], B * T)
    x = jax.ops.segment_sum(rows_occ[:, 2:], at, B * T + 1)[: B * T]
    cls = jnp.where(taken, batch["key_rank"][batch["inv"]], -1)
    cls = jnp.full((B * T + 1,), -1, jnp.int32).at[at].max(cls)[: B * T]
    cls = cls.reshape(B, T)
    target = jnp.concatenate(
        [cls[:, 1:], jnp.full((B, 1), -1, jnp.int32)], axis=1)
    ce, entropy, mass, n = jax.lax.map(
        lambda a: sequence_sums(cfg, ops, params, a[0], a[1]),
        (x.reshape(B, T, -1), target))
    loss = (ce.sum() - cfg["exit"]["beta"] * entropy.sum()) / jnp.maximum(
        n.sum(), 1)
    return loss, {"exit_mass": mass.sum(axis=0),
                  "exit_entropy": entropy.sum(), "scored": n.sum()}


def loss(cfg: dict, ops, params: dict, rows_occ, batch: dict):
    return loss_and_sums(cfg, ops, params, rows_occ, batch)[0]
