"""Kanana-2-30B-A3B's decoder, plainly, as one chip's share of a layout in
which 16 chips share each layer: the reference of the ``kanana2`` model name.

From the model's published ``config.json`` (``model_type`` deepseek_v3; the
configuration's file holds it whole; ``cfg`` below is that file).  ``n`` =
RMSNorm, eps 1e-6, learned scale; no biases; x [T, hidden]:

  attention, every layer, h = n1(x):
      q = h Wq -> [T, 32, 192], split q_nope (128) | q_pe (64); no
      low-rank step on the query (q_lora_rank null)
      h Wkv_a -> [T, 576], split the latent c (512) | k_pe (64: ONE per
      token, shared by all 32 heads)
      n_kv(c) Wkv_b -> [T, 32, 256], split k_nope (128) | v (128)
      rotary on q_pe and k_pe only: theta 1,000,000, no scaling, the pairs
      (2i, 2i + 1) (rope_interleave)
      q = [q_nope | q_pe], k = [k_nope | k_pe for every head]
      x += softmax(causal(q k^T / sqrt(192))) v  Wo      ([T, 32 * 128])
  feed-forward, h = n2(x):
      layer 0 (first_k_dense_replace 1):
          x += Wdown(silu(Wgate h) * Wup h), width 6,144
      layers >= 1:
          s = sigmoid(h Wr) over all 128 experts; the 6 with the largest
          s + b (b = e_score_correction_bias; n_group = topk_group = 1, so
          the group-limited choice is a plain top-6); w_e = 2.448 * s_e /
          (sum of the chosen s + 1e-20): b is in the choice and nowhere else
          x += sum over e chosen and HELD HERE (0 .. num_experts_held - 1)
               of w_e * Wdown_e(silu(Wgate_e h) * Wup_e h)   (width 768)
             + shared(h): one SwiGLU of width 2 * 768, unweighted
          What the absent experts would add is left out, here as in the
          program, and that partial sum goes on; the shared expert is what
          every share computes alike.
  logits = n_f(x) Whead^T over the vocab_size classes held here; loss =
  mean over the positions that have a next token of the softmax
  cross-entropy against that token's class (its key's rank among the
  table's sorted keys: ``key_rank[inv]`` of the next occurrence).

Written to fit beside the four copies of 392 M parameters a step holds
(common.make_step donates its state: parameters, Adam's two moments and
the gradient, 16 bytes a parameter): one sequence at a time (``lax.map``),
every layer rematerialised (``jax.checkpoint``), attention one head at a time
(``lax.map`` over the heads, each rematerialised: one [T, T] block of
scores alive), each held expert and each block of ``LOGIT_ROWS`` rows of
logits rematerialised.  The arithmetic is the dense one: a [T, T] mask
from positions, every held expert on every token.  Every product goes
through ``ops``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_ROWS = 1024


def sizes(cfg: dict) -> dict:
    return {
        "H": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
        "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
        "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["n_routed_experts"], "held": cfg["num_experts_held"],
        "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "L": cfg["num_hidden_layers"], "dense": cfg["first_k_dense_replace"],
        "T": cfg["feed"]["max_seq_len"],
    }


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``) for this
    description: normal weights scaled by 1/sqrt(fan-in), norm scales 1,
    the selection bias normal * 0.1 (wide enough to change some choices)."""
    z = sizes(cfg)
    H, nh = z["H"], z["nh"]

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    keys = jax.random.split(key, z["L"] + 1)
    layers = []
    for l, lk in enumerate(keys[1:]):
        ks = jax.random.split(lk, 12)
        lp = {
            "n1": jnp.ones((H,), jnp.float32),
            "n2": jnp.ones((H,), jnp.float32),
            "n_kv": jnp.ones((z["rank"],), jnp.float32),
            "wq": w(ks[0], H, nh * (z["nope"] + z["rope"]), fan_in=H),
            "wkv_a": w(ks[1], H, z["rank"] + z["rope"], fan_in=H),
            "wkv_b": w(ks[2], z["rank"], nh * (z["nope"] + z["dv"]),
                       fan_in=z["rank"]),
            "wo": w(ks[3], nh * z["dv"], H, fan_in=nh * z["dv"]),
        }
        if l < z["dense"]:
            lp.update(
                mlp_gate=w(ks[4], H, z["Fd"], fan_in=H),
                mlp_up=w(ks[5], H, z["Fd"], fan_in=H),
                mlp_down=w(ks[6], z["Fd"], H, fan_in=z["Fd"]))
        else:
            lp.update(
                router=w(ks[4], H, z["E"], fan_in=H),
                router_bias=0.1 * jax.random.normal(
                    ks[5], (z["E"],), jnp.float32),
                w_gate=w(ks[6], z["held"], H, z["F"], fan_in=H),
                w_up=w(ks[7], z["held"], H, z["F"], fan_in=H),
                w_down=w(ks[8], z["held"], z["F"], H, fan_in=z["F"]),
                shared_gate=w(ks[9], H, z["Fs"], fan_in=H),
                shared_up=w(ks[10], H, z["Fs"], fan_in=H),
                shared_down=w(ks[11], z["Fs"], H, fan_in=z["Fs"]))
        layers.append(lp)
    return {"layers": layers, "norm_f": jnp.ones((H,), jnp.float32),
            "head": w(keys[0], z["V"], H, fan_in=H)}


def rotary(cfg: dict, T: int) -> tuple:
    """cos, sin [T, rope / 2]: angle(t, i) = t * theta ** (-2i / rope)."""
    if cfg["rope_scaling"] is not None:
        raise ValueError("the reference has no scaled rotary code")
    r = cfg["qk_rope_head_dim"]
    inv = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(r // 2, dtype=np.float64) / r)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def turn(x, cos, sin):
    """x [T, heads, rope] turned in the adjacent pairs (2i, 2i + 1)."""
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(ops, h, w_gate, w_up, w_down):
    return ops.dot(jax.nn.silu(ops.dot(h, w_gate)) * ops.dot(h, w_up), w_down)


def attention(cfg: dict, ops, lp: dict, h):
    z = sizes(cfg)
    T, nh, nope, rank = h.shape[0], z["nh"], z["nope"], z["rank"]
    if not cfg["rope_interleave"]:
        raise ValueError("the reference turns adjacent pairs only")
    cos, sin = rotary(cfg, T)
    q = ops.dot(h, lp["wq"]).reshape(T, nh, nope + z["rope"])
    kv_a = ops.dot(h, lp["wkv_a"])
    kv = ops.dot(rms_norm(kv_a[:, :rank], lp["n_kv"], cfg["rms_norm_eps"]),
                 lp["wkv_b"]).reshape(T, nh, nope + z["dv"])
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:], cos, sin)], -1)
    k_pe = turn(kv_a[:, None, rank:], cos, sin)  # one for all heads
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (T, nh, z["rope"]))], axis=-1)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def head(qkv):  # one head: [T, 192], [T, 192], [T, 128]
        qh, kh, vh = qkv
        s = ops.einsum("qd,kd->qk", qh, kh) / math.sqrt(qh.shape[-1])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", p, vh)

    out = jax.lax.map(head, tuple(
        a.transpose(1, 0, 2) for a in (q, k, kv[..., nope:])))
    return ops.dot(out.transpose(1, 0, 2).reshape(T, nh * z["dv"]), lp["wo"])


def routed(cfg: dict, ops, lp: dict, h):
    """The held experts' part of the routed sum, and the shared expert."""
    z = sizes(cfg)
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 or cfg[
            "topk_group"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("the reference routes by sigmoid scores, one group")
    s = jax.nn.sigmoid(ops.dot(h, lp["router"]))
    _, top_e = jax.lax.top_k(s + lp["router_bias"], z["k"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = cfg["routed_scaling_factor"] * top_s / (
        top_s.sum(axis=-1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def expert(h, w, w_gate, w_up, w_down):
        return w[:, None] * swiglu(ops, h, w_gate, w_up, w_down)

    y = swiglu(ops, h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for e in range(z["held"]):  # experts 0 .. held-1 live here
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        y = y + expert(h, w, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return y


def sequence_loss(cfg: dict, ops, params: dict, x, target):
    """x [T, hidden]; target [T]: the next token's class, -1 where none.
    Returns (sum of cross-entropies, how many)."""
    eps = cfg["rms_norm_eps"]

    def layer(lp, x, dense):
        x = x + attention(cfg, ops, lp, rms_norm(x, lp["n1"], eps))
        h = rms_norm(x, lp["n2"], eps)
        if dense:
            return x + swiglu(ops, h, lp["mlp_gate"], lp["mlp_up"],
                              lp["mlp_down"])
        return x + routed(cfg, ops, lp, h)

    for l, lp in enumerate(params["layers"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            lp, x, l < cfg["first_k_dense_replace"])
    scored = target >= 0

    @jax.checkpoint
    def block(xt):  # LOGIT_ROWS rows of logits at a time
        xb, tb, sb = xt
        logits = ops.dot(rms_norm(xb, params["norm_f"], eps),
                         params["head"].T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(sb, tb, 0)[:, None], axis=1)[:, 0]
        return -(picked * sb).sum()

    T = x.shape[0]
    rows = math.gcd(T, LOGIT_ROWS)
    sums = jax.lax.map(block, tuple(
        a.reshape(T // rows, rows, *a.shape[1:]) for a in (x, target, scored)))
    return sums.sum(), scored.sum()


def loss(cfg: dict, ops, params: dict, rows_occ, batch: dict):
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
    sums, counts = jax.lax.map(
        lambda a: sequence_loss(cfg, ops, params, a[0], a[1]),
        (x.reshape(B, T, -1), target))
    return sums.sum() / jnp.maximum(counts.sum(), 1)
