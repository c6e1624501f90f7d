"""SDAR-30B-A3B's decoder trained by diffusion over blocks, plainly, as one
chip's share of a layout in which 16 chips share each layer: the reference
of the ``sdar`` model name.

From the model's published ``config.json`` (``model_type`` sdar_moe; the
configuration's file holds it whole; ``cfg`` below is that file) and the
objective of block discrete denoising diffusion (BD3-LM), which SDAR adapts
an autoregressive checkpoint to.  ``n`` = RMSNorm, eps ``rms_norm_eps``
1e-6, learned scale; no biases.  What the config does not give -- block
length, noise schedule -- is ``cfg["diffusion"]`` (listed under ``assumed``).

  the objective, a sequence x0 of T tokens with classes c_0 .. c_{T-1}:
      noise level t = 0.5 + the instance's first dense feature, an integer
      n of thousandths; p = eps + (1 - eps) n / 1000
      u = uniform(fold_in(fold_in(PRNGKey(noise_seed), n), c_0), [T]);
      m_i = u_i < p: position i is masked -- a pure function of the
      instance, the same in every pass
      noised stream  xt_i = mask_embed where m_i, else the row of x0_i
      the layers run ONCE over 2T positions, xt then x0, both at rotary
      positions 0 .. T-1, under the mask (block of i = i // L, L =
      block_len):
          noised query i sees noised key j iff block(j) = block(i), and
              clean key j iff block(j) < block(i)
          clean query i sees clean key j iff block(j) <= block(i), and no
              noised key
      loss = sum over b, i of m_{b,i} CE(logits^t_{b,i}, c_{b,i}) / p_b
             over B * T: noised position i scores token i ITSELF (no
             shift), the clean stream contributes no term
  layer l, x [2T, hidden]:
      h = n1(x);  q, k, v = h Wq, h Wk, h Wv  (32 query heads over 4
      key-value heads of 128)
      q = n_q(q), k = n_k(k): an RMSNorm with a learned scale over the 128
      floats of EACH query head and EACH key head, before the rotary code
      rotary on the whole head, theta 1,000,000, no scaling, dimension i
      paired with i + 64 (rotate-half)
      x += softmax(mask(q k^T / sqrt(128))) v Wo, the mask above written
      out as a boolean [2T, 2T] matrix
      h = n2(x);  s = softmax(h Wr) over all 128 experts; the 8 largest,
      renormalised to sum 1 (norm_topk_prob); for each expert e HELD HERE
      (0 .. num_experts_held - 1):
      x += w_e * Wdown_e(silu(Wgate_e h) * Wup_e h), w_e = 0 where e is
      not among the token's 8.  What the absent experts would add is left
      out, here as in the program, and that partial sum goes on; no shared
      expert, no selection bias.
  logits = n_f(x) Whead^T over the vocab_size classes held here.

Written to fit beside the four copies of 380 M parameters a step holds
(common.make_step donates its state: parameters, Adam's two moments and
the gradient, 16 bytes a parameter): one sequence at a time (``lax.map``),
every layer rematerialised (``jax.checkpoint``), attention one head at a
time (``lax.map`` over the heads' numbers, each rematerialised and reading
its key-value head in place: one [2T, 2T] block of scores alive, keys and
values never repeated; as a Python loop over pairs of heads the compiler
ran the pairs side by side and the step held 17.6 GB), the held experts
one after another (``lax.scan`` over the stacked weights, each rematerialised: one
body to compile, not eight a layer) and each block of ``LOGIT_ROWS`` rows
of logits rematerialised.  The arithmetic is the dense one: every (query, key) pair
of the 2T x 2T square, every held expert on every position.  Every product
goes through ``ops``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_ROWS = 1024
NOISE_GRID = 1000


def sizes(cfg: dict) -> dict:
    return {
        "H": cfg["hidden_size"], "nq": cfg["num_attention_heads"],
        "nkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "F": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
        "held": cfg["num_experts_held"], "k": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
        "T": cfg["feed"]["max_seq_len"],
    }


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``) for this
    description: normal weights scaled by 1/sqrt(fan-in), norm scales 1,
    ``mask_embed`` normal * 0.02 (a table row's scale)."""
    z = sizes(cfg)
    H, F, d = z["H"], z["F"], z["d"]
    hq, hkv = z["nq"] * d, z["nkv"] * d

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    keys = jax.random.split(key, z["L"] + 1)
    layers = []
    for lk in keys[1:]:
        ks = jax.random.split(lk, 8)
        layers.append({
            "n1": jnp.ones((H,), jnp.float32),
            "n2": jnp.ones((H,), jnp.float32),
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
            "wq": w(ks[0], H, hq, fan_in=H),
            "wk": w(ks[1], H, hkv, fan_in=H),
            "wv": w(ks[2], H, hkv, fan_in=H),
            "wo": w(ks[3], hq, H, fan_in=hq),
            "router": w(ks[4], H, z["E"], fan_in=H),
            "w_gate": w(ks[5], z["held"], H, F, fan_in=H),
            "w_up": w(ks[6], z["held"], H, F, fan_in=H),
            "w_down": w(ks[7], z["held"], F, H, fan_in=F),
        })
    return {"layers": layers, "norm_f": jnp.ones((H,), jnp.float32),
            "head": w(keys[0], z["V"], H, fan_in=H),
            "mask_embed": 0.02 * jax.random.normal(
                jax.random.fold_in(key, z["L"] + 1), (H,), jnp.float32)}


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


def block_mask(T: int, block_len: int):
    """bool [2T, 2T], query by key, positions 0 .. T-1 the noised stream
    and T .. 2T-1 the clean one: the three rules, written out."""
    at = jnp.arange(2 * T)
    clean, block = at >= T, (at % T) // block_len
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_block, k_block = block[:, None], block[None, :]
    return ((~q_clean & ~k_clean & (k_block == q_block))
            | (~q_clean & k_clean & (k_block < q_block))
            | (q_clean & k_clean & (k_block <= q_block)))


def attention(cfg: dict, ops, lp: dict, h):
    """One sequence's two streams, h [2T, hidden]."""
    z = sizes(cfg)
    T2, nq, nkv, d = h.shape[0], z["nq"], z["nkv"], z["d"]
    eps = cfg["rms_norm_eps"]
    cos, sin = (jnp.concatenate([a, a]) for a in rotary(cfg, T2 // 2))
    q = ops.dot(h, lp["wq"]).reshape(T2, nq, d)
    k = ops.dot(h, lp["wk"]).reshape(T2, nkv, d)
    v = ops.dot(h, lp["wv"]).reshape(T2, nkv, d)
    q = turn(rms_norm(q, lp["q_norm"], eps), cos, sin)
    k = turn(rms_norm(k, lp["k_norm"], eps), cos, sin)
    mask = block_mask(T2 // 2, cfg["diffusion"]["block_len"])
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))  # [heads, 2T, d]

    @jax.checkpoint
    def head(h):  # query head h on the key-value head it reads: [2T, 128]
        kv = h // (nq // nkv)
        s = ops.einsum("qd,kd->qk", q[h], k[kv]) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", p, v[kv])

    out = jax.lax.map(head, jnp.arange(nq))
    return ops.dot(out.transpose(1, 0, 2).reshape(T2, nq * d), lp["wo"])


def routed(cfg: dict, ops, lp: dict, h):
    """The held experts' part of the routed sum."""
    z = sizes(cfg)
    if not cfg["norm_topk_prob"]:
        raise ValueError("the reference renormalises the chosen scores")
    probs = jax.nn.softmax(ops.dot(h, lp["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, z["k"])
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)

    @jax.checkpoint
    def expert(y, held):  # experts 0 .. held-1 live here, one at a time
        e, w_gate, w_up, w_down = held
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        act = jax.nn.silu(ops.dot(h, w_gate)) * ops.dot(h, w_up)
        return y + w[:, None] * ops.dot(act, w_down), None

    return jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(z["held"]), lp["w_gate"], lp["w_up"], lp["w_down"]))[0]


def noise(cfg: dict, cls, dense) -> tuple:
    """cls [B, T] the tokens' classes (-1: no token), dense [B, >= 1] the
    instances' dense features.  Returns (masked bool [B, T], p [B])."""
    z = cfg["diffusion"]
    T = cls.shape[1]
    n = jnp.round(NOISE_GRID * dense[:, 0]).astype(jnp.int32) + NOISE_GRID // 2
    p = z["eps"] + (1.0 - z["eps"]) * (n.astype(jnp.float32) / NOISE_GRID)
    base = jax.random.PRNGKey(z["noise_seed"])
    u = jnp.stack([
        jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(base, n[b]), cls[b, 0]),
            (T,))
        for b in range(cls.shape[0])])
    return (u < p[:, None]) & (cls >= 0), p


def sequence_loss(cfg: dict, ops, params: dict, x0, cls, masked):
    """x0 [T, hidden] the clean inputs, cls [T], masked [T].  Returns the
    sum over the masked positions of the cross-entropy of the noised
    stream's logits against the position's own class."""
    eps = cfg["rms_norm_eps"]
    T = x0.shape[0]
    x = jnp.concatenate(
        [jnp.where(masked[:, None], params["mask_embed"], x0), x0])

    def layer(lp, x):
        x = x + attention(cfg, ops, lp, rms_norm(x, lp["n1"], eps))
        return x + routed(cfg, ops, lp, rms_norm(x, lp["n2"], eps))

    for lp in params["layers"]:
        x = jax.checkpoint(layer)(lp, x)

    @jax.checkpoint
    def block(xt):  # LOGIT_ROWS rows of logits at a time
        xb, tb, sb = xt
        logits = ops.dot(rms_norm(xb, params["norm_f"], eps),
                         params["head"].T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(sb, tb, 0)[:, None], axis=1)[:, 0]
        return -(picked * sb).sum()

    rows = math.gcd(T, LOGIT_ROWS)
    return jax.lax.map(block, tuple(
        a.reshape(T // rows, rows, *a.shape[1:])
        for a in (x[:T], cls, masked))).sum()


def loss(cfg: dict, ops, params: dict, rows_occ, batch: dict):
    z = sizes(cfg)
    B, T = batch["B"], z["T"]
    # the occurrence (instance i, position p < T) is position p of
    # sequence i; padding goes to the overflow row
    taken = (batch["pos"] < T) & (batch["mask"] > 0)
    at = jnp.where(taken, batch["ins"] * T + batch["pos"], B * T)
    x0 = jax.ops.segment_sum(rows_occ[:, 2:], at, B * T + 1)[: B * T]
    cls = jnp.where(taken, batch["key_rank"][batch["inv"]], -1)
    cls = jnp.full((B * T + 1,), -1, jnp.int32).at[at].max(cls)[: B * T]
    cls = cls.reshape(B, T)
    masked, p = noise(cfg, cls, batch["dense"])
    sums = jax.lax.map(
        lambda a: sequence_loss(cfg, ops, params, *a),
        (x0.reshape(B, T, -1), cls, masked))
    return (sums / p).sum() / (B * T)
