"""Plain reference of one learner's part of a SAFE train step of InternLM2,
at any width, and of the AdamW update that follows the mean.

What the step must compute, from InternLM2 (arXiv:2403.17297; the
``config.json`` of ``internlm/internlm2-1_8b``): a pre-norm decoder of
RMSNorm, grouped-query attention with rotate-half rotary positions (base
``rope_theta``) and a SwiGLU MLP, closed by a final RMSNorm and an output
head (untied unless ``tie_word_embeddings``); the loss is the mean
next-token cross-entropy of the learner's batch. The step publishes the
clear mean of the learners' gradients, and AdamW's first step applies it.

Everything is ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``: no sharding, no kernels, no fixed point, none of
the program's remat. ``loss_and_grad`` is one traceable function, so a
caller jits it whole (the chip check runs one learner per chip under
``shard_map``). To fit on one chip at the published widths beside the
parameters, the gradient is taken by hand, a piece at a time: the forward
pass keeps each layer's input (a scan over the layers); the output head
and its cross-entropy run over blocks of the vocabulary (two scans: the
log-sum-exp, then the gradient); each layer's gradient is the
``jax.vjp`` of that layer alone, one batch row at a time (a scan over the
layers in reverse, and over the rows inside it).

The parameters come in the program's tree layout (``embed``, ``lm_head``,
``final_norm``, ``blocks[0]`` with a leading layer dimension); they are
data. Departures from the published model, the program's and followed
here so that the two compute one function: the token embedding is scaled
by sqrt(hidden_size); an RMSNorm gain is stored as ``scale`` and applied
as ``1 + scale``; the weights are random from a seed; AdamW has no
gradient clipping and decays every parameter.

It imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(scale, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def rotary(x, theta):
    """x[B, S, H, hd] at positions 0..S-1, rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, cfg):
    """One decoder layer of x[B, S, d]; ``p`` holds that layer's weights."""
    B, S, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // nh, cfg["rms_norm_eps"]
    h = rms_norm(p["ln1"]["scale"], x, eps)
    a = p["attn"]
    q = rotary(_mm(h, a["wq"]).reshape(B, S, nh, hd), cfg["rope_theta"])
    k = rotary(_mm(h, a["wk"]).reshape(B, S, nkv, hd), cfg["rope_theta"])
    v = _mm(h, a["wv"]).reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)  # query head j reads kv head j // g
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / np.float32(np.sqrt(hd))
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(B, S, nh * hd)
    x = x + _mm(o, a["wo"])
    h = rms_norm(p["ln2"]["scale"], x, eps)
    m = p["mlp"]
    return x + _mm(_mm(h, m["wi"]) * jax.nn.silu(_mm(h, m["wg"])), m["wo"])


def loss_and_grad(params, tokens, cfg: dict, vocab_blocks: int = 8):
    """(loss, gradient tree) of one learner's tokens[R, S] at ``params``
    (any float dtype; taken to float32). Traceable: jit it whole."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    R, S = tokens.shape
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    blocks = p["blocks"][0]

    def forward(x, lp):
        return layer(lp, x, cfg), x

    x_top, xs = jax.lax.scan(forward, p["embed"][tokens] * np.float32(
        np.sqrt(d)), blocks)  # xs[i]: layer i's input
    h = rms_norm(p["final_norm"]["scale"], x_top, eps)
    hn = h[:, :-1].reshape(-1, d)
    targets = tokens[:, 1:].reshape(-1)
    head = p["embed"] if cfg["tie_word_embeddings"] else p["lm_head"]
    V = head.shape[0]
    nb = -(-V // vocab_blocks)
    hb = jnp.pad(head, ((0, nb * vocab_blocks - V), (0, 0))).reshape(
        vocab_blocks, nb, d)
    firsts = jnp.arange(vocab_blocks) * nb

    def logits(w, first):
        cols = first + jnp.arange(nb)
        return jnp.where(cols[None, :] < V, _mm(hn, w.T), -jnp.inf), cols

    def lse_block(carry, blk):
        m, s = carry
        lg, _ = logits(*blk)
        mx = jnp.maximum(m, jnp.max(lg, axis=-1))
        return (mx, s * jnp.exp(m - mx)
                + jnp.sum(jnp.exp(lg - mx[:, None]), axis=-1)), None

    start = (jnp.full(hn.shape[:1], -jnp.inf), jnp.zeros(hn.shape[:1]))
    (m, s), _ = jax.lax.scan(lse_block, start, (hb, firsts))
    lse = m + jnp.log(s)
    loss = jnp.mean(lse - jnp.sum(hn * head[targets], axis=-1))

    inv_n = np.float32(1.0 / hn.shape[0])

    def grad_block(dhn, blk):
        lg, cols = logits(*blk)
        g = (jnp.exp(lg - lse[:, None])
             - (cols[None, :] == targets[:, None])) * inv_n
        return dhn + _mm(g, blk[0]), _mm(g.T, hn)

    dhn, dhead = jax.lax.scan(grad_block, jnp.zeros_like(hn), (hb, firsts))
    dhead = dhead.reshape(-1, d)[:V]
    dh = jnp.concatenate([dhn.reshape(R, S - 1, d),
                          jnp.zeros((R, 1, d), jnp.float32)], axis=1)
    _, final_back = jax.vjp(lambda sc, x_: rms_norm(sc, x_, eps),
                            p["final_norm"]["scale"], x_top)
    dscale_f, dx = final_back(dh)

    def backward(dx, layer_in):
        lp, x = layer_in

        def row(acc, xr_dr):
            xr, dr = xr_dr
            _, back = jax.vjp(lambda p_, x_: layer(p_, x_[None], cfg)[0],
                              lp, xr)
            dp, dxr = back(dr)
            return jax.tree.map(jnp.add, acc, dp), dxr

        dp, dx = jax.lax.scan(row, jax.tree.map(jnp.zeros_like, lp), (x, dx))
        return dx, dp

    dx, dblocks = jax.lax.scan(backward, dx, (blocks, xs), reverse=True)
    dembed = jnp.zeros_like(p["embed"]).at[tokens.reshape(-1)].add(
        dx.reshape(-1, d) * np.float32(np.sqrt(d)))
    grad = {"blocks": [dblocks], "final_norm": {"scale": dscale_f}}
    if cfg["tie_word_embeddings"]:
        grad["embed"] = dembed + dhead
    else:
        grad["embed"], grad["lm_head"] = dembed, dhead
    return loss, grad


def probe(tree, idx) -> jax.Array:
    """Words ``idx[j]`` of the tree's j-th leaf (``jax.tree.leaves``
    order), as float32, laid end to end. Traceable."""
    return jnp.concatenate([jnp.ravel(leaf)[i].astype(jnp.float32)
                            for leaf, i in zip(jax.tree.leaves(tree), idx)])


def clear_mean(rows) -> np.ndarray:
    """The float32 mean of the learners' vectors."""
    acc = np.zeros_like(rows[0], np.float32)
    for r in rows:
        acc = acc + r.astype(np.float32)
    return acc / np.float32(len(rows))


def fixed_point_mean(rows, scale_bits: int) -> np.ndarray:
    """The mean a ``scale_bits`` fixed-point secure sum would publish:
    each vector rounded (half to even) to a multiple of 2^-scale_bits,
    summed exactly, decoded and divided by the count."""
    scale = np.float64(2.0 ** scale_bits)
    total = sum(np.round(r.astype(np.float64) * scale).astype(np.int64)
                for r in rows)
    return (total.astype(np.float32) / np.float32(scale)
            / np.float32(len(rows)))


def adamw_first_step(p, g, *, lr: float, b1: float = 0.9, b2: float = 0.95,
                     eps: float = 1e-8, weight_decay: float = 0.1
                     ) -> np.ndarray:
    """float32 parameters after AdamW's first step from zero moments on
    gradient ``g``, computed in float64: m = (1 - b1) g and v = (1 - b2)
    g^2, bias-corrected by 1 - b1 and 1 - b2; the decay is decoupled."""
    p, g = np.asarray(p, np.float64), np.asarray(g, np.float64)
    m_hat = (1 - b1) * g / (1 - b1)
    v_hat = (1 - b2) * g * g / (1 - b2)
    u = m_hat / (np.sqrt(v_hat) + eps) + weight_decay * p
    return (p - lr * u).astype(np.float32)
