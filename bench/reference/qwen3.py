"""A plain float32 Qwen3 dense decoder, written from the published
description and sharing no code with the program's ``repro.models``.

Block (Qwen3 technical report; Hugging Face ``Qwen3ForCausalLM``):
x += o_proj(attn(rope(q_norm(q)), rope(k_norm(k)), v)) on
rms_norm(x); x += down(silu(gate(h)) * up(h)) on rms_norm(x); final
rms_norm, then the head (the embedding's transpose when tied).  RMSNorm
is x / sqrt(mean(x^2) + eps) * gain; q- and k-norm act on each head's
``head_dim``; RoPE rotates the two halves of each head (theta from the
configuration); attention is causal GQA scaled by head_dim^-1/2.

Every product runs at ``Precision.HIGHEST`` in float32.  The model runs
layer by layer (one jitted layer, the layer index traced) and attention
runs in blocks of query rows, so a full-width model fits beside the
weights.  ``fp8=True`` is the control: every matmul's operands are
rounded to float8 e4m3 with a per-row (activations) or per-column
(weights) scale first, the step below the bf16 the configuration serves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 256
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with its absmax along ``axis`` mapped
    to the format's largest value; returned in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(fp8, x, w):
    """x[..., k] @ w[k, n]."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def _rope(x, pos, theta):
    """x [T, H, D]; rotate (x1, x2) halves by pos * theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, fp8):
    """Causal GQA.  q [T, Hq, D], k/v [T, Hkv, D], T a multiple of
    Q_BLOCK; query rows run a block at a time."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, Hkv, G, D)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) * D ** -0.5
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    return out.reshape(T, Hq, D)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(spec, x, blocks, i, fp8):
    """Decoder layer ``i`` of the stacked ``blocks`` on x [T, d]."""
    eps, theta, H, Hkv, D = spec
    a = jax.tree.map(lambda w: w[i].astype(F32), blocks["attn"])
    m = jax.tree.map(lambda w: w[i].astype(F32), blocks["mlp"])
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, a["ln_w"], eps)
    q = _mm(fp8, h, a["wq"]).reshape(T, H, D)
    k = _mm(fp8, h, a["wk"]).reshape(T, Hkv, D)
    v = _mm(fp8, h, a["wv"]).reshape(T, Hkv, D)
    q = _rope(_rms(q, a["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, a["k_norm"], eps), pos, theta)
    o = _attend(q, k, v, fp8).reshape(T, H * D)
    x = x + _mm(fp8, o, a["wo"])
    h = _rms(x, m["ln_w"], eps)
    g = _mm(fp8, h, m["wi_gate"])
    u = _mm(fp8, h, m["wi_up"])
    return x + _mm(fp8, jax.nn.silu(g) * u, m["wo"])


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _head(eps, x, params, rows, tied, fp8):
    """Logits at ``rows``, the vocabulary a slice at a time (a float32
    copy of a whole head would not fit beside a 14B stage's weights)."""
    h = _rms(x[rows], params["final_ln_w"].astype(F32), eps)
    w = params["embed"] if tied else params["lm_head"].T     # [V, d]
    V = w.shape[0]
    parts = next(p for p in (16, 8, 4, 2, 1) if V % p == 0)
    chunks = w.reshape(parts, V // parts, w.shape[1])
    out = jax.lax.map(lambda c: _mm(fp8, h, c.astype(F32).T), chunks)
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


def logits(params, config: dict, tokens, rows, fp8: bool = False):
    """Float32 logits [len(rows), vocab] at positions ``rows`` of the
    sequence ``tokens``."""
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    padded = -(-T // Q_BLOCK) * Q_BLOCK
    tokens = np.concatenate([tokens, np.zeros(padded - T, np.int32)])
    spec = (float(config["rms_norm_eps"]), float(config["rope_theta"]),
            int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))
    x = _embed(params["embed"], jnp.asarray(tokens))
    for i in range(int(config["num_hidden_layers"])):
        x = _layer(spec, x, params["blocks"], jnp.int32(i), fp8)
    return _head(spec[0], x, params, jnp.asarray(np.asarray(rows, np.int32)),
                 bool(config["tie_word_embeddings"]), fp8)
