"""Plain float32 forward of a decoder of grouped-query attention blocks.

Imports nothing of the program. Per block: ``x += attn(rms(x))``, then
``x += down(silu(rms(x) @ wg) * (rms(x) @ wi))``; attention has rotary
positions (the halves of each head rotated, base ``rope_theta``), a
causal mask and softmax scale ``head_dim ** -0.5``; logits are the final
RMS-normed state times the (tied) embedding. RMS norm: ``x / sqrt(mean(x^2)
+ eps) * gain``. Every matmul runs at ``precision="highest"``.

The forward runs layer by layer over a batch of whole sequences, so only
one layer's weights are on the device at a time. ``linear_bits`` rounds
every linear weight to that many bits (symmetric, per output channel,
round to nearest) before use: the lower-precision control.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "wi", "wg", "wdown")


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotate(x, pos, theta):
    """x: (B, T, H, D); the two halves of D rotated by angle pos * freq."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv              # (T, D/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def round_linear(w, bits: int):
    """(in, out) weight rounded to ``bits`` per output channel."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def block(x, p, *, n_heads, n_kv, head_dim, theta, eps, q_block=1024):
    b, t, _ = x.shape
    pos = jnp.arange(t)
    h = rms(x, p["ln1"], eps)
    q = rotate((h @ p["wq"]).reshape(b, t, n_heads, head_dim), pos, theta)
    k = rotate((h @ p["wk"]).reshape(b, t, n_kv, head_dim), pos, theta)
    v = (h @ p["wv"]).reshape(b, t, n_kv, head_dim)
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    outs = []
    for q0 in range(0, t, q_block):
        qb = q[:, q0:q0 + q_block]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * head_dim ** -0.5
        rows = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
    a = jnp.concatenate(outs, 1).reshape(b, t, n_heads * head_dim)
    x = x + a @ p["wo"]
    h = rms(x, p["ln2"], eps)
    return x + (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wdown"]


def forward_hidden(tokens: np.ndarray, embedding,
                   layer: Callable[[int], Dict[str, jax.Array]],
                   n_layers: int, cfg: Dict[str, float],
                   linear_bits: Optional[int] = None) -> jax.Array:
    """Final hidden states (B, T, d) of ``tokens`` (B, T), sequences padded
    at the end (padding never reaches an earlier position under the causal
    mask). ``layer(l)`` gives block ``l``'s float32 weights: ``ln1``,
    ``ln2`` gains and the ``(in, out)`` matrices named in
    :data:`LINEARS`."""
    kw = dict(n_heads=int(cfg["n_heads"]), n_kv=int(cfg["n_kv_heads"]),
              head_dim=int(cfg["head_dim"]), theta=float(cfg["rope_theta"]),
              eps=float(cfg["norm_eps"]))
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, p: block(x, p, **kw))
        x = jnp.asarray(embedding, jnp.float32)[jnp.asarray(tokens)]
        for l in range(n_layers):
            p = layer(l)
            if linear_bits is not None:
                p = dict(p, **{n: round_linear(p[n], linear_bits)
                               for n in LINEARS})
            x = step(x, p)
            del p
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def logits_at(x, pos, final_gain, embedding, eps: float):
    """Logits (B, P, V) of the hidden states ``x`` (B, T, d) at positions
    ``pos`` (B, P), against the tied embedding."""
    h = jnp.take_along_axis(x, pos[..., None], axis=1)
    with jax.default_matmul_precision("highest"):
        return rms(h, final_gain, eps) @ embedding.T


@jax.jit
def gaps(ref_logits, tokens, valid):
    """``max(logits) - logits[token]`` per position (0 where not
    ``valid``): how far below the reference's best a chosen token lies."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return jnp.where(valid, best - got, 0.0)
