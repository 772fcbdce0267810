"""Plain float32 reference forward of latent-attention + mixture-of-experts
decoders (DeepSeek-V3 / Moonlight-16B-A3B), in straightforward
``jax.numpy``: no kernels, no cache, no batching tricks, every matmul at
``precision="highest"``. It reads the program's parameter tree (the
``LM.init`` layout, unscanned or scanned) and its ``ArchConfig``.

Per layer: ``x += mla(rms(x))``, then ``x += ffn(rms(x))``, where ``ffn``
is the dense SwiGLU of the ``first_k_dense`` leading layers and the MoE
(routed plus shared experts) after them; logits are the final RMS-normed
state times the untied head (or the tied embedding).

* MLA, un-absorbed: ``q = x Wq`` (or ``q_up(rms(q_down x))``), the latent
  ``c = rms(x W_kv_down[:L])`` expanded by ``kv_up`` to per-head keys
  ``[k_nope ‖ rope(k_rope)]`` and values, a full causal mask, softmax
  scale ``(nope + rope) ** -0.5``.
* MoE: the router scores all ``n_experts`` in float32; ``sigmoid``
  scoring picks the top-k of ``sigmoid(logits) + score_bias`` and weights
  them by the chosen sigmoid scores, ``softmax`` the top-k of the
  softmax; weights normalised to sum to one (``norm_topk``), times
  ``routed_scale``. Only the experts held (``first_held .. first_held +
  n_held``) add their part; the shared experts (one SwiGLU of ``n_shared
  * d_expert``) add theirs once.

Departures from the published model, shared with the program: RMS norm
epsilon 1e-6 (the source states 1e-5); rotary rotates the two halves of
the rope lanes (the source interleaves pairs: the same function of a
permuted weight); under expert parallelism the layer's result is this
chip's share (what absent experts add is left out).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

EPS = 1e-6


def rms(x, gain, eps: float = EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def rotate(x, theta: float):
    """x: (B, T, H, D); the two halves of D rotated by position * freq."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _w(p):
    return jnp.asarray(p["w"], jnp.float32)


def swiglu(x, p):
    return (jax.nn.silu(x @ _w(p["wg"])) * (x @ _w(p["wi"]))) @ _w(p["wdown"])


def mla(x, p, cfg):
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    if m.q_lora is None:
        q = x @ _w(p["wq"])
    else:
        q = rms(x @ _w(p["q_down"]), p["q_norm"]["gain"]) @ _w(p["q_up"])
    q = q.reshape(b, t, h, m.nope_dim + m.rope_dim)
    ckv = x @ _w(p["kv_down"])
    c = rms(ckv[..., :m.kv_lora], p["kv_norm"]["gain"])
    k_rope = rotate(ckv[..., m.kv_lora:].reshape(b, t, 1, m.rope_dim),
                    cfg.rope_theta)
    kv = (c @ _w(p["kv_up"])).reshape(b, t, h, m.nope_dim + m.v_dim)
    k = jnp.concatenate(
        [kv[..., :m.nope_dim],
         jnp.broadcast_to(k_rope, (b, t, h, m.rope_dim))], -1)
    v = kv[..., m.nope_dim:]
    q = jnp.concatenate([q[..., :m.nope_dim],
                         rotate(q[..., m.nope_dim:], cfg.rope_theta)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
        * (m.nope_dim + m.rope_dim) ** -0.5
    pos = jnp.arange(t)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(b, t, h * m.v_dim) @ _w(p["wo"])


def route(logits, bias, moe):
    """(weights (..., K), experts (..., K)) from float32 router logits."""
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias, moe.top_k)
        w = jnp.take_along_axis(scores, idx, -1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), moe.top_k)
    if moe.norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w * moe.routed_scale, idx


def moe(x, p, shared, cfg):
    m = cfg.moe
    logits = x @ jnp.asarray(p["router"]["w"], jnp.float32)
    bias = jnp.asarray(p["router"].get("score_bias", 0.0), jnp.float32)
    w, idx = route(logits, bias, m)
    y = jnp.zeros_like(x)
    for j in range(jnp.shape(p["wi"]["w"])[0]):          # held experts
        e = m.first_held + j
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        h = jax.nn.silu(x @ jnp.asarray(p["wg"]["w"][j], jnp.float32)) \
            * (x @ jnp.asarray(p["wi"]["w"][j], jnp.float32))
        y = y + gate * (h @ jnp.asarray(p["wdown"]["w"][j], jnp.float32))
    if shared is not None:
        y = y + swiglu(x, shared)
    return y


def block(x, p, cfg, use_moe: bool):
    x = x + mla(rms(x, p["ln1"]["gain"]), p["attn"], cfg)
    hn = rms(x, p["ln2"]["gain"])
    if use_moe:
        return x + moe(hn, p["moe"], p.get("shared"), cfg)
    return x + swiglu(hn, p["ffn"])


def _layers(stack):
    """(block params, uses experts) in layer order."""
    for p in stack.get("lead", ()):
        yield p, False
    if "list" in stack:
        periods = stack["list"]
    else:
        n = jax.tree_util.tree_leaves(stack["periods"])[0].shape[0]
        periods = [jax.tree_util.tree_map(lambda a: a[i], stack["periods"])
                   for i in range(n)]
    for per in periods:
        for i in range(len(per)):
            blk = per[f"b{i}"]
            yield blk, "moe" in blk


def forward(params: Dict[str, Any], tokens, cfg) -> jax.Array:
    """Logits (B, T, vocab) of ``tokens`` (B, T) in float32."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"]["embedding"])[jnp.asarray(tokens)]
        for p, use_moe in _layers(params["stack"]):
            x = block(x, f32(p), cfg, use_moe)
        x = rms(x, f32(params["final_norm"]["gain"]))
        if cfg.tie_embeddings:
            return x @ f32(params["embedding"]["embedding"]).T
        return x @ f32(params["lm_head"]["w"])
