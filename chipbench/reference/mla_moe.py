"""Plain float32 forward of a decoder of latent-attention (MLA) blocks with
a leading dense layer and mixture-of-experts layers after it (DeepSeek-V3,
Moonlight-16B-A3B).

Imports nothing of the program. Per block: ``x += mla(rms(x))``, then
``x += ffn(rms(x))``: a dense SwiGLU ``down(silu(h @ wg) * (h @ wi))`` in
the leading layers, and in the others the routed experts plus the shared
experts (one SwiGLU). RMS norm: ``x / sqrt(mean(x^2) + eps) * gain``.
Every matmul runs at ``precision="highest"``.

* MLA, un-absorbed: ``q = x @ wq`` (16 heads of nope 128 + rope 64);
  ``[c ‖ k_rope] = x @ kv_down``, ``c`` RMS-normed (``kv_norm``) and
  expanded by ``kv_up`` to per-head ``k_nope`` and values; keys are
  ``[k_nope ‖ rope(k_rope)]`` (one rope key shared by the heads); the
  halves of each rope part are rotated, base ``rope_theta``; a causal
  mask and softmax scale ``(nope + rope) ** -0.5``.
* Routing: float32 router logits over all ``n_routed`` experts; the top
  ``top_k`` of ``sigmoid(logits) + score_bias``, weighted by their
  sigmoid scores, normalised to sum to one, times ``routed_scale``. Only
  the experts held here (``first_held`` and on, the banks' length) add
  their part: the share of one chip under expert parallelism.
* Logits: the final RMS-normed state times the untied head.

The forward runs layer by layer over a batch of whole sequences, so only
one layer's weights are on the device at a time. ``linear_bits`` rounds
every linear weight (attention, experts per expert, shared and dense
FFNs, the head; not the router) to that many bits, symmetric, per output
channel, round to nearest: the lower-precision control.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.gqa import gaps, rms, rotate  # noqa: F401  (gaps re-exported)


def round_linear(w, bits: int):
    """(…, in, out) weight rounded to ``bits`` per output channel."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def _round_tree(p, bits):
    """Every linear of a block's weights rounded (router kept)."""
    out = {}
    for k, v in p.items():
        if isinstance(v, dict):
            out[k] = v if k == "router" else _round_tree(v, bits)
        elif k == "w":
            out[k] = round_linear(v, bits)
        else:
            out[k] = v
    return out


def swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]["w"]) * (x @ p["wi"]["w"])) \
        @ p["wdown"]["w"]


def mla(x, p, c, q_block: int = 1024):
    b, t, _ = x.shape
    h, nope, rope, vd, lora = (c["n_heads"], c["nope"], c["rope"], c["v"],
                               c["kv_lora"])
    pos = jnp.arange(t)
    q = (x @ p["wq"]["w"]).reshape(b, t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], pos, c["theta"])], -1)
    ckv = x @ p["kv_down"]["w"]
    lat = rms(ckv[..., :lora], p["kv_norm"]["gain"], c["eps"])
    k_rope = rotate(ckv[..., lora:].reshape(b, t, 1, rope), pos, c["theta"])
    kv = (lat @ p["kv_up"]["w"]).reshape(b, t, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    outs = []
    for q0 in range(0, t, q_block):
        qb = q[:, q0:q0 + q_block]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * (nope + rope) ** -0.5
        rows = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
    a = jnp.concatenate(outs, 1).reshape(b, t, h * vd)
    return a @ p["wo"]["w"]


def route(logits, bias, top_k: int, scale: float):
    """(weights (…, K), experts (…, K)) of float32 router logits."""
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w * scale, idx


def experts(x, p, c):
    """The held experts' part of the routed mix, plus the shared experts."""
    w, idx = route(x @ p["moe"]["router"]["w"],
                   p["moe"]["router"]["score_bias"], c["top_k"],
                   c["routed_scale"])
    banks = p["moe"]
    y = swiglu(x, p["shared"])
    for j in range(banks["wi"]["w"].shape[0]):
        gate = jnp.sum(jnp.where(idx == c["first_held"] + j, w, 0.0), -1,
                       keepdims=True)
        h = jax.nn.silu(x @ banks["wg"]["w"][j]) * (x @ banks["wi"]["w"][j])
        y = y + gate * (h @ banks["wdown"]["w"][j])
    return y


def block(x, p, c, use_moe: bool):
    x = x + mla(rms(x, p["ln1"]["gain"], c["eps"]), p["attn"], c)
    h = rms(x, p["ln2"]["gain"], c["eps"])
    return x + (experts(h, p, c) if use_moe else swiglu(h, p["ffn"]))


def forward_hidden(tokens: np.ndarray, embedding,
                   layer: Callable[[int], Tuple[Dict, bool]],
                   n_layers: int, c: Dict[str, float],
                   linear_bits: Optional[int] = None) -> jax.Array:
    """Final hidden states (B, T, d) of ``tokens`` (B, T), sequences padded
    at the end (padding never reaches an earlier position under the causal
    mask). ``layer(l)`` gives block ``l``'s float32 weights as nested
    dicts (the program's leaf names: ``ln1``, ``ln2``, ``attn``, and
    ``ffn`` or ``moe`` and ``shared``) and whether it has experts. ``c``:
    ``n_heads``, ``nope``, ``rope``, ``v``, ``kv_lora``, ``theta``,
    ``eps``, ``top_k``, ``routed_scale``, ``first_held``."""
    with jax.default_matmul_precision("highest"):
        steps = {m: jax.jit(functools.partial(block, c=c, use_moe=m))
                 for m in (False, True)}
        x = jnp.asarray(embedding, jnp.float32)[jnp.asarray(tokens)]
        for l in range(n_layers):
            p, use_moe = layer(l)
            if linear_bits is not None:
                p = _round_tree(p, linear_bits)
            x = steps[use_moe](x, p)
            del p
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def logits_at(x, pos, final_gain, head, eps: float):
    """Logits (B, P, V) of the hidden states ``x`` (B, T, d) at positions
    ``pos`` (B, P), against the untied head ``(d, V)``."""
    h = jnp.take_along_axis(x, pos[..., None], axis=1)
    with jax.default_matmul_precision("highest"):
        return rms(h, final_gain, eps) @ head
