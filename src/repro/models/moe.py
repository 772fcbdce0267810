"""Mixture-of-Experts: softmax or sigmoid(+bias) top-k routing, GShard
capacity in training and dropless in serving, over the experts this chip
holds.

In training, dispatch/combine are dense einsums over a (tokens, experts,
capacity) one-hot tensor — the standard form GSPMD partitions into
all-to-alls when experts are sharded over the 'model' axis and tokens over
'data'/'pod' (EP) — and expert FFN compute is ``experts × capacity × d ×
ff`` with ``capacity = tokens·top_k·capacity_factor / experts``, i.e.
proportional to *active* FLOPs (MODEL_FLOPS = 6·N_active·D). In serving
(dropless) each held expert runs every token: ``held × tokens`` rows.

Expert kernels are stacked (experts, in, out) tensors named ``w`` — the
quantization pipeline treats each expert's matrix independently (per-expert
per-channel SQuant scales).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.layers import _init_dense


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             kind: str = "swiglu", *, n_routed: Optional[int] = None,
             score_bias: bool = False) -> Dict:
    """Router over ``n_routed`` experts (default ``n_experts``) and banks
    of the ``n_experts`` experts held here. ``score_bias`` adds the
    per-expert selection bias of sigmoid routing (zeros)."""
    ks = jax.random.split(key, 4)
    s = 1.0 / jnp.sqrt(d_model)
    n_routed = n_routed or n_experts

    def bank(k, din, dout, scl):
        return {"w": jax.random.normal(k, (n_experts, din, dout),
                                       jnp.float32) * scl}

    p = {"router": _init_dense(ks[0], d_model, n_routed, scale=0.02),
         "wi": bank(ks[1], d_model, d_ff, s),
         "wdown": bank(ks[3], d_ff, d_model, 1.0 / jnp.sqrt(d_ff))}
    if score_bias:
        p["router"]["score_bias"] = jnp.zeros((n_routed,), jnp.float32)
    if kind in ("swiglu", "geglu"):
        p["wg"] = bank(ks[2], d_model, d_ff, s)
    return p


def _expert_matmul(bank, x):
    """x: (E, C, din) @ bank (E, din, dout) → (E, C, dout)."""
    w = bank["w"]
    if hasattr(w, "dequantize"):                     # QuantizedTensor
        e = x.shape[0]
        din = x.shape[-1]
        # pipeline stores (E*out, in); dequant → (E, out, in) → (E, in, out)
        wd = w.dequantize(x.dtype).reshape(e, -1, din)
        return jnp.einsum("ecd,efd->ecf", x, wd)
    return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))


def route(router, xt: jnp.ndarray, *, top_k: int, scoring: str = "softmax",
          norm_topk: bool = True, routed_scale: float = 1.0):
    """Router over all routed experts, in float32. Returns ``(weights (T,
    K), experts (T, K), scores (T, E))``.

    ``softmax``: the top-k of the softmax. ``sigmoid`` (DeepSeek-V3's
    ``noaux_tc`` with one group): the top-k of ``sigmoid(logits) +
    score_bias``, weighted by the chosen sigmoid scores without the bias.
    ``norm_topk`` normalises the chosen weights to sum to one; the
    result is then times ``routed_scale``."""
    logits = xt.astype(jnp.float32) @ router["w"].astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + router["score_bias"].astype(jnp.float32)
        _, experts = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(scores, top_k)
    else:
        raise ValueError(f"unknown MoE scoring {scoring!r}")
    if norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                        1e-9)
    return weights * routed_scale, experts, scores


def _experts(params, ein, kind):
    """The held experts' FFNs on their rows ``ein`` (E, C, D)."""
    ein = shard_act(ein, ("experts", None, None))
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else \
            (lambda v: jax.nn.gelu(v, approximate=True))
        h = act(_expert_matmul(params["wg"], ein)) * \
            _expert_matmul(params["wi"], ein)
    else:
        h = jax.nn.relu(_expert_matmul(params["wi"], ein))
    h = shard_act(h, ("experts", None, "expert_ff"))
    return _expert_matmul(params["wdown"], h)                   # (E, C, D)


TOKEN_CHUNK = 8192   # dispatch-tensor bound: (chunk, E, C·chunk/T) per block


def moe_apply(params, x: jnp.ndarray, *, n_experts: int, top_k: int,
              kind: str = "swiglu", capacity_factor: float = 1.25,
              dropless: bool = False, scoring: str = "softmax",
              norm_topk: bool = True, routed_scale: float = 1.0,
              first_held: int = 0, n_held: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_loss, pairs). x: (B, S, D).

    The router scores all ``n_experts``; the banks hold the ``n_held``
    experts ``[first_held, first_held + n_held)`` of this chip (default:
    all ``n_experts``), and the output is their part of the routed mix: tokens routed
    to experts held elsewhere get nothing from them (expert parallelism
    without its exchange). ``pairs`` (B, S) int32 counts, per token, the
    top-k choices that the held experts computed.

    ``dropless=True`` (every serving mode) runs each held expert over all
    tokens, weighted by the token's routing weight for it (zero where not
    chosen): capacity = tokens, no token ever dropped, and each token's
    result independent of the batch. Training uses the GShard capacity,
    with one-hot dispatch/combine einsums over a (tokens, experts,
    capacity) tensor — the form GSPMD partitions into all-to-alls.

    Long sequences are processed in TOKEN_CHUNK blocks (scan): the dense
    (T, E, C) dispatch one-hots are quadratic-ish in T and reached
    129 GB/device at 32k-token prefills.
    Capacity competition becomes per-block — the standard microbatched-MoE
    behaviour of production serving stacks.
    """
    b, s, d = x.shape
    t = b * s
    n_held = n_experts if n_held is None else n_held
    routing = dict(scoring=scoring, norm_topk=norm_topk,
                   routed_scale=routed_scale, first_held=first_held,
                   n_held=n_held)
    if t > 2 * TOKEN_CHUNK and t % TOKEN_CHUNK == 0 and s % (
            t // TOKEN_CHUNK) == 0:
        nblk = t // TOKEN_CHUNK
        xs = x.reshape(b, nblk, s // nblk, d).swapaxes(0, 1)

        def blk(_, xb):
            y, aux, pairs = moe_apply(
                params, xb, n_experts=n_experts, top_k=top_k, kind=kind,
                capacity_factor=capacity_factor, dropless=dropless,
                **routing)
            return 0, (y, aux, pairs)

        _, (ys, auxs, pairs) = jax.lax.scan(jax.checkpoint(blk), 0, xs)
        return (ys.swapaxes(0, 1).reshape(b, s, d), jnp.mean(auxs),
                pairs.swapaxes(0, 1).reshape(b, s))
    xt = x.reshape(t, d)
    gate_vals, gate_idx, probs = route(
        params["router"], xt, top_k=top_k, scoring=scoring,
        norm_topk=norm_topk, routed_scale=routed_scale)
    local = gate_idx - first_held                                  # (T, K)
    held = (local >= 0) & (local < n_held)
    # one-hot over the held experts; other experts' choices fall off
    onehot = jax.nn.one_hot(jnp.where(held, local, n_held), n_held + 1,
                            dtype=jnp.int32)[..., :n_held]          # (T,K,E)

    if dropless:
        comb = jnp.einsum("tke,tk->te", onehot.astype(jnp.float32),
                          gate_vals)                               # (T, E)
        out = _experts(params, jnp.broadcast_to(xt, (n_held, t, d)), kind)
        y = jnp.einsum("te,etd->td", comb.astype(x.dtype), out)
        pairs = held.sum(-1)
    else:
        # GShard capacity rounds UP: floor would truncate the whole
        # capacity_factor slack at small per-block token counts (e.g.
        # t=4, k=2, E=4, cf=1.25 → floor(2.5)=2 drops tokens that the
        # 1.25 factor exists to keep, making quantized-vs-dense logits
        # diverge discontinuously whenever a router prob moves a token
        # across the cutoff).
        capacity = max(1, math.ceil(t * top_k * capacity_factor / n_experts))
        capacity = min(capacity, t)
        # position of each (token, k) within its expert's capacity buffer
        flatoh = onehot.reshape(t * top_k, n_held)
        pos = jnp.cumsum(flatoh, axis=0) * flatoh - 1              # (T*K, E)
        pos = pos.reshape(t, top_k, n_held)
        within = (pos * onehot).sum(-1)                            # (T, K)
        keep = held & (within < capacity) & (within >= 0)

        # dispatch (T, E, C) / combine (T, E, C) — accumulated over the K
        # routing slots to avoid materializing a (T, K, E, C) tensor (a
        # 12 GB blow-up for moonshot-sized cells).
        disp = jnp.zeros((t, n_held, capacity), x.dtype)
        comb = jnp.zeros((t, n_held, capacity), x.dtype)
        for kk in range(top_k):
            oh_e = onehot[:, kk].astype(x.dtype)
            oh_c = jax.nn.one_hot(jnp.where(keep[:, kk], within[:, kk],
                                            capacity), capacity + 1,
                                  dtype=x.dtype)[..., :-1]
            d_k = oh_e[:, :, None] * oh_c[:, None, :] \
                * keep[:, kk, None, None].astype(x.dtype)
            disp = disp + d_k
            comb = comb + d_k * gate_vals[:, kk, None, None].astype(x.dtype)

        ein = jnp.einsum("tec,td->ecd", disp, xt)                  # (E, C, D)
        out = _experts(params, ein, kind)
        y = jnp.einsum("tec,ecd->td", comb, out)
        pairs = keep.sum(-1)

    # load-balancing aux loss (Switch): E · Σ_e f_e · p_e, f_e the share
    # of tokens that chose expert e
    density = (jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.float32)
               .sum(1) > 0).astype(jnp.float32).mean(0)             # (E,)
    aux = n_experts * jnp.sum(density * probs.mean(0))
    return (y.reshape(b, s, d), aux,
            pairs.astype(jnp.int32).reshape(b, s))
