"""Mamba (S6 selective SSM) block — the Jamba hybrid's sequence mixer.

Training/prefill uses an associative scan over time on the diagonal SSM
recurrence  h_t = a_t ⊙ h_{t-1} + b_t  (a_t = exp(Δ_t·A), b_t = Δ_t·B_t·x_t),
O(log S) depth, sub-quadratic in sequence length. Decode is a single-step
state update (O(1) per token — why the hybrid runs the 500k-decode shape).

Shapes follow mamba-1: d_inner = expand·d_model, depthwise causal conv
(d_conv), data-dependent Δ/B/C, learned A (d_inner, d_state) and D skip.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import _init_dense, linear


def init_mamba(key, d_model: int, d_state: int = 16, d_conv: int = 4,
               expand: int = 2, dt_rank: Optional[int] = None) -> Dict:
    d_inner = expand * d_model
    dt_rank = dt_rank or max(1, d_model // 16)
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32)[None],
                 (d_inner, 1))
    return {
        "in_proj": _init_dense(ks[0], d_model, 2 * d_inner),
        "conv_w": jax.random.normal(ks[1], (d_conv, d_inner), jnp.float32)
        * (1.0 / jnp.sqrt(d_conv)),
        "conv_b": jnp.zeros((d_inner,), jnp.float32),
        "x_proj": _init_dense(ks[2], d_inner, dt_rank + 2 * d_state),
        "dt_proj": _init_dense(ks[3], dt_rank, d_inner),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (d_inner,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((d_inner,), jnp.float32),
        "out_proj": _init_dense(ks[5], d_inner, d_model),
    }


def _ssm_inputs(params, xc, dt_rank: int, d_state: int):
    """xc: (..., d_inner) post-conv. Returns (a, bx, c) per position."""
    dbc = linear(params["x_proj"], xc)
    dt = dbc[..., :dt_rank]
    b = dbc[..., dt_rank:dt_rank + d_state]
    c = dbc[..., dt_rank + d_state:]
    dt = jax.nn.softplus(linear(params["dt_proj"], dt)
                         + params["dt_bias"].astype(xc.dtype))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))          # (DI, N)
    a_t = jnp.exp(dt[..., None].astype(jnp.float32) * a)       # (..., DI, N)
    bx = (dt * xc)[..., None].astype(jnp.float32) * \
        b[..., None, :].astype(jnp.float32)                    # (..., DI, N)
    return a_t, bx, c


def _conv_train(params, x):
    """Depthwise causal conv over (B, S, DI)."""
    d_conv = params["conv_w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (d_conv - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] *
              params["conv_w"][i][None, None].astype(x.dtype)
              for i in range(d_conv))
    return out + params["conv_b"].astype(x.dtype)


def _chunk_scan(a_t, bx, h0):
    """h_t = a_t·h_{t-1} + bx_t over one chunk, given entry state h0.

    a_t, bx: (B, C, DI, N); h0: (B, DI, N). Associative scan within the
    chunk plus the decayed h0 contribution (cumprod of a via log-space).
    Returns (h_all (B, C, DI, N), h_last)."""
    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl
    _, h = jax.lax.associative_scan(comb, (a_t, bx), axis=1)
    cum = jnp.cumprod(a_t, axis=1)
    h = h + cum * h0[:, None]
    return h, h[:, -1]


def mamba(params, x: jnp.ndarray, *, d_state: int = 16,
          state: Optional[Dict] = None, mode: str = "train",
          chunk: int = 512
          ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: (B, S, D) for train/prefill; (B, 1, D) for decode.

    Train/prefill processes the sequence in ``chunk``-sized pieces
    (lax.scan over chunks, associative scan within) so the (B, C, DI, N)
    state tensor — not (B, S, DI, N) — bounds the working set.
    """
    b, s, d = x.shape
    d_inner = params["dt_bias"].shape[0]
    dt_rank = params["dt_proj"]["w"].shape[0]
    xz = linear(params["in_proj"], x)
    xr, z = xz[..., :d_inner], xz[..., d_inner:]

    if mode in ("train", "prefill"):
        xc = jax.nn.silu(_conv_train(params, xr))
        h0 = jnp.zeros((b, d_inner, d_state), jnp.float32)
        if s > chunk and s % chunk == 0:
            nc = s // chunk
            xc_r = xc.reshape(b, nc, chunk, d_inner).swapaxes(0, 1)

            def body(h_in, xc_c):
                a_t, bx, c = _ssm_inputs(params, xc_c, dt_rank, d_state)
                h_all, h_out = _chunk_scan(a_t, bx, h_in)
                yc = jnp.einsum("bsdn,bsn->bsd", h_all,
                                c.astype(jnp.float32))
                return h_out, yc.astype(x.dtype)

            # remat per chunk: the scan's backward otherwise stores the
            # (B, C, DI, N) f32 chunk-state residuals for every chunk —
            # tens of GB/chip at jamba scale
            h_last, ys = jax.lax.scan(jax.checkpoint(body), h0, xc_r)
            y = ys.swapaxes(0, 1).reshape(b, s, d_inner)
        else:
            a_t, bx, c = _ssm_inputs(params, xc, dt_rank, d_state)
            h_all, h_last = _chunk_scan(a_t, bx, h0)
            y = jnp.einsum("bsdn,bsn->bsd", h_all, c.astype(jnp.float32))
        y = y.astype(x.dtype) + xc * params["d_skip"].astype(x.dtype)
        new_state = None
        if mode == "prefill":
            d_conv = params["conv_w"].shape[0]
            new_state = {
                "h": h_last,                                    # (B, DI, N)
                "conv": xr[:, -(d_conv - 1):, :],
            }
    elif mode == "chunk":
        # partial-prefill continuation: the depthwise conv reads its left
        # context from the carried ``conv`` tail instead of zero padding,
        # and the associative scan enters at the carried ``h`` — the
        # monolithic prefill recurrence up to float reassociation of the
        # scan's chunk-split grouping, served under the measured "mamba"
        # agreement budget (see repro.serving.equivalence).
        d_conv = params["conv_w"].shape[0]
        xp = jnp.concatenate([state["conv"].astype(xr.dtype), xr], axis=1)
        conv = sum(xp[:, i:i + s, :] *
                   params["conv_w"][i][None, None].astype(xr.dtype)
                   for i in range(d_conv))
        xc = jax.nn.silu(conv + params["conv_b"].astype(xr.dtype))
        a_t, bx, c = _ssm_inputs(params, xc, dt_rank, d_state)
        h_all, h_last = _chunk_scan(a_t, bx, state["h"])
        y = jnp.einsum("bsdn,bsn->bsd", h_all, c.astype(jnp.float32))
        y = y.astype(x.dtype) + xc * params["d_skip"].astype(x.dtype)
        new_state = {"h": h_last,
                     "conv": xp[:, s:, :].astype(jnp.float32)}
    else:  # decode: one token
        d_conv = params["conv_w"].shape[0]
        conv_buf = jnp.concatenate([state["conv"], xr], axis=1)  # (B,dc,DI)
        xc = jnp.einsum("bcd,cd->bd", conv_buf.astype(jnp.float32),
                        params["conv_w"].astype(jnp.float32))
        xc = jax.nn.silu(xc + params["conv_b"]).astype(x.dtype)[:, None]
        a_t, bx, c = _ssm_inputs(params, xc, dt_rank, d_state)
        h = a_t[:, 0] * state["h"] + bx[:, 0]                  # (B, DI, N)
        y = jnp.einsum("bdn,bn->bd", h, c[:, 0].astype(jnp.float32))
        y = y[:, None].astype(x.dtype) + xc * params["d_skip"].astype(x.dtype)
        new_state = {"h": h, "conv": conv_buf[:, 1:]}

    out = jax.nn.silu(z) * y
    return linear(params["out_proj"], out), new_state


def init_mamba_state(batch: int, d_model: int, d_state: int = 16,
                     d_conv: int = 4, expand: int = 2) -> Dict:
    d_inner = expand * d_model
    return {"h": jnp.zeros((batch, d_inner, d_state), jnp.float32),
            "conv": jnp.zeros((batch, d_conv - 1, d_inner), jnp.float32)}
