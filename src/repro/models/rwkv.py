"""RWKV-6 ("Finch") block: attention-free time mix with data-dependent
per-channel decay, plus the squared-ReLU channel mix.

Two equivalent sequence-mix implementations:
* ``wkv_scan``    — per-step recurrence (the oracle; also the decode path).
* ``wkv_chunked`` — chunkwise-parallel form (intra-chunk matmuls + one state
  carry per chunk): the TPU-friendly training path. Per-channel log-decays
  factorize the inter-position decay exp(b_{i-1} − b_j) into q·k form; with
  the per-step log-decay clamped to ≥ −2 and chunk 32, every intermediate
  stays finite in f32 (documented trade-off in DESIGN.md — real RWKV allows
  faster decay; tests verify chunked == scan in the clamped regime).

Recurrence per head (state S: (D_k, D_v)):
    o_t = r_t · (S_{t-1} + diag(u)·k_tᵀ v_t)
    S_t = diag(w_t)·S_{t-1} + k_tᵀ v_t
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import _init_dense, init_norm, linear, rms_norm

LOG_W_MIN = -2.0   # per-step log-decay clamp (see module docstring)


def init_rwkv_timemix(key, d_model: int, head_dim: int = 64,
                      decay_lora: int = 64) -> Dict:
    ks = jax.random.split(key, 10)
    h = d_model // head_dim
    p = {
        # NB: the decay stream's lerp factor is keyed "d", not "w" — "w" is
        # reserved for matmul kernels (quantization/sharding conventions).
        "mu": {s: jnp.full((d_model,), 0.5, jnp.float32)
               for s in ("r", "k", "v", "g", "d")},
        "wr": _init_dense(ks[0], d_model, d_model),
        "wk": _init_dense(ks[1], d_model, d_model),
        "wv": _init_dense(ks[2], d_model, d_model),
        "wg": _init_dense(ks[3], d_model, d_model),
        "w_lora_a": _init_dense(ks[4], d_model, decay_lora, scale=0.01),
        "w_lora_b": _init_dense(ks[5], decay_lora, d_model, scale=0.01),
        "w0": jnp.full((d_model,), -1.0, jnp.float32),
        "u": jax.random.normal(ks[6], (h, head_dim), jnp.float32) * 0.1,
        "ln_x": init_norm(d_model),
        "wo": _init_dense(ks[7], d_model, d_model),
    }
    return p


def _token_shift(x, x_prev):
    """Shift right by one; position 0 sees ``x_prev`` (zeros at seq start)."""
    return jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)


def _mix_streams(p, x, xx):
    """RWKV6-style data-dependent lerp for the five streams."""
    dx = xx - x
    outs = {}
    for s in ("r", "k", "v", "g"):
        outs[s] = x + dx * p["mu"][s].astype(x.dtype)
    outs["w"] = x + dx * p["mu"]["d"].astype(x.dtype)
    # decay gets the extra data-dependent LoRA term (the "Finch" novelty)
    lora = jnp.tanh(linear(p["w_lora_a"], outs["w"]))
    outs["w_raw"] = p["w0"].astype(x.dtype) + linear(p["w_lora_b"], lora)
    return outs


def _heads(x, head_dim):
    b, s, d = x.shape
    return x.reshape(b, s, d // head_dim, head_dim).swapaxes(1, 2)


def wkv_scan(r, k, v, logw, u, s0):
    """Oracle/decode path. r/k/v/logw: (B, H, S, D); u: (H, D);
    s0: (B, H, D, D). Returns (o, s_final)."""
    def step(s, inp):
        rt, kt, vt, lwt = inp                           # (B, H, D)
        kv = kt[..., :, None] * vt[..., None, :]        # (B, H, D, D)
        o = jnp.einsum("bhd,bhdn->bhn", rt, s + u[None, :, :, None] * kv)
        s = jnp.exp(lwt)[..., None] * s + kv
        return s, o
    xs = tuple(a.swapaxes(0, 2).swapaxes(1, 2) for a in (r, k, v, logw))
    # now (S, B, H, D)
    s_fin, o = jax.lax.scan(step, s0, xs)
    return o.swapaxes(0, 1).swapaxes(1, 2), s_fin       # (B, H, S, D)


def wkv_chunked(r, k, v, logw, u, s0, chunk: int = 32):
    """Chunkwise-parallel WKV. Same signature as wkv_scan."""
    b, h, s, d = r.shape
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    n = s // chunk

    def per_chunk(state, inp):
        rc, kc, vc, lwc = inp                            # (B, H, L, D)
        bcs = jnp.cumsum(lwc, axis=2)                    # inclusive b_i
        b_prev = bcs - lwc                               # b_{i-1}
        q = rc * jnp.exp(b_prev)
        o_inter = jnp.einsum("bhid,bhdn->bhin", q, state)
        kx = kc * jnp.exp(-bcs)
        att = jnp.einsum("bhid,bhjd->bhij", q, kx)
        ii = jnp.arange(chunk)
        att = jnp.where(ii[:, None] > ii[None, :], att, 0.0)
        o_intra = jnp.einsum("bhij,bhjn->bhin", att, vc)
        cdiag = jnp.einsum("bhid,hd,bhid->bhi", rc, u, kc)
        o = o_inter + o_intra + cdiag[..., None] * vc
        kz = kc * jnp.exp(bcs[:, :, -1:, :] - bcs)
        state = jnp.exp(bcs[:, :, -1, :])[..., None] * state + \
            jnp.einsum("bhjd,bhjn->bhdn", kz, vc)
        return state, o

    def resh(a):                                          # (n, B, H, L, D)
        return a.reshape(b, h, n, chunk, d).swapaxes(0, 2).swapaxes(1, 2)

    xs = tuple(resh(a) for a in (r, k, v, logw))
    # remat per chunk: bounds backward residuals to one chunk's
    # (B,H,L,L)+(B,H,L,D) working set instead of all n chunks'
    s_fin, o = jax.lax.scan(jax.checkpoint(per_chunk), s0, xs)
    o = o.swapaxes(1, 2).swapaxes(0, 2).reshape(b, h, s, d)
    return o, s_fin


def rwkv_timemix(p, x, *, head_dim: int = 64, state: Optional[Dict] = None,
                 mode: str = "train", chunk: int = 32
                 ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    b, s, d = x.shape
    h = d // head_dim
    # train/prefill start a sequence: entry state is zeros by definition
    # (a reused serving side cache may hold a retired request's state, so
    # prefill must not read it). "chunk" is the prefill *continuation*: it
    # folds the carried token-shift row and wkv state across the chunk
    # boundary — the monolithic recurrence up to float reassociation of
    # the scan grouping (the measured "rwkv" agreement budget).
    seq_start = mode in ("train", "prefill") or state is None
    x_prev = jnp.zeros_like(x[:, 0]) if seq_start \
        else state["x_tm"].astype(x.dtype)
    xx = _token_shift(x, x_prev)
    mix = _mix_streams(p, x, xx)
    r = _heads(linear(p["wr"], mix["r"]), head_dim)
    k = _heads(linear(p["wk"], mix["k"]), head_dim)
    v = _heads(linear(p["wv"], mix["v"]), head_dim)
    g = jax.nn.silu(linear(p["wg"], mix["g"]))
    logw = jnp.clip(-jnp.exp(mix["w_raw"].astype(jnp.float32)),
                    LOG_W_MIN, -1e-4)
    logw = _heads(logw, head_dim)
    u = p["u"].astype(jnp.float32)

    s0 = jnp.zeros((b, h, head_dim, head_dim), jnp.float32) if seq_start \
        else state["wkv"]
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    if mode == "decode" or s == 1:
        o, s_fin = wkv_scan(rf, kf, vf, logw, u, s0)
    elif mode in ("train", "prefill", "chunk"):
        if s % chunk == 0:
            o, s_fin = wkv_chunked(rf, kf, vf, logw, u, s0, chunk)
        else:
            o, s_fin = wkv_scan(rf, kf, vf, logw, u, s0)
    o = o.swapaxes(1, 2).reshape(b, s, d).astype(x.dtype)
    o = rms_norm(p["ln_x"], o) * g
    out = linear(p["wo"], o)
    new_state = None
    if mode in ("prefill", "decode", "chunk"):
        new_state = {"x_tm": x[:, -1], "wkv": s_fin}
    return out, new_state


def init_rwkv_channelmix(key, d_model: int, d_ff: int) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mu": {s: jnp.full((d_model,), 0.5, jnp.float32) for s in ("k", "r")},
        "wk": _init_dense(k1, d_model, d_ff),
        "wv": _init_dense(k2, d_ff, d_model),
        "wr": _init_dense(k3, d_model, d_model),
    }


def rwkv_channelmix(p, x, *, state: Optional[Dict] = None,
                    mode: str = "train") -> Tuple[jnp.ndarray, Optional[Dict]]:
    seq_start = mode in ("train", "prefill") or state is None
    x_prev = jnp.zeros_like(x[:, 0]) if seq_start \
        else state["x_cm"].astype(x.dtype)
    xx = _token_shift(x, x_prev)
    dx = xx - x
    xk = x + dx * p["mu"]["k"].astype(x.dtype)
    xr = x + dx * p["mu"]["r"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(linear(p["wk"], xk)))
    out = jax.nn.sigmoid(linear(p["wr"], xr)) * linear(p["wv"], kk)
    new_state = {"x_cm": x[:, -1]} \
        if mode in ("prefill", "decode", "chunk") else None
    return out, new_state
