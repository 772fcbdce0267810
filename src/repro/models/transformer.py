"""Block assembly: uniform decoder stacks, hybrid (Jamba) period stacks,
RWKV stacks, and the encoder-decoder wiring — all scan-over-layers with
stacked parameters (small HLO, fast SPMD partitioning) and optional remat.

Stacks with ``first_k_dense`` leading dense layers (DeepSeek-V3,
Moonlight) keep them under ``"lead"``, a list of blocks run before the
periods, beside ``"periods"``/``"list"`` in both the parameters and the
caches.

Block kinds:
  "a"    attention block   : x += attn(ln1(x)); x += ffn_or_moe(ln2(x))
  "m"    mamba block       : x += mamba(ln1(x)); x += ffn_or_moe(ln2(x))
  "rwkv" rwkv block        : x += timemix(ln1(x)); x += channelmix(ln2(x))
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models.layers import init_norm, rms_norm


# ---------------------------------------------------------------------------
# rope helper: per-position values, computed on the fly (no 500k tables)
# ---------------------------------------------------------------------------

def rope_values(positions: jnp.ndarray, rope_dim: int, theta: float,
                dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (S,) shared across the batch, or (B, S) per-row (paged
    decode). Returns cos/sin of shape ``positions.shape + (rope_dim//2,)``;
    the per-position multiply is identical either way, so a row at absolute
    position p gets bit-identical rotary values through both shapes."""
    inv = 1.0 / (theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                           / rope_dim))
    freqs = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def _rope_dim(cfg) -> int:
    return cfg.mla.rope_dim if cfg.mla is not None else cfg.head_dim


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg, kind: str, use_moe: bool,
               cross: bool = False) -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    po = cfg.norm_plus_one
    p: Dict[str, Any] = {"ln1": init_norm(d, plus_one=po),
                         "ln2": init_norm(d, plus_one=po)}
    if cross:
        p["xln"] = init_norm(d, plus_one=po)
        p["xattn"] = attn_lib.init_cross_attention(k4, cfg)
    if kind == "a":
        p["attn"] = attn_lib.init_attention(k1, cfg)
    elif kind == "m":
        m = cfg.mamba
        p["mixer"] = mamba_lib.init_mamba(
            k1, d, d_state=m.d_state, d_conv=m.d_conv, expand=m.expand,
            dt_rank=m.dt_rank)
    elif kind == "rwkv":
        p["tm"] = rwkv_lib.init_rwkv_timemix(k1, d, cfg.rwkv_head_dim)
        p["cm"] = rwkv_lib.init_rwkv_channelmix(k2, d, cfg.d_ff)
        return p
    else:
        raise ValueError(kind)
    if use_moe:
        m = cfg.moe
        d_exp = m.d_expert or cfg.d_ff
        p["moe"] = moe_lib.init_moe(k3, d, d_exp, m.held, cfg.ffn_kind,
                                    n_routed=m.n_experts,
                                    score_bias=m.scoring == "sigmoid")
        if m.n_shared:
            p["shared"] = ffn_lib.init_ffn(k2, d, m.n_shared * d_exp,
                                           cfg.ffn_kind)
    else:
        p["ffn"] = ffn_lib.init_ffn(k3, d, cfg.d_ff, cfg.ffn_kind)
    return p


def apply_block(p, x, *, cfg, kind: str, use_moe: bool, rope, mode: str,
                cache: Optional[dict], pos,
                enc_out: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Optional[dict], jnp.ndarray,
                           Optional[jnp.ndarray]]:
    """Returns (x, new_cache, moe_aux, moe_pairs): ``moe_pairs`` (B, S)
    counts each token's expert choices the held experts computed (None
    for a block without experts)."""
    aux = jnp.zeros((), jnp.float32)
    pairs = None
    if mode == "verify" and kind != "a":
        # the speculative multi-position verify scores every draft row as
        # if it were a lockstep decode step; recurrent mixers would need a
        # per-row state rewind to do that, so verify stays attention-only
        raise NotImplementedError(
            f"{mode!r} mode is not implemented for {kind!r} blocks")
    if kind == "rwkv":
        h, st_tm = rwkv_lib.rwkv_timemix(
            p["tm"], rms_norm(p["ln1"], x, plus_one=cfg.norm_plus_one),
            head_dim=cfg.rwkv_head_dim, chunk=cfg.rwkv_chunk,
            state=cache, mode=mode)
        x = x + h
        h, st_cm = rwkv_lib.rwkv_channelmix(
            p["cm"], rms_norm(p["ln2"], x, plus_one=cfg.norm_plus_one),
            state=cache, mode=mode)
        x = x + h
        new_cache = None
        if st_tm is not None:
            new_cache = {**st_tm, **(st_cm or {})}
        return x, new_cache, aux, pairs

    if kind == "a":
        h, new_cache = attn_lib.attention(
            p["attn"], rms_norm(p["ln1"], x, plus_one=cfg.norm_plus_one),
            cfg=cfg, rope=rope, mode=mode, cache=cache, pos=pos,
            block_tables=block_tables)
    else:  # mamba
        h, new_cache = mamba_lib.mamba(
            p["mixer"], rms_norm(p["ln1"], x, plus_one=cfg.norm_plus_one),
            d_state=cfg.mamba.d_state, state=cache, mode=mode,
            chunk=cfg.mamba_chunk)
    x = x + h
    x = shard_act(x, ("batch", None, None))
    if "xattn" in p:
        hx = attn_lib.cross_attention(
            p["xattn"], rms_norm(p["xln"], x, plus_one=cfg.norm_plus_one),
            enc_out, cfg=cfg)
        x = x + hx
    h2 = rms_norm(p["ln2"], x, plus_one=cfg.norm_plus_one)
    if use_moe:
        # dropless in every serving mode: with no capacity competition each
        # token's expert mix is independent of the batch and of the chunk
        # split, so prefill, chunks, decode and speculative verify route a
        # token alike (and as a reference that drops nothing does).
        # Training keeps the GShard capacity.
        m = cfg.moe
        h_moe, aux, pairs = moe_lib.moe_apply(
            p["moe"], h2, n_experts=m.n_experts, top_k=m.top_k,
            kind=cfg.ffn_kind, capacity_factor=m.capacity_factor,
            dropless=mode != "train", scoring=m.scoring,
            norm_topk=m.norm_topk, routed_scale=m.routed_scale,
            first_held=m.first_held, n_held=m.held)
        if "shared" in p:
            h_moe = h_moe + ffn_lib.ffn(p["shared"], h2, cfg.ffn_kind)
        h2 = h_moe
    else:
        h2 = ffn_lib.ffn(p["ffn"], h2, cfg.ffn_kind)
    x = x + h2
    x = shard_act(x, ("batch", None, None))
    return x, new_cache, aux, pairs


# ---------------------------------------------------------------------------
# layer pattern
# ---------------------------------------------------------------------------

def layer_plan(cfg) -> Tuple[Tuple[str, bool], ...]:
    """(kind, use_moe) per layer of one stack period. The
    ``cfg.first_k_dense`` leading dense attention layers come before the
    periods and are not part of the plan."""
    if cfg.rwkv:
        pattern = (("rwkv", False),)
    elif cfg.block_pattern is not None:
        period = len(cfg.block_pattern)
        moe_every = cfg.moe.every if cfg.moe else 0
        pattern = tuple(
            (k, bool(moe_every) and (i % moe_every == moe_every - 1))
            for i, k in enumerate(cfg.block_pattern))
        assert (cfg.n_layers - cfg.first_k_dense) % period == 0
    elif cfg.moe is not None and cfg.moe.every > 1:
        ev = cfg.moe.every
        pattern = tuple(("a", i % ev == ev - 1) for i in range(ev))
    elif cfg.moe is not None:
        pattern = (("a", True),)
    else:
        pattern = (("a", False),)
    return pattern


def n_periods(cfg) -> int:
    return (cfg.n_layers - cfg.first_k_dense) // len(layer_plan(cfg))


def n_moe_layers(cfg) -> int:
    """Layers with experts."""
    return n_periods(cfg) * sum(1 for _, moe in layer_plan(cfg) if moe)


# ---------------------------------------------------------------------------
# stacked init / apply
# ---------------------------------------------------------------------------

def unstack_stack(stack: Dict[str, Any], periods: int) -> Dict[str, Any]:
    """{"periods": stacked} → {"list": [...]} (for real-quantized serving,
    where QuantizedTensor leaves cannot be scanned over)."""
    if "list" in stack:
        return stack
    out = {k: v for k, v in stack.items() if k != "periods"}
    out["list"] = [jax.tree_util.tree_map(lambda a: a[i], stack["periods"])
                   for i in range(periods)]
    return out


def stack_periods(stack: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`unstack_stack`: {"list": [...]} → {"periods":
    stacked} (fp leaves only)."""
    if "list" not in stack:
        return stack
    out = {k: v for k, v in stack.items() if k != "list"}
    out["periods"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                            *stack["list"])
    return out


def init_stack(key, cfg) -> Dict[str, Any]:
    pattern = layer_plan(cfg)
    periods = n_periods(cfg)
    keys = jax.random.split(key, periods)

    def one_period(k):
        ks = jax.random.split(k, len(pattern))
        return {f"b{i}": init_block(ks[i], cfg, kind, moe,
                                    cross=cfg.is_encdec)
                for i, (kind, moe) in enumerate(pattern)}

    if cfg.scan_layers and periods > 1:
        out = {"periods": jax.vmap(one_period)(keys)}
    else:
        out = {"list": [one_period(k) for k in keys]}
    if cfg.first_k_dense:
        lead_keys = jax.random.split(jax.random.fold_in(key, 1),
                                     cfg.first_k_dense)
        out["lead"] = [init_block(k, cfg, "a", False) for k in lead_keys]
    return out


def init_layer_cache(cfg, batch: int, max_len: int, kind: str,
                     quantize_kv: bool = False, dtype=jnp.bfloat16):
    if kind == "rwkv":
        d = cfg.d_model
        h = d // cfg.rwkv_head_dim
        return {"x_tm": jnp.zeros((batch, d), dtype),
                "x_cm": jnp.zeros((batch, d), dtype),
                "wkv": jnp.zeros((batch, h, cfg.rwkv_head_dim,
                                  cfg.rwkv_head_dim), jnp.float32)}
    if kind == "m":
        m = cfg.mamba
        return mamba_lib.init_mamba_state(batch, cfg.d_model, m.d_state,
                                          m.d_conv, m.expand)
    if cfg.mla is not None:
        return attn_lib.init_mla_cache(batch, max_len, cfg, dtype)
    return attn_lib.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                  cfg.head_dim, dtype, quantize_kv,
                                  cfg.window)


def init_cache(cfg, batch: int, max_len: int, quantize_kv: bool = False,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    pattern = layer_plan(cfg)
    periods = n_periods(cfg)

    def one_period():
        return {f"b{i}": init_layer_cache(cfg, batch, max_len, kind,
                                          quantize_kv, dtype)
                for i, (kind, _) in enumerate(pattern)}

    if cfg.scan_layers and periods > 1:
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (periods,) + x.shape),
            one_period())
        caches = {"periods": stacked}
    else:
        caches = {"list": [one_period() for _ in range(periods)]}
    if cfg.first_k_dense:
        caches["lead"] = [init_layer_cache(cfg, batch, max_len, "a",
                                           quantize_kv, dtype)
                          for _ in range(cfg.first_k_dense)]
    caches["pos"] = jnp.zeros((), jnp.int32)
    return caches


def apply_stack(stack, x, *, cfg, rope, mode: str, caches, pos,
                enc_out: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Any, jnp.ndarray,
                           Optional[jnp.ndarray]]:
    """Run all layers. Returns (x, new_caches, moe_aux_mean, moe_pairs):
    ``moe_pairs`` (B, S) sums :func:`apply_block`'s counts over the layers
    (None for a stack without experts)."""
    pattern = layer_plan(cfg)
    kw = dict(cfg=cfg, rope=rope, mode=mode, pos=pos, enc_out=enc_out,
              block_tables=block_tables)

    def add(total, part):
        return part if total is None else (
            total if part is None else total + part)

    def run_period(pp, xin, pcache):
        aux_sum = jnp.zeros((), jnp.float32)
        pairs = None
        new_c = {}
        for i, (kind, moe) in enumerate(pattern):
            c_in = None if pcache is None else pcache.get(f"b{i}")
            xin, c_out, aux, pairs_i = apply_block(
                pp[f"b{i}"], xin, kind=kind, use_moe=moe, cache=c_in, **kw)
            aux_sum += aux
            pairs = add(pairs, pairs_i)
            if c_out is not None:
                new_c[f"b{i}"] = c_out
        return xin, (new_c if new_c else None), aux_sum, pairs

    def run_lead(lp, xin, c_in):
        xin, c_out, _, _ = apply_block(lp, xin, kind="a", use_moe=False,
                                       cache=c_in, **kw)
        return xin, c_out

    needs_cache = mode in ("prefill", "decode", "chunk", "verify")
    lead_caches = []
    runl = jax.checkpoint(run_lead) if cfg.remat else run_lead
    for i, lp in enumerate(stack.get("lead", ())):
        pc = caches["lead"][i] if needs_cache else None
        x, new_c = runl(lp, x, pc)
        lead_caches.append(new_c)
    if "periods" in stack:
        pcaches = caches["periods"] if needs_cache else None

        def body(xc, per):
            pp, pc = per
            xout, new_c, aux, pairs = run_period(
                pp, xc, pc if needs_cache else None)
            if not needs_cache:
                new_c = 0.0
            elif new_c is None:
                new_c = pc
            return xout, (new_c, aux, pairs)

        if cfg.remat:
            body = jax.checkpoint(body)
        periods = n_periods(cfg)
        xs = (stack["periods"],
              pcaches if pcaches is not None
              else jnp.zeros((periods,), jnp.float32))
        x, (new_caches, auxs, pairs) = jax.lax.scan(body, x, xs)
        aux = jnp.mean(auxs)
        if pairs is not None:
            pairs = pairs.sum(0)
        out_caches = {"periods": new_caches} if needs_cache else None
    else:
        new_list = []
        aux_total = jnp.zeros((), jnp.float32)
        pairs = None
        runp = jax.checkpoint(run_period) if cfg.remat else run_period
        for i, pp in enumerate(stack["list"]):
            pc = caches["list"][i] if needs_cache else None
            x, new_c, aux_i, pairs_i = runp(pp, x, pc)
            aux_total += aux_i
            pairs = add(pairs, pairs_i)
            new_list.append(new_c if new_c is not None else pc)
        aux = aux_total / max(len(stack["list"]), 1)
        out_caches = {"list": new_list} if needs_cache else None
    if needs_cache and lead_caches:
        out_caches["lead"] = lead_caches
    return x, out_caches, aux, pairs


# ---------------------------------------------------------------------------
# encoder (seamless: bidirectional over stubbed frame embeddings)
# ---------------------------------------------------------------------------

def init_encoder(key, cfg) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.encoder_layers)

    def one(k):
        ks = jax.random.split(k, 3)
        return {"ln1": init_norm(cfg.d_model),
                "attn": attn_lib.init_cross_attention(ks[0], cfg),  # full MHA
                "ln2": init_norm(cfg.d_model),
                "ffn": ffn_lib.init_ffn(ks[1], cfg.d_model, cfg.d_ff,
                                        cfg.ffn_kind)}

    return {"layers": jax.vmap(one)(keys),
            "final_norm": init_norm(cfg.d_model)}


def apply_encoder(enc, frames, *, cfg) -> jnp.ndarray:
    """frames: (B, T, d) precomputed frontend embeddings (stub)."""
    s = frames.shape[1]
    cos, sin = rope_values(jnp.arange(s), cfg.head_dim, cfg.rope_theta)

    def body(x, pp):
        h = rms_norm(pp["ln1"], x)
        h = attn_lib.cross_attention(pp["attn"], h, h, cfg=cfg)
        x = x + h
        h = ffn_lib.ffn(pp["ffn"], rms_norm(pp["ln2"], x), cfg.ffn_kind)
        return x + h, 0.0

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, frames, enc["layers"])
    return rms_norm(enc["final_norm"], x)


