"""Model builder: ArchConfig → init / train_loss / prefill / decode_step.

The returned ``LM`` object is the single interface used by the trainer, the
serving engine and the quantization pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act, shard_logits
from repro.models.layers import init_embedding, rms_norm, init_norm
from repro.models.transformer import (apply_encoder, apply_stack, init_cache,
                                      init_encoder, init_stack, rope_values,
                                      _rope_dim)


def arch_features(cfg) -> Tuple[str, ...]:
    """Sequence-mixer features that make chunked prefill
    tolerance-equivalent (rather than bit-identical) to the monolithic
    path. Keys match ``repro.serving.equivalence.AGREEMENT_BUDGETS`` and
    compose multiplicatively there when features stack (e.g. mixtral is
    ``("sliding_window", "moe")``). An empty tuple means a plain-attention
    dense stack whose chunked prefill is exact."""
    from repro.models.transformer import layer_plan
    plan = layer_plan(cfg)
    feats = []
    if cfg.mla is not None:
        feats.append("mla")
    if cfg.window:
        feats.append("sliding_window")
    if any(moe for _, moe in plan):
        feats.append("moe")
    if any(kind == "m" for kind, _ in plan):
        feats.append("mamba")
    if any(kind == "rwkv" for kind, _ in plan):
        feats.append("rwkv")
    return tuple(feats)


def _xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Sharded-vocab-friendly mean cross-entropy (one-hot dot, fp32)."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    oh = jax.nn.one_hot(labels.clip(0), lg.shape[-1], dtype=jnp.float32)
    ll = jnp.einsum("...v,...v->...", oh, lg)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


@dataclasses.dataclass
class LM:
    cfg: Any

    # ----------------------------------------------------------------- init
    def init(self, key) -> Dict[str, Any]:
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {"embedding": init_embedding(k1, self.cfg.vocab,
                                         self.cfg.d_model, dt),
             "stack": init_stack(k2, self.cfg),
             "final_norm": init_norm(self.cfg.d_model,
                                     plus_one=self.cfg.norm_plus_one)}
        if not self.cfg.tie_embeddings:
            p["lm_head"] = {
                "w": jax.random.normal(
                    k3, (self.cfg.d_model, self.cfg.vocab), dt) * 0.02}
        if self.cfg.is_encdec:
            p["encoder"] = init_encoder(k4, self.cfg)
        return p

    # ------------------------------------------------------------- forward
    def _embed(self, params, tokens):
        x = params["embedding"]["embedding"][tokens]
        if self.cfg.emb_scale:
            x = x * jnp.sqrt(float(self.cfg.d_model)).astype(x.dtype)
        return shard_act(x, ("batch", None, None))

    def _logits(self, params, x):
        x = rms_norm(params["final_norm"], x, plus_one=self.cfg.norm_plus_one)
        if self.cfg.tie_embeddings:
            w = params["embedding"]["embedding"]
            logits = x @ w.T.astype(x.dtype)
        else:
            from repro.models.layers import linear
            logits = linear(params["lm_head"], x)
        return shard_logits(logits)

    def _encode(self, params, batch):
        if not self.cfg.is_encdec:
            return None
        return apply_encoder(params["encoder"], batch["enc_frames"],
                             cfg=self.cfg)

    def forward(self, params, batch, mode: str = "train",
                caches: Optional[dict] = None
                ) -> Tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
        """Returns (logits, new caches, MoE aux loss)."""
        logits, caches, aux, _ = self._forward(params, batch, mode, caches)
        return logits, caches, aux

    def _forward(self, params, batch, mode: str, caches: Optional[dict]):
        """:meth:`forward`, plus the token-expert pairs the held experts
        served over all MoE layers (an int32 scalar; paged rows without
        blocks, retired or admitting, carry no token and count nothing;
        None without experts)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        block_tables = caches.get("block_tables") \
            if caches is not None else None
        if mode == "decode":
            pos = caches["pos"]
            # contiguous caches keep ONE scalar clock for the whole batch;
            # paged caches keep a per-slot length vector, so each row gets
            # its own absolute position (rope shapes follow suit)
            positions = pos[None] if pos.ndim == 0 else pos[:, None]
        elif mode == "chunk":
            # partial-prefill continuation: the cache clock is the chunk's
            # start offset; rows live at absolute positions pos..pos+s-1
            pos = caches["pos"]
            positions = pos + jnp.arange(s)
        elif mode == "verify":
            # speculative multi-position verify: ``pos`` is the paged
            # per-slot length vector; row (b, j) sits at absolute
            # position pos[b] + j
            pos = caches["pos"]
            positions = pos[:, None] + jnp.arange(s)
        else:
            pos = jnp.zeros((), jnp.int32)
            positions = jnp.arange(s)
        rope = rope_values(positions, _rope_dim(self.cfg),
                           self.cfg.rope_theta)
        x = self._embed(params, tokens)
        enc_out = batch.get("enc_out")
        if enc_out is None:
            enc_out = self._encode(params, batch)
        x, new_caches, aux, pairs = apply_stack(
            params["stack"], x, cfg=self.cfg, rope=rope, mode=mode,
            caches=caches, pos=pos, enc_out=enc_out,
            block_tables=block_tables)
        if new_caches is not None:
            new_caches["pos"] = pos + s
            if block_tables is not None:
                new_caches["block_tables"] = block_tables
            if enc_out is not None:
                new_caches["enc_out"] = enc_out
        if pairs is not None:
            if block_tables is not None:
                pairs = pairs * (block_tables[:, :1] != 0)
            pairs = pairs.sum().astype(jnp.int32)
        logits = self._logits(params, x)
        return logits, new_caches, aux, pairs

    # --------------------------------------------------------------- train
    def train_loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        logits, _, aux = self.forward(params, batch, mode="train")
        loss = _xent(logits, batch["labels"])
        total = loss + 0.01 * aux
        return total, {"xent": loss, "moe_aux": aux}

    # --------------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int,
                   quantize_kv: bool = False) -> dict:
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        c = init_cache(self.cfg, batch, max_len, quantize_kv, dt)
        if self.cfg.is_encdec:
            enc_len = max(1, max_len // self.cfg.enc_ratio)
            c["enc_out"] = jnp.zeros((batch, enc_len, self.cfg.d_model), dt)
        return c

    def prefill(self, params, batch, caches, with_pairs: bool = False):
        """Returns (last-token logits, caches), and with ``with_pairs``
        the launch's held expert-token pairs (see :meth:`_forward`)."""
        logits, caches, _, pairs = self._forward(params, batch, "prefill",
                                                 caches)
        return (logits[:, -1], caches) + ((pairs,) if with_pairs else ())

    def prefill_chunk(self, params, batch, caches, with_pairs: bool = False):
        """Consume the next ``s`` prompt tokens of a partial prefill
        (returns as :meth:`prefill`).

        ``caches["pos"]`` is the chunk's start offset (0 for a fresh cache);
        the chunk attends over the already-written cache prefix plus itself,
        so feeding a prompt through this in any chunk split yields the same
        cache and last-token logits as one :meth:`prefill` call, bit-exact.
        The offset is traced, not baked in: the contiguous scheduler calls
        this at its shared clock, the paged backend at each slot's own
        prompt offset (including continuations over a gathered shared
        prefix) — one trace per chunk width covers both.
        Single-token chunks are padded to two rows internally: XLA lowers a
        one-row gemm as a matvec whose accumulation order differs from the
        monolithic prefill's, and the dummy row (whose cache write lands one
        past the clock, always overwritten before any masked-in read) is the
        cheapest way to stay on the gemm path. Stacks with recurrent state
        or MoE routing skip the pad — a dummy row would fold into the
        carried state / compete for expert capacity and change real
        outputs; those stacks serve under a measured agreement budget
        rather than bit-identity anyway (see repro.serving.equivalence).
        """
        toks = batch["tokens"]
        pad_ok = not any(f in ("moe", "mamba", "rwkv")
                         for f in arch_features(self.cfg))
        singleton = toks.shape[1] == 1 and pad_ok
        if singleton:
            p0 = caches["pos"]
            toks = jnp.concatenate([toks, toks[:, -1:]], axis=1)
        logits, caches, _, pairs = self._forward(params, {"tokens": toks},
                                                 "chunk", caches)
        if singleton:
            caches["pos"] = p0 + 1
        out = (logits[:, 0] if singleton else logits[:, -1]), caches
        return out + ((pairs,) if with_pairs else ())

    def moe_rows_per_token(self) -> int:
        """Rows the held experts' matmuls process per token of a serving
        launch, over all layers (dropless: each held expert runs every
        token); 0 for a stack without experts."""
        from repro.models.transformer import n_moe_layers
        moe = self.cfg.moe
        return n_moe_layers(self.cfg) * moe.held if moe else 0

    def arch_features(self) -> Tuple[str, ...]:
        """See :func:`arch_features`."""
        return arch_features(self.cfg)

    def supports_chunked_prefill(self) -> bool:
        """Every decoder-only stack has a chunk-continuation path: plain
        dense attention is bit-exact; MLA / sliding-window / MoE /
        recurrent mixers serve under their measured per-architecture
        agreement budgets (``repro.serving.equivalence``). Only
        encoder-decoder models (round-only scheduling) lack one."""
        return not self.cfg.is_encdec

    def chunked_prefill_exact(self) -> bool:
        """True when chunked prefill reproduces the monolithic path
        bit-for-bit (plain-attention dense stacks)."""
        return self.supports_chunked_prefill() \
            and not arch_features(self.cfg)

    def has_recurrent_state(self) -> bool:
        """True when the cache carries recurrent (non-positional) state —
        mamba conv/ssm carries or rwkv token-shift/wkv carries. The
        serving side-cache allocator must not reuse such caches across
        admissions (stale state is not masked out the way stale KV rows
        are)."""
        feats = arch_features(self.cfg)
        return "mamba" in feats or "rwkv" in feats

    def decode_step(self, params, tokens, caches, with_pairs: bool = False):
        """tokens: (B, 1) — one new token per sequence (returns as
        :meth:`prefill`)."""
        batch = {"tokens": tokens, "enc_out": caches.get("enc_out")}
        logits, caches, _, pairs = self._forward(params, batch, "decode",
                                                 caches)
        return (logits[:, -1], caches) + ((pairs,) if with_pairs else ())

    def verify_step(self, params, tokens, caches
                    ) -> Tuple[jnp.ndarray, dict]:
        """tokens: (B, S) — S-token runs written and scored at per-slot
        absolute positions ``pos[b] .. pos[b]+S-1`` on the paged cache.

        Returns ALL S per-position logits ``(B, S, vocab)``: row ``j``
        conditions on everything through position ``pos[b]+j``, so it is
        exactly the logits a lockstep decode step would produce there —
        the speculative verifier consumes every row (unlike prefill /
        decode, which return only the last)."""
        batch = {"tokens": tokens, "enc_out": caches.get("enc_out")}
        logits, caches, _ = self.forward(params, batch, mode="verify",
                                         caches=caches)
        return logits, caches

    # ---------------------------------------------------------------- specs
    def param_shapes(self, key=None) -> Any:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)


def build_model(cfg) -> LM:
    return LM(cfg=cfg)
