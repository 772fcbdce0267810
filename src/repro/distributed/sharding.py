"""Logical-axis sharding: the single place where model dimensions meet mesh
axes.

Model code annotates activations with *logical* axis names
(``shard_act(x, ("batch", "seq", "embed"))``); parameters get specs from
name/shape rules (``make_param_shardings``). The mapping logical→mesh lives
in an ``AxisRules`` table so the same model lowers on a laptop (trivial mesh),
a 256-chip pod, or the 512-chip 2-pod production mesh.

Defaults implement the MaxText-standard regime for this scale:
* DP over ('pod', 'data')   — batch dim
* TP over 'model'           — heads / ff / vocab / experts
* FSDP (ZeRO-3) over 'data' — every parameter's non-TP dim
* SP over 'data'            — long-context KV/state sequence dim

Divisibility-aware: a dim is only assigned a mesh axis when the axis size
divides it (e.g. mixtral's 8 experts on a 16-way 'model' axis fall back to
FSDP-only and the expert ffn dim takes TP instead).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import compat
from repro.quant.qtypes import QuantizedTensor


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis name → tuple of candidate mesh axes (first that fits)."""
    rules: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
        ("batch",        (("pod", "data"), ("data",), None)),
        ("seq",          (None,)),
        ("seq_shard",    (("data",), None)),           # SP for long context
        ("embed",        (None,)),
        ("heads",        (("model",), None)),
        ("kv_heads",     (("model",), None)),
        ("seq_model",    (("model",), None)),
        ("head_dim",     (None,)),
        ("ff",           (("model",), None)),
        ("vocab",        (("model",), None)),
        ("experts",      (("model",), None)),
        ("expert_ff",    (("model",), None)),
        ("fsdp",         (("data",), None)),
        ("conv",         (None,)),
        ("state",        (None,)),
    )

    def lookup(self, name: str) -> Tuple:
        for k, v in self.rules:
            if k == name:
                return v
        return (None,)


_STATE = threading.local()


def set_axis_rules(rules: Optional[AxisRules]):
    _STATE.rules = rules


def _get_rules() -> AxisRules:
    return getattr(_STATE, "rules", None) or AxisRules()


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Usable (non-Manual) axis sizes; works for Mesh and AbstractMesh.

    Inside a shard_map, manual axes (e.g. 'pod' in the compressed-gradient
    step) must not appear in sharding constraints — the per-shard program
    only sees the remaining auto axes.
    """
    manual = jax.sharding.AxisType.Manual
    return {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types) if t != manual}


def logical_to_mesh(logical: Sequence[Optional[str]], shape: Sequence[int],
                    mesh: Mesh, rules: Optional[AxisRules] = None) -> P:
    """Resolve logical axes to a PartitionSpec, honouring divisibility and
    never assigning one mesh axis twice."""
    rules = rules or _get_rules()
    sizes = _mesh_axis_sizes(mesh)
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        assigned = None
        if name is not None:
            for cand in rules.lookup(name):
                if cand is None:
                    break
                cand_t = cand if isinstance(cand, tuple) else (cand,)
                if any(c not in sizes for c in cand_t):
                    continue
                if any(c in used for c in cand_t):
                    continue
                total = int(np.prod([sizes[c] for c in cand_t]))
                if dim % total == 0:
                    assigned = cand_t if len(cand_t) > 1 else cand_t[0]
                    used.update(cand_t)
                    break
        out.append(assigned)
    return P(*out)


def shard_act(x: jax.Array, logical: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None) -> jax.Array:
    """Annotate an activation with a sharding constraint if a mesh is active.

    Outside a mesh context (unit tests, single-device smoke runs) this is an
    identity — model code stays mesh-agnostic.
    """
    mesh = mesh or _current_mesh()
    if mesh is None or getattr(mesh, "empty", True) or mesh.size == 1:
        return x
    spec = logical_to_mesh(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def _current_mesh():
    return compat.current_mesh()


def shard_logits(logits: jax.Array) -> jax.Array:
    """LM-head logits: vocab-sharded over 'model' when divisible (the
    matmul-natural layout from the sharded embedding), otherwise
    sequence-sharded over 'model' — never replicate a (B, S, V) f32 tensor
    (a 200 GB/chip blow-up for 256k-vocab non-divisible models)."""
    mesh = _current_mesh()
    if mesh is None or getattr(mesh, "empty", True) or mesh.size == 1:
        return logits
    sizes = _mesh_axis_sizes(mesh)
    tp = sizes.get("model", 1)
    if logits.shape[-1] % tp == 0:
        return shard_act(logits, ("batch", None, "vocab"), mesh)
    if logits.ndim == 3 and logits.shape[1] % tp == 0:
        return shard_act(logits, ("batch", "seq_model", None), mesh)
    return shard_act(logits, ("batch", None, None), mesh)


# ---------------------------------------------------------------------------
# Parameter sharding rules (path/shape based)
# ---------------------------------------------------------------------------
# Conventions (repro.models):
#   dense kernels    {"<proj>": {"w": (in, out)}}         — leaf name "w"
#   expert banks     {"moe": {"wi": {"w": (E, in, out)}}} — "moe" in path
#   embeddings       {"embedding": (V, d)}; lm head {"lm_head": {"w": (d, V)}}
#   scanned stacks prepend one period dim ("periods" in path)
#
# Megatron-style placement: column-parallel projections put TP on the out
# dim, row-parallel on the in dim; everything else gets FSDP on its largest
# eligible dim. Divisibility fallbacks in logical_to_mesh handle the rest
# (e.g. mixtral's 8 experts on model=16 fall back to expert-ff TP).

_ROW_PARALLEL = {"wo", "wdown", "out_proj", "w_lora_b", "wv_cm"}
_TP = "heads_flat"   # resolves to 'model'

_PARAM_RULES = AxisRules(rules=AxisRules().rules + (
    ("heads_flat", (("model",), None)),
))


def _pad(spec: Tuple, rank: int) -> Tuple:
    """Prepend Nones for the stack dim(s) so spec matches rank."""
    return (None,) * (rank - len(spec)) + spec if len(spec) < rank else spec


def _kernel_rules(path: Tuple[str, ...]) -> Tuple[Optional[Tuple], bool]:
    """``(spec, transposes)`` for a kernel ``w`` at ``path``: its logical
    axes in (in, out) order (``(E, in, out)`` for an expert bank), or None
    to replicate; ``transposes`` is False where the spec does not follow
    the kernel's orientation (the decay LoRA's)."""
    parent = path[-2] if len(path) >= 2 else ""
    if parent == "w" or parent == "":
        return None, False
    if parent in ("w_lora_a", "w_lora_b"):
        # rwkv decay LoRA: ~0.26 M params/layer — replicating them and
        # duplicating the tiny matmul removes a (B,S,d) psum + the
        # surrounding reshard per layer
        return ("fsdp", None), False
    if "lm_head" in path:
        return ("fsdp", "vocab"), True
    if "moe" in path:                          # expert bank (E, in, out)
        return ("experts", "fsdp", "expert_ff"), True
    key = parent
    if key == "wv" and "cm" in path:
        key = "wv_cm"                          # rwkv channel-mix down-proj
    return ((_TP, "fsdp") if key in _ROW_PARALLEL else ("fsdp", _TP)), True


def param_sharding_rules(path: Tuple[str, ...], leaf: Any) -> Tuple:
    """Logical axes for a parameter leaf."""
    rank = len(getattr(leaf, "shape", ()))
    name = path[-1] if path else ""
    if name == "embedding":
        return _pad(("vocab", "fsdp"), rank)
    if name == "conv_w":
        return _pad((None, "ff"), rank)
    if name == "w":
        spec, _ = _kernel_rules(path)
        return (None,) * rank if spec is None else _pad(spec, rank)
    # vectors / norm gains / lerp factors / u bonus: replicate
    return (None,) * rank


def make_param_shardings(mesh: Mesh, params_shape: Any,
                         rules: Optional[AxisRules] = None) -> Any:
    """NamedSharding pytree for a params pytree (of arrays or
    ShapeDtypeStructs)."""
    rules = rules or _PARAM_RULES

    def one(keypath, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in keypath)
        logical = param_sharding_rules(path, leaf)
        spec = logical_to_mesh(logical, leaf.shape, mesh, rules)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def quantized_tensor_shardings(mesh: Mesh, path: Tuple[str, ...], qt
                               ) -> Tuple[NamedSharding, NamedSharding]:
    """(codes, scale) NamedShardings for a pipeline ``QuantizedTensor``.

    ``path`` is the source kernel's tree path (…, parent, "w"). Codes are
    (out, in)-major, so they take the kernel's rules transposed (Megatron
    placement, divisibility fallbacks included); scales, (out, groups),
    take the out-dim entry alone. The pipeline's 2-D carriers collapse
    any stacked dims (experts, scan periods, conv taps) into the row dim,
    so only the spec's trailing (in, out) entries apply — non-divisible
    collapsed dims fall back to replication inside ``logical_to_mesh``.
    """
    spec, transposes = _kernel_rules(path)
    if spec is None:
        logical_q, logical_s = (None,) * qt.data.ndim, (None,) * qt.scale.ndim
    elif transposes:
        logical_q, logical_s = (spec[-1], spec[-2]), (spec[-1], None)
    else:
        logical_q = logical_s = spec[-2:]
    spec_q = logical_to_mesh(logical_q, qt.data.shape, mesh, _PARAM_RULES)
    spec_s = logical_to_mesh(logical_s, qt.scale.shape, mesh, _PARAM_RULES)
    return NamedSharding(mesh, spec_q), NamedSharding(mesh, spec_s)


def reshard_serving_tree(tree: Any, mesh: Mesh) -> Any:
    """Place every leaf of a serving weight tree (fp params, or
    ``QuantizedTensor`` leaves through :func:`quantized_tensor_shardings`)
    onto ``mesh``'s parameter shardings — the reshard-on-restore path: a
    tree checkpointed from an 8-device mesh lands bit-exactly on a 1- or
    2-device mesh because the checkpoint holds full logical arrays and
    ``device_put`` only re-splits them. Asynchronous (no host sync)."""
    def place(keypath, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in keypath)
        if isinstance(leaf, QuantizedTensor):
            return leaf.with_placement(
                *quantized_tensor_shardings(mesh, path, leaf))
        spec = logical_to_mesh(param_sharding_rules(path, leaf), leaf.shape,
                               mesh, _PARAM_RULES)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(
        place, tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
