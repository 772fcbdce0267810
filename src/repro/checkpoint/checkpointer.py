"""Checkpointing: sharded .npz payloads + JSON index, async save, atomic
commit, reshard-on-restore — for fp training state AND quantized serving
trees.

Layout:
    <dir>/step_000100/
        shard_00000.npz      (flat-key → array chunks owned by this host)
        index.json           (tree structure, shapes, dtypes, quant meta)
        COMMITTED            (written last — a step dir without it, or with
                              an unparseable index.json, is invisible to
                              restore and to the serve reload watcher:
                              torn saves are harmless)

Save is shard-agnostic: every leaf is written as the full logical array
(single-host container) or per-host shards (multi-host: each host writes its
addressable chunks). Restore never assumes the saving topology — it
reassembles from the index and reshards to the *current* mesh
(``mesh=`` on ``restore_serving`` routes through
``distributed.sharding``/``distributed.compat``), which is what makes
elastic restarts (different chip counts) work.

Quantized checkpoints (``save_serving`` with ``quant_meta``) hold the
``QuantizedTensor`` trees ``core.pipeline.quantize_tree`` returns natively:
each tensor's ``data`` and ``scale`` arrays are payload entries
(``…/w/data``, ``…/w/scale``; int4 nibbles stay packed two-per-int8-byte
on disk) and its ``bits``/``group_size``/``shape`` are listed under
``qtensors`` in ``index.json``, beside ``{"format": "quantized", "quant":
{bits, method, group_size, report…}}`` so restore can refuse a tree that
does not match the requested serve config instead of silently
dequantizing garbage.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.quant.qtypes import QuantizedTensor


class CheckpointMetaError(ValueError):
    """A checkpoint's index.json is unreadable or contradicts the caller's
    expectations (e.g. quantized w4 restored into a w8 serve config)."""


def _flatten(tree: Any):
    """``({"a/b/w": leaf}, treedef)``; a ``QuantizedTensor`` is one leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    out = {}
    for keypath, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in keypath)
        out[key] = leaf
    return out, treedef


def quant_tree_meta(bits: int, method: str, group_size: Optional[int],
                    report=None) -> dict:
    """``quant_meta`` for :meth:`Checkpointer.save_serving`: the bits/method
    contract restore validates against, plus a digest of the
    ``QuantReport`` of the ``quantize_tree`` call that made the tree."""
    meta = {"bits": bits, "method": method, "group_size": group_size,
            "packed_int4": bits <= 4}
    if report is not None:
        meta["report"] = {"layers": len(report.layers),
                          "total_ms": report.total_millis,
                          "backend": report.backend,
                          "mesh_size": report.mesh_size}
    return meta


class Checkpointer:
    def __init__(self, directory: str, async_save: bool = True,
                 keep: int = 3):
        self.dir = directory
        self.async_save = async_save
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ save
    def save(self, step: int, params: Any, opt_state: Any):
        # snapshot to host memory synchronously (cheap), write async
        payload = {}
        meta = {"step": step, "trees": {}}
        for name, tree in (("params", params), ("opt", opt_state)):
            flat, _ = _flatten(tree)
            meta["trees"][name] = {"keys": sorted(flat)}
            for k, v in flat.items():
                payload[f"{name}::{k}"] = np.asarray(v)
        self.wait()
        self._dispatch(step, payload, meta)

    def save_serving(self, step: int, params: Any,
                     quant_meta: Optional[Dict[str, Any]] = None):
        """Save a serving weight tree: fp, or with ``QuantizedTensor``
        leaves (``quantize_tree(..., dequantize=False)``).

        ``quant_meta`` (:func:`quant_tree_meta`) marks the checkpoint as
        quantized and must carry at least ``bits`` and ``method``; a tree
        with ``QuantizedTensor`` leaves needs it. Packed int4 codes are
        written as-is — nibbles stay packed on disk.
        """
        flat, _ = _flatten(params)
        payload, qtensors = {}, {}
        for k, v in flat.items():
            if isinstance(v, QuantizedTensor):
                payload[f"params::{k}/data"] = np.asarray(v.data)
                payload[f"params::{k}/scale"] = np.asarray(v.scale)
                qtensors[k] = {"bits": v.bits, "group_size": v.group_size,
                               "shape": list(v.shape)}
            else:
                payload[f"params::{k}"] = np.asarray(v)
        meta = {"step": step, "trees": {"params": {"keys": sorted(flat)}},
                "format": "fp", "quant": None}
        if quant_meta is not None:
            missing = {"bits", "method"} - set(quant_meta)
            if missing:
                raise ValueError(f"quant_meta missing {sorted(missing)}")
            meta["format"] = "quantized"
            meta["quant"] = dict(quant_meta)
            meta["qtensors"] = qtensors
        elif qtensors:
            raise ValueError("a tree with QuantizedTensor leaves needs "
                             "quant_meta")
        self.wait()
        self._dispatch(step, payload, meta)

    def _dispatch(self, step: int, payload, meta):
        if self.async_save:
            self._thread = threading.Thread(
                target=self._write, args=(step, payload, meta), daemon=True)
            self._thread.start()
        else:
            self._write(step, payload, meta)

    def _write(self, step: int, payload, meta):
        final = os.path.join(self.dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "shard_00000.npz"), **payload)
        meta["shapes"] = {k: list(v.shape) for k, v in payload.items()}
        meta["dtypes"] = {k: str(v.dtype) for k, v in payload.items()}
        with open(os.path.join(tmp, "index.json"), "w") as f:
            json.dump(meta, f)
        with open(os.path.join(tmp, "COMMITTED"), "w") as f:
            f.write(str(time.time()))
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()

    def _gc(self):
        """Keep the newest ``keep`` loadable steps; everything else —
        including torn/corrupt step dirs, which ``list_steps`` hides but
        which would otherwise accumulate forever — is deleted. Writers are
        atomic (payload + COMMITTED land in a ``.tmp`` dir, then one
        rename), so a non-``.tmp`` invalid dir is never an in-flight save."""
        keep = set(self.list_steps()[-self.keep:])
        for d in sorted(os.listdir(self.dir)):
            if not d.startswith("step_") or d.endswith(".tmp"):
                continue
            try:
                s = int(d.split("_")[1])
            except ValueError:
                continue
            if s not in keep:
                shutil.rmtree(os.path.join(self.dir, d), ignore_errors=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    # --------------------------------------------------------------- restore
    def list_steps(self):
        """Committed, loadable steps. A step dir missing COMMITTED (torn
        save) or whose index.json does not parse (torn/corrupt metadata) is
        skipped — both restore and the serve reload watcher key off this."""
        out = []
        for d in sorted(os.listdir(self.dir)):
            if not d.startswith("step_") or d.endswith(".tmp"):
                continue
            if not os.path.exists(os.path.join(self.dir, d, "COMMITTED")):
                continue
            try:
                with open(os.path.join(self.dir, d, "index.json")) as f:
                    json.load(f)
            except (OSError, ValueError):
                continue
            out.append(int(d.split("_")[1]))
        return out

    def read_meta(self, step: int) -> Dict[str, Any]:
        """Parsed index.json for a committed step."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        if not os.path.exists(os.path.join(d, "COMMITTED")):
            raise CheckpointMetaError(f"step {step}: no COMMITTED marker "
                                      f"(torn save?) in {d}")
        try:
            with open(os.path.join(d, "index.json")) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointMetaError(f"step {step}: unreadable index.json "
                                      f"({e})") from e

    def restore(self, step: int, shardings: Optional[Any] = None,
                template: Optional[Tuple[Any, Any]] = None):
        """Returns (params, opt_state, step). ``template`` provides the tree
        structures; ``shardings`` (same structure) reshards onto the current
        mesh (elastic restore)."""
        meta = self.read_meta(step)
        if meta.get("format") == "quantized":
            raise CheckpointMetaError(
                f"step {step} is a quantized serving checkpoint "
                f"(quant={meta.get('quant')}); restore it with "
                f"restore_serving(), not the training-state restore()")
        d = os.path.join(self.dir, f"step_{step:08d}")
        data = np.load(os.path.join(d, "shard_00000.npz"))

        def rebuild(name, tmpl, shards):
            flat, treedef = _flatten(tmpl)
            flat_sh, _ = _flatten(shards) if shards is not None else ({}, None)
            leaves = []
            for k in sorted(flat):
                arr = data[f"{name}::{k}"]
                if shards:
                    arr = jax.device_put(arr, flat_sh[k])
                leaves.append(arr)
            keys_sorted = sorted(flat)
            rebuilt = dict(zip(keys_sorted, leaves))
            # reassemble in original flatten order
            ordered = [rebuilt[k] for k in
                       ["/".join(str(getattr(kk, "key",
                                             getattr(kk, "idx", kk)))
                                 for kk in kp)
                        for kp, _ in jax.tree_util.tree_flatten_with_path(
                            tmpl)[0]]]
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(tmpl), ordered)

        if template is None:
            raise ValueError("restore requires a (params, opt) template")
        p_tmpl, o_tmpl = template
        p_sh = o_sh = None
        if shardings is not None:
            p_sh, o_sh = shardings
        params = rebuild("params", p_tmpl, p_sh)
        opt = rebuild("opt", o_tmpl, o_sh)
        return params, opt, step

    def restore_latest(self, shardings=None, template=None):
        steps = self.list_steps()
        if not steps:
            return None
        if template is None:
            return self._restore_raw(steps[-1])
        return self.restore(steps[-1], shardings, template)

    def _restore_raw(self, step: int, to_jax: bool = True,
                     qtensors: Optional[Dict[str, Any]] = None):
        """Tree-structure-free restore (single-host): rebuilds nested dicts
        from the flat key paths, and a ``QuantizedTensor`` at each key of
        ``qtensors`` (index.json's listing) from its data and scale.
        ``to_jax=False`` keeps leaves as host numpy arrays (for callers
        that place them on devices themselves — one transfer instead of
        commit-to-default-device-then-reshard)."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        data = np.load(os.path.join(d, "shard_00000.npz"))
        conv = jax.numpy.asarray if to_jax else (lambda a: a)

        def insert(root, path, value):
            node = root
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = value

        trees = {"params": {}, "opt": {}}
        for full_key in data.files:
            name, key = full_key.split("::", 1)
            insert(trees[name], key.split("/"), conv(data[full_key]))
        for key, q in (qtensors or {}).items():
            *parents, last = key.split("/")
            node = trees["params"]
            for p in parents:
                node = node[p]
            node[last] = QuantizedTensor(
                data=node[last]["data"], scale=node[last]["scale"],
                bits=q["bits"], group_size=q["group_size"],
                shape=tuple(q["shape"]))

        def listify(node):
            """Convert dicts with integer-contiguous keys back to lists."""
            if not isinstance(node, dict):
                return node
            keys = list(node.keys())
            if keys and all(k.isdigit() for k in keys):
                idx = sorted(int(k) for k in keys)
                if idx == list(range(len(idx))):
                    return [listify(node[str(i)]) for i in idx]
            return {k: listify(v) for k, v in node.items()}

        params = listify(trees["params"])
        opt = listify(trees["opt"])
        return params, opt, step

    def restore_serving(self, step: Optional[int] = None,
                        expect: Optional[Dict[str, Any]] = None,
                        mesh=None) -> Tuple[Any, Dict[str, Any], int]:
        """Restore a serving weight tree → ``(params, meta, step)``.

        Loads the newest committed step when ``step`` is None (torn/corrupt
        dirs are invisible). Works for both fp checkpoints (training saves —
        the opt tree is ignored) and native quantized ones.

        ``expect`` carries the serve config's quant expectations
        (``{"quantize_weights": method|None, "weight_bits": int}``): a
        quantized checkpoint whose ``bits``/``method`` metadata mismatch it
        raises :class:`CheckpointMetaError` instead of silently dequantizing
        garbage. fp checkpoints always pass (the caller re-quantizes).

        ``mesh``: reshard-on-restore — every leaf is ``device_put`` onto the
        current mesh's parameter shardings (``distributed.sharding`` rules;
        ``QuantizedTensor`` codes and scales take
        ``quantized_tensor_shardings``; bit-exact for any device count
        because the full logical arrays live on disk). Leaves
        stay on the host until the single placing transfer — a full tree is
        never first committed to one default device.
        """
        if step is None:
            steps = self.list_steps()
            if not steps:
                raise FileNotFoundError(f"no committed checkpoint in "
                                        f"{self.dir}")
            step = steps[-1]
        meta = self.read_meta(step)
        quant = meta.get("quant")
        if quant is not None and expect is not None:
            want_m = expect.get("quantize_weights")
            want_b = expect.get("weight_bits")
            if want_m is None:
                raise CheckpointMetaError(
                    f"step {step} holds {quant['method']} w{quant['bits']} "
                    f"weights but the serve config requests unquantized "
                    f"serving")
            if quant.get("bits") != want_b or quant.get("method") != want_m:
                raise CheckpointMetaError(
                    f"step {step} quant metadata mismatch: checkpoint is "
                    f"{quant.get('method')} w{quant.get('bits')}, serve "
                    f"config requests {want_m} w{want_b}")
        params, _, _ = self._restore_raw(step, to_jax=False,
                                         qtensors=meta.get("qtensors"))
        if mesh is not None:
            from repro.distributed.sharding import reshard_serving_tree
            params = reshard_serving_tree(params, mesh)
        else:
            params = jax.tree_util.tree_map(jax.numpy.asarray, params)
        return params, meta, step
