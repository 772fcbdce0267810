"""Versioned quantized weight store for the serving engine.

SQuant's sub-second, data-free cost makes quantize-on-reload viable inside a
live serving loop: fresh fp weights can be quantized *while serving
continues* and swapped in between decode rounds. This module owns that
machinery so the engine never touches ``quantize_tree`` directly.

Model
-----
* ``WeightVersion`` — an immutable (version, params, report, provenance)
  snapshot. Versions increase monotonically per store.
* ``WeightStore`` — double-buffered: exactly one **live** version (what
  rounds currently read) and at most one **staged** version (fully built,
  device-resident, waiting to be swapped in). Staging happens on a
  background worker (latest request wins); the swap itself is a pointer
  flip a scheduler performs only at its swap points via
  :meth:`WeightStore.acquire` — round boundaries for the round scheduler,
  drained (or deadline-forced) step boundaries for the continuous one —
  so an in-flight round can never observe a torn tree: it holds the
  ``WeightVersion`` it started with. Schedulers watch
  :attr:`WeightStore.staged_pending` to begin draining and report the
  drain/swap through :meth:`note_drain`/:meth:`note_swap`.
* ``watch()`` — a poll thread over a checkpoint directory
  (``checkpoint.Checkpointer`` layout). New COMMITTED steps are restored
  (torn/corrupt step dirs are skipped), validated against the serve
  config's quant expectations, and staged: quantized checkpoints
  (``QuantizedTensor`` trees) are served with no re-quantization, through
  the store's prepare_fn; fp checkpoints go through the store's
  quantize_fn (the batched/sharded ``quantize_tree`` path).
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.quant.qtypes import QuantReport


@dataclasses.dataclass(frozen=True)
class WeightVersion:
    """One immutable generation of serving weights.

    ``draft_params`` is the self-speculative drafter tree (a lower-bit
    quantization of the SAME source the target ``params`` came from),
    staged and swapped atomically with the target so a reload can never
    pair a new verifier with an old drafter. None when the store has no
    draft pipeline (speculation off)."""
    version: int                       # monotonically increasing, from 1
    params: Any                        # serving tree (fp, fake-quant, codes)
    report: Optional[QuantReport] = None
    source: str = "init"               # "init" | "ckpt:<step>" | caller tag
    step: Optional[int] = None         # checkpoint step, when applicable
    staged_ms: float = 0.0             # quantize/prepare + device wall time
    draft_params: Any = None           # speculative drafter tree (or None)


def serves_codes(cfg) -> bool:
    """True when a ``ServeConfig`` serves real integer codes
    (``QuantizedTensor`` leaves): the layer stack is then unrolled, since
    those leaves cannot be scanned over — standard for serving anyway."""
    return bool(cfg.quantize_weights) and not cfg.dequantize_for_compute


def unstacked(tree, model_cfg):
    """``tree`` with a scanned layer stack (``stack/periods``) unrolled into
    ``stack/list`` — the layout real-code serving quantizes and serves."""
    from repro.models.transformer import n_periods, unstack_stack
    if isinstance(tree, dict) and "periods" in tree.get("stack", {}):
        tree = dict(tree)
        tree["stack"] = unstack_stack(tree["stack"], n_periods(model_cfg))
    return tree


def make_weight_pipeline(model, cfg):
    """``(model', quantize_fn, prepare_fn)`` for a ``ServeConfig``.

    ``model'`` is rebuilt with the layer stack unrolled when real codes are
    served (:func:`serves_codes`). ``quantize_fn`` maps an fp tree to
    ``(serving_tree, QuantReport | None)`` per the config (identity when
    ``cfg.quantize_weights`` is None). ``prepare_fn`` turns a restored
    quantized checkpoint (``QuantizedTensor`` leaves on the unrolled stack)
    into ``model'``'s serving tree: as it is for real codes; for
    fake-quant serving, each leaf dequantized into its fp layout and the
    periods re-stacked — the tree quantize-on-load gives.
    """
    from repro.core.pipeline import quantize_tree
    from repro.models.model import build_model

    base_cfg = model.cfg
    codes = serves_codes(cfg)
    if codes:
        model = build_model(dataclasses.replace(base_cfg, scan_layers=False))

    def quantize_fn(fp_tree):
        if not cfg.quantize_weights:
            return fp_tree, None
        if codes:
            fp_tree = unstacked(fp_tree, base_cfg)
        return quantize_tree(fp_tree, method=cfg.quantize_weights,
                             bits=cfg.weight_bits,
                             dequantize=cfg.dequantize_for_compute)

    def prepare_fn(tree):
        return tree if codes else _dequantized(tree, model)

    return model, quantize_fn, prepare_fn


def _dequantized(tree, model):
    """The fp tree for ``model`` (its own stack layout) of a tree whose
    ``QuantizedTensor`` leaves sit on the unrolled stack: each leaf goes
    through the fake-quant restore ``quantize_tree(dequantize=True)``
    applies, into its fp leaf's layout and dtype."""
    from repro.core.pipeline import _restore_dense
    from repro.models.transformer import stack_periods
    from repro.quant.qtypes import QuantizedTensor, is_quantized_tree

    if not is_quantized_tree(tree):
        return tree
    shapes = model.param_shapes()
    tree = jax.tree_util.tree_map(
        lambda x, leaf: _restore_dense(x.dequantize(leaf.dtype),
                                       tuple(leaf.shape))
        if isinstance(x, QuantizedTensor) else x,
        tree, jax.eval_shape(lambda t: unstacked(t, model.cfg), shapes),
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    if "periods" in shapes["stack"]:
        tree = dict(tree, stack=stack_periods(tree["stack"]))
    return tree


def make_draft_quantize_fn(model, cfg):
    """``fp tree -> draft serving tree`` for self-speculative serving.

    The drafter is the same checkpoint quantized at ``cfg.draft_bits``
    (data-free, sub-second — SQuant makes draft models free), prepared
    for the SAME model the target pipeline serves (:func:`serves_codes`
    decides the stack layout for both). When the target serves fp
    (``quantize_weights`` None) the drafter still quantizes — the ladder
    needs a cheaper tree below the verifier — defaulting to 'squant'.
    """
    from repro.core.pipeline import quantize_tree

    codes = serves_codes(cfg)
    method = cfg.quantize_weights or "squant"

    def draft_fn(fp_tree):
        if codes:
            fp_tree = unstacked(fp_tree, model.cfg)
        tree, _ = quantize_tree(fp_tree, method=method, bits=cfg.draft_bits,
                                dequantize=cfg.dequantize_for_compute)
        return tree

    return draft_fn


class WeightStore:
    """Double-buffered, versioned owner of serving weights.

    Exactly one of ``fp_params`` / ``serving_params`` seeds version 1:
    ``fp_params`` goes through ``quantize_fn``; ``serving_params`` is an
    already-quantized tree (through ``prepare_fn``).
    """

    def __init__(self, quantize_fn: Optional[Callable] = None,
                 fp_params: Any = None, *, serving_params: Any = None,
                 prepare_fn: Optional[Callable] = None,
                 draft_quantize_fn: Optional[Callable] = None,
                 report: Optional[QuantReport] = None, source: str = "init"):
        if (fp_params is None) == (serving_params is None):
            raise ValueError("provide exactly one of fp_params or "
                             "serving_params")
        self._quantize_fn = quantize_fn
        self._prepare_fn = prepare_fn or (lambda t: t)
        # speculative serving: fp tree -> drafter tree, built alongside the
        # target in _build_and_publish so every version is a (target,
        # draft) pair. Requires fp sources: a quantized-native checkpoint
        # reload cannot rebuild the drafter, so such stages fail into
        # ``errors`` and serving continues on the previous pair.
        self._draft_quantize_fn = draft_quantize_fn
        self._lock = threading.Lock()
        self._counter = 0
        self._live: Optional[WeightVersion] = None
        self._staged: Optional[WeightVersion] = None
        self._queue: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._watch_stop: Optional[threading.Event] = None
        self._watch_thread: Optional[threading.Thread] = None
        self._last_ckpt_step = -1
        self._ckpt_retries = 0            # transient-failure retries per step
        self._staged_at = 0.0             # monotonic time of last staging
        self.swap_count = 0
        # reload-aware scheduler observability (note_drain/note_swap)
        self.drain_count = 0
        self.forced_swap_count = 0
        self.last_drain_ms = 0.0
        self.last_drain_in_flight = 0
        # bounded: a persistently failing watcher (e.g. deleted ckpt dir)
        # appends per poll and must not grow a long-lived server's memory
        self.errors: collections.deque = collections.deque(maxlen=256)
        self._build_and_publish(fp_params, serving_params, report, source,
                                None)
        with self._lock:
            self._live, self._staged = self._staged, None

    # ------------------------------------------------------------- accessors
    @property
    def current(self) -> WeightVersion:
        """The live version (no swap — see :meth:`acquire`)."""
        with self._lock:
            return self._live

    @property
    def version(self) -> int:
        return self.current.version

    @property
    def staged_pending(self) -> bool:
        """True when a fully-built version is waiting to be swapped in —
        the reload-aware scheduler's drain trigger (peek; no swap)."""
        with self._lock:
            return self._staged is not None

    def staged_info(self) -> Optional["StagedInfo"]:
        """:class:`repro.serving.api.StagedInfo` for the staged version,
        or None. ``age_ms`` is how long the version has been waiting —
        schedulers compare it against their swap deadline. (Supports
        ``["key"]`` access for pre-api.py dict-style consumers.)"""
        from repro.serving.api import StagedInfo
        with self._lock:
            if self._staged is None:
                return None
            return StagedInfo(
                version=self._staged.version,
                age_ms=(time.monotonic() - self._staged_at) * 1e3)

    # ------------------------------------------------- scheduler drain hooks
    def note_drain(self, in_flight: int = 0) -> None:
        """A scheduler observed the staged version and began draining
        (stopped admitting) with ``in_flight`` slots still decoding."""
        with self._lock:
            self.drain_count += 1
            self.last_drain_in_flight = in_flight

    def note_swap(self, forced: bool = False, drain_ms: float = 0.0) -> None:
        """A scheduler swapped after draining for ``drain_ms``; ``forced``
        means the swap-deadline expired with slots still in flight."""
        with self._lock:
            if forced:
                self.forced_swap_count += 1
            self.last_drain_ms = drain_ms

    def acquire(self) -> Tuple[WeightVersion, float]:
        """Swap in any fully-staged version and return ``(live, swap_ms)``.

        This is the ONLY place a new version becomes live. The engine calls
        it at decode-round boundaries; the returned snapshot stays valid for
        the whole round regardless of concurrent staging.
        """
        t0 = time.perf_counter()
        with self._lock:
            if self._staged is not None:
                self._live, self._staged = self._staged, None
                self.swap_count += 1
            live = self._live
        return live, (time.perf_counter() - t0) * 1e3

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            live, staged = self._live, self._staged
            return {"version": live.version, "source": live.source,
                    "step": live.step, "staged_ms": live.staged_ms,
                    "versions_built": self._counter,
                    "swaps": self.swap_count,
                    "drains": self.drain_count,
                    "forced_swaps": self.forced_swap_count,
                    "last_drain_ms": self.last_drain_ms,
                    "last_drain_in_flight": self.last_drain_in_flight,
                    "staged_pending": staged is not None,
                    "staged_version":
                        staged.version if staged is not None else None,
                    "watching": self._watch_thread is not None,
                    "errors": list(self.errors)}

    # --------------------------------------------------------------- staging
    def _build_and_publish(self, fp_params, serving_params, report, source,
                           step):
        t0 = time.perf_counter()
        if serving_params is not None:
            tree, rep = self._prepare_fn(serving_params), report
        else:
            if self._quantize_fn is None:
                raise ValueError("store has no quantize_fn; cannot stage "
                                 "fp params")
            tree, rep = self._quantize_fn(fp_params)
        draft = None
        if self._draft_quantize_fn is not None:
            if fp_params is None:
                # background stage() routes this into ``errors`` and keeps
                # serving the previous (target, draft) pair — a reload must
                # never drop the drafter out from under a speculating slot
                raise ValueError(
                    "speculative serving stages (target, draft) pairs from "
                    "one fp source; a quantized-native serving tree cannot "
                    "rebuild the drafter — reload fp checkpoints instead")
            draft = self._draft_quantize_fn(fp_params)
        # materialize now so the round-boundary swap is a pointer flip
        jax.block_until_ready(jax.tree_util.tree_leaves((tree, draft)))
        staged_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self._counter += 1
            self._staged = WeightVersion(self._counter, tree, rep, source,
                                         step, staged_ms, draft)
            self._staged_at = time.monotonic()

    def stage(self, fp_params: Any = None, *, serving_params: Any = None,
              report: Optional[QuantReport] = None, source: str = "manual",
              step: Optional[int] = None, block: bool = False):
        """Quantize/prepare a new weight tree and stage it for the next swap.

        ``block=False`` hands the work to the background worker (latest
        request wins if several arrive while one is building);
        ``block=True`` builds synchronously in the caller's thread.
        """
        if (fp_params is None) == (serving_params is None):
            raise ValueError("provide exactly one of fp_params or "
                             "serving_params")
        if block:
            self._build_and_publish(fp_params, serving_params, report,
                                    source, step)
            return
        self._queue.put((fp_params, serving_params, report, source, step))
        with self._lock:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(target=self._stage_loop,
                                                daemon=True)
                self._worker.start()

    def _stage_loop(self):
        while True:
            req = self._queue.get()
            if req is None:
                return
            try:            # drain: only the newest pending request matters
                while True:
                    nxt = self._queue.get_nowait()
                    if nxt is None:
                        return
                    req = nxt
            except queue.Empty:
                pass
            try:
                self._build_and_publish(*req)
            except Exception as e:          # serving must outlive bad stages
                with self._lock:
                    self.errors.append(f"stage({req[3]}) failed: {e!r}")

    def wait_staged(self, version: Optional[int] = None,
                    timeout: float = 30.0) -> bool:
        """Block until a version newer than ``version`` (default: current
        live) has been built (staged or already swapped in)."""
        base = self.version if version is None else version
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self._counter > base:
                    return True
            time.sleep(0.005)
        return False

    # ------------------------------------------------------ checkpoint watch
    def poll_checkpoints(self, checkpointer, expect: Optional[dict] = None,
                         mesh=None) -> Optional[int]:
        """One watcher step: stage the newest unseen COMMITTED checkpoint.

        Torn step dirs (no COMMITTED) and corrupt ``index.json`` are
        invisible via ``list_steps``. Failures are recorded in ``errors``;
        metadata mismatches (permanent) are never retried, transient
        restore/stage failures are retried on the next few polls before the
        step is given up on. Returns the staged step, or None.
        """
        from repro.checkpoint.checkpointer import CheckpointMetaError

        steps = checkpointer.list_steps()
        if not steps or steps[-1] < self._last_ckpt_step or (
                steps[-1] == self._last_ckpt_step and
                self._ckpt_retries == 0):
            return None
        step = steps[-1]
        if step > self._last_ckpt_step:
            self._last_ckpt_step, self._ckpt_retries = step, 3
        try:
            tree, meta, _ = checkpointer.restore_serving(
                step, expect=expect, mesh=mesh)
            src = f"ckpt:{step}"
            if meta.get("format") == "quantized":
                self.stage(serving_params=tree, source=src, step=step,
                           block=True)
            else:
                self.stage(fp_params=tree, source=src, step=step,
                           block=True)
        except CheckpointMetaError as e:
            self._ckpt_retries = 0       # permanent: wrong bits/method
            with self._lock:
                self.errors.append(f"reload step {step} rejected: {e}")
            return None
        except Exception as e:
            self._ckpt_retries -= 1      # transient? retry a few polls
            with self._lock:
                self.errors.append(f"reload step {step} failed "
                                   f"({self._ckpt_retries} retries left): "
                                   f"{e!r}")
            return None
        self._ckpt_retries = 0
        return step

    def watch(self, ckpt_dir, poll_s: float = 1.0,
              expect: Optional[dict] = None, mesh=None):
        """Poll ``ckpt_dir`` in a daemon thread and stage new steps."""
        from repro.checkpoint.checkpointer import Checkpointer
        ck = Checkpointer(ckpt_dir, async_save=False) \
            if isinstance(ckpt_dir, str) else ckpt_dir
        if self._watch_thread is not None:
            raise RuntimeError("already watching a checkpoint directory")
        self._watch_stop = threading.Event()

        def loop():
            while not self._watch_stop.wait(poll_s):
                try:
                    self.poll_checkpoints(ck, expect=expect, mesh=mesh)
                except Exception as e:
                    with self._lock:
                        self.errors.append(f"watcher: {e!r}")

        self._watch_thread = threading.Thread(target=loop, daemon=True)
        self._watch_thread.start()

    def close(self):
        """Stop the watcher and the staging worker (idempotent)."""
        if self._watch_stop is not None:
            self._watch_stop.set()
            self._watch_thread.join(timeout=5)
            self._watch_thread, self._watch_stop = None, None
        if self._worker is not None and self._worker.is_alive():
            self._queue.put(None)
            self._worker.join(timeout=5)
        self._worker = None
