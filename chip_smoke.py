"""Chip smoke test: SQuant quantize-and-serve on one TPU, end to end.

    python chip_smoke.py            # one chip: kernel phase + serve phase
    python chip_smoke.py --chips 4  # four chips: sharded quantization only

Everything runs in this one process (a chip belongs to one process at a
time). The script needs a TPU: on any other platform it fails before any
phase runs, and it never falls back to the CPU.

* Kernel phase — each Pallas kernel runs compiled (``use_pallas="pallas"``)
  at published widths and is compared with its jnp reference on the same
  chip: ``squant_flip`` codes bit for bit, the others within the
  tolerances stated beside each check.
* Serve phase — rwkv6-1.6b at its published widths (24 x 2048, d_ff 7168,
  vocab 65536, bf16, seeded weights) is built through
  ``repro.launch.serve.build_engine``, quantized on the fly to real int8
  SQuant codes by the compiled ``squant_flip``, and served with the
  continuous scheduler (every linear runs the compiled
  ``dequant_matmul``). A second fp tree (seed 1) is staged while
  requests are in flight and swapped in.
* ``--chips 4`` — rwkv6-1.6b quantized over a 4-device ``data`` mesh
  (``quantize_tree(mesh=)``) and compared bitwise with the unsharded run
  on device 0; nothing else runs.

Timings and memory printed here are bring-up observations, not benchmark
metrics. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed check exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SERVE_ARCH = "rwkv6-1.6b"
PROMPT_LENS = (32, 128, 300, 512)     # mixed-length requests
MAX_NEW = 32

# (M, N) weight shapes of granite-3-8b: attention/output and FFN rows
SQUANT_SHAPES = ((4096, 4096), (12800, 4096))
SQUANT_BITS = (4, 8)
GROUP = 128
# dequant_matmul: granite FFN up-projection (M=d_ff, N=d_model); batch 8
# is a decode step, 148 a prefill batch that is not a multiple of 8
DEQUANT_MN = (12800, 4096)
DEQUANT_BATCHES = (8, 148)
# paged decode: (name, heads, kv heads, head dim), block 16, 2048 context
PAGED_ARCHS = (("minitron-4b", 24, 8, 128), ("granite-3-8b", 32, 8, 128))
PAGED_BLOCK = 16
PAGED_CONTEXT = 2048
PAGED_SLOTS = 8

# Tolerances. A kernel writes bf16 output (8 significant bits, one
# rounding: relative step 2**-8). Its f32 accumulation runs in another
# order than the reference's, and the MXU may take an f32 operand
# (softmax weights, dequantized KV tiles) at bf16 precision, which costs
# up to 2**-9 relative per product. The references run at
# ``precision="highest"`` in f32. So each kernel output must lie within
# 2**-7 of the reference, relative to the element and to the output's
# largest magnitude (cancellation makes single elements arbitrarily
# small).
RTOL = 2.0 ** -7


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Checks:
    """Collects pass/fail per check; every phase runs even after a
    failure so one chip run reports everything it can."""

    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"{'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def _close(got, want, what: str, checks: Checks) -> None:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want)))
    err = np.abs(got - want)
    bound = RTOL * (np.abs(want) + scale)
    worst = float(np.max(err / np.maximum(bound, 1e-30)))
    checks.expect(bool(np.all(np.isfinite(got))) and worst <= 1.0,
                  f"{what}: max|err| {float(err.max()):.3e}, "
                  f"{worst:.3f} of tolerance (max|ref| {scale:.3e})")


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def kernel_phase(checks: Checks, *, impl: str = "pallas",
                 squant_shapes=SQUANT_SHAPES, dequant_mn=DEQUANT_MN,
                 dequant_batches=DEQUANT_BATCHES, paged_archs=PAGED_ARCHS,
                 paged_context=PAGED_CONTEXT) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_ref)
    from repro.kernels.paged_attention_quant import (
        paged_attention_quant, paged_attention_quant_ref)
    from repro.models.attention import _quant_tok
    from repro.quant.qtypes import from_codes
    from repro.quant.scales import compute_scale

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    # squant_flip: codes bit-identical to the jnp reference. The kernel
    # scales the weights with the reference's own XLA divide and both
    # make the same integer decisions, so equality is exact.
    for m, n in squant_shapes:
        w = jax.random.normal(jax.random.fold_in(key, m), (m, n),
                              jnp.float32)
        for bits in SQUANT_BITS:
            s = compute_scale(w, bits, "max")
            got = ops.squant_flip(w, s, bits=bits, group_size=GROUP,
                                  use_pallas=impl)
            want = ref.squant_ref(w, s, bits=bits, group_size=GROUP)
            bad = int(jnp.sum(got != want))
            checks.expect(bad == 0, f"squant_flip {m}x{n} g{GROUP} w{bits}: "
                                    f"{bad} codes differ from squant_ref")

    # dequant_matmul: w8 and packed w4 at decode and prefill batches
    m, n = dequant_mn
    k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 1), 3)
    for bits in (8, 4):
        qmax = 2 ** (bits - 1) - 1
        codes = jax.random.randint(k1, (m, n), -qmax, qmax + 1
                                   ).astype(jnp.int8)
        scale = jax.random.uniform(k2, (m, 1), jnp.float32, 1e-3, 2e-2)
        qt = from_codes(codes, scale, bits)
        for b in dequant_batches:
            x = jax.random.normal(k3, (b, n), jnp.bfloat16)
            got = ops.dequant_matmul(x, qt, use_pallas=impl)
            with jax.default_matmul_precision("highest"):
                want = ref.dequant_matmul_ref(x.astype(jnp.float32), qt.data,
                                              scale, bits=bits, group_size=n)
            checks.expect(got.shape == (b, m) and got.dtype == x.dtype,
                          f"dequant_matmul w{bits} B={b}: shape {got.shape}")
            _close(got, want, f"dequant_matmul w{bits} B={b} {m}x{n}",
                   checks)

    # paged decode, fp and int8 pools, mixed lengths up to the full context
    nb = paged_context // PAGED_BLOCK
    nblocks = PAGED_SLOTS * nb + 1                      # + trash block 0
    for name, h, kv, d in paged_archs:
        ks = jax.random.split(jax.random.fold_in(key, h), 4)
        q = jax.random.normal(ks[0], (PAGED_SLOTS, h, d), jnp.bfloat16)
        k_fp = jax.random.normal(ks[1], (nblocks, PAGED_BLOCK, kv, d),
                                 jnp.float32)
        v_fp = jax.random.normal(ks[2], (nblocks, PAGED_BLOCK, kv, d),
                                 jnp.float32)
        tables = (1 + jax.random.permutation(ks[3], nblocks - 1)
                  ).reshape(PAGED_SLOTS, nb).astype(jnp.int32)
        lengths = jnp.linspace(paged_context - 1, 0, PAGED_SLOTS
                               ).astype(jnp.int32)
        sm = d ** -0.5
        kb, vb = k_fp.astype(jnp.bfloat16), v_fp.astype(jnp.bfloat16)
        got = paged_attention(q, kb, vb, tables, lengths, scale=sm,
                              use_pallas=impl)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_ref(
                q.astype(jnp.float32), kb.astype(jnp.float32),
                vb.astype(jnp.float32), tables, lengths, scale=sm)
        _close(got, want, f"paged_attention {name} h{h} kv{kv} d{d} "
                          f"ctx{paged_context}", checks)
        kq, ksc = _quant_tok(k_fp)
        vq, vsc = _quant_tok(v_fp)
        got = paged_attention_quant(q, kq, vq, ksc, vsc, tables, lengths,
                                    scale=sm, use_pallas=impl)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_quant_ref(q.astype(jnp.float32), kq, vq,
                                             ksc, vsc, tables, lengths,
                                             scale=sm)
        _close(got, want, f"paged_attention_quant {name} h{h} kv{kv} "
                          f"d{d} ctx{paged_context}", checks)
    log(f"kernel phase {time.perf_counter() - t0:.1f} s")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _report_buckets(tag: str, report) -> None:
    log(f"{tag}: {report.summary()}")
    for b in report.buckets:
        log(f"  bucket {b.key}: impl={b.impl} {b.num_layers} layers")


def serve_phase(checks: Checks, *, arch: str = SERVE_ARCH,
                reduced: bool = False, prompt_lens=PROMPT_LENS,
                max_new: int = MAX_NEW, quant_backend: str = "pallas"
                ) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import (build_arch_model, build_engine,
                                    init_params, max_len_for)
    from repro.quant.qtypes import QuantizedTensor
    from repro.serving.engine import Request, ServeConfig

    model = build_arch_model(arch, reduced)
    c = model.cfg
    log(f"serve: {c.name} layers={c.n_layers} d_model={c.d_model} "
        f"d_ff={c.d_ff} vocab={c.vocab} dtype={c.dtype}")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c.vocab, size=n).tolist() for n in prompt_lens]
    cfg = ServeConfig(max_batch=len(prompts),
                      max_len=max_len_for(prompts, max_new, 16),
                      quantize_weights="squant", weight_bits=8,
                      dequantize_for_compute=False, scheduler="continuous",
                      max_slots=len(prompts))

    t0 = time.perf_counter()
    eng = build_engine(model, cfg, seed=0)
    build_s = time.perf_counter() - t0
    cold = eng.quant_report
    checks.expect(cold.backend == quant_backend,
                  f"quantize backend {cold.backend}")
    _report_buckets(f"quantize (cold, build {build_s:.2f} s)", cold)
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    qts = [x for x in leaves if isinstance(x, QuantizedTensor)]
    checks.expect(bool(qts) and all(q.data.dtype == jnp.int8 and q.bits == 8
                                    for q in qts),
                  f"serving tree holds {len(qts)} int8 QuantizedTensor "
                  f"kernels (every linear runs dequant_matmul)")
    log(f"peak HBM after build: {_peak_bytes()} bytes")

    finite = []
    eng.on_step = lambda info: finite.append(
        jnp.all(jnp.isfinite(eng.scheduler.kv.logits)))
    reqs = [Request(prompt=p, max_new_tokens=max_new, request_id=i)
            for i, p in enumerate(prompts)]

    def serve(tag):
        t = time.perf_counter()
        outs = eng.generate(reqs)
        wall = time.perf_counter() - t
        for o in outs:
            ok = (len(o.tokens) == max_new
                  and all(0 <= tok < c.vocab for tok in o.tokens))
            checks.expect(ok, f"{tag} req {o.request_id} "
                              f"(prompt {len(reqs[o.request_id].prompt)}): "
                              f"{len(o.tokens)} tokens in vocab, "
                              f"v{o.weights_version}, prefill "
                              f"{o.prefill_ms:.1f} ms, decode "
                              f"{o.decode_ms:.1f} ms")
        log(f"{tag}: {wall:.2f} s wall")
        return outs

    serve("serve cold")
    serve("serve warm")

    # the on-the-fly reload: a fresh fp tree quantized while serving
    fresh = init_params(eng.model, seed=1)
    eng.store.stage(fp_params=fresh, source="seed1")
    del fresh                                   # the store's queue owns it
    outs = serve("serve during reload")
    for _ in range(3):                  # the stage may outlast one batch
        st = eng.store.stats()
        if st["swaps"] >= 1 or st["errors"]:
            break
        eng.store.wait_staged(version=1, timeout=120.0)
        outs = serve("serve after reload")
    w = eng.stats()["weights"]
    checks.expect(w["swaps"] == 1 and w["version"] == 2,
                  f"reload swapped in: version {w['version']}, "
                  f"{w['swaps']} swaps")
    checks.expect(any(o.weights_version == 2 for o in outs),
                  "requests served by the reloaded version")
    checks.expect(not w["errors"], f"weight store errors: {w['errors']}")
    warm = eng.quant_report
    _report_buckets(f"quantize (warm, staged {w['staged_ms']:.1f} ms)", warm)
    n_finite = int(jnp.sum(jnp.stack(finite))) if finite else 0
    checks.expect(bool(finite) and n_finite == len(finite),
                  f"served logits finite at {n_finite}/{len(finite)} steps")
    log(f"peak HBM after reload: {_peak_bytes()} bytes")
    eng.close()


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def four_chip_phase(checks: Checks, *, arch: str = SERVE_ARCH,
                    reduced: bool = False, backend: str = "pallas",
                    n_devices: int = 4) -> None:
    import jax
    import numpy as np
    from repro.core.pipeline import quantize_tree
    from repro.launch.mesh import make_quantize_mesh
    from repro.launch.serve import build_arch_model, init_params
    from repro.quant.qtypes import QuantizedTensor

    model = build_arch_model(arch, reduced)
    params = init_params(model, seed=0)
    mesh = make_quantize_mesh(n_devices)
    log(f"4-chip: {model.cfg.name} over mesh {dict(mesh.shape)}")
    kw = dict(method="squant", bits=8, backend=backend)
    sharded, rep = quantize_tree(params, mesh=mesh, **kw)
    log(f"sharded (cold): {rep.summary()}")
    sharded, rep = quantize_tree(params, mesh=mesh, **kw)
    log(f"sharded (warm): {rep.summary()}")
    for sh in rep.shards:
        log(f"  device {sh.device}: {sh.rows} rows (+{sh.pad_rows} pad)")
    checks.expect(len(rep.shards) == n_devices
                  and all(sh.rows > 0 for sh in rep.shards),
                  f"rows on all {n_devices} devices")

    def qts(tree):
        return [x for x in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
            if isinstance(x, QuantizedTensor)]

    held = {}
    for qt in qts(sharded):
        for arr in (qt.data, qt.scale):
            for shard in arr.addressable_shards:
                d = shard.device.id
                held[d] = held.get(d, 0) + shard.data.nbytes
    log(f"  bytes of codes+scales held per device: {held}")
    checks.expect(len(held) == n_devices,
                  f"sharded codes placed on {len(held)} devices")

    with jax.default_device(jax.devices()[0]):
        plain, rep0 = quantize_tree(params, **kw)
    log(f"unsharded on device 0: {rep0.summary()}")
    a, b = qts(sharded), qts(plain)
    same = len(a) == len(b) and all(
        np.array_equal(np.asarray(x.data), np.asarray(y.data))
        and np.array_equal(np.asarray(x.scale), np.asarray(y.scale))
        for x, y in zip(a, b))
    checks.expect(same, f"sharded codes and scales bitwise identical to "
                        f"unsharded ({len(a)} tensors)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-quantization phase")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        log(f"FAIL: the repro package is not next to this script ({SRC})")
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"FAIL: no TPU (JAX found {dev.platform}); this smoke test "
            f"runs only on the chip")
        return 2
    if len(devices) < args.chips:
        log(f"FAIL: --chips {args.chips} but JAX sees {len(devices)}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    compile_s = [0.0]
    cache = {"hits": 0, "misses": 0}

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            compile_s[0] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    checks = Checks()
    phases = ([("4-chip", four_chip_phase)] if args.chips == 4 else
              [("kernel", kernel_phase), ("serve", serve_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(checks)
        except Exception as e:           # report, then fail the run below
            import traceback
            traceback.print_exc()
            checks.expect(False, f"{name} phase raised {e!r}")
        log(f"{name} phase: {time.perf_counter() - t0:.1f} s, compile "
            f"{compile_s[0]:.1f} s so far, persistent cache "
            f"{cache['hits']} hits / {cache['misses']} misses, peak HBM "
            f"{_peak_bytes()} bytes")
    if checks.failed:
        log(f"{len(checks.failed)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
