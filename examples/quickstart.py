"""Quickstart: on-the-fly data-free quantization with SQuant.

Quantizes a freshly-initialized reduced LM to 4-bit in milliseconds — no
data, no back-prop, no fine-tuning — and shows the CASE objective the
algorithm minimizes (per-kernel/per-channel absolute sums of error) dropping
versus plain rounding.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.pipeline import quantize_tree
from repro.core.squant import SQuantConfig, squant
from repro.models.model import build_model


def main():
    # --- single matrix: watch CASE collapse ------------------------------
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(512, 2048)).astype(np.float32))
    print("one 512x2048 matrix, 4-bit, group 128:")
    for tag, (ek, ec) in {"rounding (SQuant-E)": (False, False),
                          "SQuant-E&K": (True, False),
                          "SQuant-E&K&C": (True, True)}.items():
        qt, stats = squant(w, SQuantConfig(bits=4, group_size=128,
                                           enable_k=ek, enable_c=ec))
        d = np.asarray(qt.codes(), np.float64) - np.asarray(w) / \
            np.asarray(qt.scale)
        grp = np.abs(d.reshape(512, -1, 128).sum(-1))
        print(f"  {tag:22s} mean|kernel ASE|={grp.mean():6.3f}  "
              f"mean|channel ASE|={np.abs(d.sum(1)).mean():6.3f}  "
              f"flips K/C={int(stats['flips_k'])}/{int(stats['flips_c'])}")

    # --- whole model: sub-second, data-free, batched ---------------------
    # The batched pipeline groups same-shape layers into buckets, runs one
    # vmapped/Pallas dispatch per bucket, and syncs with the device once.
    # backend="auto" resolves TPU→pallas kernel, CPU→jnp reference.
    cfg = get_config("granite-3-8b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    quantize_tree(params, method="squant", bits=4, dequantize=False)  # jit
    t0 = time.perf_counter()
    qparams, report = quantize_tree(params, method="squant", bits=4,
                                    dequantize=False, backend="auto")
    dt = time.perf_counter() - t0
    print(f"\nwhole {cfg.name}: {report.summary()} "
          f"(wall {dt*1e3:.0f} ms, no data, no BP)")
    for b in report.buckets:
        print(f"  bucket {b.key}: {b.num_layers} layers, impl={b.impl}")
    from repro.quant.qtypes import QuantizedTensor
    qbytes = sum(
        leaf.nbytes() for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(leaf, QuantizedTensor))
    print(f"done — int4 codes + per-channel scales, {qbytes/1e6:.2f} MB "
          "of quantized kernels.")


if __name__ == "__main__":
    main()
