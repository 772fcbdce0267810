"""Data-free quantization CLI: checkpoint in, SQuant-ed checkpoint out.

The black-box post-processing deployment mode the paper argues for: no data,
no back-prop, sub-second per network.

Example:
    python -m repro.launch.quantize --arch granite-3-8b --reduced \
        --method squant --bits 4 --out /tmp/granite_w4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

from repro.checkpoint.checkpointer import Checkpointer, quant_tree_meta
from repro.configs import get_config, list_archs
from repro.core.dispatch import BACKENDS
from repro.core.pipeline import quantize_tree
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import init_params
from repro.models.model import build_model
from repro.serving.weights import unstacked


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="toy widths in f32 (default); --no-reduced runs "
                         "the published config in its own dtype")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (default: fresh init)")
    ap.add_argument("--method", default="squant",
                    choices=["rtn", "squant", "squant_e", "squant_ek",
                             "squant_ec"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=128)
    ap.add_argument("--backend", default="auto", choices=list(BACKENDS),
                    help="kernel backend: auto (TPU→pallas, CPU→ref), ref "
                         "(jnp), pallas (compiled TPU kernel), interpret "
                         "(kernel body on CPU, for validation)")
    ap.add_argument("--serial", action="store_true",
                    help="legacy per-layer loop with one device sync per "
                         "layer (baseline for the batched pipeline)")
    ap.add_argument("--mesh", default="off",
                    help="sharded quantization: 'off' (default), 'auto' "
                         "(1-axis 'data' mesh over every host device), or an "
                         "integer device count. Row-partitions each bucket "
                         "under shard_map; bit-identical to the unsharded "
                         "path")
    ap.add_argument("--out", default="/tmp/repro_quantized")
    ap.add_argument("--serving-ckpt", default=None, metavar="DIR",
                    help="additionally write a *native* quantized serving "
                         "checkpoint (QuantizedTensor leaves on the "
                         "unrolled layer stack, int4 kept packed on disk, "
                         "quant metadata in index.json) that "
                         "repro.launch.serve --reload-from hot-loads "
                         "without re-quantizing")
    ap.add_argument("--serving-step", type=int, default=0,
                    help="step number for --serving-ckpt (watchers reload "
                         "steps in increasing order)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.reduced:
        cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    if args.ckpt:
        ck = Checkpointer(args.ckpt)
        params, _, step = ck.restore_latest()
        print(f"[quantize] loaded step {step} from {args.ckpt}")
    else:
        params = init_params(model, seed=0)

    mesh = None
    if args.mesh != "off":
        from repro.launch.mesh import make_quantize_mesh
        mesh = make_quantize_mesh(None if args.mesh == "auto"
                                  else int(args.mesh))
        print(f"[quantize] sharding rows over mesh {dict(mesh.shape)}")

    qtree, report = quantize_tree(params, method=args.method, bits=args.bits,
                                  group_size=args.group_size,
                                  dequantize=True, backend=args.backend,
                                  batched=not args.serial, mesh=mesh)
    print(f"[quantize] {report.summary()}")
    os.makedirs(args.out, exist_ok=True)
    Checkpointer(args.out, async_save=False).save(0, qtree, {"step": 0})
    with open(os.path.join(args.out, "quant_report.json"), "w") as f:
        json.dump({"method": args.method, "bits": args.bits,
                   "backend": report.backend,
                   "batched": not args.serial,
                   "total_ms": report.total_millis,
                   "mesh_axis": report.mesh_axis,
                   "mesh_size": report.mesh_size,
                   "shards": [{"device": s.device, "rows": s.rows,
                               "pad_rows": s.pad_rows}
                              for s in report.shards],
                   "buckets": [{"key": b.key, "layers": b.num_layers,
                                "impl": b.impl}
                               for b in report.buckets],
                   "layers": [{"path": l.path, "shape": list(l.shape),
                               "ms": l.millis, "bucket": l.bucket}
                              for l in report.layers]},
                  f, indent=1)
    print(f"[quantize] wrote {args.out}")

    if args.serving_ckpt:
        # what a real-code serving engine serves: integer codes on the
        # unrolled layer stack
        qserve, srep = quantize_tree(unstacked(params, cfg),
                                     method=args.method, bits=args.bits,
                                     group_size=args.group_size,
                                     backend=args.backend,
                                     batched=not args.serial, mesh=mesh)
        Checkpointer(args.serving_ckpt, async_save=False).save_serving(
            args.serving_step, qserve,
            quant_meta=quant_tree_meta(args.bits, args.method,
                                       args.group_size, report=srep))
        print(f"[quantize] wrote serving checkpoint step "
              f"{args.serving_step} → {args.serving_ckpt}")


if __name__ == "__main__":
    main()
