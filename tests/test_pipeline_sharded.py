"""Sharded multi-device quantize_tree (ISSUE 2 tentpole).

The contract: row-partitioning each bucket over the mesh's 'data' axis under
shard_map must be *bit-exact* against both the unsharded batched path and
the serial per-layer oracle — SQuant's flip objective is row-independent, so
the partition is exact, not approximate. Real multi-device coverage comes
from the ``multidevice_run`` conftest harness, which spawns subprocesses
that genuinely see 2 or 8 host-platform devices (CI's CPU-only runners
included). The in-process tests at the bottom additionally run on however
many devices the parent holds (1 on the fast lane; 8 on CI's ``multidevice``
lane, which sets ``--xla_force_host_platform_device_count=8``).
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import quantize_tree
from repro.launch.mesh import make_quantize_mesh

# Tree covers: three dense layers sharing one bucket whose stacked row count
# (3 × 9 = 27) does NOT divide 2 or 8 (exercises the padding), an expert
# bank, and a never-quantized vector.
_TREE_SCRIPT = """
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.core.pipeline import quantize_tree
from repro.launch.mesh import make_quantize_mesh

assert len(jax.devices()) == {devices}, jax.devices()
rng = np.random.default_rng(0)
def w(*shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))
tree = {{"blk0": {{"attn": {{"w": w(16, 9)}},
                  "norm": {{"gain": jnp.ones((16,), jnp.float32)}}}},
         "blk1": {{"attn": {{"w": w(16, 9)}}}},
         "blk2": {{"attn": {{"w": w(16, 9)}}}},
         "moe": {{"w": w(3, 16, 24)}}}}

mesh = make_quantize_mesh()
q_sh, rep = quantize_tree(tree, method="squant", bits=4, group_size=8,
                          mesh=mesh)
q_un, _ = quantize_tree(tree, method="squant", bits=4, group_size=8)
q_se, _ = quantize_tree(tree, method="squant", bits=4, group_size=8,
                        batched=False)
for path in (("blk0", "attn"), ("blk1", "attn"), ("blk2", "attn"),
             ("moe",)):
    a, b, c = q_sh, q_un, q_se
    for k in path:
        a, b, c = a[k], b[k], c[k]
    a, b, c = a["w"], b["w"], c["w"]
    assert np.array_equal(np.asarray(a.codes()), np.asarray(b.codes())), path
    assert np.array_equal(np.asarray(a.scale), np.asarray(b.scale)), path
    assert np.array_equal(np.asarray(a.codes()), np.asarray(c.codes())), path
    assert np.array_equal(np.asarray(a.scale), np.asarray(c.scale)), path

# shard breakdown: every device accounted for, rows sum to the real total
assert rep.mesh_axis == "data" and rep.mesh_size == {devices}
assert len(rep.shards) == {devices}
total_rows = 9 * 3 + 3 * 24          # dense bucket rows + expert bank rows
assert sum(s.rows for s in rep.shards) == total_rows, rep.shards
if {devices} > 1:
    assert sum(s.pad_rows for s in rep.shards) > 0   # 27 % ndev != 0
# codes/scales inherited mesh shardings (not single-device)
sh = q_sh["blk0"]["attn"]["w"].data.sharding
assert getattr(sh, "mesh", None) is not None and sh.mesh.size == {devices}, sh
print("SHARDED-OK", rep.summary())
"""


@pytest.mark.parametrize("devices", [2, 8])
def test_sharded_bit_exact_multidevice(multidevice_run, devices):
    """Sharded vs unsharded vs serial codes+scales, 2- and 8-device meshes,
    non-divisible row counts exercising the padding."""
    out = multidevice_run(_TREE_SCRIPT.format(devices=devices),
                          devices=devices, timeout=900)
    assert "SHARDED-OK" in out


def test_sharded_rtn_and_backends_multidevice(multidevice_run):
    """RTN (no flip kernel) and the interpret backend (Pallas kernel body)
    both survive the shard_map row partition bit-exactly."""
    out = multidevice_run(textwrap.dedent("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.pipeline import quantize_tree
        from repro.launch.mesh import make_quantize_mesh
        rng = np.random.default_rng(1)
        tree = {"a": {"w": jnp.asarray(
            rng.normal(size=(16, 12)).astype(np.float32))}}
        mesh = make_quantize_mesh(4)
        for method, backend in (("rtn", "ref"), ("squant", "interpret"),
                                ("squant_e", "ref")):
            q_sh, _ = quantize_tree(tree, method=method, bits=4, group_size=8,
                                    mesh=mesh, backend=backend)
            q_un, _ = quantize_tree(tree, method=method, bits=4, group_size=8,
                                    backend=backend)
            assert np.array_equal(np.asarray(q_sh["a"]["w"].codes()),
                                  np.asarray(q_un["a"]["w"].codes())), method
            assert np.array_equal(np.asarray(q_sh["a"]["w"].scale),
                                  np.asarray(q_un["a"]["w"].scale)), method
        print("BACKENDS-OK")
    """), devices=4, timeout=900)
    assert "BACKENDS-OK" in out


# ---------------------------------------------------------------------------
# In-process coverage: runs on however many devices this process sees
# (1 on the plain fast lane — still a real mesh through the real shard_map
# code path; 8 on the CI multidevice lane).
# ---------------------------------------------------------------------------

def _tree(rng):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return {"a": {"w": w(16, 8)}, "b": {"w": w(16, 8)},
            "moe": {"w": w(2, 16, 8)}}


def test_sharded_inprocess_bit_exact(rng):
    mesh = make_quantize_mesh()
    src = _tree(rng)
    q_sh, rep = quantize_tree(src, bits=4, group_size=8, mesh=mesh)
    q_un, _ = quantize_tree(src, bits=4, group_size=8)
    for k in ("a", "b", "moe"):
        np.testing.assert_array_equal(np.asarray(q_sh[k]["w"].codes()),
                                      np.asarray(q_un[k]["w"].codes()))
        np.testing.assert_array_equal(np.asarray(q_sh[k]["w"].scale),
                                      np.asarray(q_un[k]["w"].scale))
    ndev = len(jax.devices())
    assert rep.mesh_size == ndev and len(rep.shards) == ndev
    assert sum(s.rows for s in rep.shards) == 8 * 2 + 2 * 8
    if ndev > 1:
        assert rep.mesh_axis == "data"
        assert "sharded data=" in rep.summary()


def test_sharded_bf16_tree_bit_exact(rng):
    """bf16 weights (the published configs' dtype): scales are computed in
    f32, so the sharded program — which fuses the scale into the divide —
    and the unsharded one quantize against the same scale bits (a bf16
    scale let XLA skip its rounding in one program and not the other)."""
    mesh = make_quantize_mesh()
    src = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                 {"a": {"w": jnp.asarray(
                                     rng.normal(size=(256, 384)),
                                     jnp.float32)}})
    q_sh, _ = quantize_tree(src, bits=4, group_size=128, mesh=mesh,
                            backend="ref")
    q_un, _ = quantize_tree(src, bits=4, group_size=128, backend="ref")
    np.testing.assert_array_equal(np.asarray(q_sh["a"]["w"].codes()),
                                  np.asarray(q_un["a"]["w"].codes()))
    np.testing.assert_array_equal(np.asarray(q_sh["a"]["w"].scale),
                                  np.asarray(q_un["a"]["w"].scale))


def test_sharded_dequantize_matches_unsharded(rng):
    mesh = make_quantize_mesh()
    src = _tree(rng)
    t_sh, _ = quantize_tree(src, bits=4, group_size=8, mesh=mesh,
                            dequantize=True)
    t_un, _ = quantize_tree(src, bits=4, group_size=8,
                            dequantize=True)
    for a, b in zip(jax.tree_util.tree_leaves(t_sh),
                    jax.tree_util.tree_leaves(t_un)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_single_sync(rng, monkeypatch):
    """The sharded path keeps the batched pipeline's ONE-sync contract."""
    from repro.core import pipeline
    calls = []
    real = pipeline._sync
    monkeypatch.setattr(pipeline, "_sync",
                        lambda x: (calls.append(1), real(x))[1])
    quantize_tree(_tree(rng), bits=4, group_size=8,
                  mesh=make_quantize_mesh())
    assert len(calls) == 1


def test_mesh_validation(rng):
    with pytest.raises(ValueError):        # serial is single-device
        quantize_tree(_tree(rng), mesh=make_quantize_mesh(), batched=False)
    from repro.distributed import compat
    no_data = compat.make_mesh((1,), ("model",))
    with pytest.raises(ValueError):        # mesh must carry the row axis
        quantize_tree(_tree(rng), mesh=no_data)
