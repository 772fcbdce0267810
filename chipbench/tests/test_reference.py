"""The plain references against the program, on the CPU at the program's
reduced widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights
from conftest import TINY_ARCH
from reference import gqa
from reference import squant as rs


def granite_tiny():
    cf = harness.load_json(harness.HERE / "configs" / "granite-3-8b-pp2.json")
    cfg = harness.program_config(cf, dict(TINY_ARCH, dtype="float32",
                                          scan_layers=False))
    return cf, cfg


def test_gqa_reference_matches_program_forward():
    from repro.models.model import build_model
    cf, cfg = granite_tiny()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, 2**32 + 3, jnp.float32)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    get = weights.leaf_maker(shapes, 2**32 + 3, jnp.float32)

    def layer(l):
        pre = ("stack", "list", str(l), "b0")
        p = {"ln1": get(pre + ("ln1", "gain")),
             "ln2": get(pre + ("ln2", "gain"))}
        for n in ("wq", "wk", "wv", "wo"):
            p[n] = get(pre + ("attn", n, "w"))
        for n in ("wi", "wg", "wdown"):
            p[n] = get(pre + ("ffn", n, "w"))
        return p

    rcfg = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                norm_eps=cf["departures"]["rms_norm_eps"]["run"])
    emb = get(("embedding", "embedding"))
    x = gqa.forward_hidden(tokens, emb, layer, cfg.n_layers, rcfg)
    pos = jnp.asarray(np.tile(np.arange(48), (2, 1)))
    got = gqa.logits_at(x, pos, get(("final_norm", "gain")), emb,
                        eps=rcfg["norm_eps"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(np.abs(want).max()))
    # served greedy tokens lie 0 below the best; the runner-up does not
    top2 = np.argsort(np.asarray(want), -1)[..., -2:]
    valid = jnp.ones((2, 48), bool)
    assert float(gqa.gaps(got, jnp.asarray(top2[..., 1]), valid).max()) == 0
    assert float(gqa.gaps(got, jnp.asarray(top2[..., 0]), valid).min()) > 0


def test_leaf_maker_gives_the_tree_bits():
    _, cfg = granite_tiny()
    from repro.models.model import build_model
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    tree = weights.make_params(shapes, 77, jnp.bfloat16)
    get = weights.leaf_maker(shapes, 77, jnp.bfloat16)
    for kp, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        assert np.array_equal(np.asarray(get(weights.path_names(kp))),
                              np.asarray(x))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape,group", [((48, 512), 128), ((40, 64), None),
                                         ((16, 2048), 128)])
def test_squant_reference_matches_program(bits, shape, group):
    from repro.kernels.ref import squant_ref
    from repro.quant.qtypes import from_codes
    from repro.quant.scales import compute_scale
    rng = np.random.default_rng(bits * 100 + shape[1])
    w = np.asarray(jnp.asarray(rng.standard_normal(shape) * 0.05,
                               jnp.bfloat16), np.float32)
    s = compute_scale(jnp.asarray(w), bits, "max")
    want = np.asarray(squant_ref(jnp.asarray(w), s, bits=bits,
                                 group_size=group))
    got, _ = rs.codes(w, bits, group, s=np.asarray(s))
    assert np.array_equal(got, want)
    # own scales agree to the last bit or one unit in it
    own = rs.scales(w, bits)
    assert np.max(np.abs(own - np.asarray(s)) / own) <= 2.0 ** -23
    qt = from_codes(jnp.asarray(want), s, bits)
    assert np.array_equal(rs.pack(got, bits), np.asarray(qt.data))
