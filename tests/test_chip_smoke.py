"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases' checks hold at toy size on the CPU (the jnp references, the
reduced rwkv6 config), so a chip run fails only for the chip's own
reasons.
"""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_fails_without_tpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and "no TPU" in out[-1]
    with pytest.raises(ValueError):
        json.loads(out[-1])


def test_kernel_phase_toy(smoke):
    # the phase's own plumbing and checks; the kernels' interpret-mode
    # parity has its own tests (the CPU cannot run a bf16 x bf16 -> f32
    # dot inside interpret mode, which the packed w4 kernel needs)
    checks = smoke.Checks()
    smoke.kernel_phase(checks, impl="ref", squant_shapes=((16, 256),),
                       dequant_mn=(24, 512), dequant_batches=(8, 11),
                       paged_archs=(("toy", 4, 2, 128),), paged_context=64)
    assert not checks.failed


def test_serve_phase_toy(smoke):
    checks = smoke.Checks()
    smoke.serve_phase(checks, reduced=True, prompt_lens=(3, 9), max_new=4,
                      quant_backend="ref")
    assert not checks.failed
