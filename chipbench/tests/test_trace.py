"""The trace reduction, on a TPU v5e trace kept beside this file: two
rounds of ``dequant_matmul``, ``squant_flip`` and ``paged_attention`` at
small shapes, recorded on the chip."""
from pathlib import Path

import pytest

import harness
import trace as T

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb"
KERNELS = {k: harness.load_module("kernels", k).PATTERNS
           for k in ("dequant_matmul", "squant_flip", "paged_attention")}


@pytest.fixture(scope="module")
def planes():
    return T.read_planes(DATA)


def test_recorded_trace_kernels(planes):
    devices, host = planes
    assert len(devices) == 1 and len(devices[0]) == 32
    r = T.reduce(devices, host, KERNELS)
    # every kernel ran twice: squant_flip is two custom calls a round
    assert r["kernel_events"] == {"dequant_matmul": 2, "squant_flip": 4,
                                  "paged_attention": 2}
    assert r["kernels"]["squant_flip"] == pytest.approx(778.017e-6)
    assert r["kernels"]["paged_attention"] == pytest.approx(95.964e-6)
    assert r["kernels"]["dequant_matmul"] == pytest.approx(2.014e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    ops = dict(r["device_ops"])
    assert ops["squant_pallas"] == r["kernels"]["squant_flip"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)


def test_missing_kernel_reads_none(planes):
    r = T.reduce(*planes, {"absent": [r"^%no_such_kernel"]})
    assert r["kernels"]["absent"] is None


def test_union_window_and_gap_labels():
    dev = [[("%a.1 = f32[] fusion()", 10.0, 10.0),
            ("%b = f32[] fusion()", 15.0, 10.0),     # overlaps a
            ("%a.2 = f32[] fusion()", 40.0, 10.0),
            ("%c = f32[] custom-call()", 95.0, 20.0)]]  # runs past the end
    host = [(T.WINDOW_SPAN, 0.0, 100.0), ("outer", 20.0, 70.0),
            ("dispatch", 30.0, 5.0)]
    r = T.reduce(dev, host, {"a": [r"^%a\b"]})
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((15 + 10 + 5) * 1e-9)
    assert r["kernels"]["a"] == pytest.approx(20e-9)
    assert dict(r["device_ops"])["a"] == pytest.approx(20e-9)
    gaps = dict(r["idle_gaps"])
    # gaps [0,10), [25,40), [50,95): the first lies under no host event,
    # the middle of the second (32.5) under the shorter "dispatch", the
    # third under "outer" alone
    assert gaps["dispatch"] == pytest.approx(15e-9)
    assert gaps["outer"] == pytest.approx(45e-9)
    assert gaps["host: no span"] == pytest.approx(10e-9)


def test_op_name():
    assert T.op_name("%dequant_matmul_pallas.1 = bf16[16,256] "
                     "custom-call(...)") == "dequant_matmul_pallas"
    assert T.op_name("%copy-start = (f32[2]) copy-start()") == "copy-start"
    assert T.op_name("%fusion.12.3 = f32[] fusion()") == "fusion"
