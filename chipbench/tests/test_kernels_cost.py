"""Operations and bytes of each kernel, against shapes worked by hand."""
import pytest

import harness

dm = harness.load_module("kernels", "dequant_matmul")
pa = harness.load_module("kernels", "paged_attention")
sq = harness.load_module("kernels", "squant_flip")


def test_dequant_matmul_granite_up_projection():
    # 64 decode rows through a 12800 x 4096 int8 weight: 2*64*12800*4096
    # operations; the codes (52428800 B) and f32 scales (51200 B) once,
    # bf16 activations in (64*4096*2) and out (64*12800*2)
    f, b = dm.cost(64, 12800, 4096, 8)
    assert f == 6710886400.0
    assert b == 52428800 + 51200 + 524288 + 1638400
    # packed int4 halves the code bytes; three calls read them three times
    f4, b4 = dm.cost(64, 12800, 4096, 4, calls=3)
    assert f4 == f
    assert b4 == 3 * (26214400 + 51200) + 524288 + 1638400


def test_paged_attention_granite_context():
    # 1000 positions of granite (32 heads, 8 KV heads of 128), bf16 pool:
    # q.k and p.v are 2 * 2 * 1000 * 32 * 128 operations; K and V are
    # 2 * 1000 * 8 * 128 * 2 bytes; one query in and one out, 32*128*2 each
    f, b = pa.cost(1000, 1, 32, 8, 128)
    assert f == 16384000.0
    assert b == 4096000 + 16384


def test_squant_flip_rwkv_matrix():
    # one 2048 x 2048 bf16 matrix to w4: read 8 MiB, write 2 MiB of
    # packed codes and 2048 f32 scales
    f, b = sq.cost(2048, 2048, 4)
    assert f == 0.0
    assert b == 8388608 + 2097152 + 8192


@pytest.mark.parametrize("name", ["dequant_matmul", "paged_attention",
                                  "squant_flip"])
def test_kernel_files_declare_patterns_and_peak(name):
    mod = harness.load_module("kernels", name)
    assert mod.PATTERNS and mod.PEAK in harness.peaks_for("TPU v5 lite")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def _rec(trace_kernels, work):
    return harness.Record(
        e2e={}, checks=[], attempted=1, failed=0, memory_peak_bytes=0,
        layer={"work": work, "peaks": harness.peaks_for("TPU v5 lite")},
        trace={"kernels": trace_kernels})


def test_roofline_reader_takes_the_binding_bound():
    read = harness.load_module("metrics", "dequant_matmul_roofline").read
    # 819e9 bytes need 1 s at the HBM peak; 197e12 operations need 1 s too:
    # the larger bound over 4 s of kernel time is 25%
    rec = _rec({"dequant_matmul": 4.0},
               {"dequant_matmul": {"flops": 197e12, "bytes": 819e9 / 2}})
    assert read(rec) == pytest.approx(25.0)
    rec = _rec({"dequant_matmul": 4.0},
               {"dequant_matmul": {"flops": 0.0, "bytes": 819e9}})
    assert read(rec) == pytest.approx(25.0)


def test_roofline_reader_without_events_reads_none():
    read = harness.load_module("metrics", "paged_attention_roofline").read
    rec = _rec({"paged_attention": None},
               {"paged_attention": {"flops": 1.0, "bytes": 1.0}})
    assert read(rec) is None
