"""The benchmark's work functions and peaks table, on shapes worked by hand."""
import pytest

from bench import peaks, work


def test_spmm_counts_flops_and_compulsory_bytes():
    # 3 x 4 CSR with 5 entries, F = 2: 2 * 5 * 2 operations; rowptr 4,
    # colind 5, values 5 words; B 4 x 2 read, C 3 x 2 written
    w = work.spmm(3, 4, 5, 2)
    assert w.flops == 20
    assert w.bytes == (4 + 5 + 5) * 4 + (8 + 6) * 4


def test_matmul_counts_flops_and_bytes():
    w = work.matmul(2, 3, 4)
    assert w.flops == 48
    assert w.bytes == (6 + 12 + 8) * 4


@pytest.mark.parametrize("flops,nbytes,want", [
    (197e12, 1.0, 1.0),      # compute-bound: 1 s of peak FLOPs
    (1.0, 819e9 * 2, 2.0),   # bandwidth-bound: 2 s of peak bytes
])
def test_least_time_is_the_larger_bound(flops, nbytes, want):
    assert work.Work(flops, nbytes).least_s(197e12, 819e9) == pytest.approx(want)


def test_sage_spmms_one_forward_and_one_backward_per_layer():
    spmms = work.sage_spmms(10, 30, (5, 8, 8, 3))
    assert [w.flops for w in spmms] == [2 * 30 * f for f in (8, 8, 8, 8, 3, 3)]


def test_sage_step_flops_by_hand():
    # n = 10, nnz = 30, widths 5 -> 8 -> 3
    # layer 0: matmul 2*10*5*8 = 800, spmm 2*30*8 = 480:
    #   forward 2*800 + 480, backward 2*800 + 480 (no input gradient)
    # layer 1: matmul 2*10*8*3 = 480, spmm 2*30*3 = 180:
    #   forward 2*480 + 180, backward 2*480 + 180, input gradient 2*480
    want = 2 * (1600 + 480) + 2 * (960 + 180) + 960
    assert work.sage_step_flops(10, 30, (5, 8, 3)) == want


def test_peaks_of_the_v5e():
    p = peaks.for_kind("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5e", "cpu", ""])
def test_peaks_refuse_an_unknown_device_kind(kind):
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        peaks.for_kind(kind)
