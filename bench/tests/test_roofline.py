import numpy as np
import pytest

from bench import roofline


def test_scan_work_counts_distinct_buckets_once():
    d = 4
    # live rows: bucket A x3, bucket B x2, bucket C x1 (k = 2)
    A, B, C = [0, 1], [2, 3], [4, 5]
    live = np.array([[A, A, A, B, B, C]], np.int32)          # (T=1, 6, 2)
    # one batch of two queries, L = 3 probes each
    probes = np.array([[[A, A, B],                           # q0: A, B
                        [B, [9, 9], B]]], np.int32)          # q1: B, absent
    (flops, nbytes), = roofline.scan_work(live, [probes], d)
    # q0 pairs: 3 + 2 rows, q1: 2 rows -> 7 pairs of 2*d flops
    assert flops == 7 * 2 * d
    # distinct buckets of the batch: A, B (absent reads nothing)
    assert nbytes == (3 + 2) * (4 * d + 4)


def test_tables_are_part_of_the_bucket():
    d = 2
    live = np.array([[[1, 1]], [[1, 1]]], np.int32)           # (T=2, 1, 2)
    probes = np.array([[[[1, 1]]], [[[1, 1]]]], np.int32)     # (2, 1, 1, 2)
    (flops, nbytes), = roofline.scan_work(live, [probes], d)
    assert flops == 2 * 2 * d and nbytes == 2 * (4 * d + 4)


def test_least_seconds_per_batch_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    t, bound = roofline.least_seconds([(100.0, 1.0), (1.0, 100.0)], peak)
    assert t == pytest.approx(1.0 + 10.0) and bound == "bytes"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
