"""The traffic generator and the work counter, at tiny sizes."""

import numpy as np
import pytest

import traffic_gen
import workcount
from reference import STENCILS

BIG_SEED = 2**31 + 12345


def test_poisson_schedule_offers_rate_times_window_in_order():
    a = traffic_gen.arrival_times(40.0, 20.0, BIG_SEED)
    b = traffic_gen.arrival_times(40.0, 20.0, BIG_SEED)
    c = traffic_gen.arrival_times(40.0, 20.0, BIG_SEED + 1)
    assert len(a) == len(c) == 800
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 20.0
    # Gaps of a Poisson process: exponential, mean 1 / rate.
    assert np.mean(np.diff(a)) == pytest.approx(1 / 40, rel=0.1)


def test_stream_blocks_hold_exact_zipf_shares_and_every_frame_equally():
    traffic = {"apps": {"law": "zipf", "s": 1.1}}
    s = traffic_gen.RequestStream(traffic, 5, 16, BIG_SEED)
    block = [s[k] for k in range(traffic_gen.BLOCK)]
    apps = np.bincount([a for _, a in block], minlength=5)
    frames = np.bincount([f for f, _ in block], minlength=16)
    weights = [1 / (i + 1) ** 1.1 for i in range(5)]
    want = [traffic_gen.BLOCK * w / sum(weights) for w in weights]
    assert sum(apps) == traffic_gen.BLOCK
    assert all(abs(n - w) < 1 for n, w in zip(apps, want))
    assert list(frames) == [traffic_gen.BLOCK // 16] * 16
    other = traffic_gen.RequestStream(traffic, 5, 16, BIG_SEED + 1)
    assert [other[k][1] for k in range(64)] != [a for _, a in block[:64]]
    assert sorted(other[k][1] for k in range(traffic_gen.BLOCK)) == sorted(
        a for _, a in block)


def test_closed_loop_client_count_is_the_concurrency():
    import threading
    import time

    import loadgen

    inflight, peak = [0], [0]
    lock = threading.Lock()

    class Handle:
        def result(self, timeout=None):
            time.sleep(0.002)
            with lock:
                inflight[0] -= 1
            return np.zeros((2, 2), np.int32)

    def submit(k):
        with lock:
            inflight[0] += 1
            peak[0] = max(peak[0], inflight[0])
        return Handle()

    load = loadgen.Load(submit)
    loadgen.closed_loop(load, 5, time.perf_counter(), 0.3)
    assert peak[0] == 5
    assert all(r.digest == loadgen.digest(np.zeros((2, 2), np.int32))
               for r in load.records.values())


def test_frame_size_law_is_seed_free_and_pixels_are_seeded():
    frames = {"law": "fixed", "height": 6, "width": 10, "pool": 16,
              "values": [0, 255]}
    a = traffic_gen.frame_pool(frames, BIG_SEED, np.int32)
    b = traffic_gen.frame_pool(frames, BIG_SEED, np.int32)
    c = traffic_gen.frame_pool(frames, BIG_SEED + 1, np.int32)
    assert [x.shape for x in a] == [x.shape for x in c] == [(6, 10)] * 16
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert min(int(x.min()) for x in a) == 0 and max(int(x.max()) for x in a) == 255
    assert a[0].dtype == np.int32
    with pytest.raises(ValueError):
        traffic_gen.frame_pool(dict(frames, law="ragged"), BIG_SEED, np.int32)


def test_work_counts_the_dataflow_graph_and_one_read_one_write():
    assert workcount.ops_per_pixel("sobel_x") == 17
    assert workcount.ops_per_pixel("gauss3") == 18
    assert workcount.ops_per_pixel("threshold") == 1
    assert {workcount.ops_per_pixel(a) for a in STENCILS} == {17, 18}
    ops, nbytes = workcount.request_work(["gauss3", "sobel_x", "threshold"],
                                         (1080, 1920), 4)
    assert ops == 36 * 1080 * 1920 and nbytes == 8 * 1080 * 1920
    peak = {"int32_vector_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert workcount.roofline_s(ops, nbytes, peak) == pytest.approx(
        8 * 1080 * 1920 / 1e11)
    with pytest.raises(KeyError):
        workcount.peaks("no such chip")
    assert workcount.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
