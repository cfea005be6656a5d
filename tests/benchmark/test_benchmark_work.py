"""The work-counting functions against hand-worked values, the table of
peaks, and percentile and failure accounting."""

import pytest

import bench_paths  # noqa: F401 - sets sys.path

from harness import stats, work
from harness.peaks import peaks_for

MINILM = {"hidden_size": 384, "num_hidden_layers": 6, "intermediate_size": 1536}
GPT2M = {"n_embd": 1024, "n_layer": 24, "n_inner": None, "vocab_size": 50257}


def test_encoder_flops_minilm_l6_at_128():
    # per layer: 2*128*384*(4*384 + 2*1536) = 452,984,832 for the matrices
    # and 4*128*128*384 = 25,165,824 for attention; six layers
    assert work.encoder_flops(MINILM, 128) == 6 * (452984832 + 25165824)
    assert work.encoder_flops(MINILM, 128) == pytest.approx(2.869e9, rel=1e-3)


def test_knn_scan_is_6_44_gb_and_memory_bound_below_240_queries():
    nbytes = work.knn_scan_bytes(8388608, 384)
    assert nbytes == 8388608 * 384 * 2 == 6442450944
    peaks = peaks_for("TPU v5 lite")
    least, bound = work.least_seconds(
        work.knn_scan_flops(32, 8388608, 384), nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(7.866e-3, rel=1e-3)
    _least, bound = work.least_seconds(
        work.knn_scan_flops(256, 8388608, 384), nbytes, peaks)
    assert bound == "compute"


def test_decode_step_bytes_gpt2_medium():
    # matrices: 24 * 12 * 1024^2 = 301,989,888; tied head 50,257 * 1024
    assert work.decoder_matmul_param_count(GPT2M) == 301989888 + 51463168
    params = work.decoder_param_bytes(GPT2M)
    assert params == pytest.approx(707.4e6, rel=2e-3)
    # K and V of one position: 2 * 24 * 1024 * 2 bytes
    assert work.kv_bytes_per_token(GPT2M) == 98304
    assert work.decode_step_bytes(GPT2M, 16 * 480) == params + 7680 * 98304


def test_answer_flops_counts_one_prefill_and_the_steps_after_it():
    prefill = work.prefill_flops(GPT2M, 450)
    assert work.answer_flops(GPT2M, 450, 1) == prefill
    two = work.answer_flops(GPT2M, 450, 2)
    assert two - prefill == work.decode_step_flops(GPT2M, 1, 451)
    # about 0.7 GFLOP a token through 354M parameters
    assert work.decode_step_flops(GPT2M, 1, 0) == pytest.approx(0.707e9,
                                                                rel=1e-2)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_others():
    row = peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks_for("_source")


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (95, 4.8), (25, 2.0)])
def test_percentile_interpolates_between_closest_ranks(q, want):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(want)


def test_a_failed_request_stays_in_the_percentile_as_a_miss():
    ok = [100.0] * 18
    lat = stats.latencies_with_misses(ok, 2, timeout_ms=60000.0)
    assert len(lat) == 20 and max(lat) == 60000.0
    assert stats.percentile(lat, 50) == 100.0
    assert stats.percentile(lat, 95) > 100.0
    # a latency longer than the timeout sets the miss
    lat = stats.latencies_with_misses([70000.0], 1, timeout_ms=60000.0)
    assert lat == [70000.0, 70000.0]
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics

    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))


def test_host_watch_reports_collections_and_pauses_inside_the_window():
    import gc
    import time

    from harness.generators import HostWatch

    with HostWatch() as watch:
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
        gc.collect()            # after the window: not counted
    facts = watch.facts(t0, t1)
    assert facts["gc_full_collections"] == 1
    assert facts["gc_longest"][2] == 2 and facts["gc_longest"][0] >= 0
    # a collection holds the interpreter, so in a process with a large heap
    # the sleeper may itself have seen it: only a pause put in by hand is
    # compared, one inside the window and one after it
    watch.pauses[:] = [(t0 + 0.5 * (t1 - t0), 3.4), (t1 + 1.0, 0.5)]
    assert watch.facts(t0, t1)["host_pauses"] == [
        [round(0.5 * (t1 - t0), 2), 3.4]]
    assert watch._on_gc not in gc.callbacks
