"""The benchmark's own arithmetic: seeded traffic, the Zipf law, due-time
latency, the window's percentiles and rate, and kernel counts."""
import time
from concurrent.futures import Future

import numpy as np
import pytest

from chipbench import counts
from chipbench.tests.conftest import smoke_config
from chipbench.traffic import (BLOCK, Recorder, Traffic, open_loop,
                               readings, zipf_head_share)

SPECS = {"user_profile": ("user", (4,), "float32"),
         "user_seq_ids": ("user", (3,), "int32"),
         "item_ids": ("item", (), "int32"),
         "cross_context": ("cross", (2,), "float32")}
CFG = {"item_vocab": 1000, "init": {"feature_std": 1.0}}


def mix(**over):
    m = {"loop": "open", "rate_per_s": 50.0,
         "users": {"kind": "zipf", "s": 1.1, "universe": 100_000},
         "warm_users": 8, "pool": {"min": 16, "max": 64},
         "user_feature_pool": 32, "candidate_rows": 256, "base_seed": 9}
    m.update(over)
    return m


def test_seeded_traffic_repeats_and_seeds_permute_one_multiset():
    big = 2**31 + 12345
    a = Traffic(mix(), SPECS, CFG, big, 10.0)
    b = Traffic(mix(), SPECS, CFG, big, 10.0)
    for name in ("uids", "sizes", "due", "offsets"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    for k in a.cand_arrays:
        np.testing.assert_array_equal(a.cand_arrays[k], b.cand_arrays[k])
    for k in a.user_arrays:
        np.testing.assert_array_equal(a.user_arrays[k], b.user_arrays[k])
    c = Traffic(mix(), SPECS, CFG, big + 1, 10.0)
    assert not np.array_equal(a.sizes, c.sizes)
    np.testing.assert_array_equal(np.sort(a.sizes), np.sort(c.sizes))
    # each block of requests holds the same multiset under every seed, so
    # any window does the same work up to its last part-block
    for lo in range(0, a.n, BLOCK):
        np.testing.assert_array_equal(np.sort(a.sizes[lo:lo + BLOCK]),
                                      np.sort(c.sizes[lo:lo + BLOCK]))
        np.testing.assert_array_equal(np.sort(a.uids[lo:lo + BLOCK]),
                                      np.sort(c.uids[lo:lo + BLOCK]))
    np.testing.assert_array_equal(np.sort(a.uids), np.sort(c.uids))
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0)),
                               np.sort(np.diff(c.due, prepend=0)))
    assert a.user_feeds(5)["user_profile"].shape == (1, 4)
    assert a.cand_feeds(3)["cross_context"].shape == (a.sizes[3], 2)


def test_fresh_users_never_repeat():
    t = Traffic(mix(loop="closed", requests=50_000, clients=2,
                    users={"kind": "fresh", "universe": 100_000_000}),
                SPECS, CFG, 7, 1.0)
    assert len(np.unique(t.uids)) == t.n
    assert t.warm_users() == []


def test_zipf_head_share_matches_its_formula():
    # the top 8192 of 1M users draw 0.81 of requests
    assert zipf_head_share(1.1, 1_000_000, 8192) == pytest.approx(0.808,
                                                                  abs=1e-3)
    w = 1.0 / np.arange(1, 101) ** 1.1
    assert zipf_head_share(1.1, 100, 10) == pytest.approx(
        w[:10].sum() / w.sum(), rel=1e-12)
    t = Traffic(mix(loop="closed", requests=200_000, clients=1), SPECS, CFG,
                3, 1.0)
    share = float((t.uids < 1000).mean())
    assert share == pytest.approx(zipf_head_share(1.1, 100_000, 1000),
                                  abs=0.005)


class _Plan:
    """Due times every 10 ms and pools of 100 rows."""

    def __init__(self, n):
        self.n = n
        self.due = np.arange(n) * 0.01
        self.sizes = np.full(n, 100)


def _done(value=None):
    f = Future()
    f.set_result(value)
    return f


def test_due_time_latency_counts_a_stall_on_every_later_request():
    plan = _Plan(10)
    rec = Recorder(plan.n)

    def submit(i):
        if i == 3:                 # the sending path stalls for 200 ms
            time.sleep(0.2)
        return _done(i)

    t0, sent = open_loop(submit, plan, 1.0, rec)
    assert rec.wait_all(5)
    r = readings(rec, plan, t0, sent, 1.0)
    lat = (rec.done - (t0 + plan.due)) * 1e3
    # every request due while the stall lasted is timed from its due time
    for i in range(4, 10):
        assert lat[i] >= 200 - 10 * (i - 3) - 5
        assert r["late_ms"][i] >= 200 - 10 * (i - 3) - 5
    assert r["p50_ms"] >= 120
    assert r["p99_ms"] == pytest.approx(np.percentile(lat, 99))


def test_p99_over_every_request_and_rate_over_the_whole_window():
    n, seconds = 200, 1.0
    plan = _Plan(n)
    plan.due = np.linspace(0, 0.99, n)
    rec = Recorder(n)
    t0 = 100.0
    lat = np.full(n, 0.001)
    lat[::40] = 0.5                # 5 slow requests, some past the close
    for i in range(n):
        rec.results[i] = object()
        rec.done[i] = t0 + plan.due[i] + lat[i]
    rec.submitted[:] = t0 + plan.due
    r = readings(rec, plan, t0, n, seconds)
    assert r["p99_ms"] == pytest.approx(np.percentile(lat * 1e3, 99))
    assert r["p99_ms"] > 100       # the slow tail is in, late or not
    finished = rec.done <= t0 + seconds
    assert r["candidates_per_s"] == pytest.approx(
        plan.sizes[finished].sum() / seconds)
    assert finished.sum() < n


def test_kernel_counts_against_hand_worked_values():
    # DIN's mlp_0 candidate side: 4096 rows, K = 18 + 18 + 12 = 48,
    # N = 200, a stacked table of 2 users' partials
    assert counts.mari_matmul(4096, 48, 200, 2, True) == (
        78_643_200, 4 * (196_608 + 9_600 + 819_200 + 400) + 16_384)
    # a row-wise (B, N) acc-init block, no index
    assert counts.mari_matmul(128, 500, 64, 128, False) == (
        8_192_000, 4 * (64_000 + 32_000 + 8_192 + 8_192))
    # bd,uldh->blh: 4096 rows, 2 users, L=100, D=18, H=80
    assert counts.gather_einsum(4096, 2, 100, 18, 80) == (
        1_179_648_000, 4 * (73_728 + 288_000 + 32_768_000) + 16_384)
    # bl,uld->bd
    assert counts.gather_einsum(4096, 2, 100, 18, None) == (
        14_745_600, 4 * (409_600 + 3_600 + 73_728) + 16_384)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.min_seconds(1_179_648_000, 132_535_296, peak) == (
        pytest.approx(132_535_296 / 819e9))
    assert counts.min_seconds(10**12, 1, peak) == pytest.approx(1 / 197)


def _reference(name):
    from chipbench import spec
    return spec._module(spec.ROOT / "chipbench" / "reference" /
                        f"{name}.py", "test")


def test_mari_form_flops_per_candidate_against_hand_worked_values():
    import json
    from chipbench.tests.conftest import BENCH
    pr = json.loads((BENCH / "configs" / "paper-ranking.json").read_text())
    # attn_q 2*500*64 + q.k and p.v 2*2*128*64 + experts 4*(2*1064*512 +
    # 2*512*256) + gates 2*2*1064*4 + mixes 2*2*4*256 + towers
    # 2*(2*256*128 + 2*128*64 + 2*64)
    assert _reference("paper-ranking").stage2_flops_per_candidate(pr) == (
        64_000 + 32_768 + 4 * (1_089_536 + 262_144) + 17_024 + 4_096
        + 2 * (65_536 + 16_384 + 128))
    assert smoke_config("paper-ranking")["d_user_profile"] == 120
