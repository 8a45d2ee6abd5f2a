"""Percentile and tokens/s arithmetic on a hand-made record set."""

import pytest

from harness import metrics as M


def rec(i, due, first, n, gap, max_new=None, **kw):
    times = [first + k * gap for k in range(n)]
    r = {"id": f"r{i}", "due": due, "sent": due + 0.001, "status": 200,
         "error": None, "token_times": times, "tokens": [1] * n,
         "done": times[-1] + 0.001 if times else None,
         "max_new": n if max_new is None else max_new}
    r.update(kw)
    return r


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert M.percentile(xs, 95) == 95
    assert M.percentile(xs, 50) == 50
    assert M.percentile([5.0], 95) == 5.0
    assert M.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        M.percentile([], 95)


def test_ttft_is_taken_from_due_and_a_stalled_request_is_the_limit():
    recs = [rec(i, 10.0 + i, 10.0 + i + 0.1, 4, 0.02) for i in range(19)]
    # one that never got an answer, one refused
    recs.append(rec(19, 29.0, 0, 0, 0, max_new=4, done=None))
    recs.append(rec(20, 30.0, 0, 0, 0, max_new=4, status=429,
                    error="full"))
    t = M.ttft_ms(recs, drain_limit_s=60.0)
    assert t[:19] == pytest.approx([100.0] * 19)
    assert t[19] == t[20] == 60000.0
    assert M.percentile(t, 95) == 60000.0        # 2 of 21 beyond p90
    # the mean is over ALL requests too, the stalled ones at the limit
    from harness.runner import end_to_end
    e2e = end_to_end({"in_window": recs, "records": recs, "t_open": 10.0,
                      "t_close": 31.0, "seconds": 21.0, "setup_s": 1.0},
                     {"drain_limit_s": 60.0})
    assert e2e["ttft_mean_ms"][0] == pytest.approx(
        (19 * 100.0 + 2 * 60000.0) / 21)
    assert e2e["ttft_p95_ms"][0] == 60000.0
    assert M.in_flight(recs, 10.15) == 1 and M.in_flight(recs, 9.0) == 0
    assert sum(M.request_failed(r) for r in recs) == 2
    # sent late: TTFT still counts from when it was DUE
    late = rec(0, 10.0, 10.5, 2, 0.1, sent=10.4)
    assert M.ttft_ms([late], 60.0)[0] == pytest.approx(500.0)
    assert M.lateness_ms([late])["max_ms"] == pytest.approx(400.0)


def test_a_short_answer_is_a_failed_request():
    assert M.request_failed(rec(0, 1.0, 1.1, 3, 0.1, max_new=4))
    assert not M.request_failed(rec(0, 1.0, 1.1, 4, 0.1))


def test_gaps_are_pooled_over_requests():
    recs = [rec(0, 0.0, 1.0, 3, 0.010), rec(1, 0.0, 1.0, 2, 0.500)]
    gaps = M.inter_token_ms(recs)
    assert sorted(gaps) == pytest.approx([10.0, 10.0, 500.0])
    assert M.percentile(gaps, 95) == pytest.approx(500.0)


def test_tokens_per_second_counts_events_inside_the_window_only():
    recs = [rec(0, 5.0, 9.0, 40, 0.1),      # 9.0 .. 12.9: 10..12.9 inside
            rec(1, 11.0, 11.5, 100, 0.1)]   # 11.5 .. 21.4: to 19.9 inside
    n = M.tokens_in_window(recs, 10.0, 20.0)
    assert n == 30 + 85
    assert [r["id"] for r in M.in_window(recs, 10.0, 20.0)] == ["r1"]


def test_tick_gap_is_the_host_time_between_two_ticks():
    from harness import cells
    _, read = cells.layer_metric("tick_gap_p50_ms.chat")
    ticks = [{"ts": 10.000, "dur_ms": 50.0}, {"ts": 10.080, "dur_ms": 70.0},
             {"ts": 10.170, "dur_ms": 50.0}, {"ts": 10.260, "dur_ms": 50.0}]
    run = {"window": {"ticks": ticks[::-1], "ring_full": False}}
    assert read(run, {}) == pytest.approx(30.0)     # gaps 30, 20, 40
    assert read({"window": {"ticks": ticks[:1], "ring_full": False}},
                {}) is None
    assert read({"window": {"ticks": ticks, "ring_full": True}}, {}) is None
