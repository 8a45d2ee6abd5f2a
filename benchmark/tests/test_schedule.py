"""The traffic generator: one seed one schedule, every seed the same work."""

from collections import Counter

from harness import cells, schedule

CHAT = cells.load_json(cells.os.path.join(cells.BENCH_DIR, "traffic",
                                          "chat.json"))
BATCH = cells.load_json(cells.os.path.join(cells.BENCH_DIR, "traffic",
                                           "batch.json"))


def _chat(seed):
    return schedule.make(CHAT, seed=seed, seconds=20, vocab=1000,
                         rate_rps=10.0)


def test_same_seed_same_schedule_and_seeds_differ():
    assert _chat(7) == _chat(7)
    assert _chat(7) != _chat(8)
    big = 2 ** 31 + 12345
    assert _chat(big) == _chat(big) and _chat(big) != _chat(7)


def test_every_seed_has_the_same_sizes_and_gaps():
    a, b = _chat(1), _chat(2)
    size = lambda rows: Counter((len(r["tokens"]), r["max_new"])
                                for r in rows)
    assert size(a) == size(b)
    gaps = lambda rows: sorted(round(y["due"] - x["due"], 5) for x, y in
                               zip(rows, rows[1:]))
    ramp = CHAT["ramp_s"]
    assert len(a) == 10 * ramp + 200 == len(b)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    assert gaps(a)[5:-5] != gaps(a)[:0]     # non-empty


def test_the_window_alone_holds_the_same_requests_for_every_seed():
    """Ramp and window are drawn apart: which long prompts are due in the
    window is not the seed's to choose, and none is due at or after its
    close."""
    ramp = CHAT["ramp_s"]
    win = lambda rows: [r for r in rows if r["due"] >= ramp]
    size = lambda rows: Counter((len(r["tokens"]), r["max_new"])
                                for r in rows)
    a, b = win(_chat(11)), win(_chat(2 ** 31 + 5))
    assert len(a) == 200 == len(b)
    assert size(a) == size(b)
    assert max(len(r["tokens"]) for r in a) == 2048     # the longest is in
    assert max(r["max_new"] for r in a) == 512
    for rows in (_chat(11), _chat(2 ** 31 + 5)):
        assert all(0 < r["due"] < ramp + 20 for r in rows)
        assert sum(r["due"] < ramp for r in rows) == 10 * ramp
        assert 19.0 < win(rows)[-1]["due"] - ramp < 20.0


def test_lengths_follow_the_mix():
    rows = _chat(3)
    plens = sorted(len(r["tokens"]) for r in rows)
    assert plens[0] >= 16 and plens[-1] <= 2048
    assert 230 <= plens[len(plens) // 2] <= 285       # median 256
    news = sorted(r["max_new"] for r in rows)
    assert news[0] >= 8 and news[-1] <= 512
    assert 115 <= news[len(news) // 2] <= 142         # median 128
    assert all(1 <= t < 1000 for r in rows for t in r["tokens"])


def test_closed_loop_deals_every_client_its_share():
    rows = schedule.make(BATCH, seed=5, seconds=30, vocab=500)
    assert len(rows) == 64 * 24
    per = Counter(r["client"] for r in rows)
    assert set(per.values()) == {24} and len(per) == 64
    plens = sorted(len(r["tokens"]) for r in rows)
    assert plens[0] >= 128 and plens[-1] <= 2048
    other = schedule.make(BATCH, seed=6, seconds=30, vocab=500)
    assert Counter(len(r["tokens"]) for r in rows) == Counter(
        len(r["tokens"]) for r in other)
    assert [r["tokens"] for r in rows] != [r["tokens"] for r in other]
