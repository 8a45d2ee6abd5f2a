"""The fan-out readers (``fanout_cpu_ms.*``, ``fanout_wakes_per_event.*``,
``fanout_lag_mean_ms.*``) on synthetic ticks: their arithmetic, None when
the ring wrapped, and None where no tick carries the flight ring's
``fanout_*`` fields, as on a program from before them."""

import pytest

from harness import cells

MIXES = ("chat", "batch", "longdoc", "longgen")
# two cycles of a streaming window and one without a client to write to
TICKS = [
    {"fanout_broker_cpu_ms": 4.0, "fanout_door_cpu_ms": 2.0,
     "fanout_wakes": 96, "fanout_events": 32, "fanout_lag_ms_sum": 64.0,
     "fanout_lag_n": 30, "fanout_lag_ms_max": 3.5},
    {"fanout_broker_cpu_ms": 1.5, "fanout_door_cpu_ms": 0.5,
     "fanout_wakes": 8, "fanout_events": 8, "fanout_lag_ms_sum": 16.0,
     "fanout_lag_n": 8, "fanout_lag_ms_max": 2.5},
    {"fanout_broker_cpu_ms": 0.0, "fanout_door_cpu_ms": 0.0,
     "fanout_wakes": 0, "fanout_events": 0, "fanout_lag_ms_sum": 0.0,
     "fanout_lag_n": 0, "fanout_lag_ms_max": 0.0},
]
WANT = {"fanout_cpu_ms": (6.0 + 2.0 + 0.0) / 3,
        "fanout_wakes_per_event": 104 / 40,
        "fanout_lag_mean_ms": 80.0 / 38}


def _read(name, ticks, ring_full=False):
    spec, read = cells.layer_metric(name)
    return read({"window": {"ring_full": ring_full, "ticks": ticks}},
                spec.get("params", {}))


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("reader", sorted(WANT))
def test_the_arithmetic(reader, mix):
    # a tick of the parent's program (no fields) is passed over
    got = _read(f"{reader}.{mix}", TICKS + [{"dur_ms": 12.0}])
    assert got == pytest.approx(WANT[reader])


@pytest.mark.parametrize("reader", sorted(WANT))
def test_a_wrapped_ring_reads_none(reader):
    for mix in MIXES:
        assert _read(f"{reader}.{mix}", TICKS, ring_full=True) is None


@pytest.mark.parametrize("reader", sorted(WANT))
def test_no_tick_with_the_fields_reads_none(reader):
    for mix in MIXES:
        assert _read(f"{reader}.{mix}", []) is None
        assert _read(f"{reader}.{mix}",
                     [{"dur_ms": 12.0, "flush_events": 32}]) is None


def test_nothing_written_reads_none_where_it_divides():
    assert _read("fanout_wakes_per_event.chat", TICKS[2:]) is None
    assert _read("fanout_lag_mean_ms.chat", TICKS[2:]) is None
    assert _read("fanout_cpu_ms.chat", TICKS[2:]) == 0.0


def test_twelve_entries_beside_pump_broker_ms():
    """One entry a reader and mix, in the layer of ``pump_broker_ms.*``,
    moving what it moves, in its cells."""
    per_layer = {m["name"]: m for m in cells.benchmark()["per_layer"]}
    for reader in WANT:
        for mix in MIXES:
            m, ref = (per_layer[f"{reader}.{mix}"],
                      per_layer[f"pump_broker_ms.{mix}"])
            assert m["better"] == "lower"
            assert [m[k] for k in ("layer", "moves", "workloads")] == \
                [ref[k] for k in ("layer", "moves", "workloads")]
