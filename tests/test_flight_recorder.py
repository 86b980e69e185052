"""FlightRecorder unit tests on synthetic round streams: each anomaly
kind fires exactly once per episode and re-arms after the condition
clears, the ring stays bounded, postmortems round-trip through JSON,
and ``anomalies_total{kind}`` tracks the episode counts.  No models —
the recorder is fed hand-built per-round records (the same dict shape
``Engine._flight_round`` produces).
"""
import json

import pytest

from repro.serving import ANOMALY_KINDS, MetricsRegistry
from repro.serving.flight_recorder import FlightRecorder


def _round(wall_s=0.01, drafted=4, accepted=3, admitted=0, queued=0,
           active=2, free_target=10, free_draft=10, **kw):
    rec = {
        "round": kw.pop("round_idx", 0),
        "mode": "two_phase",
        "rows": active,
        "wall_s": wall_s,
        "drafted": drafted,
        "accepted": accepted,
        "admitted": admitted,
        "queued": queued,
        "active": active,
        "free_pages": {"target": free_target, "draft": free_draft},
        "t": 0.0,
    }
    rec.update(kw)
    return rec


def _warm(fr, n=20, **kw):
    """Feed n healthy rounds (past the default warmup of 16)."""
    fired = []
    for _ in range(n):
        fired += fr.record(_round(**kw))
    return fired


def test_disabled_recorder_is_inert():
    fr = FlightRecorder(0)
    assert not fr.enabled
    assert fr.record(_round()) == []
    snap = fr.snapshot()
    assert snap["rounds_recorded"] == 0 and snap["ring"] == []


def test_ring_stays_bounded():
    fr = FlightRecorder(8)
    for i in range(30):
        fr.record(_round(round_idx=i))
    snap = fr.snapshot()
    assert snap["rounds_recorded"] == 30
    assert len(snap["ring"]) == 8
    # the ring holds the LAST 8 rounds, in order
    assert [r["seq"] for r in snap["ring"]] == list(range(22, 30))


def test_slow_round_fires_once_per_episode_and_rearms():
    m = MetricsRegistry()
    fr = FlightRecorder(64, metrics=m)
    assert _warm(fr, 20) == []  # healthy warmup: nothing fires
    # 10x the median wall -> fires on the transition...
    assert fr.record(_round(wall_s=0.1)) == ["slow_round"]
    # ...but a CONTINUING slow episode does not re-fire
    assert fr.record(_round(wall_s=0.1)) == []
    # recovery re-arms; the next excursion is a new episode
    assert fr.record(_round()) == []
    assert fr.record(_round(wall_s=0.1)) == ["slow_round"]
    assert fr.snapshot()["anomalies"]["slow_round"] == 2
    assert m.value("anomalies_total", kind="slow_round") == 2


def test_slow_round_armed_only_after_warmup():
    fr = FlightRecorder(64)
    # round 3 is 100x the others — inside warmup, must NOT fire (compile
    # stalls look exactly like this)
    for i in range(10):
        assert fr.record(_round(wall_s=1.0 if i == 3 else 0.01)) == []


def test_acceptance_collapse_windowed():
    fr = FlightRecorder(64)
    _warm(fr, 20)  # healthy: accept rate 0.75
    fired = []
    for _ in range(8):  # 8-round window of 4 drafted / 0 accepted
        fired += fr.record(_round(accepted=0))
    assert fired == ["acceptance_collapse"]  # exactly once for the episode
    # recovery clears the window average above the floor -> re-arms
    for _ in range(8):
        assert fr.record(_round()) == []
    fired = []
    for _ in range(8):
        fired += fr.record(_round(accepted=0))
    assert fired == ["acceptance_collapse"]


def test_pool_exhausted_requires_queued_and_zero_free():
    fr = FlightRecorder(64)
    # zero free pages with an EMPTY queue is fine (drain tail)
    assert fr.record(_round(free_target=0)) == []
    # queued work + a dry pool is the anomaly — either pool
    assert fr.record(_round(queued=2, free_target=0)) == ["pool_exhausted"]
    assert fr.record(_round(queued=2, free_target=0)) == []  # latched
    assert fr.record(_round(queued=0)) == []  # clears
    assert fr.record(_round(queued=1, free_draft=0)) == ["pool_exhausted"]


def test_admission_stall_counts_consecutive_rounds():
    fr = FlightRecorder(64, stall_rounds=4)
    fired = []
    for _ in range(3):
        fired += fr.record(_round(queued=1, admitted=0))
    assert fired == []
    # an admission resets the run
    fr.record(_round(queued=1, admitted=1))
    for _ in range(3):
        assert fr.record(_round(queued=1, admitted=0)) == []
    # the 4th consecutive stalled round fires
    assert fr.record(_round(queued=1, admitted=0)) == ["admission_stall"]
    assert fr.record(_round(queued=1, admitted=0)) == []  # latched


def test_postmortem_shape_and_json_roundtrip(tmp_path):
    m = MetricsRegistry()
    fr = FlightRecorder(16, metrics=m, dump_dir=str(tmp_path))
    _warm(fr, 20)
    fr.record(_round(wall_s=0.5))
    snap = fr.snapshot()
    assert len(snap["postmortems"]) == 1
    pm = snap["postmortems"][0]
    assert pm["kind"] == "slow_round"
    assert pm["record"]["wall_s"] == 0.5
    assert pm["record"]["anomalies"] == ["slow_round"]
    assert pm["fired_at_round"] == pm["record"]["seq"] == 20
    assert len(pm["ring"]) <= 16 and pm["ring"][-1] is not None
    # the whole snapshot survives a JSON round-trip (what /debug/flight
    # serves and what dump_dir receives)
    again = json.loads(json.dumps(snap))
    assert again["anomalies"]["slow_round"] == 1
    # the on-disk dump exists and parses
    files = list(tmp_path.glob("flight_slow_round_*.json"))
    assert len(files) == 1
    disk = json.loads(files[0].read_text())
    assert disk["kind"] == "slow_round"


def test_dump_on_demand(tmp_path):
    fr = FlightRecorder(8)
    _warm(fr, 5)
    out = tmp_path / "manual.json"
    snap = fr.dump(str(out), reason="operator")
    assert snap["reason"] == "operator"
    assert snap["dumped_to"] == str(out)
    assert json.loads(out.read_text())["rounds_recorded"] == 5


def test_all_anomaly_series_materialized_at_zero():
    m = MetricsRegistry()
    FlightRecorder(8, metrics=m)
    for kind in ANOMALY_KINDS:
        assert m.value("anomalies_total", kind=kind) == 0.0
    text = m.render()
    for kind in ANOMALY_KINDS:
        assert f'serving_anomalies_total{{kind="{kind}"}} 0' in text


def test_negative_ring_capacity_rejected():
    from repro.serving import EngineConfig
    with pytest.raises(ValueError):
        EngineConfig(flight_ring=-1)
