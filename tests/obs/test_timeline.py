"""Timeline export: Chrome-trace format invariants (per-lane rebase,
span/instant phases, process-name metadata) and the Prometheus text
rendering of a registry."""

import json

from repro.obs import metrics
from repro.obs.timeline import (LANES, Timeline, dump_chrome_trace,
                                export_prom, to_chrome_trace)


def _sample_tl():
    tl = Timeline()
    # train lane: host-clock microseconds
    t0 = 1.7e15
    tl.span("train_step", "train", t0, 1500.0, step=0, loss=2.5)
    tl.span("train_step", "train", t0 + 2000.0, 1400.0, step=1)
    # fleet lane: virtual integer tick clock
    tl.span("fleet_tick", "fleet", 4.0, 1.0, track="1", latency_s=0.01)
    tl.instant("replica_crash", "fleet", 5.0, track="1")
    tl.instant("chaos_crash", "chaos", 5.0, track="1", magnitude=1.0)
    return tl


def test_span_and_instant_phases():
    trace = to_chrome_trace(_sample_tl())
    rows = {r["name"]: r for r in trace["traceEvents"]
            if r.get("ph") in ("X", "i")}
    assert rows["train_step"]["ph"] == "X"
    assert rows["train_step"]["dur"] == 1400.0  # dict keeps the last span
    assert rows["replica_crash"]["ph"] == "i"
    assert rows["replica_crash"]["s"] == "p"
    assert "dur" not in rows["replica_crash"]


def test_wall_clock_lane_rebased_virtual_lane_untouched():
    trace = to_chrome_trace(_sample_tl())
    train = [r for r in trace["traceEvents"] if r["name"] == "train_step"]
    assert train[0]["ts"] == 0.0          # first wall-clock event -> 0
    assert train[1]["ts"] == 2000.0
    fleet = [r for r in trace["traceEvents"] if r["name"] == "fleet_tick"]
    assert fleet[0]["ts"] == 4.0          # tick clock passes through


def test_host_clock_lanes_rebased_at_any_scale():
    """``perf_counter`` starts anywhere: every host-clock lane is rebased
    to its own first event, and the tick lanes never are."""
    tl = Timeline()
    tl.span("serve.queue", "serve", 5.0e8, 300.0, rid=0)
    tl.instant("jax.compile", "runtime", 5.0e8 + 40.0, duration_s=0.1)
    tl.span("train_step", "train", 3.0, 2.0)
    tl.span("fleet_tick", "fleet", 2.0e12, 1.0)
    rows = {r["name"]: r for r in to_chrome_trace(tl)["traceEvents"]
            if r.get("ph") in ("X", "i")}
    assert rows["serve.queue"]["ts"] == 0.0
    assert rows["jax.compile"]["ts"] == 0.0     # its own lane's first
    assert rows["train_step"]["ts"] == 0.0
    assert rows["fleet_tick"]["ts"] == 2.0e12


def test_host_span_lands_in_the_profiler_trace(tmp_path):
    """``host_span`` writes a ``TraceAnnotation``: inside a profiler
    session it is an event of the trace's host plane."""
    import jax
    from repro.obs.timeline import host_span
    with jax.profiler.trace(str(tmp_path)):
        with host_span("serve.decode"):
            jax.numpy.ones(4).block_until_ready()
    with host_span("serve.decode"):        # no profiler: records nothing
        pass
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(pb))
    names = [e.name for p in pd.planes if p.name.startswith("/host")
             for line in p.lines for e in line.events]
    assert names.count("serve.decode") == 1


def test_lanes_get_distinct_pids_with_metadata():
    trace = to_chrome_trace(_sample_tl())
    meta = {r["args"]["name"]: r["pid"] for r in trace["traceEvents"]
            if r.get("ph") == "M"}
    for lane in LANES:
        assert meta[lane] == LANES.index(lane) + 1
    by_name = {r["name"]: r["pid"] for r in trace["traceEvents"]
               if r.get("ph") in ("X", "i")}
    assert by_name["train_step"] == meta["train"]
    assert by_name["chaos_crash"] == meta["chaos"]
    assert by_name["fleet_tick"] != by_name["train_step"]


def test_json_dict_roundtrip_and_dump(tmp_path):
    tl = _sample_tl()
    back = Timeline.from_json_dict(
        json.loads(json.dumps(tl.to_json_dict())))
    assert back.to_json_dict() == tl.to_json_dict()
    path = str(tmp_path / "trace.json")
    dump_chrome_trace(tl, path)
    with open(path) as f:
        loaded = json.load(f)   # the CI smoke's "loads in json.load" gate
    assert loaded == to_chrome_trace(tl)
    assert loaded["displayTimeUnit"] == "ms"


def test_disabled_timeline_records_nothing(fresh_timeline):
    tl = fresh_timeline
    with metrics.disabled():
        tl.span("train_step", "train", 0.0, 1.0)
        tl.instant("x", "fleet", 0.0)
    assert len(tl) == 0
    tl.span("train_step", "train", 0.0, 1.0)
    assert len(tl) == 1


def test_export_prom_format():
    reg = metrics.Registry()
    reg.inc("collective_calls", 3.0, backend="bine", topology="lumi")
    reg.set_gauge("fleet_mttr_ticks", 2.0)
    for x in (1.0, 2.0, 3.0, 4.0):
        reg.observe("fleet_tick_seconds", x, replica="0")
    text = export_prom(reg)
    lines = text.splitlines()
    assert "# TYPE collective_calls_total counter" in lines
    assert ('collective_calls_total{backend="bine",topology="lumi"} 3'
            in lines)
    assert "# TYPE fleet_mttr_ticks gauge" in lines
    assert "fleet_mttr_ticks 2" in lines
    assert "# TYPE fleet_tick_seconds summary" in lines
    assert ('fleet_tick_seconds{quantile="0.5",replica="0"} 2' in lines)
    assert 'fleet_tick_seconds_count{replica="0"} 4' in lines
    assert 'fleet_tick_seconds_sum{replica="0"} 10' in lines
    assert text.endswith("\n")


def test_export_prom_escapes_label_values():
    reg = metrics.Registry()
    reg.inc("c", 1.0, path='a"b\\c')
    text = export_prom(reg)
    assert 'path="a\\"b\\\\c"' in text


def test_export_prom_empty_registry():
    assert export_prom(metrics.Registry()) == ""
