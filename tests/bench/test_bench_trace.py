"""Trace reduction on synthetic traces: busy union, idle share,
collective/compute overlap, op classification, idle attribution."""

from types import SimpleNamespace as NS

from bench import trace as tr


def test_interval_algebra():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6), (9, 12)]) == \
        [(0, 2), (3, 5), (6, 9)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.length(tr.clip([(0, 4), (6, 9)], 2, 7)) == 3


def test_classification():
    for name in ("all-reduce.3", "all-gather-start.1", "reduce-scatter.2",
                 "collective-permute-done.7", "all-to-all", "rs_step_kernel",
                 "fusion_rs_step_kernel_q.2", "ag_step_kernel",
                 "ring_update_kernel"):
        assert tr.is_collective(name), name
    for name in ("fusion.12", "convolution.3", "copy.1",
                 "dynamic-update-slice.4"):
        assert not tr.is_collective(name), name
    assert tr._stem("collective-permute-start.3") == \
        ("collective-permute.3", "start")
    assert tr._stem("collective-permute-done.3") == \
        ("collective-permute.3", "done")
    assert tr._stem("fusion-start") is None


def synthetic():
    """Two devices over a 100 ns window [0, 100)."""
    d0 = tr.Device(
        ops=[("fusion.1", 0, 30),
             ("collective-permute-start.1", 30, 31),
             ("fusion.2", 31, 50),                 # overlaps the permute
             ("collective-permute-done.1", 50, 60),
             ("all-reduce.2", 70, 80)],            # synchronous
        modules=[("jit_stepped", 0, 60), ("jit_stepped", 70, 80)])
    d1 = tr.Device(ops=[("fusion.1", 0, 50), ("rs_step_kernel", 50, 90)],
                   modules=[("jit_stepped", 0, 90)])
    spans = [("bench.window", 0, 100), ("bench.step", 0, 62),
             ("bench.fetch", 58, 75), ("bench.put", 78, 100)]
    return {"/device:TPU:0": d0, "/device:TPU:1": d1}, spans


def test_reduce_busy_collectives_and_gaps():
    devices, spans = synthetic()
    s = tr.reduce(devices, spans)
    assert s.window == (0, 100) and abs(s.window_s - 1e-7) < 1e-20
    # busy: d0 [0,60)+[70,80) = 70, d1 [0,90) = 90 -> mean 80
    assert s.busy_ns == 80
    # d0: permute spans [30,60), all-reduce [70,80) -> 40; d1: kernel 40
    assert s.collective_ns == 40
    # d0 exposed: [30,31) and [50,60) of the permute, all of the all-reduce
    # -> 21; d1: 40 -> mean 30.5
    assert s.exposed_collective_ns == 30.5
    assert s.module("stepped") == ((60 + 10 + 90) / 2, 2)
    # d0 idle: [60,70) under bench.fetch (innermost cover), [80,100) under
    # bench.put
    assert {k: round(v * 1e9) for k, v in s.idle_by_span.items()} == \
        {"bench.fetch": 10, "bench.put": 20}
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "bench.put"


def test_read_planes_picks_device_lines_and_host_spans():
    def ev(n, s, d, stats=()):
        return NS(name=n, start_ns=s, duration_ns=d, stats=list(stats))
    kernel = ev("custom-call.5", 16, 2,
                [("long_name", "custom-call.5 = tpu_custom_call "
                  "rs_step_kernel")])
    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[ev("fusion.1", 10, 5), kernel,
                                       ev("custom-call.6", 18, 1)]),
            NS(name="XLA Modules", events=[ev("jit_f", 10, 6)]),
            NS(name="Steps", events=[ev("0", 10, 6)])]),
        NS(name="/host:CPU", lines=[
            NS(name="python", events=[ev("bench.window", 0, 20),
                                      ev("$profiler.py:1 f", 0, 3)])]),
        NS(name="/host:metadata", lines=[])])
    devices, spans = tr.read_planes(pd)
    assert list(devices) == ["/device:TPU:0"]
    d = devices["/device:TPU:0"]
    assert d.ops == [("fusion.1", 10, 15), ("custom-call.5", 16, 18),
                     ("custom-call.6", 18, 19)]
    assert d.collective == {"fusion.1": False, "custom-call.5": True,
                            "custom-call.6": False}
    assert d.modules == [("jit_f", 10, 16)]
    assert spans == [("bench.window", 0, 20)]
    s = tr.reduce(devices, spans)
    assert s.busy_ns == 8 and abs(s.window_s - 2e-8) < 1e-20
    assert s.collective_ns == 2 == s.exposed_collective_ns


def test_hlo_text_names_nesting_and_async_collectives():
    """Ops as a TPU trace names them: an op inside a loop lies within the
    loop's event, and the Async XLA Ops line spans a permute in flight."""
    def ev(n, s, d, stats=()):
        return NS(name=n, start_ns=s, duration_ns=d, stats=list(stats))
    assert tr.short_name("%fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.2"
    assert tr.short_name("fusion.2") == "fusion.2"
    ops = [("while.1", 0, 50), ("fusion.3", 5, 15), ("fusion.4", 20, 30),
           ("fusion.5", 60, 70)]
    self_ns, leaf = tr.nesting(ops)
    assert self_ns == [30, 10, 10, 10] and leaf == [False, True, True, True]
    pd = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("%while.1 = (s32[]) while(%t), body=%b", 0, 50),
            ev("%fusion.3 = f32[2] fusion(%x), kind=kLoop", 5, 10),
            ev("%collective-permute-done.1 = f32[2] "
               "collective-permute-done(%c)", 40, 10),
            ev("%fusion.5 = f32[2] fusion(%y), kind=kLoop", 60, 10),
            ev('%shard_map.25 = f32[4] custom-call(%a, %b), custom_call_'
               'target="tpu_custom_call", metadata={op_name="jit(stepped)/'
               'shard_map/pallas_call"}', 70, 5)]),
        NS(name="Async XLA Ops", events=[
            ev("%collective-permute-start.1 = (f32[2]) "
               "collective-permute-start(%z)", 10, 40),
            ev("%copy-start.2 = (f32[2]) copy-start(%w)", 0, 70)]),
        NS(name="XLA Modules", events=[ev("jit_stepped(123)", 0, 70),
                                       ev("jit_stepped(123)", 90, 20)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("bench.window", 0, 100), ev("bench.fetch", 75, 25)])])])
    devices, spans = tr.read_planes(pd)
    d = devices["/device:TPU:0"]
    assert [o[0] for o in d.ops] == ["while.1", "fusion.3",
                                     "collective-permute-done.1", "fusion.5",
                                     "shard_map.25"]
    assert d.collective["shard_map.25"] and not d.collective["fusion.5"]
    assert d.async_ops == [("collective-permute-start.1", 10, 50)]
    s = tr.reduce(devices, spans)
    # busy [0,50)+[60,70); the permute spans [10,50), none of it under an
    # innermost compute op except nothing (fusion.3 ends at 15): [15,40)
    # and the done [40,50) are exposed, [10,15) is hidden
    assert s.busy_ns == 65 and s.collective_ns == 45
    assert s.exposed_collective_ns == 40
    # a run cut by the window's end counts the share inside it
    assert s.module("stepped") == (80, 1.5)
    assert s.op_ns["while.1"] == 30
    assert round(s.idle_by_span["bench.fetch"] * 1e9) == 25
