"""The scope reduction (``bench/scopes.py``) on synthetic traces, and the
accepted per-layer readers unmoved by the program's spans and scopes."""

import struct
from types import SimpleNamespace as NS

import pytest

import bench_helpers as bc
from bench import core, scopes, trace


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def hlo(op):
    """An op's event name as a TPU trace gives it: its HLO text, which
    carries no metadata."""
    return f"%{op} = bf16[8]{{0}} fusion(%p), kind=kLoop"


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_slots_fn)/while/body/decode_attn/dot_general",
     "decode_attn"),
    ("jit(decode_slots_fn)/while/body/kv_write/scatter", "kv_write"),
    ("jit(stepped)/jvp(embed)/gather", "embed"),
    ("jit(stepped)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/tanh", "attn"),
    ("jit(f)/jvp()/while/body/attn.remat/dot_general", "attn"),
    ("jit(f)/transpose(jvp(attn.remat))/dot_general", "attn"),
    ("jit(f)/jvp(loss)/mul", "loss"),
    ("jit(f)/head/mlp/jit(silu)/mul", "mlp"),        # innermost wins
    ("jit(f)/attn/jit(_where)/select_n", "attn"),
    ("jit(stepped)/shard_map/rs/b3/reduce_scatter", "rs/b3"),
    ("jit(stepped)/shard_map/ag/b12/all_gather", "ag/b12"),
    ("jit(stepped)/transpose(jvp(rs/b0))/add", "rs/b0"),
    ("jit(stepped)/shard_map/adamw/sqrt", "adamw"),
    ("jit(stepped)/shard_map/grad_norm/psum", "grad_norm"),
    ("jit(stepped)/shard_map/rs/mul", "unscoped"),
    ("jit(stepped)/attention_like/headroom/add", "unscoped"),
    ("jit(f)/while/body/add", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_paths(path, scope):
    assert scopes.scope_of(path) == scope


def _varint(n):
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(field, value)``: an int as a varint, a
    float as a double, text or bytes length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", v)
        elif isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, stat_names, events):
    """An ``XPlane``: stat metadata ``{id: name}``; events
    ``[(id, name, [(stat id, XStat field, value)])]``."""
    fields = [(1, 5), (2, name),
              (3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 10), (3, 5)))))]
    for eid, event, stats in events:
        meta = _msg((1, eid), (2, event), (4, event[:9]),
                    *[(5, _msg((1, sid), (kind, v))) for sid, kind, v in stats])
        fields.append((4, _msg((1, eid), (2, meta))))
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    fields.append((6, _msg((1, 3), (2, 197.0))))
    return _msg(*fields)


def test_metadata_paths_read_from_the_xplane_file():
    """``tf_op`` and ``program_id`` live in an op's event metadata, as on
    a TPU trace: a string or a reference to an interned one, the id a
    64-bit unsigned number; ops sharing their HLO text across programs
    keep their own paths; ops without ``tf_op`` and host planes give
    none."""
    big = 18344124621498073700
    names = {1: "tf_op", 2: "program_id", 3: "flops",
             4: "jit(g)/mlp/dot_general:"}
    device = _plane("/device:TPU:0", names, [
        (10, hlo("fusion.1"), [(1, 5, "jit(f)/attn/dot_general:"),
                               (2, 3, 7), (3, 2, 12.0)]),
        (11, hlo("fusion.1"), [(2, 3, big), (1, 7, 4)]),
        (12, hlo("copy.3"), [(2, 3, 7)]),
        (13, hlo("fusion.4"), [(1, 5, "jit(f)/rs/b0/psum:all-reduce"),
                               (2, 4, 7)]),
    ])
    host = _plane("/host:CPU", names, [
        (20, "serve.decode", [(1, 5, "jit(f)/x:"), (2, 3, 7)])])
    data = _msg((1, device), (1, host), (4, "host0"))
    assert scopes.metadata_paths(data) == {
        ("7", hlo("fusion.1")): "jit(f)/attn/dot_general",
        (str(big), hlo("fusion.1")): "jit(g)/mlp/dot_general",
        ("7", hlo("fusion.4")): "jit(f)/rs/b0/psum",
    }
    assert scopes.program_id(f"jit_decode_slots_fn({big})") == str(big)
    assert scopes.split_path("a/jvp(rs/b0)/x") == ["a", "jvp(rs/b0)", "x"]


#: ``metadata_paths`` of :func:`_trace`: the programs ``7``
#: (``decode_slots``) and ``9`` (``insert``) share op names; ``copy.3``,
#: as XLA's copies do, has no path
PATHS = {
    ("7", hlo("while.1")): "jit(decode_slots_fn)/while",
    ("7", hlo("fusion.1")):
        "jit(decode_slots_fn)/while/body/decode_attn/dot_general",
    ("7", hlo("fusion.2")): "jit(decode_slots_fn)/while/body/kv_write/scatter",
    ("7", hlo("fusion.4")): "jit(decode_slots_fn)/head/dot_general",
    ("9", hlo("fusion.1")): "jit(insert_fn)/attn/dot_general",
    ("9", hlo("fusion.2")): "jit(insert_fn)/mlp/dot_general",
}


def _trace(program_spans=True):
    """One device: two runs of ``decode_slots`` and one of ``insert``
    whose ops share names; the host's benchmark and program spans."""
    ops = [
        # decode_slots run 1 [0, 40): a loop holding two scoped ops
        ev(hlo("while.1"), 0, 30),
        ev(hlo("fusion.1"), 2, 10),
        ev(hlo("fusion.2"), 12, 8),
        ev(hlo("copy.3"), 30, 6),
        ev(hlo("fusion.4"), 36, 4),
        # insert [50, 70): the same op names, other scopes
        ev(hlo("fusion.1"), 50, 12),
        ev(hlo("fusion.2"), 62, 8),
        # decode_slots run 2 [80, 120), cut by the window's end at 110;
        # a gap [96, 100) between its ops
        ev(hlo("fusion.1"), 80, 16),
        ev(hlo("fusion.2"), 100, 20),
    ]
    modules = [ev("jit_decode_slots_fn(7)", 0, 40),
               ev("jit_insert_fn(9)", 50, 20),
               ev("jit_decode_slots_fn(7)", 80, 40)]
    host = [ev("bench.window", 0, 110), ev("bench.sched_step", 38, 46)]
    if program_spans:
        host += [ev("serve.decode", 38, 4), ev("serve.sample", 42, 6),
                 ev("serve.admit", 48, 24), ev("serve.retire", 72, 6)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=modules)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def _reduce(pd):
    return scopes.reduce(*scopes.read_planes(pd), PATHS)


def test_ops_go_to_the_module_that_holds_them_and_add_up():
    s = _reduce(_trace())
    dec = s.scopes("decode_slots")
    # loop self time 30 - 18 = 12 and the copy 6 are unscoped; run 2's
    # kv_write is cut to [100, 110)
    assert dec == {"unscoped": 18, "decode_attn": 26, "kv_write": 18,
                   "head": 4}
    assert s.scopes("insert") == {"attn": 12, "mlp": 8}
    assert s.ops[("jit_decode_slots_fn(7)", "fusion.1")][:2] == (
        "decode_attn", "jit(decode_slots_fn)/while/body/decode_attn/"
                       "dot_general")
    assert s.ops[("jit_insert_fn(9)", "fusion.1")][0] == "attn"
    # scopes and unscoped add up exactly to the busy time of each module;
    # what they leave of the module's device time is the gap between ops
    assert sum(s.scopes("insert").values()) == s.module_ns["jit_insert_fn(9)"]
    assert s.module_gap_ns("insert") == 0
    assert s.module_gap_ns("decode_slots") == 4
    # per run: 1.75 runs of decode_slots in the window
    assert s.module_runs["jit_decode_slots_fn(7)"] == 1.75
    assert s.per_run_ms("decode_slots", "decode_attn") == 26 / 1.75 / 1e6
    assert s.per_run_ms("stepped", "attn") is None


def test_idle_gap_goes_to_the_innermost_program_span():
    s = _reduce(_trace())
    # idle [40, 50) lies under bench.sched_step [38, 84) and, within it,
    # serve.sample [42, 48) and serve.decode [38, 42): the innermost span
    # covering most of it is serve.sample; [70, 80) goes to serve.retire
    # [72, 78) (6 of 10) rather than the wider bench.sched_step; nothing
    # covers [96, 100)
    got = {k: round(v * 1e9) for k, v in s.idle_by_span.items()}
    assert got == {"serve.sample": 10, "serve.retire": 10,
                   "no host span": 4}
    # the benchmark's own reduction is unchanged by them
    t = trace.reduce(*trace.read_planes(_trace()))
    assert {k: round(v * 1e9) for k, v in t.idle_by_span.items()} == \
        {"bench.sched_step": 20, "no host span": 4}


def _serve_run(pd):
    run = bc.tiny_run("serve.phi4-mini.chat")
    run.trace = True
    run.trace_summary = trace.reduce(*trace.read_planes(pd))
    run.extra["trace_host_window"] = (10.0, 10.0 + 110e-9)
    run.decode_calls = [(10.0, 10.0 + 40e-9, 3, 900),
                        (10.0 + 80e-9, 10.0 + 120e-9, 3, 903)]
    run.requests = [{"due": 1.0 + i, "admitted": 1.05 + 1.01 * i,
                     "stamps": [1.1 + i]} for i in range(5)]
    return run


def _train_run(pd):
    run = bc.tiny_run("train1.qwen3-32b.stage")
    run.trace = True
    run.trace_summary = trace.reduce(*trace.read_planes(pd))
    return run


def _train_trace(program_spans=True):
    ops = [ev(hlo("fusion.2"), 0, 30), ev(hlo("fusion.5"), 30, 50)]
    host = [ev("bench.window", 0, 100), ev("bench.fetch", 80, 20)]
    if program_spans:
        host += [ev("train.fetch", 82, 18)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=[ev("jit_stepped(3)", 0, 80)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


ACCEPTED = {
    "step_mfu.train": _train_run, "device_idle_pct.train": _train_run,
    "decode_step_ms.serve": _serve_run, "prefill_ms.serve": _serve_run,
    "admission_wait_p95_ms.serve": _serve_run,
    "decode_step_roofline": _serve_run, "step_mfu.serve": _serve_run,
    "device_idle_pct.serve": _serve_run,
}


def test_accepted_list_is_the_manifest_before_the_program_readers():
    names = [m["name"] for m in core.load_manifest()["per_layer"]]
    assert set(ACCEPTED) <= set(names) and len(ACCEPTED) == 8


@pytest.mark.parametrize("metric", sorted(ACCEPTED))
def test_accepted_readers_read_the_same_with_program_spans(metric):
    make = ACCEPTED[metric]
    traces = _train_trace if make is _train_run else _trace
    read = core.load_module("metrics", metric).read
    plain = read(make(traces(program_spans=False)))
    assert plain is not None
    assert read(make(traces(program_spans=True))) == plain


def test_scope_report_reads_the_scope_metrics_and_tracing_cost():
    from bench import scope_report as sr
    rep = sr.scope_report(_reduce(_trace()))
    assert rep["metrics"]["decode_attn_ms.serve"] == 26 / 1.75 / 1e6
    assert rep["metrics"]["kv_write_ms.serve"] == 18 / 1.75 / 1e6
    assert rep["metrics"]["attn_ms.train"] is None
    dec = rep["modules"]["decode_slots"]
    assert dec["gap_pct"] == 100.0 * 4 / 70
    assert rep["top_ops"][0][:3] == ["jit_decode_slots_fn(7)", "fusion.1",
                                     "decode_attn"]
    run = _serve_run(_trace())
    run.decode_calls.insert(0, (9.0, 9.0 + 30e-9, 3, 800))
    cost = sr.tracing_cost(run)
    assert cost["decode_call_ms_before"] == pytest.approx(30e-6)
    assert cost["decode_call_ms_traced"] == pytest.approx(40e-6)
