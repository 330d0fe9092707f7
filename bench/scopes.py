"""Device time by the program's named scopes, and idle gaps by the
program's host spans, from a profiler trace (``.xplane.pb``).

This extends ``bench/trace.py``'s reduction and changes none of it.  The
program names its layers with ``jax.named_scope``; XLA keeps each
scope in the ``op_name`` path of the metadata of every HLO op it
produced (``jit(stepped)/jvp(embed)/...``,
``jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/attn/...``).
On a TPU trace that path is the ``tf_op`` stat of the op's event
metadata (``jit(decode_slots_fn)/embed/jit(_take)/gather:``), which
``jax.profiler.ProfileData`` does not show: :func:`metadata_paths`
reads it from the ``.xplane.pb`` file itself, by program id and the
event's name (its HLO text).  Ops XLA adds itself, such as the copies
of loop-carried buffers, carry none.

* an op's scope is the innermost path component, with wrappers such as
  ``jvp(...)``, ``transpose(...)`` and a ``.remat`` suffix taken off,
  that names one of :data:`SCOPES` or a bucket's ``rs/b<k>`` /
  ``ag/b<k>``; an op under none is :data:`UNSCOPED`;
* an op belongs to the ``XLA Modules`` event that holds it on its device;
* its self time (``trace.nesting``) inside the window, mean over
  devices, goes to its (module, scope): a module's scopes add up to its
  busy time, which falls short of its device time by the gaps between
  its ops (:meth:`ScopeSummary.module_gap_ns`);
* idle gaps of the first device go to the innermost host span covering
  them, the program's (``serve.*``, ``train.*``) beside the
  benchmark's (``bench.*``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench import trace

#: the program's named scopes (besides each bucket's ``rs/b<k>`` and
#: ``ag/b<k>``)
SCOPES = ("attn", "mlp", "embed", "head", "loss", "kv_write", "decode_attn",
          "grad_norm", "adamw")
BUCKET = re.compile(r"^(rs|ag)/b\d+$")
UNSCOPED = "unscoped"
#: prefixes of the host spans idle gaps are put down to
HOST_SPANS = ("bench.", "serve.", "train.")

WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def split_path(op_name: str) -> List[str]:
    """``op_name``'s components: split at ``/`` outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


def unwrap(part: str) -> str:
    """``attn`` of ``transpose(jvp(attn))`` or ``attn.remat``."""
    while True:
        m = WRAPPED.match(part)
        if not m:
            break
        part = m.group(1)
    return part[:-len(".remat")] if part.endswith(".remat") else part


def scope_of(op_name: Optional[str]) -> str:
    """The innermost scope an op's ``op_name`` path names."""
    if not op_name:
        return UNSCOPED
    parts = [unwrap(p) for p in split_path(op_name)]
    found = UNSCOPED
    for i, p in enumerate(parts):
        if p in SCOPES or BUCKET.match(p):
            found = p
        elif i + 1 < len(parts) and BUCKET.match(f"{p}/{parts[i + 1]}"):
            found = f"{p}/{parts[i + 1]}"
    return found


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        out |= (byte & 0x7F) << shift
        i += 1
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """A protobuf message's ``(field, value)`` pairs: a varint as an int,
    a length-delimited field as its ``(start, end)`` in ``buf``, a fixed
    field as None (nothing here reads one)."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


#: ``tsl/profiler/protobuf/xplane.proto`` field numbers read here
XSPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2                                  # a map entry's value
META_NAME, META_STATS = 2, 5                   # XEventMetadata
STAT_META_ID, STAT_META_NAME = 1, 2            # XStatMetadata
STAT_ID, STAT_U64, STAT_I64, STAT_STR, STAT_REF = 1, 3, 4, 5, 7   # XStat


def metadata_paths(data: bytes) -> Dict[Tuple[str, str], str]:
    """``(program id, event name) -> op_name`` path of every device op
    whose event metadata carries a ``tf_op`` stat, from an ``.xplane.pb``
    file's bytes (the path without ``tf_op``'s ``:<type>`` suffix)."""
    out: Dict[Tuple[str, str], str] = {}
    for f, plane in _fields(data):
        if f != XSPACE_PLANES:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(data, *plane):
            if g == PLANE_NAME:
                name = _text(data, v)
            elif g == PLANE_EVENT_METADATA:
                metas.append(v)
            elif g == PLANE_STAT_METADATA:
                sm = dict(_fields(data, *v)).get(MAP_VALUE)
                sm = dict(_fields(data, *sm)) if sm else {}
                if STAT_META_NAME in sm:
                    stat_names[sm.get(STAT_META_ID, 0)] = _text(
                        data, sm[STAT_META_NAME])
        if not name.startswith("/device:"):
            continue
        for entry in metas:
            meta = dict(_fields(data, *entry)).get(MAP_VALUE)
            if meta is None:
                continue
            event, stats = "", {}
            for g, v in _fields(data, *meta):
                if g == META_NAME:
                    event = _text(data, v)
                elif g == META_STATS:
                    st = dict(_fields(data, *v))
                    key = stat_names.get(st.get(STAT_ID))
                    if STAT_STR in st:
                        stats[key] = _text(data, st[STAT_STR])
                    elif STAT_REF in st:
                        stats[key] = stat_names.get(st[STAT_REF], "")
                    elif STAT_U64 in st or STAT_I64 in st:
                        stats[key] = str(st.get(STAT_U64, st.get(STAT_I64)))
            if stats.get("tf_op") and "program_id" in stats:
                op = stats["tf_op"]
                cut = op.rfind(":")
                if cut > op.rfind("/"):
                    op = op[:cut]
                out[(stats["program_id"], event)] = op
    return out


def program_id(module: str) -> str:
    """``123`` of a module event ``jit_stepped(123)``."""
    m = re.search(r"\((\d+)\)$", module)
    return m.group(1) if m else ""


@dataclass
class ScopeSummary:
    """A traced window's device time by (module, scope)."""
    window: trace.Interval
    n_devices: int
    #: (module, scope) -> self ns inside the window, mean over devices
    scope_ns: Dict[Tuple[str, str], float]
    #: module -> ns inside the window, mean over devices
    module_ns: Dict[str, float]
    #: module -> runs inside the window on the first device (a run cut by
    #: the window counts the share inside it)
    module_runs: Dict[str, float]
    #: (module, op) -> (scope, op_name path, self ns summed over devices)
    ops: Dict[Tuple[str, str], Tuple[str, Optional[str], float]]
    #: idle seconds of the first device by innermost host span
    idle_by_span: Dict[str, float]

    def scopes(self, fragment: str) -> Dict[str, float]:
        """Scope -> ns of the modules whose name holds ``fragment``."""
        out: Dict[str, float] = defaultdict(float)
        for (mod, sc), ns in self.scope_ns.items():
            if fragment in mod:
                out[sc] += ns
        return dict(out)

    def per_run_ms(self, fragment: str, scope: str) -> Optional[float]:
        """Device ms under ``scope`` per run of the modules whose name
        holds ``fragment``; None where none ran."""
        runs = sum(v for k, v in self.module_runs.items() if fragment in k)
        if not runs:
            return None
        return self.scopes(fragment).get(scope, 0.0) / runs / 1e6

    def module_gap_ns(self, fragment: str) -> float:
        """The modules' device ns less the ns of all their scopes: the
        gaps between their ops."""
        ns = sum(v for k, v in self.module_ns.items() if fragment in k)
        return ns - sum(self.scopes(fragment).values())


def read_planes(pd):
    """Per device plane: ops as ``(name, event name, start, end)`` and
    module events; and the host spans of :data:`HOST_SPANS`."""
    devices, spans = {}, []
    short: Dict[str, str] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        if e.name not in short:
                            short[e.name] = trace.short_name(e.name)
                        ops.append((short[e.name], e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
                elif line.name == "XLA Modules":
                    modules = trace._events(line)
            if ops or modules:
                devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [ev for ev in trace._events(line)
                          if ev[0].startswith(HOST_SPANS)]
    return devices, spans


def reduce(devices, spans,
           paths: Dict[Tuple[str, str], str]) -> ScopeSummary:
    """``read_planes``' ops and spans reduced, each op's path looked up
    in ``paths`` (:func:`metadata_paths`) by its module's program id."""
    win = [s for s in spans if s[0] == trace.WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        evs = [o for ops, _ in devices.values() for o in ops]
        lo, hi = min(o[2] for o in evs), max(o[3] for o in evs)
    inner = [s for s in spans if s[0] != trace.WINDOW_SPAN]
    n = max(len(devices), 1)
    scope_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    module_ns: Dict[str, float] = defaultdict(float)
    module_runs: Dict[str, float] = defaultdict(float)
    ops_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    op_path: Dict[Tuple[str, str], Optional[str]] = {}
    idle: Dict[str, float] = defaultdict(float)
    for i, (_, (ops, modules)) in enumerate(sorted(devices.items())):
        ops = [o for o in ops if o[3] > lo and o[2] < hi]
        mods = sorted(modules, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        self_ns, _ = trace.nesting([(o[0], o[2], o[3]) for o in ops])
        for (name, event, s, e), t in zip(ops, self_ns):
            k = bisect.bisect_right(starts, s) - 1
            mod = mods[k][0] if k >= 0 and s < mods[k][2] else "no module"
            path = paths.get((program_id(mod), event))
            inside = t * (min(e, hi) - max(s, lo)) / max(e - s, 1)
            scope_ns[(mod, scope_of(path))] += inside / n
            ops_ns[(mod, name)] += inside
            op_path[(mod, name)] = path
        for name, s, e in mods:
            if e > lo and s < hi:
                module_ns[name] += (min(e, hi) - max(s, lo)) / n
                if i == 0:
                    module_runs[name] += (min(e, hi) - max(s, lo)) \
                        / max(e - s, 1)
        if i == 0:
            busy = trace.clip(trace.union([(o[2], o[3]) for o in ops]),
                              lo, hi)
            for gs, ge in trace.gaps(busy, lo, hi):
                idle[trace._cause(inner, gs, ge)] += (ge - gs) * 1e-9
    return ScopeSummary(
        window=(lo, hi), n_devices=len(devices), scope_ns=dict(scope_ns),
        module_ns=dict(module_ns), module_runs=dict(module_runs),
        ops={k: (scope_of(op_path[k]), op_path[k], v)
             for k, v in ops_ns.items()},
        idle_by_span=dict(idle))


def load(path: str) -> bytes:
    """The ``.xplane.pb`` under a profiler's output directory, as
    ``trace.load`` finds it."""
    pb = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True))
    if not pb:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    with open(pb[-1], "rb") as f:
        return f.read()


def summarize(data: bytes) -> ScopeSummary:
    """The scope reduction of an ``.xplane.pb`` file's bytes."""
    from jax.profiler import ProfileData
    devices, spans = read_planes(ProfileData.from_serialized_xspace(data))
    if not devices:
        raise ValueError("the trace holds no device operations")
    return reduce(devices, spans, metadata_paths(data))
