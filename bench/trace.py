"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per HLO operation (or Pallas kernel) that ran, named by its HLO
text (``%fusion.2 = bf16[...] fusion(...), ...``); an op inside a loop
or call lies within the event of its ``while``/``call``.  Their ``Async
XLA Ops`` line holds asynchronous operations from start to done, and
their ``XLA Modules`` line one event per program run
(``jit_stepped(<id>)``).  Host planes hold the spans the benchmark
writes with ``jax.profiler.TraceAnnotation`` (``bench.*``); the span
``bench.window`` marks the traced window.  All times are on the
profiler's clock, in nanoseconds.

* busy: the union of a device's op intervals inside the window; idle is
  the rest of the window;
* an op's self time is its time less that of the ops inside it;
* collectives: HLO collectives (``all-reduce``, ``all-gather``,
  ``reduce-scatter``, ``collective-permute``, ``all-to-all``; an
  asynchronous one from its ``-start`` to the end of its ``-done``) and
  the fused collective kernels (``is_collective``).  The part of their
  span in which no other innermost op runs on that device is exposed;
* idle gaps are put down to the host span that covers most of each gap.
"""

from __future__ import annotations

import glob
import os
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

COLLECTIVE_HLO = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
#: the fused collective kernels (``repro.kernels.collectives.kernel``)
COLLECTIVE_KERNELS = ("rs_step_kernel", "rs_step_kernel_q", "ag_step_kernel",
                      "ring_update_kernel")
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float]


def is_collective(name: str, detail: str = "") -> bool:
    """An HLO collective by its op name, or a fused collective kernel.

    ``detail`` is a custom call's own HLO text and stats.  The compiled
    HLO does not carry a Pallas kernel's name, only ``tpu_custom_call``
    and the ``pallas_call`` in its op path; the kernels the program runs
    inside a ``shard_map`` are the fused collective kernels (the model
    calls none), so such a call counts as one."""
    n = name.lower()
    return (n.startswith(COLLECTIVE_HLO)
            or any(k in n or k in detail for k in COLLECTIVE_KERNELS)
            or ("tpu_custom_call" in detail and "shard_map" in detail
                and "pallas_call" in detail))


def union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(ivs: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def length(ivs: List[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` outside ``b``."""
    b = union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def _stem(name: str) -> Optional[Tuple[str, str]]:
    """``("collective-permute", ".3")``-like key of an async half, and
    which half, or None."""
    m = re.match(r"^(.*?)-(start|done)(\.\d+)?$", name)
    if not m or not m.group(1).lower().startswith(COLLECTIVE_HLO):
        return None
    return (m.group(1) + (m.group(3) or ""), m.group(2))


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    #: asynchronous collectives, start to done
    async_ops: List[Tuple[str, float, float]] = field(default_factory=list)
    #: op name -> is a collective, where the trace's details decided it
    collective: Dict[str, bool] = field(default_factory=dict)


@dataclass
class Summary:
    """A traced window, reduced."""
    window: Interval
    n_devices: int
    busy_ns: float                  # mean over devices
    op_ns: Dict[str, float]         # summed over devices
    collective_ns: float            # mean over devices
    exposed_collective_ns: float    # mean over devices
    module_ns: Dict[str, float]     # mean over devices
    module_runs: Dict[str, float]   # first device; a run cut by the
    #                                 window counts the share inside it
    idle_by_span: Dict[str, float]  # first device, seconds
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module(self, fragment: str) -> Tuple[float, float]:
        """(mean device ns, runs) of the programs whose name holds
        ``fragment``, inside the window."""
        ns = sum(v for k, v in self.module_ns.items() if fragment in k)
        runs = sum(v for k, v in self.module_runs.items() if fragment in k)
        return ns, runs

    def breakdown(self) -> dict:
        n = max(self.n_devices, 1)
        ops = sorted(((k, v * 1e-9 / n) for k, v in self.op_ns.items()),
                     key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def load(path: str):
    from jax.profiler import ProfileData
    pb = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True))
    if not pb:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(pb[-1])


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def short_name(text: str) -> str:
    """``fusion.2`` of ``%fusion.2 = bf16[...] fusion(...)``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def _op_events(line, kinds: Dict[str, bool]):
    """The line's events by short name; ``kinds`` learns, once per op,
    whether it is a collective (a custom call by its own HLO text)."""
    out = []
    for e in line.events:
        name = short_name(e.name)
        if name not in kinds:
            detail = e.name.lower()
            if "custom" in detail:
                detail += " ".join(str(v) for _, v in e.stats).lower()
            else:
                detail = ""
            kinds[name] = is_collective(name, detail)
        out.append((name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def read_planes(pd) -> Tuple[Dict[str, Device], list]:
    """Device op/module events by device plane, and host spans."""
    devices: Dict[str, Device] = {}
    spans = []
    kinds: Dict[str, bool] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = Device(collective=kinds)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d.ops = _op_events(line, kinds)
                elif line.name == "Async XLA Ops":
                    d.async_ops = [ev for ev in _op_events(line, kinds)
                                   if kinds[ev[0]] or _stem(ev[0])]
                elif line.name == "XLA Modules":
                    d.modules = _events(line)
            if d.ops or d.modules:
                devices[plane.name] = d
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith("bench.")]
    return devices, spans


def nesting(ops):
    """Self time of each op event and which events hold no other op."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2]))
    self_ns = [ops[k][2] - ops[k][1] for k in range(len(ops))]
    leaf = [True] * len(ops)
    stack: List[int] = []
    for k in order:
        s, e = ops[k][1], ops[k][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            leaf[p] = False
            self_ns[p] -= min(e, ops[p][2]) - s
        stack.append(k)
    return self_ns, leaf


def _cause(spans, gs: float, ge: float) -> str:
    """The innermost host span that covers most of the gap [gs, ge)."""
    cover = [(min(e, ge) - max(s, gs), e - s, n) for n, s, e in spans]
    cover = [c for c in cover if c[0] > 0]
    if not cover:
        return "no host span"
    top = max(c[0] for c in cover)
    return min((c for c in cover if c[0] >= 0.5 * top),
               key=lambda c: c[1])[2]


def reduce(devices: Dict[str, Device], spans) -> Summary:
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        evs = [e for d in devices.values() for e in d.ops]
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    busy, coll, exposed = [], [], []
    op_ns: Counter = Counter()
    module_ns: Counter = Counter()
    module_runs: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    for i, (name, d) in enumerate(sorted(devices.items())):
        ops = [(n, s, e) for n, s, e in d.ops if e > lo and s < hi]
        b = clip(union([(s, e) for _, s, e in ops]), lo, hi)
        busy.append(length(b))
        self_ns, leaf = nesting(ops)
        for (n, s, e), t in zip(ops, self_ns):
            op_ns[n] += t * (min(e, hi) - max(s, lo)) / max(e - s, 1)
        # collective spans: async halves paired, the rest as they ran
        open_: Dict[str, float] = {}
        cspans, other = [], []
        for k in sorted(range(len(ops)), key=lambda k: ops[k][1]):
            n, s, e = ops[k]
            half = _stem(n)
            if half and half[1] == "start":
                open_[half[0]] = s
            elif half and half[1] == "done":
                cspans.append((open_.pop(half[0], s), e))
            elif d.collective.get(n, is_collective(n)):
                cspans.append((s, e))
            elif leaf[k]:
                other.append((s, e))
        cspans += [(s, e) for _, s, e in d.async_ops]
        cu = clip(union(cspans), lo, hi)
        coll.append(length(cu))
        exposed.append(length(subtract(cu, other)))
        for n, s, e in d.modules:
            if e > lo and s < hi:
                inside = min(e, hi) - max(s, lo)
                module_ns[n] += inside / len(devices)
                if i == 0:
                    module_runs[n] += inside / max(e - s, 1)
        if i == 0:
            for gs, ge in gaps(b, lo, hi):
                idle_by[_cause(inner, gs, ge)] += (ge - gs) * 1e-9
    n = max(len(devices), 1)
    return Summary(window=(lo, hi), n_devices=len(devices),
                   busy_ns=sum(busy) / n, op_ns=dict(op_ns),
                   collective_ns=sum(coll) / n,
                   exposed_collective_ns=sum(exposed) / n,
                   module_ns=dict(module_ns), module_runs=dict(module_runs),
                   idle_by_span=dict(idle_by), spans=inner)


def summarize(path: str) -> Summary:
    devices, spans = read_planes(load(path))
    if not devices:
        raise ValueError("the trace holds no device operations")
    return reduce(devices, spans)



def describe(path: str, top: int = 25) -> dict:
    """Planes, lines and each line's heaviest event names, to look at a
    trace by hand."""
    out = []
    for plane in load(path).planes:
        lines = []
        for line in plane.lines:
            ns: Counter = Counter()
            for e in line.events:
                ns[e.name[:600]] += e.duration_ns
            lines.append({"line": line.name, "top": ns.most_common(top)})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
