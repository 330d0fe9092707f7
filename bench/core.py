"""What every cell shares: the manifest and the files found by name, the
run record that metric readers read, the device check, the compile cache
and the result line.

A cell is ``bench/workloads/<cell>.json``.  It names its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``generator`` names
``bench/traffic/<generator>.py``) and its kind (``bench/kinds/<kind>.py``).
Every metric of ``BENCHMARK.json`` is a reader
``bench/metrics/<metric>.py`` with ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(SystemExit):
    """A run that cannot measure: exits non-zero with no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise BenchError(f"{what} {name!r} is not a valid name")
    return name


def find(sub: str, name: str, ext: str, root: Path = BENCH) -> Path:
    """``root/sub/<name><ext>``; a name that finds no file is an error."""
    check_name(name, sub)
    path = root / sub / f"{name}{ext}"
    if not path.is_file():
        raise BenchError(f"no {sub} file {path.relative_to(root.parent)}")
    return path


def load_module(sub: str, name: str, root: Path = BENCH):
    """Import ``root/sub/<name>.py`` (names may hold dots)."""
    path = find(sub, name, ".py", root)
    key = f"bench_{sub}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no manifest at {path}")
    return load_json(path)


def load_cell(name: str, root: Path = BENCH) -> dict:
    """The workload, its configuration and its traffic mix."""
    w = load_json(find("workloads", name, ".json", root))
    cfg = load_json(find("configs", check_name(w["config"], "config"),
                         ".json", root))
    traffic = load_json(find("traffic", check_name(w["traffic"], "traffic"),
                             ".json", root))
    if w["chips"] not in (1, 4):
        raise BenchError(f"cell {name}: chips must be 1 or 4")
    return {"workload": w, "config": cfg, "traffic": traffic}


def metrics_for(manifest: dict, cell: str, per_layer: bool) -> List[dict]:
    """The manifest's metrics that this cell reports: those that list it,
    and those that list no cells."""
    group = manifest["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit seed from any whole number (``jax.random.key`` keeps only
    the low 32 bits of a larger one, and ``RandomState`` refuses it)."""
    import numpy as np
    return int(np.random.SeedSequence([int(seed) & (2**128 - 1), salt])
               .generate_state(1)[0])


@dataclass
class Run:
    """Everything one run measured; metric readers read this."""
    cell: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float = field(default_factory=time.perf_counter)
    peaks: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    setup_s: Optional[float] = None
    #: host clock (perf_counter seconds) of the measured window
    window: Optional[tuple] = None
    #: train: one ``(t_start, t_end, tokens)`` per step in the window
    steps: List[tuple] = field(default_factory=list)
    #: serve: one dict per request due in the window
    requests: List[dict] = field(default_factory=list)
    #: serve: one ``(t_start, t_end, n_active, ctx)`` per decode call,
    #: ``ctx`` the keys each active slot attends to
    decode_calls: List[tuple] = field(default_factory=list)
    #: the reduced profiler trace of the traced window (``trace.Summary``)
    trace_summary: Any = None
    #: (name, value, limit) of each number compared with the reference
    checks: List[tuple] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v == v and v <= lim for _, v, lim in self.checks)


def load_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def require_chips(n: int) -> dict:
    """The accelerator this run measures on, or exit: never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r});"
                         f" the benchmark never measures on the CPU")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def enable_cache() -> None:
    """Every program goes to the in-checkout persistent cache, so that
    only the first run of a cell in a checkout compiles."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)   # jax does not make it
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def percentile(xs: List[float], q: float) -> float:
    """Nearest-rank percentile of every sample."""
    import math
    xs = sorted(xs)
    if not xs:
        return float("nan")
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def result_line(run: Run, metrics: dict, breakdown: Optional[dict]) -> str:
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": run.device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in run.checks}
    return json.dumps(out)


def start_trace() -> str:
    import tempfile
    import jax
    path = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(path)
    return path


#: ``run.py --dump-trace``: where to describe the raw trace, if anywhere
DUMP_TRACE: Optional[str] = None


def stop_trace(path: str):
    """Stop the profiler, reduce its trace and delete it."""
    import shutil
    import jax
    from bench import trace
    jax.profiler.stop_trace()
    try:
        if DUMP_TRACE:
            with open(DUMP_TRACE, "w") as f:
                json.dump(trace.describe(path), f, indent=1)
        return trace.summarize(path)
    finally:
        shutil.rmtree(path, ignore_errors=True)


class WindowTrace:
    """Record the profiler from ``at`` to ``at + length`` seconds into a
    window, marking that span ``bench.window``; ``host`` is its start (the
    call that starts the profiler) and end on the host clock."""

    def __init__(self, at: float, length: float):
        self.at, self.end = at, at + length
        self.path = None
        self.span = None
        self.summary = None
        self.host = (None, None)

    @property
    def pending(self) -> bool:
        return self.summary is None

    def tick(self, now: float) -> None:
        from jax.profiler import TraceAnnotation
        if self.path is None and now >= self.at:
            # from before the profiler starts: starting it stalls the host
            self.host = (time.perf_counter(), None)
            self.path = start_trace()
            self.span = TraceAnnotation("bench.window")
            self.span.__enter__()
        elif self.span is not None and now >= self.end:
            self.finish()

    def finish(self):
        if self.span is not None:
            self.host = (self.host[0], time.perf_counter())
            self.span.__exit__(None, None, None)
            self.span = None
            self.summary = stop_trace(self.path)
        return self.summary
