"""Microbenchmark harness: compile and time the REAL collectives.

For every (collective × backend × payload × p) cell of a grid, the probe
builds the same shard_map program production tracing would build (the
``collectives.api`` dispatch — shmap schedules and the pallas_fused step
kernels alike), compiles it once, warms it up, and times it with a
trimmed median over repetitions.  Payloads are deterministic (seeded
arange-derived, never RNG-at-probe-time) so two probe runs time
bit-identical programs.

The probe measures the machine it runs on; ``topology`` is only the
decision-table key the measurements are filed under (which table
``refresh`` will rebuild).  On CPU hosts the pallas_fused cells execute
in interpret mode (the ``kernels.collectives`` default off-TPU) — real
dispatch plumbing, not real kernel speed; the measured tables such a run
produces are for wiring tests, not performance claims (see README).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.tuner.store import Measurement, MeasurementSet

#: collectives the probe can drive end-to-end through collectives.api
PROBE_COLLECTIVES = ("allreduce", "reduce_scatter", "allgather")


class ProbeTimeout(RuntimeError):
    """One probe cell exceeded its wall-clock budget (a hung compile or a
    wedged collective); the grid sweep retries or skips the cell instead
    of hanging the whole tune run."""


def call_with_budget(fn: Callable[[], object],
                     budget_s: Optional[float]) -> object:
    """Run ``fn()`` with a wall-clock budget; ``None`` = unbudgeted.

    The call runs on a worker thread and the caller joins with a timeout:
    a wedged jax compile/execute cannot be interrupted from Python, so on
    timeout the worker is *abandoned* (a daemon thread that dies with the
    process) and :class:`ProbeTimeout` raises — the price of not hanging
    the sweep.  Exceptions from ``fn`` re-raise in the caller.
    """
    if budget_s is None:
        return fn()
    if budget_s <= 0:
        raise ValueError(f"budget_s must be > 0, got {budget_s}")
    box: Dict[str, object] = {}

    def worker():
        try:
            box["result"] = fn()
        except BaseException as e:   # re-raised in the caller below
            box["error"] = e

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    t.join(budget_s)
    if t.is_alive():
        raise ProbeTimeout(
            f"probe cell exceeded its {budget_s:g}s wall-clock budget "
            f"(worker abandoned)")
    if "error" in box:
        raise box["error"]  # type: ignore[misc]
    return box.get("result")


@dataclass(frozen=True)
class GridSpec:
    """One probe grid: the cells ``probe_grid`` compiles and times."""
    name: str
    collectives: Tuple[str, ...]
    sizes: Tuple[int, ...]          # FULL-vector payload bytes (pow2)
    ps: Tuple[int, ...]
    warmup: int = 2
    reps: int = 10
    #: per-cell wall-clock budget (compile + warmup + reps), seconds;
    #: None = unbudgeted (the pre-resilience behavior)
    budget_s: Optional[float] = None
    #: extra attempts after a timed-out/failed cell before skipping it
    retries: int = 0
    #: sleep between attempts, seconds (linear: attempt * backoff_s)
    backoff_s: float = 0.0


#: named grids for launch/tune.py.  Sizes sit exactly on decision-table
#: bucket edges (SIZE_BUCKETS) so every measurement lands in the cell it
#: was aimed at.  "tiny" is the CPU/CI smoke grid.
GRIDS: Dict[str, GridSpec] = {
    "tiny": GridSpec("tiny", PROBE_COLLECTIVES,
                     sizes=(1 << 16, 1 << 18, 1 << 20), ps=(4,),
                     warmup=1, reps=5),
    "small": GridSpec("small", PROBE_COLLECTIVES,
                      sizes=(1 << 16, 1 << 20, 1 << 24), ps=(4, 8),
                      warmup=2, reps=10),
    "full": GridSpec("full", PROBE_COLLECTIVES,
                     sizes=tuple(1 << k for k in range(14, 27, 2)),
                     ps=(4, 8, 16), warmup=2, reps=20),
}


def trimmed_median(times: List[float], trim: float = 0.2) -> float:
    """Median of the middle (1 - 2*trim) of the sorted samples.

    Robust to the one-off hiccups (GC, interrupts) that poison a mean and
    to the cold tail a plain min hides behind.
    """
    if not times:
        raise ValueError("no samples")
    xs = sorted(times)
    k = int(len(xs) * trim)
    xs = xs[k:len(xs) - k] or xs
    mid = len(xs) // 2
    if len(xs) % 2:
        return xs[mid]
    return 0.5 * (xs[mid - 1] + xs[mid])


def _payload(nbytes: int, p: int) -> np.ndarray:
    """Deterministic full-vector payload, one row per rank ([p, n]).

    Cached below: every backend of a (p, nbytes) cell times the identical
    array, so the O(p * nbytes) construction runs once per grid point,
    not once per candidate."""
    n = max(p, nbytes // 4)
    n -= n % p
    base = (np.arange(n, dtype=np.float32) % 977.0) / 977.0
    rows = np.stack([np.roll(base, r) for r in range(p)])
    return rows


_payload_cache: Dict[Tuple[int, int], np.ndarray] = {}


def _payload_cached(nbytes: int, p: int) -> np.ndarray:
    key = (nbytes, p)
    if key not in _payload_cache:
        _payload_cache.clear()   # one grid point live at a time
        _payload_cache[key] = _payload(nbytes, p)
    return _payload_cache[key]


def _build_fn(collective: str, backend: str, p: int, mesh, axis: str,
              topology: Optional[str] = None,
              wire_dtype: str = "float32"):
    """jitted shard_map program for one probe cell: [p, ...] in, per-rank
    rows, through the exact ``collectives.api`` dispatch path.

    ``topology`` seeds the config preset so ``bine_hier`` cells execute
    the tier stack of the table the measurement is filed under.
    ``wire_dtype`` times the codec'd program — quantize/dequantize
    included, exactly what production would run."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.collectives import api
    from jax import shard_map

    cfg = api.CollectiveConfig(backend=backend, wire_dtype=wire_dtype)
    if topology is not None:
        cfg = cfg.replace(topology=topology)

    if collective == "allreduce":
        def body(v):
            return api.allreduce(v.reshape(-1), axis, cfg).reshape(v.shape)
    elif collective == "reduce_scatter":
        def body(v):
            return api.reduce_scatter(v.reshape(-1), axis, cfg)[None]
    elif collective == "allgather":
        def body(v):
            return api.allgather(v.reshape(-1), axis, cfg)[None]
    else:
        raise ValueError(f"probe cannot drive collective {collective!r}")

    # check_vma=False, as in the train step's shard_map: the probe times
    # the program production runs
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False))


def time_collective(collective: str, backend: str, p: int, nbytes: int,
                    mesh=None, axis: str = "x", warmup: int = 2,
                    reps: int = 10,
                    topology: Optional[str] = None,
                    wire_dtype: str = "float32",
                    budget_s: Optional[float] = None) -> Measurement:
    """Compile + warm up + time one cell; returns its ``Measurement``.

    ``allgather`` is fed its block input (``nbytes/p`` per rank) so the
    FULL-vector payload — the decision-table key — is ``nbytes`` for
    every collective alike (and stays the float32 payload whatever
    ``wire_dtype`` the timed program compresses to).  ``budget_s`` caps
    the cell's whole compile+warmup+reps wall clock
    (:func:`call_with_budget`; raises :class:`ProbeTimeout` past it).
    """
    import jax

    if mesh is None:
        mesh = _mesh_for(p, axis)
    rows = _payload_cached(nbytes, p)
    if collective == "allgather":
        rows = rows[:, :rows.shape[1] // p]

    def cell() -> List[float]:
        fn = _build_fn(collective, backend, p, mesh, axis, topology,
                       wire_dtype)
        x = jax.device_put(rows)
        for _ in range(max(1, warmup)):
            jax.block_until_ready(fn(x))
        ts = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(time.perf_counter() - t0)
        return ts

    times = call_with_budget(cell, budget_s)
    return Measurement(collective=collective, backend=backend, p=p,
                       nbytes=int(nbytes), time_s=trimmed_median(times),
                       reps=len(times), wire_dtype=wire_dtype)


def _mesh_for(p: int, axis: str):
    import jax

    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < p:
        raise RuntimeError(
            f"probe needs {p} devices, have {len(devs)} "
            f"(set --xla_force_host_platform_device_count or --devices)")
    return make_mesh((p,), (axis,), devices=devs[:p])


def probe_backends(collective: str,
                   topology: Optional[str] = None) -> Tuple[str, ...]:
    """The candidate set a measured cell must cover — exactly what the
    decision table for ``topology`` minimizes over (``bine_hier`` is not
    a candidate on the torus)."""
    if topology is not None:
        from repro.topology.cost import candidates_for
        return candidates_for(collective, topology)
    from repro.topology import CANDIDATES
    return CANDIDATES[collective]


def probe_wire_pairs(collective: str,
                     topology: str) -> Tuple[Tuple[str, str], ...]:
    """The *compressed* ``(backend, wire_dtype)`` cells of the joint wire
    grid — the float32 pairs are already covered by the plain backend
    sweep, so the probe only adds the codec variants on top."""
    from repro.topology.cost import wire_candidates
    return tuple(bw for bw in wire_candidates(collective, topology)
                 if bw[1] != "float32")


def _probe_cell_with_retry(spec: GridSpec, collective: str, backend: str,
                           p: int, nbytes: int, mesh, topology: str,
                           wire: str,
                           sleep: Callable[[float], None] = time.sleep
                           ) -> Optional[Measurement]:
    """One cell under the spec's budget/retry policy; ``None`` = gave up.

    Retries cover timeouts AND in-cell runtime errors (a flaky device can
    throw once and succeed on the retry); config errors (ValueError /
    TypeError from a bad backend/wire combo) propagate — retrying a
    deterministic rejection only wastes the budget.
    """
    attempts = 1 + max(0, spec.retries)
    last: Optional[BaseException] = None
    for attempt in range(attempts):
        if attempt and spec.backoff_s > 0:
            sleep(attempt * spec.backoff_s)
        try:
            return time_collective(collective, backend, p, nbytes,
                                   mesh=mesh, warmup=spec.warmup,
                                   reps=spec.reps, topology=topology,
                                   wire_dtype=wire, budget_s=spec.budget_s)
        except (ValueError, TypeError):
            raise
        except Exception as e:
            last = e
    assert last is not None
    return None


def probe_grid(spec: GridSpec, topology: str,
               timestamp: Optional[str] = None,
               progress: bool = False,
               sleep: Callable[[float], None] = time.sleep
               ) -> List[MeasurementSet]:
    """Run every cell of ``spec``; one ``MeasurementSet`` per rank count.

    Rank counts the host cannot provide devices for are skipped loudly
    (recorded in the set's provenance as ``skipped_ps``) rather than
    silently shrinking the grid.  Cells that exhaust the spec's
    budget/retry policy (``budget_s``/``retries``/``backoff_s``) are
    dropped the same way — recorded in ``failed_cells`` provenance, the
    rest of the grid still measured and the partial store still valid
    (``tuner.refresh`` only flips table cells with full candidate
    coverage, so a failed cell can never skew a decision).  ``sleep`` is
    injectable for tests.
    """
    import jax

    device_kind = jax.devices()[0].device_kind
    out: List[MeasurementSet] = []
    skipped: List[int] = []
    for p in spec.ps:
        if len(jax.devices()) < p:
            skipped.append(p)
            continue
        mesh = _mesh_for(p, "x")
        failed: List[str] = []
        ms = MeasurementSet(
            device_kind=device_kind, topology=topology, p=p,
            provenance={
                "grid": spec.name,
                "timestamp": timestamp,
                "jax": jax.__version__,
                "platform": jax.default_backend(),
                "warmup": str(spec.warmup), "reps": str(spec.reps),
            })
        # sizes outermost: every candidate of a (p, nbytes) grid point
        # reuses the one cached payload array (see _payload_cached)
        for nbytes in spec.sizes:
            for collective in spec.collectives:
                cells = [(b, "float32")
                         for b in probe_backends(collective, topology)]
                cells += list(probe_wire_pairs(collective, topology))
                for backend, wire in cells:
                    m = _probe_cell_with_retry(spec, collective, backend, p,
                                               nbytes, mesh, topology, wire,
                                               sleep=sleep)
                    if m is None:
                        failed.append(
                            f"{collective}:{backend}:{wire}:{nbytes}")
                        if progress:
                            print(f"[probe] p={p} {collective:>14} "
                                  f"{backend:>12} {wire:>8} {nbytes:>10}B "
                                  f"   FAILED (budget/retries exhausted)")
                        continue
                    ms.measurements.append(m)
                    if progress:
                        print(f"[probe] p={p} {collective:>14} "
                              f"{backend:>12} {wire:>8} {nbytes:>10}B "
                              f"{m.time_s * 1e6:10.1f}us")
        if failed:
            ms.provenance["failed_cells"] = ",".join(failed)
        out.append(ms)
    if skipped:
        for ms in out:
            ms.provenance["skipped_ps"] = ",".join(map(str, skipped))
        if progress:
            print(f"[probe] skipped p={skipped}: not enough devices "
                  f"({len(jax.devices())} available)")
    return out
