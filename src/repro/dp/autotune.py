"""Measured-cost calibration for the dispatcher (DESIGN.md §6).

The analytical step-count model (``backends.linear_costs``) cannot see
constant factors, trace overheads, or host↔device transfer costs — the seed
``BENCH_dp_zoo.json`` sweep showed it routing 16 of 24 measured
(problem, size) cells to a backend that is NOT the fastest (worst: viterbi
n=8, 8.2× regret). This module fixes the misrouting at its root: dispatch
consults *measured* latencies whenever they exist and keeps the analytical
model only as prior and tiebreak.

Three sources feed one :class:`CalibrationTable`, keyed
``(jax_backend, backend_name, shape_key)``:

  * ``calibrate()`` — offline sweep over registry problems × sizes; warm
    cache, min-of-N, synced through the numpy conversion (same protocol as
    ``benchmarks/dp_zoo_bench.py``).
  * ``calibrate_spec()`` — the same for one spec (the bench calls this per
    cell so its regret gate runs against exact-shape entries).
  * ``observe()`` — online: ``DPEngine`` folds realized per-bucket drain
    latencies in by exponential moving average, so a long-running engine
    converges to the true fastest route without any offline pass.

Measurement *regimes* never share entries: plain keys hold single-instance
timings (offline calibration), while the engine observes under
regime-suffixed keys — ``… + ("batch",)`` for amortized per-instance bucket
drains, ``… + ("reconstruct",)`` for arg-emitting solves — because the
three cost profiles differ and comparing across them reintroduces
misrouting (``backends.shape_key_distance`` refuses cross-regime
interpolation too).

Ranking (:func:`rank`) is two-tier: routes with a measured cost (exact entry
or a nearest-shape interpolation scaled by the analytical cost ratio) sort
by measured ms; unmeasured routes follow in analytical order. Batch pools
use :func:`rank_batch` over batch-regime entries, where a loop-fallback
route needs an amortized drain observation to overrule the batching prior.
An empty table reproduces the analytical ordering bit-for-bit, so overrides
and pre-calibration behavior are untouched.

Tables persist as JSON (:meth:`CalibrationTable.save` / ``load``); a corrupt
or unreadable file degrades to the analytical model with a warning, never an
error. Env ``REPRO_DP_CALIB`` names a table to auto-load on first use.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.dp import backends as _backends
from repro.dp import envknobs
from repro.dp import telemetry as _telemetry
from repro.dp.problem import Spec

_log = _telemetry.get_logger("autotune")

#: EMA weight of one online observation folded into an existing entry.
EMA_ALPHA = 0.3
#: Nearest-shape interpolation gives up past this table-length ratio.
MAX_INTERP_RATIO = 4.0
#: Env var naming a persisted table to auto-load on first ``get_table()``.
ENV_PATH = "REPRO_DP_CALIB"
#: LRU bound on the per-table measured_ms memo.
MEMO_MAX = 4096

Key = Tuple[str, str, tuple]  # (jax_backend, backend_name, shape_key)


def _jax_backend() -> str:
    """The platform axis of every measurement key. A non-default
    ``REPRO_KERNELS`` override is part of the platform: the kernel-tier
    routes trace a different program per mode (Pallas VMEM kernel vs jnp
    fallback vs the ~32× Python interpreter), so a timing measured under an
    overridden mode must never outrank the analytical model under another —
    the measured-cost analogue of the batch-jit ``cache_tag``
    (DESIGN.md §4). The visible device count is part of the platform for
    the same reason: a process forced to N host devices
    (``--xla_force_host_platform_device_count``) splits every core's cycles
    N ways, so its timings must never pollute single-device calibration
    entries (or vice versa). A non-default ``REPRO_VMEM_BUDGET`` joins the
    key too: the budget sizes the tiled kernels' streaming windows (a
    different traced program with different tile shapes), so timings under
    an overridden budget would mislead the default-budget ranking."""
    import jax

    from repro.kernels import ops

    jb = jax.default_backend()
    mode = ops.kernel_mode()
    default = "pallas" if jb == "tpu" else "ref"
    base = jb if mode == default else f"{jb}+{mode}"
    budget = ops.vmem_budget_bytes()
    if budget != ops.DEFAULT_VMEM_BUDGET_BYTES:
        base += f"+vmem{budget}"
    ndev = jax.device_count()
    return base if ndev == 1 else f"{base}x{ndev}dev"


@dataclasses.dataclass
class Entry:
    """One measured latency: per-instance milliseconds, how many
    measurements folded in, and where they came from (``calibrate`` /
    ``online`` / ``mixed``)."""

    ms: float
    count: int = 1
    source: str = "calibrate"


def _key_to_json(x):
    return [_key_to_json(v) for v in x] if isinstance(x, (tuple, list)) else x


def _key_from_json(x):
    return tuple(_key_from_json(v) for v in x) if isinstance(x, list) else x


class CalibrationTable:
    """Per-(jax_backend, backend, shape_key) latency table with JSON
    persistence. All latencies are per-instance milliseconds."""

    VERSION = 1

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._entries: Dict[Key, Entry] = {}
        #: (jax_backend, backend) -> {shape_key: Entry}, so cost resolution
        #: scans only one backend's entries instead of the whole table
        self._by_backend: Dict[tuple, Dict[tuple, Entry]] = {}
        #: memoized measured_ms resolutions (incl. interpolation misses);
        #: any write invalidates it, and it is LRU-bounded — dispatching
        #: endless fresh shapes against a read-only table must not grow
        #: process memory (same invariant as every other per-shape cache)
        self._memo: "OrderedDict[tuple, Optional[float]]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def items(self):
        return self._entries.items()

    def entries_for(self, backend: str,
                    jax_backend: Optional[str] = None) -> Dict[tuple, Entry]:
        jb = jax_backend or _jax_backend()
        return self._by_backend.get((jb, backend), {})

    def _key(self, backend: str, shape_key: tuple,
             jax_backend: Optional[str]) -> Key:
        return (jax_backend or _jax_backend(), backend, tuple(shape_key))

    def _put(self, key: Key, entry: Entry) -> Entry:
        self._entries[key] = entry
        self._by_backend.setdefault(key[:2], {})[key[2]] = entry
        self._memo.clear()
        return entry

    def lookup(self, backend: str, shape_key: tuple,
               jax_backend: Optional[str] = None) -> Optional[Entry]:
        return self._entries.get(self._key(backend, shape_key, jax_backend))

    def record(self, backend: str, shape_key: tuple, ms: float,
               jax_backend: Optional[str] = None,
               source: str = "calibrate") -> Entry:
        """Overwrite-style write (offline calibration: min-of-N already
        summarized the samples)."""
        key = self._key(backend, shape_key, jax_backend)
        prev = self._entries.get(key)
        return self._put(key, Entry(ms=float(ms),
                                    count=(prev.count + 1 if prev else 1),
                                    source=source))

    def observe(self, backend: str, shape_key: tuple, ms: float,
                alpha: float = EMA_ALPHA,
                jax_backend: Optional[str] = None) -> Entry:
        """EMA fold of one realized latency (the engine's online feedback)."""
        key = self._key(backend, shape_key, jax_backend)
        prev = self._entries.get(key)
        if prev is None:
            entry = Entry(ms=float(ms), source="online")
        else:
            entry = Entry(ms=(1.0 - alpha) * prev.ms + alpha * float(ms),
                          count=prev.count + 1,
                          source="online" if prev.source == "online" else "mixed")
        return self._put(key, entry)

    # -- persistence -------------------------------------------------------
    def to_json(self) -> dict:
        return {
            "version": self.VERSION,
            "entries": [
                {"jax_backend": jb, "backend": name,
                 "shape_key": _key_to_json(shape_key),
                 "ms": round(e.ms, 6), "count": e.count, "source": e.source}
                for (jb, name, shape_key), e in sorted(
                    self._entries.items(), key=lambda kv: repr(kv[0]))
            ],
        }

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no path configured for this calibration table")
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)
        self.path = path
        return os.path.abspath(path)

    @classmethod
    def load(cls, path: str) -> "CalibrationTable":
        """Load a persisted table; anything unreadable (missing file,
        corrupt JSON, wrong schema) degrades to an EMPTY table — dispatch
        then falls back to the analytical model, it never errors."""
        table = cls(path=path)
        if not os.path.exists(path):
            return table
        try:
            with open(path) as f:
                raw = json.load(f)
            if raw.get("version") != cls.VERSION:
                raise ValueError(f"unsupported version {raw.get('version')!r}")
            for row in raw["entries"]:
                key = (str(row["jax_backend"]), str(row["backend"]),
                       _key_from_json(row["shape_key"]))
                table._put(key, Entry(
                    ms=float(row["ms"]), count=int(row.get("count", 1)),
                    source=str(row.get("source", "calibrate"))))
        except Exception as exc:  # corrupt cache must never break dispatch
            _log.warning("ignoring corrupt calibration table %r: %s "
                         "(falling back to the analytical model)", path, exc)
            table._entries.clear()
            table._by_backend.clear()
            table._memo.clear()
        return table


# ---------------------------------------------------------------------------
# Process-global table
# ---------------------------------------------------------------------------
_TABLE: Optional[CalibrationTable] = None


def get_table() -> CalibrationTable:
    """The process-global table; auto-loads ``$REPRO_DP_CALIB`` when set."""
    global _TABLE
    if _TABLE is None:
        path = envknobs.read(ENV_PATH)
        _TABLE = CalibrationTable.load(path) if path else CalibrationTable()
    return _TABLE


def set_table(table: CalibrationTable) -> CalibrationTable:
    global _TABLE
    _TABLE = table
    return table


def reset() -> None:
    """Drop all calibration state (tests; next use re-resolves the env)."""
    global _TABLE
    _TABLE = None


def load(path: str) -> CalibrationTable:
    return set_table(CalibrationTable.load(path))


def observe(backend_name: str, shape_key: tuple, ms: float,
            alpha: float = EMA_ALPHA) -> Entry:
    return get_table().observe(backend_name, shape_key, ms, alpha=alpha)


def has_measurement(backend_name: str, shape_key: tuple) -> bool:
    """Exact-entry check (the engine's exploration criterion, on the
    regime-suffixed key) — interpolated estimates and other regimes don't
    count, a route stays explorable until actually timed in this regime."""
    return get_table().lookup(backend_name, shape_key) is not None


# ---------------------------------------------------------------------------
# Cost resolution: exact entry > nearest-shape interpolation > None
# ---------------------------------------------------------------------------
def measured_ms(backend, spec: Spec,
                table: Optional[CalibrationTable] = None,
                suffix: tuple = ()) -> Optional[float]:
    """Measured latency of ``backend`` on ``spec``'s shape under the current
    JAX backend. Exact entries win; otherwise the nearest compatible shape
    (``backends.shape_key_distance``) within a :data:`MAX_INTERP_RATIO` size
    ratio is scaled by the analytical cost ratio — the step-count model as
    interpolation prior. ``None`` when nothing transfers. ``suffix``
    selects a measurement regime — e.g. ``("reconstruct",)`` keys the
    arg-emitting solve observations separately from plain ones, whose cost
    profiles differ (distance rules keep the regimes from cross-matching)."""
    t = table if table is not None else get_table()
    if not len(t):
        return None
    jb = _jax_backend()
    key = spec.shape_key() + tuple(suffix)
    memo_key = (jb, backend.name, key)
    if memo_key in t._memo:
        t._memo.move_to_end(memo_key)
        return t._memo[memo_key]
    return _backends.lru_put(t._memo, memo_key,
                             _resolve_ms(t, jb, backend, spec, key), MEMO_MAX)


def _resolve_ms(t: CalibrationTable, jb: str, backend, spec: Spec,
                key: tuple) -> Optional[float]:
    by_shape = t.entries_for(backend.name, jax_backend=jb)
    exact = by_shape.get(key)
    if exact is not None:
        return exact.ms
    best = None
    for ekey, entry in by_shape.items():
        d = _backends.shape_key_distance(key, ekey)
        if d is None:
            continue
        n0, n1 = _backends.shape_key_size(key), _backends.shape_key_size(ekey)
        if max(n0, n1) > MAX_INTERP_RATIO * max(1, min(n0, n1)):
            continue
        if best is None or d < best[0]:
            best = (d, ekey, entry)
    if best is None:
        return None
    _, ekey, entry = best
    try:
        ref = _backends.spec_from_shape_key(ekey)
        scale = backend.cost(spec) / max(backend.cost(ref), 1e-9)
    except Exception:  # cost models only read shapes, but stay defensive
        scale = 1.0
    return entry.ms * max(scale, 1e-9)


def _rank_by(pool: list, resolve) -> list:
    """Shared two-tier sort: tier 0 = resolved measured ms (ascending),
    tier 1 = unresolved, input order preserved (the structural/analytical
    prior); input order also breaks measured ties. With no resolved entry
    the input order is returned unchanged — an empty table is bit-identical
    to the analytical dispatcher."""
    decorated = []
    any_measured = False
    for i, b in enumerate(pool):
        ms = resolve(i, b)
        if ms is None:
            decorated.append((1, 0.0, i, b))
        else:
            any_measured = True
            decorated.append((0, ms, i, b))
    if not any_measured:
        return pool
    decorated.sort(key=lambda d: d[:3])
    return [d[3] for d in decorated]


def rank(spec: Spec, cands: Sequence, suffix: tuple = ()) -> list:
    """Two-tier ordering of candidate backends: tier 0 = measured cost,
    tier 1 = unmeasured in analytical order (the model as prior and
    tiebreak). ``suffix`` selects the measurement regime (see
    :func:`measured_ms`)."""
    t = get_table()
    if not len(t):
        return list(cands)
    return _rank_by(list(cands), lambda i, b: measured_ms(
        b, spec, table=t, suffix=suffix))


def rank_batch(spec: Spec, batchable: Sequence, loop_only: Sequence,
               batch_suffix: tuple = ("batch",),
               loop_suffix: Optional[tuple] = None) -> list:
    """:func:`rank` for a batch pool, where single-instance entries and
    the batch regime can disagree: plain (offline) entries time a SINGLE
    ``run``, but a batchable route amortizes a whole bucket in one device
    call. Routes resolve against batch-regime measurements (the engine's
    amortized drain observations) first; a batchable route may fall back to
    its single-instance entry as a prior, a loop-fallback route may not —
    winning a single-run comparison never buys it the right to break
    batching (tier 1 keeps batchable-first order). ``loop_suffix``
    (default: ``batch_suffix``) is the regime loop-fallback routes rank on —
    the sharded engine ranks batchable routes on its ``("shard", ndev)``
    regime while loop fallbacks, which it executes unsharded, stay on the
    single-device batch regime."""
    t = get_table()
    pool = list(batchable) + list(loop_only)
    if not len(t):
        return pool
    loop_suffix = batch_suffix if loop_suffix is None else loop_suffix

    def resolve(i, b):
        if i < len(batchable):
            ms = measured_ms(b, spec, table=t, suffix=batch_suffix)
            if ms is None:
                ms = measured_ms(b, spec, table=t)
            return ms
        return measured_ms(b, spec, table=t, suffix=loop_suffix)

    return _rank_by(pool, resolve)


# ---------------------------------------------------------------------------
# Offline calibration
# ---------------------------------------------------------------------------
def _time_ms(fn, repeats: int) -> float:
    """Warm once (compile + caches), then min-of-N. ``fn`` must block — the
    backends' numpy conversion is the sync point."""
    fn()
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def calibrate_spec(spec: Spec, repeats: int = 3,
                   table: Optional[CalibrationTable] = None) -> dict:
    """Time every supporting backend on one spec and record the results.
    Returns ``{backend_name: ms}``. Entries are single-instance latencies
    under the plain (regime-less) keys; the engine's amortized per-bucket
    observations live under the ``("batch",)`` regime and never mix."""
    t = table if table is not None else get_table()
    out = {}
    for b in _backends.candidates(spec):
        ms = _time_ms(lambda b=b: b.run(spec), repeats)
        t.record(b.name, spec.shape_key(), ms)
        out[b.name] = ms
    return out


def calibrate(problems: Optional[Sequence[str]] = None,
              sizes: Sequence[int] = (8, 16, 32), repeats: int = 3,
              seed: int = 0, path: Optional[str] = None) -> CalibrationTable:
    """Offline calibration sweep: representative instances of each problem
    (all registered ones by default) at each size, every supporting backend
    timed warm min-of-N. Persists to ``path`` (or the table's own path) when
    given; the populated table immediately drives dispatch."""
    from repro.dp import registry as _registry

    t = get_table()
    rng = np.random.default_rng(seed)
    names = list(problems) if problems is not None else _registry.names()
    for name in names:
        prob = _registry.get(name)
        for size in sizes:
            kw = prob.sample(rng, int(size))
            calibrate_spec(prob.encode(**kw), repeats=repeats, table=t)
    if path is not None:
        t.save(path)
    elif t.path:
        t.save()
    return t


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------
def routing_report(table: Optional[CalibrationTable] = None) -> dict:
    """Measured-vs-analytical dispatch audit over every calibrated shape on
    the current JAX backend: which route each policy picks, whether they
    agree, and the *analytical regret* — measured ms of the analytical pick
    over measured ms of the true fastest (1.0 = the model was right).
    Rows are grouped per (shape, measurement regime); only rows where at
    least two routes were measured enter the agree/regret statistics —
    a single-backend row can't disagree with anything."""
    t = table if table is not None else get_table()
    jb = _jax_backend()
    by_shape: Dict[tuple, Dict[str, Entry]] = {}
    for (ejb, name, shape_key), e in t.items():
        if ejb == jb:
            by_shape.setdefault(shape_key, {})[name] = e
    shapes, regrets = [], []
    for shape_key, measured in sorted(by_shape.items(),
                                      key=lambda kv: repr(kv[0])):
        spec = _backends.spec_from_shape_key(shape_key)
        _, regime = _backends.split_shape_key(shape_key)
        analytic = {}
        for name in measured:
            try:
                analytic[name] = float(_backends.get(name).cost(spec))
            except Exception:
                analytic[name] = float("inf")
        measured_choice = min(measured, key=lambda n: (measured[n].ms, n))
        analytic_choice = min(analytic, key=lambda n: (analytic[n], n))
        regret = (measured[analytic_choice].ms
                  / max(measured[measured_choice].ms, 1e-9))
        comparable = len(measured) >= 2
        if comparable:
            regrets.append(regret)
        shapes.append({
            "shape_key": shape_key,
            "regime": regime or "single",
            "comparable": comparable,
            "measured_choice": measured_choice,
            "analytical_choice": analytic_choice,
            "agree": measured_choice == analytic_choice,
            "analytical_regret": round(regret, 3),
            "measured_ms": {n: round(e.ms, 4)
                            for n, e in sorted(measured.items())},
        })
    return {
        "jax_backend": jb,
        "shapes": shapes,
        "disagreements": sum(1 for s in shapes
                             if s["comparable"] and not s["agree"]),
        "median_analytical_regret":
            float(np.median(regrets)) if regrets else 1.0,
        "max_analytical_regret": float(max(regrets)) if regrets else 1.0,
    }
