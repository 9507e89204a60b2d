"""Declarative DP problem specs — the contract between the problem zoo and
the solver backends (DESIGN.md §3).

A *spec* is the canonical form of one problem instance: fully materialized,
or, for a grid with a plane source, the source its planes derive from.
Spec classes form an open **family protocol**: each family (a dataclass with
a ``family`` tag) registers itself via :func:`register_family` and carries
every family-specific behaviour as hooks on the class — shape-key tagging
and compatibility, phantom-spec reconstruction, the route cost vocabulary,
digest hashing, argument/traceback support, dependency/probe models for the
static schedule-hazard verifier — so the dispatch, calibration,
reconstruction, engine, and sharding layers stay family-agnostic. Adding a
fourth family is: write the dataclass + hooks, register it, register
solvers for it.

Three families cover the zoo today:

``LinearSpec`` — the paper's (weighted) S-DP recurrence on a 1-D table:

    ST[i] = ⊕_{1≤j≤k} ( ST[i - a_j] ⊙ w[i, j] ),   ST[0..a_1-1] preset,

  with ``(⊕, ⊙)`` the semiring whose ``add`` is the semigroup ``op``
  (min→min-plus, max→max-plus, add→plus-times) and ``w ≡ one`` when
  ``weights`` is None. Grid DPs (edit distance, LCS, Viterbi trellises)
  linearize into this form with semiring-zero weights masking the ragged
  row boundaries.

``TriangularSpec`` — the canonical split recurrence on the upper triangle,
  diagonal-major linearized exactly like the paper's MCM table:

    m[i, j] = min_{0≤e<d} ( m[i, i+e] + m[i+e+1, j] + W[lin(i,d), e] ),

  diagonal-0 cells preset to 0. MCM, optimal BST, and polygon triangulation
  are all instances; MCM-shaped specs additionally carry ``dims`` so
  GEMM-structured backends (tropical-tile ``blocked_mcm``) stay eligible.

``GridSpec`` — multi-plane 2-D tables solved wavefront-by-wavefront
  (DESIGN.md §9). Two schedules share the family:

  * ``"antidiag"`` — alignment grids: every cell combines *shift moves*
    ``(p_to, p_from, di, dj)`` with per-cell weight planes; cells on one
    anti-diagonal ``i + j = t`` are independent (Needleman–Wunsch, Gotoh
    affine-gap with its M/X/Y planes, edit distance, LCS).
  * ``"spandiag"`` — parse charts: the triangular split recurrence
    generalized to planes, combining *binary rules*
    ``(p_to, p_left, p_right)`` over every split (CKY parsing with
    planes = nonterminals).

A ``DPProblem`` bundles the instance encoder with a *numpy oracle* (an
independent reference implementation), an answer extractor, and a random
instance sampler — everything tests, the dispatcher, and the benchmark
sweep need to treat problems uniformly.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Callable, ClassVar, Optional, Union

import numpy as np


# --- canonical triangular layout (the paper's diagonal-major linearization) --
def num_cells(n: int) -> int:
    return n * (n + 1) // 2


def lin_index(i, d, n):
    """Diagonal-major linear index of cell (i, i+d) in an n-wide table."""
    return d * n - (d * (d - 1)) // 2 + i


# --- the family registry -----------------------------------------------------
#: family tag -> spec class. Open: new families register themselves and every
#: family-generic layer (backends, routing, autotune, reconstruct, engine,
#: sharding, registry) resolves behaviour through the class hooks.
FAMILIES: dict = {}


def register_family(cls):
    """Register a spec family class (keyed by its ``family`` tag)."""
    if cls.family in FAMILIES:
        raise ValueError(f"duplicate spec family {cls.family!r}")
    FAMILIES[cls.family] = cls
    return cls


def family_class(tag: str):
    """Spec class of a family tag (the first element of a shape_key)."""
    try:
        return FAMILIES[tag]
    except KeyError:
        raise KeyError(f"unknown spec family {tag!r}; "
                       f"registered: {sorted(FAMILIES)}") from None


# --- shared cost-vocabulary constants (see route_costs hooks) ---------------
def _log2(x: float) -> float:
    return math.log2(max(x, 2.0))


#: n below which the analytical prior prices fixed dispatch overhead: at
#: tiny n the solve itself is a handful of device steps, so the per-route
#: launch/gather/vmap machinery dominates wall time. Without these floors
#: the step-count model calls every fancy route ~free at n ≤ 16 and the
#: unmeasured prior routes small instances to device pipelines that lose to
#: the plain sequential loop (the PR-4 dispatch-regret regression).
_SMALL_N = 16
#: per-route fixed-overhead floors, in the same 'vectorized device steps'
#: unit — rough dispatch-cost ranks, not measurements (calibration
#: overwrites them with real timings).
_LINEAR_OVERHEAD = {"sequential": 0.0, "tournament": 8.0, "pipeline": 8.0,
                    "blocked": 6.0, "companion_scan": 16.0}
_TRIANGULAR_OVERHEAD = {"wavefront": 0.0, "mcm_pipeline": 64.0,
                        "blocked_mcm": 24.0, "tiled_wavefront": 0.0}
_GRID_OVERHEAD = {"grid_wavefront": 0.0}


def _floored(costs: dict, overhead: dict, n: int) -> dict:
    if n <= _SMALL_N:
        costs = {name: c + overhead[name] for name, c in costs.items()}
    return {name: max(1.0, c) for name, c in costs.items()}


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    """Weighted S-DP instance: table length ``n``, strictly-decreasing
    ``offsets``, semigroup ``op``, ``init`` of length a_1, optional
    ``(n, k)`` semiring ``weights``."""

    offsets: tuple
    op: str
    n: int
    init: np.ndarray
    weights: Optional[np.ndarray] = None

    family: ClassVar[str] = "linear"
    #: whether traceback entry points (problem ``start`` hooks) apply
    uses_start: ClassVar[bool] = True

    @property
    def geometry(self) -> str:
        return self.family

    def shape_key(self) -> tuple:
        """Instances with equal keys can be vmapped into one device call.
        The first element is always the family tag (the calibration layer's
        cross-family firewall)."""
        return ("linear", self.op, tuple(int(a) for a in self.offsets),
                int(self.n), self.weights is not None)

    def validate(self) -> None:
        a = np.asarray(self.offsets)
        if not (a.ndim == 1 and a.size and np.all(np.diff(a) < 0) and a[-1] > 0):
            raise ValueError(f"offsets must be strictly decreasing > 0: {self.offsets}")
        if len(self.init) != int(a[0]):
            raise ValueError(f"init must have a_1={int(a[0])} entries, got {len(self.init)}")
        if self.n <= int(a[0]):
            raise ValueError(f"n={self.n} must exceed a_1={int(a[0])}")
        if self.weights is not None and self.weights.shape != (self.n, a.size):
            raise ValueError(f"weights must be (n, k)=({self.n}, {a.size}), "
                             f"got {self.weights.shape}")

    # --- family protocol hooks ---------------------------------------------
    def digest_into(self, h) -> None:
        h.update(b"linear")
        h.update(self.op.encode())
        h.update(repr(tuple(int(a) for a in self.offsets)).encode())
        h.update(str(int(self.n)).encode())
        _hash_array(h, self.init)
        _hash_array(h, self.weights)

    @classmethod
    def shape_key_size(cls, key: tuple) -> int:
        return int(key[3])

    @classmethod
    def shape_key_compatible(cls, a: tuple, b: tuple) -> bool:
        """Same traced program modulo table length: op, offsets, and
        weightedness must match (those change the program, not its size)."""
        return len(a) == len(b) and (a[1], a[2], a[4]) == (b[1], b[2], b[4])

    @classmethod
    def from_shape_key(cls, key: tuple) -> "LinearSpec":
        _, op, offsets, n, weighted = key
        offsets = tuple(int(a) for a in offsets)
        n, k = int(n), len(offsets)
        return cls(offsets=offsets, op=op, n=n,
                   init=np.zeros(offsets[0], np.float32),
                   weights=np.zeros((n, k), np.float32) if weighted else None)

    def route_costs(self) -> dict:
        """Step-count cost model for the linear solver family (§III of the
        paper + DESIGN.md §3). Units are 'vectorized device steps'. Every
        count is floored at one step: a preset-only table (n ≤ a_1,
        constructible without ``validate()``) gives ``ceil((n-a1)/B) = 0``,
        which let ``blocked`` degenerately auto-win at cost 0. Below
        ``_SMALL_N`` each route additionally pays its fixed
        dispatch-overhead floor."""
        n, k = self.n, len(self.offsets)
        a1, ak = int(self.offsets[0]), int(self.offsets[-1])
        blocked_steps = max(1, math.ceil((n - a1) / max(1, min(ak, 512))))
        costs = {
            "sequential": float(n * k),
            "tournament": float(n * (1.0 + _log2(k))),
            "pipeline": float(n + k - a1 - 1),
            "blocked": blocked_steps * (1.0 + _log2(k)),
            # log-depth scan, O(n·a1³) work spread over the vector units
            "companion_scan": _log2(n) * (a1 ** 3) / 64.0 + a1,
        }
        return _floored(costs, _LINEAR_OVERHEAD, n)

    def schedule_model(self):
        """Ground-truth dependency structure for the schedule-hazard
        verifier (DESIGN.md §10): candidate ``j`` of cell ``c`` reads the
        single operand ``c - a_{j+1}``; cells ``< a_1`` are preset."""
        from repro.dp.schedule import DependencyModel

        a1 = int(self.offsets[0])
        cands = tuple(
            () if c < a1 else tuple((c - int(a),) for a in self.offsets)
            for c in range(self.n))
        return DependencyModel(
            label=f"linear(offsets={tuple(int(a) for a in self.offsets)}, "
                  f"n={self.n}, op={self.op})",
            cells=self.n, preset=frozenset(range(a1)), candidates=cands)

    @classmethod
    def probe_specs(cls) -> tuple:
        """Small valid instances the static analyzer verifies every
        registered route against (exhaustive symbolic simulation stays
        trivial at these sizes). Coverage: multi-offset, weighted deep
        fan-in, single-offset degenerate, and a non-selective op (the
        linter's ``supports_args`` probe)."""

        def mk(offsets, n, weighted=False, op="min"):
            return cls(offsets=offsets, op=op, n=n,
                       init=np.zeros(offsets[0], np.float32),
                       weights=(np.ones((n, len(offsets)), np.float32)
                                if weighted else None))

        return (mk((2, 1), 6), mk((3, 2, 1), 8, weighted=True),
                mk((1,), 4), mk((2, 1), 6, op="add"))

    def supports_args(self) -> bool:
        """Linear specs need a selective semigroup (min/max — op="add"
        folds every lane, so there is no winning argument)."""
        return self.op in ("min", "max")

    def args_unsupported_reason(self) -> str:
        return f"op={self.op!r} folds every lane"

    def default_start(self, table) -> int:
        return self.n - 1

    def args_from_table(self, table: np.ndarray) -> np.ndarray:
        from repro.core.sdp import linear_args_np

        return linear_args_np(table, self.offsets, self.op,
                              weights=self.weights)

    def traceback_host(self, args: np.ndarray, start: int = -1) -> "Path":
        from repro.core.sdp import linear_traceback_np

        cells, lanes, stop = linear_traceback_np(
            args, self.offsets, start if start >= 0 else self.n - 1)
        return LinearPath(cells=cells, lanes=lanes, stop=int(stop))

    def traceback_program(self):
        """(key, build, post) of the batched device traceback: ``build``
        returns the jitted vmapped walk (logging ``key`` to the TRACE_LOG
        at trace time), ``post(walk, argss, starts)`` executes it and
        unpacks per-instance paths."""
        import jax
        import jax.numpy as jnp

        from repro.core.sdp import linear_traceback
        from repro.dp import backends as _backends

        offsets, n = self.offsets, self.n
        key = ("traceback", "linear", offsets, n)

        def build():
            def call(args_b, starts_b):
                _backends.log_trace(key)
                return jax.vmap(
                    lambda a, s: linear_traceback(a, offsets, n, s)
                )(args_b, starts_b)

            return jax.jit(call)

        def post(walk, argss, starts):
            if starts is None:
                starts = [n - 1] * len(argss)
            cells, lanes, valid, stop = walk(
                jnp.stack([jnp.asarray(a) for a in argss]),
                jnp.asarray(np.asarray(starts, dtype=np.int32)))
            cells, lanes = np.asarray(cells), np.asarray(lanes)
            valid, stop = np.asarray(valid), np.asarray(stop)
            return [LinearPath(cells=cells[b][valid[b]],
                               lanes=lanes[b][valid[b]], stop=int(stop[b]))
                    for b in range(len(argss))]

        return key, build, post

    # --- streaming/extension hooks (DESIGN.md §11) --------------------------
    def extend_length(self) -> int:
        """Steps along the growth axis (appendable table cells)."""
        return int(self.n)

    def min_prefix_len(self) -> int:
        """Smallest valid prefix length along the growth axis."""
        return int(self.offsets[0]) + 1

    def split_spec(self, length: int) -> "LinearSpec":
        """The first ``length`` steps as a standalone spec: same init,
        bitwise weight-row prefix — its cold table is exactly the first
        ``length`` cells of this spec's cold table (cell i reads only
        cells < i and weight row i)."""
        length = int(length)
        if not self.min_prefix_len() <= length <= self.n:
            raise ValueError(f"prefix length {length} outside "
                             f"[{self.min_prefix_len()}, {self.n}]")
        w = (None if self.weights is None
             else np.ascontiguousarray(self.weights[:length]))
        return dataclasses.replace(self, n=length, weights=w)

    def extension_delta(self, prefix: "LinearSpec") -> dict:
        """The delta turning ``prefix`` into ``self`` — raises unless
        ``prefix`` is a strict bitwise prefix of this spec."""
        if (not isinstance(prefix, LinearSpec)
                or (prefix.op, tuple(prefix.offsets))
                != (self.op, tuple(self.offsets))
                or not prefix.n < self.n
                or not _same_array(prefix.init, self.init)
                or (prefix.weights is None) != (self.weights is None)
                or (self.weights is not None
                    and not _same_array(prefix.weights,
                                        self.weights[:prefix.n]))):
            raise ValueError("spec is not a bitwise extension of the prefix")
        tail = (None if self.weights is None
                else np.ascontiguousarray(self.weights[prefix.n:]))
        return {"steps": int(self.n - prefix.n), "weights": tail}

    def extend_spec(self, delta: dict) -> "LinearSpec":
        """Append ``delta['steps']`` cells (and their weight rows)."""
        k = int(delta["steps"])
        if k < 1:
            raise ValueError(f"extension must append at least one step, got {k}")
        tail = delta.get("weights")
        if (tail is None) != (self.weights is None):
            raise ValueError("extension weights must match the spec's "
                             "weightedness")
        w = None
        if self.weights is not None:
            tail = np.asarray(tail, dtype=self.weights.dtype)
            if tail.shape != (k, len(self.offsets)):
                raise ValueError(f"extension weights must be "
                                 f"({k}, {len(self.offsets)}), got {tail.shape}")
            w = np.concatenate([self.weights, tail])
        ext = dataclasses.replace(self, n=self.n + k, weights=w)
        ext.validate()
        return ext

    def extension_state(self, table, args=None) -> dict:
        """Minimal resume payload: the last a₁ cells — every extension
        cell i ≥ n reads only cells i - a_j ≥ n - a₁."""
        a1 = int(self.offsets[0])
        return {"suffix": np.array(np.asarray(table)[-a1:])}

    def prefix_cell_map(self, prefix: "LinearSpec") -> np.ndarray:
        """Extended-layout cell id of every prefix-layout cell (identity
        for the linear family)."""
        return np.arange(prefix.n, dtype=np.int64)

    def saved_state_cells(self, prefix: "LinearSpec") -> np.ndarray:
        """Extended-layout cell ids the resume state retains."""
        a1 = int(self.offsets[0])
        return np.arange(prefix.n - a1, prefix.n, dtype=np.int64)

    def stitch_extension(self, prefix, prefix_table, ext_out) -> np.ndarray:
        """Full extended table from the retained prefix table plus the
        extend solver's new cells."""
        return np.concatenate([np.asarray(prefix_table), np.asarray(ext_out)])

    def chain_seed(self) -> bytes:
        """Digest of everything the chain commits to besides the per-step
        payloads (family tag, semiring, offsets, presets, weight dtype)."""
        h = hashlib.sha256()
        h.update(b"linear")
        h.update(self.op.encode())
        h.update(repr(tuple(int(a) for a in self.offsets)).encode())
        _hash_array(h, self.init)
        h.update(b"none" if self.weights is None
                 else str(self.weights.dtype).encode())
        return h.digest()

    def step_payloads(self, start: int = 0) -> list:
        """Chain payloads of steps ``start..n`` (the step's weight row).
        One bulk ``tobytes`` plus byte slicing, and only over the
        requested tail — the chain's Python-loop cost must stay far below
        a cold solve or streaming appends lose their win."""
        if self.weights is None:
            return [b""] * (self.n - start)
        w = np.ascontiguousarray(self.weights[start:])
        buf, row = w.tobytes(), w[:1].nbytes
        return [buf[i * row:(i + 1) * row] for i in range(self.n - start)]

    def flat_payload_digest(self, upto: int) -> bytes:
        """One unchained hash over payloads ``0..upto`` — the
        :class:`~repro.dp.streaming.ChainCursor` prefix-unchanged check,
        in a single C-speed pass over the contiguous weight rows."""
        if self.weights is None:
            return hashlib.sha256().digest()
        return hashlib.sha256(
            np.ascontiguousarray(self.weights[:upto]).tobytes()).digest()

    def content_extends(self, prev: "LinearSpec") -> bool:
        """Whether ``prev``'s step payloads equal this instance's first
        ``prev.n`` — a direct array memcmp (callers have already matched
        ``chain_seed``, which pins everything else payloads depend on)."""
        if self.weights is None:
            return True
        return bool(np.array_equal(self.weights[:prev.n], prev.weights))

    def prefix_digest_chain(self) -> dict:
        """``{L: digest}`` for every valid prefix length L: chained
        per-step digests over everything the first L cells' answers depend
        on, independent of this spec's total length — equal chains at L
        imply bit-equal prefix tables (the longest-prefix cache contract)."""
        return chain_digests(self.chain_seed(), self.step_payloads(),
                             self.min_prefix_len())[0]


@dataclasses.dataclass(frozen=True)
class TriangularSpec:
    """Canonical triangular instance: width ``n``; ``weights`` is the dense
    (num_cells(n), n-1) split-major table (``core.mcm.weight_table``).
    ``dims`` is set for MCM-shaped weights (w = p_i·p_{s+1}·p_{j+1})."""

    n: int
    weights: np.ndarray
    dims: Optional[np.ndarray] = None

    family: ClassVar[str] = "triangular"
    uses_start: ClassVar[bool] = False

    @property
    def geometry(self) -> str:
        return self.family

    def shape_key(self) -> tuple:
        return ("triangular", int(self.n))

    def validate(self) -> None:
        want = (num_cells(self.n), max(self.n - 1, 1))
        if self.weights.shape != want:
            raise ValueError(f"weights must be {want}, got {self.weights.shape}")
        if self.dims is not None and len(self.dims) != self.n + 1:
            raise ValueError(f"dims must have n+1={self.n + 1} entries")

    # --- family protocol hooks ---------------------------------------------
    def digest_into(self, h) -> None:
        h.update(b"triangular")
        h.update(str(int(self.n)).encode())
        _hash_array(h, self.weights)
        _hash_array(h, self.dims)

    @classmethod
    def shape_key_size(cls, key: tuple) -> int:
        return int(key[1])

    @classmethod
    def shape_key_compatible(cls, a: tuple, b: tuple) -> bool:
        return len(a) == len(b)

    @classmethod
    def from_shape_key(cls, key: tuple) -> "TriangularSpec":
        n = int(key[1])
        return cls(n=n,
                   weights=np.zeros((num_cells(n), max(n - 1, 1)), np.float32))

    def route_costs(self) -> dict:
        """Step-count cost model for the triangular solver family (the
        §3/§6 vocabulary; one shared table so every registering module
        prices against the same figures). Units and floors as in
        :meth:`LinearSpec.route_costs`."""
        n, cells = self.n, num_cells(self.n)
        costs = {
            "wavefront": float(n),                  # one masked combine/diagonal
            "mcm_pipeline": float(cells + n),       # Fig.-8 skewed head + drain
            # O(n) wavefront depth with GEMM-fed combines: favored beyond n ≈ 64
            "blocked_mcm": float(n) * 0.75 + 16.0,
            # O(n) wavefront depth over banded tiles: the dense masked combine
            # pays ~2× the band's work per diagonal, the tile loop doesn't — it
            # overtakes wavefront past the flat streaming-setup term
            "tiled_wavefront": float(n) * 0.85 + 24.0,
        }
        return _floored(costs, _TRIANGULAR_OVERHEAD, n)

    def schedule_model(self):
        """Split-recurrence dependencies: candidate ``e`` of cell
        ``(i, i+d)`` reads ``(i, i+e)`` and ``(i+e+1, i+d)``; diagonal 0 is
        preset. Candidates are ordered by split offset ``e`` ascending (the
        canonical order every route's ``consume`` aligns with)."""
        from repro.dp.schedule import DependencyModel

        n = self.n
        cands = [()] * num_cells(n)
        for d in range(1, n):
            for i in range(n - d):
                cands[lin_index(i, d, n)] = tuple(
                    (lin_index(i, e, n), lin_index(i + e + 1, d - e - 1, n))
                    for e in range(d))
        return DependencyModel(
            label=f"triangular(n={n})", cells=num_cells(n),
            preset=frozenset(range(n)),      # lin_index(i, 0, n) == i
            candidates=tuple(cands))

    @classmethod
    def probe_specs(cls) -> tuple:
        """n=4 is the smallest width where the paper-order pipeline hazard
        manifests (DESIGN.md §2); the n=6 probe carries real MCM dims so
        the GEMM-structured ``blocked_mcm`` route (dims-gated, needs a
        divisible tile) is exercised rather than silently skipped."""
        from repro.core.mcm import mcm_weight_fn, weight_table

        dims = np.arange(1.0, 8.0)           # n + 1 = 7 matrix dimensions
        return (
            cls(n=4, weights=np.zeros((num_cells(4), 3), np.float32)),
            cls(n=5, weights=np.zeros((num_cells(5), 4), np.float32)),
            cls(n=6, weights=weight_table(6, mcm_weight_fn(dims)),
                dims=dims),
        )

    def supports_args(self) -> bool:
        """Triangular specs always reduce by min — always selective."""
        return True

    def args_unsupported_reason(self) -> str:
        return "no argument structure"

    def default_start(self, table) -> int:
        return -1

    def args_from_table(self, table: np.ndarray) -> np.ndarray:
        from repro.core.mcm import triangular_args_np

        return triangular_args_np(table, self.weights, self.n)

    def traceback_host(self, args: np.ndarray, start: int = -1) -> "Path":
        from repro.core.mcm import triangular_traceback_np

        return TriangularPath(nodes=triangular_traceback_np(args, self.n))

    def traceback_program(self):
        import jax
        import jax.numpy as jnp

        from repro.core.mcm import triangular_traceback
        from repro.dp import backends as _backends

        n = self.n
        key = ("traceback", "triangular", n)

        def build():
            def call(args_b):
                _backends.log_trace(key)
                return jax.vmap(lambda a: triangular_traceback(a, n))(args_b)

            return jax.jit(call)

        def post(walk, argss, starts):
            ii, dd, ee = walk(jnp.stack([jnp.asarray(a) for a in argss]))
            nodes = np.stack([np.asarray(ii), np.asarray(dd), np.asarray(ee)],
                             axis=2)
            return [TriangularPath(nodes=nodes[b].astype(np.int64))
                    for b in range(len(argss))]

        return key, build, post

    # --- streaming/extension hooks (DESIGN.md §11) --------------------------
    def extend_length(self) -> int:
        """Growth axis = chain width (appendable matrices/leaves)."""
        return int(self.n)

    def min_prefix_len(self) -> int:
        return 2

    def split_spec(self, length: int) -> "TriangularSpec":
        """Width-``length`` prefix: the logical weight entries of every
        chain [i, j ≤ length-1], re-laid-out into the narrower
        diagonal-major table (padding beyond e ≥ d is zeroed — the masked
        combine never reads it)."""
        L = int(length)
        if not self.min_prefix_len() <= L <= self.n:
            raise ValueError(f"prefix length {L} outside "
                             f"[{self.min_prefix_len()}, {self.n}]")
        w = np.zeros((num_cells(L), max(L - 1, 1)), self.weights.dtype)
        for d in range(1, L):
            src, dst = lin_index(0, d, self.n), lin_index(0, d, L)
            w[dst:dst + (L - d), :d] = self.weights[src:src + (L - d), :d]
        dims = (None if self.dims is None
                else np.ascontiguousarray(self.dims[:L + 1]))
        return dataclasses.replace(self, n=L, weights=w, dims=dims)

    def _logical_prefix_equal(self, other: "TriangularSpec") -> bool:
        """Do ``other``'s logical weight entries equal this spec's first
        ``other.n`` columns' entries, bitwise (layout-independent)?"""
        if other.weights.dtype != self.weights.dtype or other.n > self.n:
            return False
        for d in range(1, other.n):
            src, dst = lin_index(0, d, self.n), lin_index(0, d, other.n)
            rows = other.n - d
            if not np.array_equal(self.weights[src:src + rows, :d],
                                  other.weights[dst:dst + rows, :d]):
                return False
        return True

    def extension_delta(self, prefix: "TriangularSpec") -> dict:
        if (not isinstance(prefix, TriangularSpec)
                or not prefix.n < self.n
                or not self._logical_prefix_equal(prefix)
                or (prefix.dims is None) != (self.dims is None)
                or (self.dims is not None
                    and not _same_array(prefix.dims,
                                        self.dims[:prefix.n + 1]))):
            raise ValueError("spec is not a bitwise extension of the prefix")
        return {"steps": int(self.n - prefix.n),
                "weights": self.weights, "dims": self.dims}

    def extend_spec(self, delta: dict) -> "TriangularSpec":
        """Append ``delta['steps']`` matrices. Because the diagonal-major
        layout is width-dependent, the delta carries the FULL new weight
        table; its logical prefix must match this spec bitwise."""
        k = int(delta["steps"])
        if k < 1:
            raise ValueError(f"extension must append at least one step, got {k}")
        n2 = self.n + k
        w = np.asarray(delta["weights"])
        want = (num_cells(n2), max(n2 - 1, 1))
        if w.shape != want:
            raise ValueError(f"extension weights must be {want}, got {w.shape}")
        dims = delta.get("dims")
        if (dims is None) != (self.dims is None):
            raise ValueError("extension dims must match the spec's dims-ness")
        if dims is not None:
            dims = np.asarray(dims)
            if len(dims) != n2 + 1 or not _same_array(
                    np.asarray(dims[:self.n + 1]), self.dims):
                raise ValueError("extension dims must extend the prefix dims")
        ext = dataclasses.replace(self, n=n2, weights=w, dims=dims)
        if not ext._logical_prefix_equal(self):
            raise ValueError("extension weights do not preserve the prefix")
        ext.validate()
        return ext

    def extension_state(self, table, args=None) -> dict:
        """The split recurrence consumes whole rows: extension cell
        (i, j ≥ n) reads (i, s) for EVERY s < j, so every prefix cell is a
        live operand and the minimal resume state is the full prefix
        triangle (a trailing-diagonals-only state is provably
        insufficient — the analysis verifier's undersized fixture)."""
        return {"suffix": np.array(np.asarray(table))}

    def prefix_cell_map(self, prefix: "TriangularSpec") -> np.ndarray:
        m = np.empty(num_cells(prefix.n), np.int64)
        for d in range(prefix.n):
            src, dst = lin_index(0, d, prefix.n), lin_index(0, d, self.n)
            m[src:src + (prefix.n - d)] = np.arange(
                dst, dst + (prefix.n - d), dtype=np.int64)
        return m

    def saved_state_cells(self, prefix: "TriangularSpec") -> np.ndarray:
        return self.prefix_cell_map(prefix)

    def stitch_extension(self, prefix, prefix_table, ext_out) -> np.ndarray:
        # the windowed extend solver already emits the full new-layout table
        return np.asarray(ext_out)

    def chain_seed(self) -> bytes:
        h = hashlib.sha256()
        h.update(b"triangular")
        h.update(str(self.weights.dtype).encode())
        if self.dims is None:
            h.update(b"none")
        else:
            h.update(str(self.dims.dtype).encode())
            h.update(_arr_bytes(self.dims[:1]))
        return h.digest()

    def step_payloads(self, start: int = 0) -> list:
        """Payloads of steps ``start..n``. Payload j: the logical weights
        of every chain ending at leaf j (layout-independent slices) plus
        dims[j+1]."""
        out = []
        for j in range(start, self.n):
            parts = [self.weights[lin_index(i, j - i, self.n), :j - i]
                     for i in range(j)]
            payload = b"".join(_arr_bytes(p) for p in parts)
            if self.dims is not None:
                payload += _arr_bytes(self.dims[j + 1:j + 2])
            out.append(payload)
        return out

    def flat_payload_digest(self, upto: int) -> bytes:
        return hashlib.sha256(
            b"".join(self.step_payloads()[:upto])).digest()

    def content_extends(self, prev: "TriangularSpec") -> bool:
        """Triangular weight tables re-layout as the chart widens (row
        widths grow with n), so no direct memcmp exists — fall back to
        comparing the layout-independent flat payload digests."""
        n_old = prev.extend_length()
        return self.flat_payload_digest(n_old) == \
            prev.flat_payload_digest(n_old)

    def prefix_digest_chain(self) -> dict:
        """Chain step j commits to the logical weights of every chain
        ending at leaf j (layout-independent slices) plus dims[j+1]."""
        return chain_digests(self.chain_seed(), self.step_payloads(),
                             self.min_prefix_len())[0]


@dataclasses.dataclass(frozen=True)
class PlaneBuilder:
    """How a :class:`PlaneSource` expands into an antidiag grid's planes.
    ``device(arrays, meta)`` is pure jnp and returns ``device_arrays()``
    inside a batch program; ``host(arrays, meta)`` is numpy and returns
    ``(weights, init, init_mask)`` as the spec holds them; both give the
    same planes bit for bit. ``shapes(meta)`` gives each source array's
    ``(shape, dtype)``."""

    device: Callable
    host: Callable
    shapes: Callable


#: plane builders by name
_PLANE_BUILDERS: dict = {}


def register_plane_builder(name: str, device: Callable, host: Callable,
                           shapes: Callable) -> None:
    """Register the builders that a :class:`PlaneSource` named ``name`` is
    expanded with: on the device inside a batch program, on the host when
    a host reader asks for the planes."""
    if name in _PLANE_BUILDERS:
        raise ValueError(f"duplicate plane builder {name!r}")
    _PLANE_BUILDERS[name] = PlaneBuilder(device, host, shapes)


def plane_builder(name: str) -> Callable:
    """The device (jnp) builder of the source ``name``."""
    return _PLANE_BUILDERS[name].device


@dataclasses.dataclass(frozen=True)
class PlaneSource:
    """Compact form of an antidiag grid's planes (DESIGN.md §9): the
    registered builder ``builder`` maps ``arrays`` (small per-instance host
    arrays whose shapes follow from the spec's ``static_meta()``) and the
    meta to the spec's planes, bit for bit."""

    builder: str
    arrays: tuple


class _SourcedPlane:
    """A plane field of :class:`GridSpec` (``weights``, ``init``,
    ``init_mask``). A spec given a :class:`PlaneSource` and no planes
    holds none; the first read of any of the three builds them all on the
    host (:meth:`GridSpec._build_planes`) and keeps them on the spec."""

    def __set_name__(self, owner, name):
        self.slot = "_" + name

    def __get__(self, spec, owner=None):
        if spec is None:
            return None                       # the dataclass default
        value = spec.__dict__.get(self.slot)
        if value is None and spec.source is not None:
            spec._build_planes()
            value = spec.__dict__[self.slot]
        return value

    def __set__(self, spec, value):
        spec.__dict__[self.slot] = value


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Multi-plane 2-D wavefront instance (DESIGN.md §9).

    ``schedule="antidiag"`` (alignment grids): the table is ``planes``
    stacked ``(rows, cols)`` grids; *shift moves* ``(p_to, p_from, di, dj)``
    (``di + dj ≥ 1``) each carry a per-cell weight plane
    ``weights[ℓ] (rows, cols)``;

        ST[p, i, j] = op_{ℓ: p_to=p} ( ST[p_from, i-di, j-dj] + w_ℓ[i, j] )

    with preset cells given by ``init``/``init_mask`` (``(planes, rows,
    cols)``). Out-of-grid or invalid moves must be masked with the semiring
    zero (±inf) in their weight plane. Public table/args layout: row-major
    ``(planes·rows·cols,)`` flat by ``(p, i, j)``.

    ``schedule="spandiag"`` (parse charts; ``rows == cols == n``): the
    triangular split recurrence over planes — cell ``(p, i, i+d)`` combines
    *binary rules* ``(p_to, p_left, p_right)`` with scalar log-weights
    ``rule_weights[r]`` over every split offset ``e``:

        ST[A, lin(i,d)] = op_{e, r: p_to=A}
            ( ST[B, lin(i,e)] + ST[C, lin(i+e+1, d-e-1)] + rw[r] )

    with diagonal 0 preset from ``init`` (``(planes, n)``: per-position
    per-plane leaf scores). Layout: ``(planes·num_cells(n),)`` flat,
    diagonal-major per plane. The packed arg of a cell is
    ``e·len(rules) + r``.

    ``source`` (optional, antidiag): a :class:`PlaneSource` from which a
    batch program builds ``device_arrays()`` on the device instead of
    receiving them. A sourced spec is its source: the digest hashes the
    source, not the planes, and ``weights``/``init``/``init_mask`` are
    derived — built on the host on first read, when a host route,
    streaming or ``device_arrays()`` asks. Any spec derived from other
    planes drops the source.
    """

    rows: int
    cols: int
    op: str
    schedule: str
    planes: int = 1
    moves: tuple = ()
    rules: tuple = ()
    weights: Optional[np.ndarray] = _SourcedPlane()
    rule_weights: Optional[np.ndarray] = None
    init: Optional[np.ndarray] = _SourcedPlane()
    init_mask: Optional[np.ndarray] = _SourcedPlane()
    source: Optional[PlaneSource] = dataclasses.field(default=None,
                                                      compare=False)

    family: ClassVar[str] = "grid"
    uses_start: ClassVar[bool] = True

    @property
    def geometry(self) -> str:
        return self.family

    @property
    def cells(self) -> int:
        """Cells per plane (schedule-dependent layout length)."""
        if self.schedule == "spandiag":
            return num_cells(self.rows)
        return self.rows * self.cols

    def shape_key(self) -> tuple:
        return ("grid", self.schedule, self.op, int(self.planes),
                int(self.rows), int(self.cols),
                tuple(tuple(int(v) for v in m) for m in self.moves),
                tuple(tuple(int(v) for v in r) for r in self.rules))

    def validate(self) -> None:
        if self.op not in ("min", "max"):
            raise ValueError(f"grid op must be min or max, got {self.op!r}")
        if self.schedule not in ("antidiag", "spandiag"):
            raise ValueError(f"unknown grid schedule {self.schedule!r}")
        if self.planes < 1 or self.rows < 1 or self.cols < 1:
            raise ValueError("planes, rows, cols must be positive")
        if self.schedule == "antidiag":
            if self.rules:
                raise ValueError("antidiag grids take shift moves, not rules")
            if not self.moves:
                raise ValueError("antidiag grids need at least one move")
            for m in self.moves:
                p_to, p_from, di, dj = m
                if not (0 <= p_to < self.planes and 0 <= p_from < self.planes):
                    raise ValueError(f"move {m} references a plane out of range")
                if di < 0 or dj < 0 or di + dj < 1:
                    raise ValueError(f"move {m} must step strictly forward "
                                     "(di, dj >= 0, di + dj >= 1)")
            if self.source is not None:
                self._check_source()
                return
            shape = (len(self.moves), self.rows, self.cols)
            if self.weights is None or self.weights.shape != shape:
                raise ValueError(f"weights must be {shape}, got "
                                 f"{None if self.weights is None else self.weights.shape}")
            pshape = (self.planes, self.rows, self.cols)
            if self.init is None or self.init.shape != pshape:
                raise ValueError(f"init must be {pshape}")
            if self.init_mask is None or self.init_mask.shape != pshape:
                raise ValueError(f"init_mask must be {pshape}")
            if not bool(np.all(self.init_mask[:, 0, 0])):
                raise ValueError("cell (0, 0) must be preset on every plane "
                                 "(no move can reach it)")
        else:
            if self.source is not None:
                raise ValueError("only antidiag grids take a plane source")
            if self.moves:
                raise ValueError("spandiag grids take rules, not shift moves")
            if not self.rules:
                raise ValueError("spandiag grids need at least one rule")
            if self.rows != self.cols or self.rows < 2:
                raise ValueError("spandiag grids need rows == cols >= 2")
            for r in self.rules:
                if len(r) != 3 or not all(0 <= p < self.planes for p in r):
                    raise ValueError(f"rule {r} references a plane out of range")
            if (self.rule_weights is None
                    or self.rule_weights.shape != (len(self.rules),)):
                raise ValueError(f"rule_weights must be ({len(self.rules)},)")
            if self.init is None or self.init.shape != (self.planes, self.rows):
                raise ValueError(f"init must be ({self.planes}, {self.rows})")

    def _check_source(self) -> None:
        """A sourced spec is checked by its source, without its planes."""
        build = _PLANE_BUILDERS.get(self.source.builder)
        if build is None:
            raise ValueError(f"unknown plane builder {self.source.builder!r}")
        want = tuple((tuple(shape), np.dtype(dtype))
                     for shape, dtype in build.shapes(self.static_meta()))
        got = tuple((np.shape(a), np.asarray(a).dtype)
                    for a in self.source.arrays)
        if got != want:
            raise ValueError(f"{self.source.builder!r} source arrays must be "
                             f"{want}, got {got}")

    def _build_planes(self) -> None:
        """Build a sourced spec's missing planes on the host, under the
        profiler span ``dp.planes``, and keep them on the spec."""
        from repro.dp import telemetry

        with telemetry.trace_span("dp.planes"):
            built = _PLANE_BUILDERS[self.source.builder].host(
                self.source.arrays, self.static_meta())
        for name, plane in zip(("_weights", "_init", "_init_mask"), built):
            if self.__dict__.get(name) is None:
                self.__dict__[name] = plane

    # --- family protocol hooks ---------------------------------------------
    def digest_into(self, h) -> None:
        """A sourced spec hashes its builder, structure and source arrays
        under its own tag: the builder is a pure function of them, so
        equal digests still mean bit-equal planes. Any other spec hashes
        its planes."""
        if self.source is not None:
            h.update(b"grid-source")
            h.update(self.source.builder.encode())
            h.update(repr(self.static_meta()).encode())
            for a in self.source.arrays:
                _hash_array(h, a)
            return
        h.update(b"grid")
        h.update(self.schedule.encode())
        h.update(self.op.encode())
        h.update(repr((int(self.planes), int(self.rows),
                       int(self.cols))).encode())
        h.update(repr(self.shape_key()[6:]).encode())   # moves, rules
        _hash_array(h, self.weights)
        _hash_array(h, self.rule_weights)
        _hash_array(h, self.init)
        _hash_array(h, None if self.init_mask is None
                    else self.init_mask.astype(np.uint8))

    @classmethod
    def shape_key_size(cls, key: tuple) -> int:
        return int(key[4]) * int(key[5])

    @classmethod
    def shape_key_compatible(cls, a: tuple, b: tuple) -> bool:
        """Only the grid extents may differ: schedule, op, planes, moves,
        and rules all change the traced program."""
        return (len(a) == len(b)
                and (a[1], a[2], a[3], a[6], a[7])
                == (b[1], b[2], b[3], b[6], b[7]))

    @classmethod
    def from_shape_key(cls, key: tuple) -> "GridSpec":
        _, schedule, op, planes, rows, cols, moves, rules = key
        planes, rows, cols = int(planes), int(rows), int(cols)
        if schedule == "antidiag":
            mask = np.zeros((planes, rows, cols), bool)
            mask[:, 0, 0] = True          # the minimal valid preset set
            return cls(rows=rows, cols=cols, op=op, schedule=schedule,
                       planes=planes, moves=moves,
                       weights=np.zeros((len(moves), rows, cols), np.float32),
                       init=np.zeros((planes, rows, cols), np.float32),
                       init_mask=mask)
        return cls(rows=rows, cols=cols, op=op, schedule=schedule,
                   planes=planes, rules=rules,
                   rule_weights=np.zeros((len(rules),), np.float32),
                   init=np.zeros((planes, rows), np.float32))

    def route_costs(self) -> dict:
        """Step-count model for the grid family: one masked combine per
        wavefront — ``rows + cols - 1`` anti-diagonals, or ``rows``
        span-diagonals — times the per-front fan-in (planes × moves, or the
        rule count). Same units and small-n floors as the other families."""
        if self.schedule == "antidiag":
            fronts = self.rows + self.cols - 1
            fan = max(1, len(self.moves))
        else:
            fronts = self.rows
            fan = max(1, len(self.rules))
        costs = {"grid_wavefront": float(fronts) * (1.0 + _log2(fan) / 4.0)}
        return _floored(costs, _GRID_OVERHEAD,
                        min(self.rows, self.cols))

    def schedule_model(self):
        """Grid dependencies in plane-major flat cell ids. antidiag: each
        non-preset cell reads ``(p_from, i-di, j-dj)`` per in-range move
        targeting its plane, in move declaration order. spandiag: the
        per-plane split recurrence, split-major then rule order. Cells of
        planes no move/rule targets keep their initialized value — they
        carry no candidates and routes may treat them as preset-final."""
        from repro.dp.schedule import DependencyModel

        per = self.cells
        cands = [()] * (self.planes * per)
        preset = set()
        if self.schedule == "antidiag":
            R, C = self.rows, self.cols
            for p in range(self.planes):
                for i in range(R):
                    for j in range(C):
                        cell = p * per + i * C + j
                        if bool(self.init_mask[p, i, j]):
                            preset.add(cell)
                            continue
                        cands[cell] = tuple(
                            (pf * per + (i - di) * C + (j - dj),)
                            for (pt, pf, di, dj) in self.moves
                            if pt == p and i >= di and j >= dj)
        else:
            n = self.rows
            for p in range(self.planes):
                for i in range(n):
                    preset.add(p * per + i)   # diagonal 0
                for d in range(1, n):
                    for i in range(n - d):
                        cands[p * per + lin_index(i, d, n)] = tuple(
                            (b * per + lin_index(i, e, n),
                             c * per + lin_index(i + e + 1, d - e - 1, n))
                            for e in range(d)
                            for (a, b, c) in self.rules if a == p)
        return DependencyModel(
            label=f"grid[{self.schedule}](planes={self.planes}, "
                  f"rows={self.rows}, cols={self.cols})",
            cells=self.planes * per, preset=frozenset(preset),
            candidates=tuple(cands))

    @classmethod
    def probe_specs(cls) -> tuple:
        """One single-plane and one multi-plane probe per schedule: an
        edit-distance-shaped 3×4 antidiag, a Gotoh-like two-plane 3×3
        (plane 1 feeding back into plane 0), a one-nonterminal CKY chart,
        and a three-rule two-nonterminal chart."""
        mask1 = np.zeros((1, 3, 4), bool)
        mask1[:, 0, :] = mask1[:, :, 0] = True
        mask2 = np.zeros((2, 3, 3), bool)
        mask2[:, 0, :] = mask2[:, :, 0] = True
        return (
            cls(rows=3, cols=4, op="min", schedule="antidiag", planes=1,
                moves=((0, 0, 1, 0), (0, 0, 0, 1), (0, 0, 1, 1)),
                weights=np.zeros((3, 3, 4), np.float32),
                init=np.zeros((1, 3, 4), np.float32), init_mask=mask1),
            cls(rows=3, cols=3, op="max", schedule="antidiag", planes=2,
                moves=((0, 0, 1, 1), (0, 1, 1, 1),
                       (1, 0, 0, 1), (1, 1, 0, 1)),
                weights=np.zeros((4, 3, 3), np.float32),
                init=np.zeros((2, 3, 3), np.float32), init_mask=mask2),
            cls(rows=4, cols=4, op="min", schedule="spandiag", planes=1,
                rules=((0, 0, 0),),
                rule_weights=np.zeros((1,), np.float32),
                init=np.zeros((1, 4), np.float32)),
            cls(rows=4, cols=4, op="max", schedule="spandiag", planes=2,
                rules=((0, 0, 1), (1, 0, 0), (0, 1, 1)),
                rule_weights=np.zeros((3,), np.float32),
                init=np.zeros((2, 4), np.float32)),
        )

    def supports_args(self) -> bool:
        return True         # validate() restricts op to min/max

    def args_unsupported_reason(self) -> str:
        return "no argument structure"

    def default_start(self, table) -> int:
        """Plane 0 at the far corner (antidiag) or the full-span root cell
        (spandiag); problems with a different optimum define ``start``."""
        if self.schedule == "spandiag":
            return int(lin_index(0, self.rows - 1, self.rows))
        return (self.rows - 1) * self.cols + (self.cols - 1)

    # --- solver plumbing (consumed by backends.grid_backend) ----------------
    def device_arrays(self) -> tuple:
        """The per-instance arrays a grid solver consumes, in a fixed slot
        order per schedule — the batch builder stacks each slot."""
        if self.schedule == "antidiag":
            return (np.asarray(self.weights, np.float32),
                    np.asarray(self.init, np.float32),
                    np.asarray(self.init_mask, np.float32))
        return (np.asarray(self.rule_weights, np.float32),
                np.asarray(self.init, np.float32))

    def static_meta(self) -> tuple:
        """Hashable structure-only tuple — the static argument of the grid
        solvers (everything but the instance arrays)."""
        return self.shape_key()[1:]

    def args_from_table(self, table: np.ndarray) -> np.ndarray:
        from repro.core.grid import grid_args_np

        return grid_args_np(table, self)

    def traceback_host(self, args: np.ndarray, start: int = -1) -> "Path":
        from repro.core.grid import grid_traceback_np

        return grid_traceback_np(
            args, self, start if start >= 0 else self.default_start(None))

    def traceback_program(self):
        import jax
        import jax.numpy as jnp

        from repro.core.grid import grid_traceback
        from repro.dp import backends as _backends

        meta = self.static_meta()
        key = ("traceback",) + self.shape_key()
        default = self.default_start(None)
        spandiag = self.schedule == "spandiag"

        def build():
            def call(args_b, starts_b):
                _backends.log_trace(key)
                return jax.vmap(
                    lambda a, s: grid_traceback(a, s, meta))(args_b, starts_b)

            return jax.jit(call)

        def post(walk, argss, starts):
            if starts is None:
                starts = [default] * len(argss)
            out = walk(jnp.stack([jnp.asarray(a) for a in argss]),
                       jnp.asarray(np.asarray(starts, dtype=np.int32)))
            pp, aa, bb, vv, valid, stop = (np.asarray(x) for x in out)
            paths = []
            for b in range(len(argss)):
                nodes = np.stack([pp[b], aa[b], bb[b], vv[b]],
                                 axis=1)[valid[b]].astype(np.int64)
                paths.append(GridPath(
                    nodes=nodes, stop=-1 if spandiag else int(stop[b])))
            return paths

        return key, build, post

    # --- streaming/extension hooks (DESIGN.md §11) --------------------------
    def extend_length(self) -> int:
        """Growth axis: appendable columns (antidiag) or chart width
        (spandiag)."""
        return int(self.cols) if self.schedule == "antidiag" else int(self.rows)

    def frontier_cols(self) -> int:
        """Trailing-column window an antidiag extension can reach back
        into: max dj over the moves (floored at one column so the
        extension sub-grid always has a fully-preset first column)."""
        return max(1, max((int(m[3]) for m in self.moves), default=1))

    def min_prefix_len(self) -> int:
        if self.schedule == "antidiag":
            return self.frontier_cols()
        return 2

    def split_spec(self, length: int) -> "GridSpec":
        L = int(length)
        if not self.min_prefix_len() <= L <= self.extend_length():
            raise ValueError(f"prefix length {L} outside "
                             f"[{self.min_prefix_len()}, {self.extend_length()}]")
        if self.schedule == "antidiag":
            return dataclasses.replace(
                self, cols=L,
                weights=np.ascontiguousarray(self.weights[:, :, :L]),
                init=np.ascontiguousarray(self.init[:, :, :L]),
                init_mask=np.ascontiguousarray(self.init_mask[:, :, :L]),
                source=None)
        return dataclasses.replace(
            self, rows=L, cols=L,
            init=np.ascontiguousarray(self.init[:, :L]), source=None)

    def extension_delta(self, prefix: "GridSpec") -> dict:
        same = (isinstance(prefix, GridSpec)
                and (prefix.schedule, prefix.op, prefix.planes)
                == (self.schedule, self.op, self.planes)
                and prefix.moves == self.moves
                and prefix.rules == self.rules
                and _same_array(prefix.rule_weights, self.rule_weights))
        if self.schedule == "antidiag":
            C = None if not same else prefix.cols
            if (not same or prefix.rows != self.rows
                    or not C < self.cols
                    or not _same_array(prefix.weights,
                                       self.weights[:, :, :C])
                    or not _same_array(prefix.init, self.init[:, :, :C])
                    or not _same_array(prefix.init_mask,
                                       self.init_mask[:, :, :C])):
                raise ValueError("spec is not a bitwise extension of the prefix")
            return {"cols": int(self.cols - C),
                    "weights": np.ascontiguousarray(self.weights[:, :, C:]),
                    "init": np.ascontiguousarray(self.init[:, :, C:]),
                    "init_mask": np.ascontiguousarray(self.init_mask[:, :, C:])}
        if (not same or not prefix.rows < self.rows
                or not _same_array(prefix.init, self.init[:, :prefix.rows])):
            raise ValueError("spec is not a bitwise extension of the prefix")
        return {"steps": int(self.rows - prefix.rows),
                "init": np.ascontiguousarray(self.init[:, prefix.rows:])}

    def extend_spec(self, delta: dict) -> "GridSpec":
        """Append columns (antidiag) or leaves (spandiag)."""
        if self.schedule == "antidiag":
            k = int(delta["cols"])
            if k < 1:
                raise ValueError("extension must append at least one column")
            w = np.asarray(delta["weights"], dtype=self.weights.dtype)
            ini = np.asarray(delta["init"], dtype=self.init.dtype)
            mask = np.asarray(delta["init_mask"], dtype=bool)
            want = (len(self.moves), self.rows, k)
            pwant = (self.planes, self.rows, k)
            if w.shape != want or ini.shape != pwant or mask.shape != pwant:
                raise ValueError(f"extension arrays must be {want}/{pwant}")
            ext = dataclasses.replace(
                self, cols=self.cols + k,
                weights=np.concatenate([self.weights, w], axis=2),
                init=np.concatenate([self.init, ini], axis=2),
                init_mask=np.concatenate([self.init_mask, mask], axis=2),
                source=None)
        else:
            k = int(delta["steps"])
            if k < 1:
                raise ValueError("extension must append at least one leaf")
            ini = np.asarray(delta["init"], dtype=self.init.dtype)
            if ini.shape != (self.planes, k):
                raise ValueError(f"extension init must be "
                                 f"({self.planes}, {k}), got {ini.shape}")
            ext = dataclasses.replace(
                self, rows=self.rows + k, cols=self.cols + k,
                init=np.concatenate([self.init, ini], axis=1), source=None)
        ext.validate()
        return ext

    def extension_state(self, table, args=None) -> dict:
        """antidiag: the last ``frontier_cols()`` columns — new-column
        cells reach back at most max(dj) columns. spandiag: like the
        triangular family, the split recurrence keeps every prefix cell
        live, so the full prefix chart is the minimal state."""
        if self.schedule == "antidiag":
            W = self.frontier_cols()
            t = np.asarray(table).reshape(self.planes, self.rows, self.cols)
            return {"suffix": np.array(t[:, :, self.cols - W:])}
        return {"suffix": np.array(np.asarray(table))}

    def prefix_cell_map(self, prefix: "GridSpec") -> np.ndarray:
        if self.schedule == "antidiag":
            R, Cn, Co = self.rows, self.cols, prefix.cols
            p = np.arange(self.planes, dtype=np.int64)[:, None, None]
            i = np.arange(R, dtype=np.int64)[None, :, None]
            j = np.arange(Co, dtype=np.int64)[None, None, :]
            return (p * R * Cn + i * Cn + j).ravel()
        no, nn = prefix.rows, self.rows
        base = np.empty(num_cells(no), np.int64)
        for d in range(no):
            src, dst = lin_index(0, d, no), lin_index(0, d, nn)
            base[src:src + (no - d)] = np.arange(dst, dst + (no - d),
                                                 dtype=np.int64)
        p = np.arange(self.planes, dtype=np.int64)[:, None]
        return (p * num_cells(nn) + base[None, :]).ravel()

    def saved_state_cells(self, prefix: "GridSpec") -> np.ndarray:
        if self.schedule == "antidiag":
            R, Cn, Co = self.rows, self.cols, prefix.cols
            W = self.frontier_cols()
            p = np.arange(self.planes, dtype=np.int64)[:, None, None]
            i = np.arange(R, dtype=np.int64)[None, :, None]
            j = np.arange(Co - W, Co, dtype=np.int64)[None, None, :]
            return (p * R * Cn + i * Cn + j).ravel()
        return self.prefix_cell_map(prefix)

    def stitch_extension(self, prefix, prefix_table, ext_out) -> np.ndarray:
        if self.schedule == "antidiag":
            ext_out = np.asarray(ext_out)
            full = np.empty((self.planes, self.rows, self.cols),
                            ext_out.dtype)
            full[:, :, :prefix.cols] = np.asarray(prefix_table).reshape(
                self.planes, self.rows, prefix.cols)
            full[:, :, prefix.cols:] = ext_out
            return full.reshape(-1)
        return np.asarray(ext_out)

    def chain_seed(self) -> bytes:
        h = hashlib.sha256()
        h.update(b"grid")
        h.update(self.schedule.encode())
        h.update(self.op.encode())
        if self.schedule == "antidiag":
            h.update(repr((int(self.planes), int(self.rows))).encode())
            h.update(repr(self.shape_key()[6]).encode())   # moves
            h.update(str(self.weights.dtype).encode())
            h.update(str(self.init.dtype).encode())
        else:
            h.update(str(int(self.planes)).encode())
            h.update(repr(self.shape_key()[7]).encode())   # rules
            _hash_array(h, self.rule_weights)
            h.update(str(self.init.dtype).encode())
        return h.digest()

    def _payload_rows(self, start: int = 0,
                      stop: Optional[int] = None) -> np.ndarray:
        """Byte matrix of step payloads ``start..stop``, one row per step:
        weight/init/mask column bytes (antidiag) or the leaf presets
        (spandiag). Bulk numpy transposes — no per-column Python loop, so
        streaming appends can hash/slice thousands of columns cheaply."""
        if self.schedule == "antidiag":
            parts = [self.weights[:, :, start:stop],
                     self.init[:, :, start:stop],
                     self.init_mask[:, :, start:stop].astype(np.uint8)]
            rows = [np.ascontiguousarray(np.moveaxis(p, 2, 0))
                    .reshape(p.shape[2], p.shape[0] * p.shape[1])
                    .view(np.uint8) for p in parts]
            return np.concatenate(rows, axis=1)
        return np.ascontiguousarray(
            self.init[:, start:stop].T).view(np.uint8)

    def step_payloads(self, start: int = 0) -> list:
        """Payloads of steps ``start..extend_length()``. Payload j:
        everything column j contributes — weight/init/mask columns
        (antidiag) or the leaf presets (spandiag)."""
        rows = self._payload_rows(start)
        buf, rb = rows.tobytes(), rows.shape[1]
        return [buf[i * rb:(i + 1) * rb] for i in range(rows.shape[0])]

    def flat_payload_digest(self, upto: int) -> bytes:
        return hashlib.sha256(
            self._payload_rows(0, upto).tobytes()).digest()

    def content_extends(self, prev: "GridSpec") -> bool:
        """Column prefixes are plain array slices here, so the cursor's
        prefix-unchanged check is a set of memcmps — no byte-matrix
        materialization, no hashing."""
        c = prev.extend_length()
        if self.schedule == "antidiag":
            return (np.array_equal(self.weights[:, :, :c], prev.weights)
                    and np.array_equal(self.init[:, :, :c], prev.init)
                    and np.array_equal(self.init_mask[:, :, :c],
                                       prev.init_mask))
        return bool(np.array_equal(self.init[:, :c], prev.init))

    def prefix_digest_chain(self) -> dict:
        return chain_digests(self.chain_seed(), self.step_payloads(),
                             self.min_prefix_len())[0]


Spec = Union[LinearSpec, TriangularSpec, GridSpec]

register_family(LinearSpec)
register_family(TriangularSpec)
register_family(GridSpec)


def _hash_array(h, a: Optional[np.ndarray]) -> None:
    if a is None:
        h.update(b"\x00none")
        return
    a = np.ascontiguousarray(a)
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())


def _chain(prev: bytes, payload: bytes) -> bytes:
    """One link of a prefix digest chain (DESIGN.md §11): the digest at
    step ``s`` commits to the digest at ``s-1`` plus step ``s``'s payload,
    so equal chain values at a length imply bit-equal logical prefixes."""
    return hashlib.sha256(prev + payload).digest()


def chain_digests(seed: bytes, payloads: list,
                  lo: int, base: int = 0,
                  acc: Optional[bytes] = None) -> tuple:
    """Walk a digest chain: returns ``({L: digest for L >= lo}, acc)``
    where ``acc`` is the chain value after the last payload. ``payloads``
    are the payloads of steps ``base..base+len(payloads)``; ``base`` /
    ``acc`` resume a partially walked chain (the streaming
    :class:`~repro.dp.streaming.ChainCursor` uses this to chain only an
    append's new steps, without materializing the old ones)."""
    acc = seed if acc is None else acc
    chain = {}
    for i, payload in enumerate(payloads, start=base):
        acc = _chain(acc, payload)
        if i + 1 >= lo:
            chain[i + 1] = acc
    return chain, acc


def _arr_bytes(a) -> bytes:
    return np.ascontiguousarray(a).tobytes()


def _same_array(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> bool:
    """Bitwise array equality (dtype + shape + values); None matches None."""
    if a is None or b is None:
        return a is None and b is None
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and bool(np.array_equal(a, b))


def spec_digest(spec: Spec) -> str:
    """Content digest of a canonical instance. Two payloads that encode to
    the same spec digest identically — which is exactly the dedup/cache
    contract: ``extract`` and ``decode`` read only (table, args, spec, path),
    all functions of the spec, so equal digests imply bit-equal Answers.
    A problem whose answer depended on payload data *outside* its encoded
    spec would break this invariant (DESIGN.md §7) — the digest must cover
    everything answer-relevant. It need not hash derived content: a grid
    spec with a :class:`PlaneSource` hashes the source, from which its
    planes are built bit for bit, and not the planes. Hashing is a family
    hook (``digest_into``) so new families join the contract by
    implementing it."""
    h = hashlib.sha256()
    spec.digest_into(h)
    return h.hexdigest()


class _CountingHash:
    """SHA-256 that counts the bytes it is fed."""

    def __init__(self):
        self._h = hashlib.sha256()
        self.nbytes = 0

    def update(self, data: bytes) -> None:
        self.nbytes += len(data)
        self._h.update(data)

    def hexdigest(self) -> str:
        return self._h.hexdigest()


def spec_digest_bytes(spec: Spec) -> tuple:
    """``(spec_digest(spec), the number of bytes it hashes)``."""
    h = _CountingHash()
    spec.digest_into(h)
    return h.hexdigest(), h.nbytes


# --- reconstruction vocabulary ---------------------------------------------
@dataclasses.dataclass(frozen=True)
class LinearPath:
    """Argument walk over a linear table, in traceback order (start cell
    first, strictly decreasing). ``cells[t]`` took lane ``lanes[t]``, i.e. its
    winning predecessor is ``cells[t] - offsets[lanes[t]]``; ``stop`` is the
    preset init cell the walk terminated in."""

    cells: np.ndarray
    lanes: np.ndarray
    stop: int


@dataclasses.dataclass(frozen=True)
class TriangularPath:
    """Split tree of a triangular table as a ``(m, 3)`` preorder array of
    internal nodes ``(i, d, e)``: cell ``(i, i+d)`` split at ``s = i + e``
    into children ``(i, e)`` and ``(i+e+1, d-e-1)``."""

    nodes: np.ndarray


@dataclasses.dataclass(frozen=True)
class GridPath:
    """Argument structure of a grid table, as an ``(m, 4)`` node array.

    antidiag: the walk in traceback order — node ``(plane, i, j, move)``
    took shift move ``move`` into preset-region cell ``stop`` (flat
    ``p·rows·cols + i·cols + j`` index).

    spandiag: the parse tree in preorder — node ``(plane, i, d, a)`` with
    packed arg ``a = e·len(rules) + r``: rule ``r`` split cell ``(i, i+d)``
    at offset ``e`` into ``(p_left, i, e)`` and ``(p_right, i+e+1,
    d-e-1)``; ``stop`` is -1 (leaves are implied by the rules)."""

    nodes: np.ndarray
    stop: int


Path = Union[LinearPath, TriangularPath, GridPath]


@dataclasses.dataclass(frozen=True)
class Answer:
    """A solved instance with its reconstructed solution.

    ``value`` is exactly what the scalar ``extract`` path returns; ``solution``
    is the problem-level structure produced by ``DPProblem.decode`` (tree,
    alignment, state path, …); ``table``/``args`` are the linearized cost and
    argument tables; ``source`` records where the args came from: ``"device"``
    (arg-emitting solver) or ``"host"`` (numpy fallback from the cost table).

    Treat Answers as immutable: the engine's dedup fan-out and the service's
    answer cache share one Answer across requests, and engine-produced
    ``table``/``args`` arrays are frozen (non-writeable) for exactly that
    reason.
    """

    value: Any
    solution: Any
    table: np.ndarray
    args: np.ndarray
    source: str


@dataclasses.dataclass(frozen=True)
class DPProblem:
    """One zoo entry.

    encode(**instance) -> Spec        canonical form of an instance
    oracle(**instance) -> np.ndarray  independent numpy reference producing
                                      the full linearized table
    extract(table, spec) -> Any       the problem-level answer from a table
    sample(rng, size) -> dict         random instance kwargs (tests/benches)
    decode(table, args, spec, path)   structured solution from the arg
                                      traceback (None: no reconstruction)
    start(table, spec) -> int         traceback start cell for families with
                                      ``uses_start`` whose optimum is not the
                                      default cell (None: spec default)
    """

    name: str
    geometry: str
    encode: Callable[..., Spec]
    oracle: Callable[..., np.ndarray]
    extract: Callable[[np.ndarray, Spec], Any]
    sample: Callable[[np.random.Generator, int], dict]
    doc: str = ""
    decode: Optional[Callable[[np.ndarray, np.ndarray, Spec, Path], Any]] = None
    start: Optional[Callable[[np.ndarray, Spec], int]] = None

    def solve_reference(self, **instance) -> Any:
        """Oracle answer for an instance (tests and the engine's self-check)."""
        spec = self.encode(**instance)
        return self.extract(self.oracle(**instance), spec)
