"""Matrix-chain multiplication (MCM) solvers — §IV of the paper.

Cells ``(i, j)`` with ``0 ≤ i ≤ j < n`` (chain of ``n`` matrices; matrix ``t``
has shape ``p[t] × p[t+1]``). Diagonal ``d = j - i``; diagonal-major
linearization (paper Fig. 5/7):

    lin(i, d) = d·n - d(d-1)/2 + i            (diagonal d holds n-d cells)

Cell ``(i, j)``, ``d ≥ 1`` has ``k = d`` split candidates,

    cand(s) = m[i, s] + m[s+1, j] + p[i]·p[s+1]·p[j+1],   reduced by ↓ = min.

The paper's Fig.-8 pipeline assigns candidate slot ``j`` (executed at step
``c + j``, 0-based) to split ``s = i + j`` — the "j-th element from the left"
of Lemmas 1/2.

**Finding (dependency hazard in the paper's schedule).** Theorem 1 proves
*same-substep address distinctness* but not *operand finalization*. Slot 0's
right operand is cell ``(i+1, j)`` on diagonal ``d-1``: it sits one position
before ``c`` in linear order yet still needs ``d-2`` more candidates when the
read happens. For any ``n ≥ 5`` random instances produce inflated results
(see ``tests/test_mcm.py::test_paper_order_hazard``). S-DP does not suffer
this because its offsets strictly decrease (``a_j ≥ a_k + (k-j)`` gives each
stage a safety margin).

**Repair (order="safe", the default).** Keep the paper's machinery — skewed
head, one candidate/cell/step, cell ``c`` finalized at step ``c + k_c - 1`` —
but permute each cell's candidates by *earliest operand-ready step*. A
Hall-type argument (see DESIGN.md §2) shows the greedy assignment is always
feasible (validated exhaustively in tests); the step count and the O(n²)
complexity claim are unchanged. Write distinctness is preserved (cells per
step stay distinct); *read* distinctness may be lost, which on a GPU would
re-introduce serialization but on TPU a vector gather with duplicate
addresses costs the same — a hardware adaptation recorded in DESIGN.md.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "mcm_reference",
    "reference_linear",
    "num_cells",
    "lin_index",
    "diag_of",
    "build_pipeline_tables",
    "build_tables",
    "mcm_weight_fn",
    "weight_table",
    "solve_wavefront",
    "solve_wavefront_tab",
    "solve_wavefront_tab_with_args",
    "triangular_traceback",
    "triangular_args_np",
    "triangular_traceback_np",
    "solve_pipeline",
    "solve_pipeline_np",
    "pipeline_num_steps",
    "PipelineTables",
]

INF = jnp.inf


def num_cells(n: int) -> int:
    return n * (n + 1) // 2


def lin_index(i, d, n):
    """Diagonal-major linear index of cell (i, i+d) in an n-chain table."""
    return d * n - (d * (d - 1)) // 2 + i


def diag_of(c: int, n: int) -> int:
    """Diagonal containing linear cell c (host-side helper)."""
    d, off = 0, 0
    while off + (n - d) <= c:
        off += n - d
        d += 1
    return d


# ---------------------------------------------------------------------------
# numpy oracle (CLRS 15.2)
# ---------------------------------------------------------------------------
def mcm_reference(dims) -> tuple[np.ndarray, np.ndarray]:
    """O(n³) DP in float64, vectorized over each diagonal's cells and
    splits (O(n) numpy steps). Returns (m, split): m[i][j] = min cost of
    A_i..A_j and its first-occurrence best split s."""
    p = np.asarray(dims, dtype=np.float64)
    n = len(p) - 1
    m = np.zeros((n, n))
    split = np.full((n, n), -1, dtype=np.int64)
    for d in range(1, n):
        i = np.arange(n - d)[:, None]               # cells (i, i + d)
        s = i + np.arange(d)[None, :]               # splits s = i .. i+d-1
        c = m[i, s] + m[s + 1, i + d] + p[i] * p[s + 1] * p[i + d + 1]
        best = np.argmin(c, axis=1)
        m[i[:, 0], i[:, 0] + d] = c[np.arange(n - d), best]
        split[i[:, 0], i[:, 0] + d] = i[:, 0] + best
    return m, split


def reference_linear(dims) -> np.ndarray:
    """Oracle table flattened in the paper's diagonal-major order."""
    p = np.asarray(dims)
    n = len(p) - 1
    m, _ = mcm_reference(dims)
    st = np.zeros(num_cells(n))
    for d in range(n):
        for i in range(n - d):
            st[lin_index(i, d, n)] = m[i, i + d]
    return st


# ---------------------------------------------------------------------------
# Pipeline index tables (the l/r/w maps of equation (2))
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PipelineTables:
    """Per-(cell, slot) index maps. O(n³/2) entries — paper-scale only."""

    n: int
    order: str
    left: np.ndarray    # (cells, n-1) linear index of the slot's left operand
    right: np.ndarray   # (cells, n-1) linear index of the slot's right operand
    weight: np.ndarray  # (cells, n-1) p_i * p_{s+1} * p_{j+1}
    k: np.ndarray       # (cells,) candidate count (= diagonal of the cell)
    feasible: bool      # every slot's operands finalized before its read step


def build_tables(n: int, weight_fn, order: str = "safe") -> PipelineTables:
    """Pipeline tables for ANY canonical triangular DP (DESIGN.md §3):

        m[i, j] = ↓_{0≤e<d} ( m[i, i+e] + m[i+e+1, j] + weight_fn(i, i+e, j) )

    with d = j - i and diagonal-0 cells preset to 0. MCM is
    ``weight_fn(i, s, j) = p[i]·p[s+1]·p[j+1]``; optimal BST and polygon
    triangulation reduce to the same shape with different ``weight_fn``
    (see ``repro.dp.zoo``).

    order="paper": Fig.-8 slot j ↔ split i+j (has the hazard above).
    order="safe": earliest-ready-first permutation (default, exact)."""
    cells = num_cells(n)
    maxk = max(n - 1, 1)
    left = np.zeros((cells, maxk), dtype=np.int64)
    right = np.zeros((cells, maxk), dtype=np.int64)
    weight = np.zeros((cells, maxk), dtype=np.float64)
    kk = np.zeros((cells,), dtype=np.int64)

    # finalize step of each cell: c + k_c - 1 (diag-0 cells are preset)
    final = np.full(cells, -(10**9), dtype=np.int64)
    for d in range(1, n):
        for i in range(n - d):
            c = lin_index(i, d, n)
            final[c] = c + d - 1

    feasible = True
    for d in range(1, n):
        for i in range(n - d):
            c = lin_index(i, d, n)
            kk[c] = d
            cand = []
            for e in range(d):  # split s = i + e; left diag e, right diag d-e-1
                s = i + e
                L = lin_index(i, e, n)
                R = lin_index(s + 1, d - e - 1, n)
                ready = max(final[L], final[R]) + 1
                cand.append((ready, L, R, weight_fn(i, s, i + d)))
            if order == "safe":
                cand.sort(key=lambda x: x[0])
            elif order != "paper":
                raise ValueError(order)
            for jc, (ready, L, R, w) in enumerate(cand):
                if c + jc < ready:
                    feasible = False
                left[c, jc], right[c, jc], weight[c, jc] = L, R, w
    return PipelineTables(n=n, order=order, left=left, right=right,
                          weight=weight, k=kk, feasible=feasible)


def mcm_weight_fn(dims):
    """The MCM instance of the canonical triangular weight: p_i·p_{s+1}·p_{j+1}."""
    p = np.asarray(dims, dtype=np.float64)
    return lambda i, s, j: p[i] * p[s + 1] * p[j + 1]


def build_pipeline_tables(dims, order: str = "safe") -> PipelineTables:
    """MCM wrapper around :func:`build_tables` (the seed API)."""
    n = len(np.asarray(dims)) - 1
    return build_tables(n, mcm_weight_fn(dims), order=order)


def weight_table(n: int, weight_fn) -> np.ndarray:
    """Dense (cells, n-1) split-major weight array: W[lin(i,d), e] =
    weight_fn(i, i+e, i+d). The canonical triangular-spec payload consumed by
    :func:`solve_wavefront_tab` (and vmapped over in ``repro.dp.batch_solve``).

    This sits on the per-instance encode path, so ``weight_fn`` is called
    once per diagonal with broadcast index arrays (O(n) Python iterations,
    not O(n³)) — it must accept numpy integer arrays."""
    cells = num_cells(n)
    maxk = max(n - 1, 1)
    w = np.zeros((cells, maxk), dtype=np.float64)
    for d in range(1, n):
        ii = np.arange(n - d)[:, None]          # (rows, 1)
        ee = np.arange(d)[None, :]              # (1, d)
        rows = lin_index(ii[:, 0], d, n)
        w[rows[:, None], ee] = weight_fn(ii, ii + ee, ii + d)
    return w


def pipeline_num_steps(n: int) -> int:
    """Outer steps of Fig. 8: head sweeps cells n..cells-1 plus (n-2) drain."""
    return num_cells(n) + (n - 1) - 1 - n


# ---------------------------------------------------------------------------
# Wavefront solver — arithmetic indexing, no tables; fori_loop over diagonals.
# The standard parallelization the paper contrasts against (and the
# throughput-optimal form on TPU: each step is a dense masked (n × n) combine).
# ---------------------------------------------------------------------------
def _wavefront_loop(n: int, dtype, weight_of, with_args: bool = False):
    """Shared masked-diagonal body; ``weight_of(d, ii, ee)`` yields the split
    weights for diagonal d (arithmetic from dims, or a table gather). With
    ``with_args`` the loop also records each cell's winning split offset e
    (-1 on the preset diagonal 0) and returns ``(st, args)``."""
    cells = num_cells(n)
    st = jnp.zeros((cells,), dtype=dtype)    # diagonal 0 preset to 0
    ar = jnp.full((cells,), -1, dtype=jnp.int32)
    ii = jnp.arange(n)[:, None]              # rows (padded)
    ee = jnp.arange(max(n - 1, 1))[None, :]  # split offsets (padded)

    def body(d, carry):
        st, ar = carry
        valid = (ii < n - d) & (ee < d)
        li = lin_index(ii, ee, n)                            # cell (i, i+e)
        ri = lin_index(ii + ee + 1, d - ee - 1, n)           # cell (i+e+1, i+d)
        cand = jnp.where(valid,
                         st[jnp.clip(li, 0, cells - 1)]
                         + st[jnp.clip(ri, 0, cells - 1)] + weight_of(d, ii, ee),
                         INF)
        out = jnp.min(cand, axis=1)                          # (n,)
        widx = jnp.where(ii[:, 0] < n - d, lin_index(ii[:, 0], d, n), cells)
        st = st.at[widx].set(out, mode="drop", unique_indices=True)
        if with_args:
            ar = ar.at[widx].set(jnp.argmin(cand, axis=1).astype(jnp.int32),
                                 mode="drop", unique_indices=True)
        return st, ar

    st, ar = jax.lax.fori_loop(1, n, body, (st, ar))
    return (st, ar) if with_args else st


@functools.partial(jax.jit, static_argnames=("n",))
def solve_wavefront(p: jnp.ndarray, n: int) -> jnp.ndarray:
    """p: (n+1,) dims. Returns the linearized table ST."""
    def weight_of(d, ii, ee):
        return p[ii] * p[jnp.clip(ii + ee + 1, 0, n)] * p[jnp.clip(ii + d + 1, 0, n)]

    return _wavefront_loop(n, p.dtype, weight_of)


# ---------------------------------------------------------------------------
# Generic triangular wavefront: same schedule as solve_wavefront but weights
# come from a precomputed (cells, n-1) table, so ANY canonical triangular DP
# (optimal BST, polygon triangulation, …) runs through the one jitted solver —
# and a batch of same-n instances is a single vmap over the table axis.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n",))
def solve_wavefront_tab(wtab: jnp.ndarray, n: int) -> jnp.ndarray:
    """wtab: (cells, n-1) split-major weights (see :func:`weight_table`).
    Returns the linearized table ST (diagonal-0 cells preset to 0)."""
    cells = num_cells(n)

    def weight_of(d, ii, ee):
        ci = lin_index(ii, d, n)                             # cell (i, i+d)
        return wtab[jnp.clip(ci, 0, cells - 1), ee]

    return _wavefront_loop(n, wtab.dtype, weight_of)


# ---------------------------------------------------------------------------
# Warm-start extension (DESIGN.md §11). The split recurrence keeps every
# prefix cell live (cell (i, j ≥ n_old) reads (i, s) for every s < j), so the
# resume state is the full prefix triangle, re-embedded into the wider
# diagonal-major layout host-side; the device loop then recomputes only the
# ≤ k = n - n_old trailing rows of each diagonal with the cold solver's exact
# per-cell candidate vector (full split axis, INF-masked, same jnp.min), so
# every new cell is bit-identical to the cold solve.
# ---------------------------------------------------------------------------
def embed_prefix_table(st_old: np.ndarray, n_old: int, n: int) -> np.ndarray:
    """Re-embed a width-``n_old`` table into the width-``n`` diagonal-major
    layout (new cells zeroed — diagonal-0 presets are 0 by the family
    contract, and the windowed loop overwrites the rest)."""
    out = np.zeros(num_cells(n), dtype=np.asarray(st_old).dtype)
    for d in range(n_old):
        src, dst = lin_index(0, d, n_old), lin_index(0, d, n)
        out[dst:dst + (n_old - d)] = st_old[src:src + (n_old - d)]
    return out


@functools.partial(jax.jit, static_argnames=("n", "n_old"))
def extend_wavefront_tab(st0: jnp.ndarray, wtab: jnp.ndarray, n: int,
                         n_old: int) -> jnp.ndarray:
    """Windowed wavefront over the extension region: ``st0`` the full
    width-``n`` table with the prefix embedded (:func:`embed_prefix_table`),
    ``wtab`` the extended spec's weight table. Returns the full table —
    O(n²·k) work instead of the cold solve's O(n³)."""
    cells = num_cells(n)
    k = n - n_old
    ee = jnp.arange(max(n - 1, 1))[None, :]
    lanes = jnp.arange(k)[:, None]

    def body(d, st):
        ii = jnp.maximum(0, n_old - d) + lanes   # trailing rows of diagonal d
        valid = (ii < n - d) & (ee < d)
        li = lin_index(ii, ee, n)
        ri = lin_index(ii + ee + 1, d - ee - 1, n)
        ci = lin_index(ii, d, n)
        cand = jnp.where(valid,
                         st[jnp.clip(li, 0, cells - 1)]
                         + st[jnp.clip(ri, 0, cells - 1)]
                         + wtab[jnp.clip(ci, 0, cells - 1), ee],
                         INF)
        out = jnp.min(cand, axis=1)
        widx = jnp.where(ii[:, 0] < n - d, lin_index(ii[:, 0], d, n), cells)
        return st.at[widx].set(out, mode="drop", unique_indices=True)

    return jax.lax.fori_loop(1, n, body, st0)


@functools.partial(jax.jit, static_argnames=("n",))
def solve_wavefront_tab_with_args(wtab: jnp.ndarray, n: int):
    """``solve_wavefront_tab`` + the best-split table: returns ``(st, args)``
    with ``args[lin(i,d)] = e`` such that split ``s = i+e`` wins cell
    ``(i, i+d)`` (-1 on diagonal 0)."""
    cells = num_cells(n)

    def weight_of(d, ii, ee):
        ci = lin_index(ii, d, n)
        return wtab[jnp.clip(ci, 0, cells - 1), ee]

    return _wavefront_loop(n, wtab.dtype, weight_of, with_args=True)


# ---------------------------------------------------------------------------
# Traceback: expand the best-split table into the full split tree. The device
# version runs an explicit DFS stack inside ``lax.scan`` — a triangular table
# over n leaves has exactly n-1 internal nodes, so n-1 fixed steps emit the
# whole tree in preorder; vmapping the scan reconstructs an engine bucket in
# one jitted call (DESIGN.md §5).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n",))
def triangular_traceback(args: jnp.ndarray, n: int):
    """Returns preorder ``(ii, dd, ee)`` arrays of length n-1: internal node
    (i, i+d) chose split offset e (children (i, e) and (i+e+1, d-e-1))."""
    cells = num_cells(n)
    size = n + 1                        # DFS stack capacity (≤ n live nodes)

    def step(state, _):
        si, sd, sp = state
        top = sp - 1
        i = si[jnp.clip(top, 0, size - 1)]
        d = sd[jnp.clip(top, 0, size - 1)]
        c = lin_index(i, d, n)
        e = jnp.clip(args[jnp.clip(c, 0, cells - 1)], 0, jnp.maximum(d - 1, 0))
        sp = sp - 1
        # push right child first so the left child pops next (preorder)
        rd = d - e - 1
        idx = jnp.where(rd >= 1, sp, size)
        si = si.at[idx].set(i + e + 1, mode="drop")
        sd = sd.at[idx].set(rd, mode="drop")
        sp = sp + (rd >= 1).astype(sp.dtype)
        idx = jnp.where(e >= 1, sp, size)
        si = si.at[idx].set(i, mode="drop")
        sd = sd.at[idx].set(e, mode="drop")
        sp = sp + (e >= 1).astype(sp.dtype)
        return (si, sd, sp), (i, d, e)

    si = jnp.zeros((size,), dtype=jnp.int32)
    sd = jnp.zeros((size,), dtype=jnp.int32).at[0].set(n - 1)
    sp = jnp.int32(1)
    _, (ii, dd, ee) = jax.lax.scan(step, (si, sd, sp), None,
                                   length=max(n - 1, 0))
    return ii, dd, ee


def triangular_args_np(table: np.ndarray, wtab: np.ndarray, n: int) -> np.ndarray:
    """Numpy fallback: best-split table from a finished cost table (for
    backends that only return costs); candidates recomputed in float64."""
    table = np.asarray(table, dtype=np.float64)
    wtab = np.asarray(wtab, dtype=np.float64)
    args = np.full(num_cells(n), -1, dtype=np.int32)
    for d in range(1, n):
        ii = np.arange(n - d)[:, None]          # (rows, 1)
        ee = np.arange(d)[None, :]              # (1, d)
        rows = lin_index(ii[:, 0], d, n)
        cand = (table[lin_index(ii, ee, n)]
                + table[lin_index(ii + ee + 1, d - ee - 1, n)]
                + wtab[rows[:, None], ee])
        args[rows] = np.argmin(cand, axis=1)
    return args


def triangular_traceback_np(args: np.ndarray, n: int) -> np.ndarray:
    """Host DFS with the same preorder contract as :func:`triangular_traceback`;
    returns an (n-1, 3) array of (i, d, e) internal nodes."""
    nodes = []
    stack = [(0, n - 1)] if n >= 2 else []
    while stack:
        i, d = stack.pop()
        e = int(args[lin_index(i, d, n)])
        nodes.append((i, d, e))
        if d - e - 1 >= 1:
            stack.append((i + e + 1, d - e - 1))
        if e >= 1:
            stack.append((i, e))
    return np.asarray(nodes, dtype=np.int64).reshape(-1, 3)


# ---------------------------------------------------------------------------
# The paper's pipeline (Fig. 8) on the linearized table, vectorized over the
# n-1 stages: one gather/gather/f/min-scatter per outer step.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n",))
def solve_pipeline(left: jnp.ndarray, right: jnp.ndarray, weight: jnp.ndarray,
                   k: jnp.ndarray, n: int) -> jnp.ndarray:
    """Run the pipeline given (possibly permuted) tables.

    Substeps 1–4 of Fig. 8 map to: gather l, gather r, f = l+r+w, ↓-accumulate.
    Write addresses are consecutive cells — unique by construction (Thm. 1).
    """
    cells = num_cells(n)
    maxk = left.shape[1]
    js = jnp.arange(maxk)
    st = jnp.zeros((cells,), dtype=weight.dtype)

    def body(t, st):
        c = t - js                                           # (maxk,) cells
        cc = jnp.clip(c, 0, cells - 1)
        active = (c >= n) & (c < cells) & (js < k[cc])
        v_l = st[jnp.clip(left[cc, js], 0, cells - 1)]       # substep 1
        v_r = st[jnp.clip(right[cc, js], 0, cells - 1)]      # substep 2
        v_s = v_l + v_r + weight[cc, js]                     # substep 3
        new = jnp.where(js == 0, v_s, jnp.minimum(st[cc], v_s))  # substep 4
        widx = jnp.where(active, c, cells)
        return st.at[widx].set(new, mode="drop", unique_indices=True)

    return jax.lax.fori_loop(n, cells + maxk - 1, body, st)


def solve_mcm_pipeline(dims, order: str = "safe") -> np.ndarray:
    """Convenience wrapper: tables + JAX pipeline -> linearized table."""
    t = build_pipeline_tables(dims, order=order)
    st = solve_pipeline(jnp.asarray(t.left), jnp.asarray(t.right),
                        jnp.asarray(t.weight), jnp.asarray(t.k), t.n)
    return np.asarray(st)


def solve_pipeline_np(dims, order: str = "safe", check_conflicts: bool = False):
    """Host-side step-by-step pipeline used by tests.

    Returns (st, stats) with stats = dict(max_read_dup, max_write_dup,
    dependency_violations) measured per substep — Theorem 1 says write dup
    must be 1; the safe order may raise read dup (harmless on TPU).
    """
    t = build_pipeline_tables(dims, order=order)
    n, cells = t.n, num_cells(t.n)
    maxk = t.left.shape[1]
    st = np.zeros(cells)
    final = {lin_index(i, d, n): lin_index(i, d, n) + d - 1
             for d in range(1, n) for i in range(n - d)}
    stats = {"max_read_dup": 1, "max_write_dup": 1, "dependency_violations": 0}
    for step in range(n, cells + maxk - 1):
        js = np.arange(maxk)
        c = step - js
        ok = (c >= n) & (c < cells)
        cc = np.where(ok, c, 0)
        active = ok & (js < t.k[cc])
        if check_conflicts and active.any():
            for name, addr in (("read", t.left[cc, js][active]),
                               ("read", t.right[cc, js][active]),
                               ("write", c[active])):
                _, counts = np.unique(addr, return_counts=True)
                key = f"max_{name}_dup"
                stats[key] = max(stats[key], int(counts.max()))
            for src in (t.left[cc, js][active], t.right[cc, js][active]):
                for a in src:
                    if a in final and final[a] >= step:
                        stats["dependency_violations"] += 1
        snap = st.copy()
        v = snap[t.left[cc, js]] + snap[t.right[cc, js]] + t.weight[cc, js]
        for j in np.nonzero(active)[0]:
            ci = c[j]
            st[ci] = v[j] if j == 0 else min(st[ci], v[j])
    return st, stats


# ---------------------------------------------------------------------------
# Backend registration (repro.dp): triangular routes.
# ---------------------------------------------------------------------------
from repro.dp import backends as _dp_backends  # noqa: E402


def tables_from_weight_array(wtab: np.ndarray, n: int,
                             order: str = "safe") -> PipelineTables:
    """Pipeline tables for a dense (cells, n-1) split-major weight array."""
    return build_tables(
        n, lambda i, s, j: wtab[lin_index(i, j - i, n), s - i], order=order)


def _pipeline_run(spec) -> np.ndarray:
    t = tables_from_weight_array(np.asarray(spec.weights), spec.n)
    st = solve_pipeline(jnp.asarray(t.left), jnp.asarray(t.right),
                        jnp.asarray(t.weight), jnp.asarray(t.k), t.n)
    return np.asarray(st)


def _run_extend(spec, n_old: int, state: dict) -> np.ndarray:
    """``Backend.run_extend`` for the wavefront route: host-side prefix
    re-embedding + the windowed device loop, traced/cached under an
    ``("extend", n_old)`` key."""
    n_old = int(n_old)
    key = ("wavefront", spec.shape_key(), ("extend", n_old))

    def build():
        n = spec.n

        def call(st0, wtab):
            _dp_backends.log_trace(key)
            return extend_wavefront_tab(st0, wtab, n, n_old)

        return jax.jit(call)

    fn = _dp_backends.lru_cached(_dp_backends._BATCH_CACHE, key, build,
                                 _dp_backends._BATCH_CACHE_MAX)
    st0 = embed_prefix_table(np.asarray(state["suffix"]), n_old, spec.n)
    return np.asarray(fn(jnp.asarray(st0), jnp.asarray(spec.weights)))


def _register_backends() -> None:
    from repro.dp import schedule as _sched

    _dp_backends.register(_dp_backends.triangular_tab_backend(
        "wavefront", solve_wavefront_tab,
        cost=lambda s: _dp_backends.triangular_costs(s)["wavefront"],
        jax_arg_fn=solve_wavefront_tab_with_args,
        schedule=_sched.triangular_wavefront_schedule,
        run_extend=_run_extend,
        doc="dense masked per-diagonal combine (n-1 vectorized steps)"))
    _dp_backends.register(_dp_backends.Backend(
        name="mcm_pipeline", geometry="triangular",
        run=_pipeline_run,
        cost=lambda s: _dp_backends.triangular_costs(s)["mcm_pipeline"],
        supports=lambda s: True,
        batch_run=None,  # host-side table build per instance — loop fallback
        schedule=lambda s: _sched.mcm_pipeline_schedule(s, order="safe"),
        doc="paper Fig.-8 pipeline (order=safe); O(n²) outer steps"))


_register_backends()
