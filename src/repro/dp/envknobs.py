"""Central catalog of ``REPRO_*`` environment knobs with validated readers.

Every env knob the stack consults is declared here once — name, kind,
valid values, default, and whether it is *codegen-affecting* for the DP
routes (changes the traced program, so it must be folded into backend
``cache_tag``s and ``autotune._jax_backend``). Consumers read through
:func:`read` (or validate a raw string with :func:`parse`), which
guarantees the validated-on-read contract the registry linter
(``repro.analysis``) enforces: a malformed value always raises
``ValueError`` naming the env var, never a bare ``int()`` traceback or a
silent fallthrough.

This module is a dependency leaf (stdlib only) so every layer — kernels,
telemetry, autotune, launch tooling — can import it without cycles.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

__all__ = [
    "DEFAULT_VMEM_BUDGET_BYTES", "KNOBS", "Knob", "dp_codegen_knobs",
    "knob", "parse", "read", "register_knob", "set_env",
]

#: default per-launch VMEM working-set budget (v5e has ~16 MiB/core; half of
#: it leaves room for Mosaic's own spills and the double-buffered DMA stage)
DEFAULT_VMEM_BUDGET_BYTES = 8 << 20

_UNSET = object()


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared env knob.

    ``kind`` is ``"choice"`` (value must be one of ``choices``),
    ``"positive_int"`` (strictly positive integer), or ``"path"`` (any
    string; consumers validate the target themselves). ``what``/``unit``
    feed the error messages. ``dp_codegen`` marks knobs that change the
    traced program of DP routes — the linter verifies those are folded
    into backend cache tags and the calibration platform key. ``probe``
    is a valid, non-default value the linter flips the knob to when
    checking that folds actually react."""

    name: str
    kind: str
    what: str
    default: object = None
    choices: Tuple[str, ...] = ()
    unit: str = ""
    dp_codegen: bool = False
    probe: Optional[str] = None
    description: str = ""


#: name -> Knob. Open like the backend/family registries: the linter's
#: coverage check fails on any ``REPRO_*`` token in the source tree that is
#: not declared here.
KNOBS: dict = {}


def register_knob(k: Knob) -> Knob:
    if k.name in KNOBS:
        raise ValueError(f"duplicate env knob {k.name!r}")
    if k.kind not in ("choice", "positive_int", "path"):
        raise ValueError(f"unknown knob kind {k.kind!r} for {k.name}")
    KNOBS[k.name] = k
    return k


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(f"unknown env knob {name!r}; "
                       f"declared: {sorted(KNOBS)}") from None


def parse(name: str, raw: str):
    """Validate a raw string for knob ``name`` and return the parsed value.
    Raises ``ValueError`` naming the env var on any malformed value (the
    REPRO_KERNELS guard's pattern, shared by every knob)."""
    k = knob(name)
    if k.kind == "choice":
        if raw not in k.choices:
            raise ValueError(
                f"{name}={raw!r} is not a valid {k.what}; "
                f"expected one of {', '.join(k.choices)}")
        return raw
    if k.kind == "positive_int":
        try:
            value = int(raw)
        except ValueError:
            raise ValueError(
                f"{name}={raw!r} is not a valid {k.what}; "
                f"expected {k.unit}") from None
        if value < 1:
            raise ValueError(f"{name}={raw!r} must be {k.unit}")
        return value
    return raw                                   # path: any string


def read(name: str, default=_UNSET):
    """Read and validate knob ``name`` from the environment. An unset var
    yields ``default`` when given, else the knob's declared default."""
    raw = os.environ.get(name)
    if raw is None:
        k = knob(name)
        return k.default if default is _UNSET else default
    return parse(name, raw)


def set_env(name: str, raw: str):
    """Validate ``raw`` for knob ``name``, then write it to ``os.environ``
    — the only sanctioned way to *set* a REPRO_ var programmatically
    (a malformed write would otherwise detonate at some distant read)."""
    value = parse(name, raw)
    os.environ[name] = raw
    return value


def dp_codegen_knobs() -> Tuple[Knob, ...]:
    """The knobs whose value changes DP routes' traced programs — the set
    the linter's cache-tag / platform-key fold checks iterate."""
    return tuple(k for k in KNOBS.values() if k.dp_codegen)


# ---------------------------------------------------------------------------
# The catalog. Defaults/choices mirror the consuming modules, which alias
# them from here (kernels.ops, dp.telemetry) so there is one source of truth.
# ---------------------------------------------------------------------------
register_knob(Knob(
    name="REPRO_KERNELS", kind="choice", what="kernel mode",
    choices=("auto", "pallas", "ref", "interpret"), default="auto",
    dp_codegen=True, probe="interpret",
    description="kernel dispatch mode: Pallas lowering, jnp reference, or "
                "the interpreted kernel body (tests)"))

register_knob(Knob(
    name="REPRO_VMEM_BUDGET", kind="positive_int", what="VMEM budget",
    unit="a positive integer byte count", default=DEFAULT_VMEM_BUDGET_BYTES,
    dp_codegen=True, probe="4096",
    description="per-launch VMEM working-set budget in bytes; gates "
                "kernel-route eligibility and sizes streaming windows"))

register_knob(Knob(
    name="REPRO_FLASH_CHUNK", kind="positive_int", what="chunk size",
    unit="a positive integer", default=None, probe="256",
    description="flash-attention KV chunk override (launch stack; not a "
                "DP-route knob)"))

register_knob(Knob(
    name="REPRO_LOG", kind="choice", what="log level",
    choices=("off", "error", "warning", "info", "debug"), default="off",
    probe="error",
    description="repro.dp logging level"))

register_knob(Knob(
    name="REPRO_DP_CALIB", kind="path", what="calibration table path",
    default=None,
    description="persisted calibration table auto-loaded on first "
                "get_table(); a corrupt file degrades with a warning"))

register_knob(Knob(
    name="REPRO_SESSION_TTL_MS", kind="positive_int", what="session TTL",
    unit="a positive integer millisecond count", default=600_000,
    probe="120000",
    description="idle time after which a DPService streaming session's "
                "resume state is reclaimed (DESIGN.md §11)"))

register_knob(Knob(
    name="REPRO_SESSION_MAX", kind="positive_int", what="session limit",
    unit="a positive integer", default=256, probe="16",
    description="maximum concurrently retained DPService streaming "
                "sessions; least-recently-used sessions evict past it"))

register_knob(Knob(
    name="REPRO_SESSION_PREFIX_INDEX", kind="positive_int",
    what="prefix index capacity", unit="a positive integer", default=512,
    probe="64",
    description="entry capacity of the longest-prefix answer cache "
                "(chained per-step digests -> solved tables); each entry "
                "retains one full DP table, so size it to memory"))
