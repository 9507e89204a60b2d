"""The ``repro.dp`` timing layer: profiler spans and the logger (DESIGN.md §8).

  * **Profiler spans.** :func:`trace_span` names the host work of each
    layer (``dp.submit``, ``dp.encode``, ``dp.digest``, ``dp.step``,
    ``dp.admit``, ``dp.drain``, ``dp.stack``, ``dp.place``, ``dp.launch``,
    ``dp.fetch``, ``dp.traceback``, ``dp.decode``, ``dp.extract``,
    ``dp.resolve``, ``dp.planes``) as a ``jax.profiler.TraceAnnotation``
    while the profiler captures, so the host path lands on the device
    trace's clock; counts such as the host→device bytes of a drain ride on
    the span as its args. Outside a capture a span is one shared no-op and
    costs one ``is_enabled()`` check; :func:`tracing` guards args that cost
    something to compute. The counts of a run live in the engine's and the
    service's ``stats`` dicts, not here.
  * **Logging.** :func:`get_logger` hands out
    ``logging.getLogger("repro.dp.<mod>")`` loggers whose level is driven
    by ``REPRO_LOG={off,error,warning,info,debug}`` (validated on read via
    dp/envknobs, so a typo raises; unset = silent ``NullHandler``).
"""
from __future__ import annotations

import logging

from jax.profiler import TraceAnnotation

from repro.dp import envknobs

__all__ = ["get_logger", "log_level", "trace_span", "tracing"]

# ---------------------------------------------------------------------------
# Logging hierarchy: REPRO_LOG={off,error,warning,info,debug}
# ---------------------------------------------------------------------------
ENV_LOG = "REPRO_LOG"
#: aliased from the central knob catalog (dp/envknobs.py)
_LOG_LEVELS = envknobs.knob(ENV_LOG).choices
_LOG_LEVEL_NO = {"off": logging.CRITICAL + 10, "error": logging.ERROR,
                 "warning": logging.WARNING, "info": logging.INFO,
                 "debug": logging.DEBUG}
_log_configured = False


def log_level() -> str:
    """The configured ``repro.dp`` log level, validated on read via
    dp/envknobs (a typo raises instead of silencing diagnostics)."""
    return envknobs.read(ENV_LOG)


def _configure_logging() -> None:
    global _log_configured
    if _log_configured:
        return
    _log_configured = True
    root = logging.getLogger("repro.dp")
    if not any(isinstance(h, logging.NullHandler) for h in root.handlers):
        root.addHandler(logging.NullHandler())
    level = log_level()
    root.setLevel(_LOG_LEVEL_NO[level])
    if level != "off" and not any(isinstance(h, logging.StreamHandler)
                                  and not isinstance(h, logging.NullHandler)
                                  for h in root.handlers):
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s: %(message)s"))
        root.addHandler(handler)


def get_logger(module: str) -> logging.Logger:
    """``logging.getLogger("repro.dp.<module>")``, with the hierarchy root
    configured from ``REPRO_LOG`` on first use. Diagnostics that used to go
    through ``warnings.warn`` / ``print`` route here instead."""
    _configure_logging()
    name = module if module.startswith("repro.dp") else f"repro.dp.{module}"
    return logging.getLogger(name)


# ---------------------------------------------------------------------------
# Profiler spans: the host path on the device trace's clock
# ---------------------------------------------------------------------------
class _NoSpan:
    """The span :func:`trace_span` hands out while the profiler is not
    capturing: enters, exits and takes metadata, and records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()


def tracing() -> bool:
    """Whether the profiler is capturing (``jax.profiler.start_trace``), so
    :func:`trace_span` records. Callers compute costly span args only when
    this holds."""
    return TraceAnnotation.is_enabled()


def trace_span(name: str, **args):
    """A named host span for the profiler: a ``jax.profiler.
    TraceAnnotation`` carrying ``args`` while the profiler captures, one
    shared no-op otherwise. Counts known only at the end go on through the
    span's ``set_metadata(**args)``. Every name starts with ``dp.``; the
    benchmark's trace reduction charges device idle time to the innermost
    one open."""
    if not TraceAnnotation.is_enabled():
        return _NO_SPAN
    return TraceAnnotation(name, **args)
