"""Seconds from the start of ``bench/run.py`` to the first timed request:
imports, device start, service construction, compiling or loading every
program the mix uses, and the warm-up traffic."""


def read(run):
    return run.setup_s
