"""The one general traffic generator and its closed-loop clients.

A configuration (``configs/<config>.json``) says what an instance of its
deployment looks like (``instance``: its kind, whose maker is
``instances/<kind>.py``, and the parameters that maker reads); a traffic
mix (``traffic/<mix>.json``) says how many, of which sizes, how often a
decoded solution is asked for, and how a client sends them (``loop``):

- ``rounds``: one client submits a round of ``round`` fresh instances, an
  equal share of each of ``sizes``, every ``reconstruct_every``-th of each
  size asking for its decoded solution, then steps the service until the
  round is answered, and repeats. The window holds whole rounds.
- ``callers``: ``callers`` clients each hold one request open; each sends
  its next instance (cycling through ``sizes``) as soon as its answer
  comes back.

Every instance is drawn from the run's seed, so one seed gives the same
inputs; different seeds give the same sizes and loop, with other symbols.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import os
import sys
import time
import traceback
from typing import Any, Optional

clock = time.perf_counter
HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """The module in file ``path`` (a file under ``bench/`` found by a
    name in a configuration, a mix or ``BENCHMARK.json``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Req:
    problem: str
    payload: dict
    shape: tuple
    reconstruct: bool
    tid: int = -1
    t_answer: float = float("nan")
    result: Any = None


class Generator:
    """Instances of one configuration under one traffic mix, from ``rng``."""

    def __init__(self, config: dict, traffic: dict, rng):
        self.problem = config["problem"]
        self.inst = config["instance"]
        self.kind = load_module(os.path.join(HERE, "instances",
                                             self.inst["kind"] + ".py"))
        self.traffic = traffic
        self.rng = rng
        self.sizes = list(traffic["sizes"])
        self.every = int(traffic.get("reconstruct_every", 0))
        self._count = {s: 0 for s in self.sizes}
        self._next = 0

    def make(self, size: int) -> Req:
        k = self._count[size]
        self._count[size] += 1
        recon = self.every > 0 and k % self.every == 0
        payload, shape = self.kind.make(self.inst, size, self.rng)
        return Req(self.problem, payload, shape, recon)

    def round(self) -> list:
        per = self.traffic["round"] // len(self.sizes)
        return [self.make(s) for s in self.sizes for _ in range(per)]

    def one(self) -> Req:
        size = self.sizes[self._next % len(self.sizes)]
        self._next += 1
        return self.make(size)


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t_end: float = 0.0
    reqs: list = dataclasses.field(default_factory=list)
    #: (start, end, requests answered) of every service step in the window
    steps: list = dataclasses.field(default_factory=list)
    #: span name -> [(start, end)] on the host clock
    spans: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def completed(self) -> list:
        """Requests answered inside the window."""
        return [r for r in self.reqs if r.t_answer <= self.t_end
                and r.result is not None]


class Client:
    """Drives ``svc`` (a ``DPService``) through ``submit``/``step``/``poll``
    and records each call as a span. ``annotate`` wraps each span for the
    profiler (``jax.profiler.TraceAnnotation``) in a traced run."""

    def __init__(self, svc, gen: Generator, annotate=None):
        self.svc = svc
        self.gen = gen
        self.annotate = annotate
        self.rec: Optional[Window] = None
        self._open: dict = {}
        #: set when a service call raised: the loops stop, and what is
        #: still open counts as never answered
        self.broken = False

    @contextlib.contextmanager
    def span(self, name: str):
        cm = self.annotate(name) if self.annotate else contextlib.nullcontext()
        t = clock()
        with cm:
            yield
        if self.rec is not None:
            self.rec.spans.setdefault(name, []).append((t, clock()))

    def submit(self, req: Req) -> None:
        with self.span("submit"):
            req.tid = self.svc.submit(req.problem, reconstruct=req.reconstruct,
                                      **req.payload)
        self._open[req.tid] = req
        if self.rec is not None:
            self.rec.reqs.append(req)

    def step(self) -> list:
        """One service step; returns the requests it answered."""
        t = clock()
        try:
            with self.span("step"):
                tids = self.svc.step()
        except Exception:
            print("# DPService.step raised; the run stops here:",
                  file=sys.stderr)
            traceback.print_exc()
            self.broken = True
            return []
        done = []
        with self.span("poll"):
            for tid in tids:
                req = self._open.pop(tid)
                req.result = self.svc.poll(tid)
                req.t_answer = clock()
                done.append(req)
        if self.rec is not None:
            self.rec.steps.append((t, clock(), len(done)))
        return done

    def outstanding(self) -> int:
        return len(self._open)

    def generate(self, what: str):
        with self.span("generate"):
            return self.gen.round() if what == "round" else self.gen.one()

    # -- loops -------------------------------------------------------------
    def run(self, seconds: float, units: Optional[int] = None) -> None:
        """Run the mix's loop until ``seconds`` have passed or ``units``
        rounds / caller steps are done. The time is checked after each
        whole round, or after each step of the callers' loop."""
        loop = self.gen.traffic["loop"]
        until = clock() + seconds
        done_units = 0
        if loop == "rounds":
            while True:
                for req in self.generate("round"):
                    self.submit(req)
                while self.outstanding() and not self.broken:
                    # a sound service answers something every step; one
                    # that stops answering is given up on at the deadline
                    if not self.step() and clock() >= until:
                        return
                done_units += 1
                if clock() >= until or self.broken or (
                        units is not None and done_units >= units):
                    return
        elif loop == "callers":
            for _ in range(self.gen.traffic["callers"] - self.outstanding()):
                self.submit(self.generate("one"))
            while True:
                answered = self.step()
                done_units += 1
                if clock() >= until or self.broken or (
                        units is not None and done_units >= units):
                    return
                for _ in answered:
                    self.submit(self.generate("one"))
        else:
            raise ValueError(f"unknown loop {loop!r}")

    def drain(self, grace_s: float) -> None:
        """Step until every open request is answered, the service holds
        nothing more, or ``grace_s`` has passed."""
        until = clock() + grace_s
        while (self.outstanding() and self.svc.pending() and not self.broken
               and clock() < until):
            self.step()

    def measure(self, seconds: float) -> Window:
        """The measured window: the loop for ``seconds``, closed at the end
        of the round (or the callers' step) that crosses it. A round is
        measured whole: cut inside one, a window's rate would swing with
        where the cut falls, after the round's submits or after its last
        drain."""
        self.rec = Window()
        with self.span("window"):
            self.rec.t0 = clock()
            self.run(seconds)
            self.rec.t_end = clock()
        rec, self.rec = self.rec, None
        return rec
