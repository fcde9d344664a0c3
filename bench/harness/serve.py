"""One run of one cell: build the engine through the serving launcher,
warm up every shape the traffic uses, drive the schedule through
``ServingEngine.submit`` / ``tick`` and record, on the host clock, when
each request was due, admitted and given each token.

Only the engine's public surface is read: ``submit``, ``tick``,
``queue``, ``n_active``, ``stats`` and each ``Request``'s ``generated``,
``pending`` and ``done``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import numpy as np
from jax.profiler import TraceAnnotation

from bench.harness import traffic as T

GRACE_S = 60.0          # how long a request due in the window may wait


@dataclass
class Tick:
    start: float
    end: float
    decode_pos: List[int]           # query position of each live slot
    admitted: List[int]             # Req.idx admitted by this tick
    shared_tokens: int              # prompt tokens attached, not prefilled


@dataclass
class Window:
    open: float = 0.0
    close: float = 0.0              # deadline: ticks start before it
    end: float = 0.0                # end of the last tick of the window
    ticks: List[Tick] = field(default_factory=list)
    backpressure: int = 0
    held_at_open: int = 0           # closed loop: clients not admitted
    compiles: List[str] = field(default_factory=list)
    late_s: List[float] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.open


class CompileCounter:
    """Counts compilations and compile-cache reads by JAX's own
    monitoring events, with their times."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if "backend_compile" in name or "cache_retrieval" in name:
            self.events.append((time.perf_counter(), name, secs))

    def between(self, t0: float, t1: float) -> List[str]:
        return [n for (t, n, _) in self.events if t0 <= t <= t1]


def build_engine(argv: list, model_cfg, fam, m: dict, weight_seed: int):
    """The engine exactly as the serving launcher builds it (one replica,
    no router), weights drawn on the device from the seed, then set by
    the family's ``prepare_params``."""
    import jax
    from repro.launch import serve as launcher
    args = launcher.build_parser().parse_args(argv)
    engine = launcher.engine_builder(args, model_cfg)()
    engine.params = fam.prepare_params(engine.params, m, weight_seed)
    jax.block_until_ready(engine.params)
    return engine


def warm_up(engine, vocab: int, seed: int) -> int:
    """Compile every program the traffic can reach: the engine's own
    warm-up, then one short request of each last-chunk width, served
    through ``submit`` / ``tick``.  Returns the widths served."""
    from repro.serve.engine import Request
    engine.warmup()
    rng = np.random.default_rng([seed, 3])
    widths = T.prompt_widths(engine.chunk, engine.page_size)
    for i, w in enumerate(widths):
        engine.submit(Request(-1 - i, rng.integers(0, vocab, w).tolist(),
                              max_new=2))
    while engine.queue or engine.n_active:
        engine.tick()
    engine.finished.clear()
    return len(widths)


class Runner:
    """Drives one schedule through one engine."""

    def __init__(self, engine, reqs: List[T.Req], mix: dict,
                 seconds: float, tracer=None, counter=None):
        self.engine = engine
        self.reqs = reqs
        self.mix = mix
        self.seconds = seconds
        self.tracer = tracer
        self.counter = counter
        self.closed = mix["loop"] == "closed"
        self.inflight: List[T.Req] = []
        self.win = Window()
        self._next = 0                  # open loop: next request to submit
        self._pending_closed = []       # closed loop: (request, due time)

    # -- submission ------------------------------------------------------

    def _submit(self, r: T.Req, due_at: float) -> None:
        from repro.serve.engine import Request
        r.due_at = due_at
        r.submitted_at = time.perf_counter()
        r.engine_req = Request(r.idx, r.prompt, max_new=r.max_new)
        self.engine.submit(r.engine_req)
        self.inflight.append(r)

    def _submit_due(self, now: float, t0: float, last_due: float) -> None:
        while self._next < len(self.reqs):
            r = self.reqs[self._next]
            if r.due > last_due or t0 + r.due > now:
                return
            self._submit(r, t0 + r.due)
            self.win.late_s.append(r.submitted_at - r.due_at)
            self._next += 1

    # -- one tick ----------------------------------------------------------

    def _tick(self, in_window: bool) -> Tick:
        eng = self.engine
        shared0 = eng.stats["shared_tokens"]
        bp0 = eng.stats["backpressure"]
        ts = time.perf_counter()
        with TraceAnnotation("bench.tick"):
            eng.tick()
        te = time.perf_counter()
        with TraceAnnotation("bench.record"):
            tick = Tick(ts, te, [], [], eng.stats["shared_tokens"] - shared0)
            if in_window:
                self.win.backpressure += eng.stats["backpressure"] - bp0
            still = []
            for r in self.inflight:
                er = r.engine_req
                n = len(er.generated)
                if r.admitted_tick_start is None and (n or er.pending >= 0):
                    r.admitted_tick_start = ts
                    tick.admitted.append(r.idx)
                for k in range(len(r.token_times), n):
                    r.token_times.append(te)
                    tick.decode_pos.append(len(r.prompt) + k)
                if er.done:
                    self._finished(r, te)
                else:
                    still.append(r)
            self.inflight = still
        return tick

    def _finished(self, r: T.Req, now: float) -> None:
        """Closed loop: the client sends its next request at once."""
        if self.closed:
            j = r.idx + self.mix["clients"]
            if j < len(self.reqs):
                self._pending_closed.append((self.reqs[j], now))

    # -- the run -----------------------------------------------------------

    def run(self) -> Window:
        return self._run_closed() if self.closed else self._run_open()

    def _start_window(self, at: float) -> None:
        self.win.open = at
        self.win.close = at + self.seconds
        if self.tracer is not None:
            self._win_span = TraceAnnotation("bench.window")
            self._win_span.__enter__()

    def _end_window(self, end: float) -> None:
        self.win.end = end
        if self.tracer is not None:
            self._win_span.__exit__(None, None, None)
        if self.counter is not None:
            self.win.compiles = self.counter.between(self.win.open, end)

    def _run_closed(self) -> Window:
        clients = self.mix["clients"]
        t0 = time.perf_counter()
        for r in self.reqs[:clients]:
            self._submit(r, t0)
        first = list(self.reqs[:clients])
        # the admission wave: until every client has its first token, or
        # the pool holds the rest back (a tick that admits no one)
        while True:
            tick = self._tick(False)
            if all(r.token_times for r in first):
                break
            if not tick.admitted and self.engine.queue:
                break
        self.win.held_at_open = len(self.engine.queue)
        if self.tracer is not None:
            self.tracer.start()
        self._start_window(time.perf_counter())
        last = self.win.open
        while True:
            now = time.perf_counter()
            if now >= self.win.close:
                break
            for (r, due) in self._pending_closed:
                self._submit(r, due)
            self._pending_closed = []
            if not (self.engine.queue or self.engine.n_active):
                raise RuntimeError("closed loop: every client's schedule "
                                   "ran out before the window closed")
            tick = self._tick(True)
            self.win.ticks.append(tick)
            last = tick.end
        self._end_window(last)
        if self.tracer is not None:
            self.tracer.stop()
        return self.win

    def _run_open(self) -> Window:
        pre = self.mix["preroll_s"]
        t0 = time.perf_counter()
        last_due = pre + self.seconds
        self._start_at = t0 + pre
        started = False
        last = None
        while True:
            now = time.perf_counter()
            if not started and now >= self._start_at - 1.0:
                if self.tracer is not None:
                    self.tracer.start()
                started = True
            if started and self.win.open == 0.0 and now >= self._start_at:
                self._start_window(now)
            if self.win.open and now >= self.win.close:
                break
            with TraceAnnotation("bench.submit"):
                self._submit_due(now, t0, last_due)
            if not (self.engine.queue or self.engine.n_active):
                nxt = (t0 + self.reqs[self._next].due
                       if self._next < len(self.reqs) else self.win.close)
                wake = min(nxt, self._start_at if not self.win.open
                           else self.win.close)
                if not started:
                    wake = min(wake, self._start_at - 1.0)
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
                continue
            tick = self._tick(bool(self.win.open))
            if self.win.open:
                self.win.ticks.append(tick)
                last = tick.end
        self._end_window(last if last is not None else time.perf_counter())
        if self.tracer is not None:
            self.tracer.stop()
        # requests due in the window keep their claim to a first token
        lo, hi = self.win.open, self.win.close
        due = [r for r in self.reqs if r.due_at is not None
               and lo <= r.due_at < hi]
        limit = time.perf_counter() + GRACE_S
        while (any(not r.token_times for r in due)
               and time.perf_counter() < limit
               and (self.engine.queue or self.engine.n_active)):
            self._tick(False)
        return self.win

