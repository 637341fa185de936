"""Set-up and the measured window of one cell, driven through the server.

The window drives only the program's public serving entry:
``ServerSession.submit`` for each event that is due, ``TreantServer.step``
while events are queued, ``TreantServer.idle`` when none is due.  An event
is complete when every viz it re-rendered has its result ready on the
device.  The served results are taken from ``ServerSession.last_result``,
the result the step produced; ``ServerSession.read`` is not called, since
it executes the viz's query again rather than returning what was served.
"""

from __future__ import annotations

import dataclasses
import time

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

from repro.core import ClearFilter, SetFilter, Treant, jt_from_catalog
from repro.core import predictive
from repro.core import semiring as sr
from repro.serve.server import TreantServer

from bench import catalog as catalog_mod
from bench import generator

clock = time.perf_counter


class Compiles:
    """Backend compile requests (persistent-cache hits included) and
    persistent-cache hits, counted through ``jax.monitoring``."""

    _installed: "Compiles | None" = None

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: list[str] = []  # the compiled functions, in order

    @classmethod
    def install(cls) -> "Compiles":
        if cls._installed is None:
            c = cls._installed = cls()

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    c.cache_hits += 1

            def on_duration(event, secs, fun_name="?", **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    c.requests += 1
                    c.seconds += secs
                    c.names.append(fun_name)

            monitoring.register_event_listener(on_event)
            monitoring.register_event_duration_secs_listener(on_duration)
        return cls._installed

    def snapshot(self) -> tuple[int, int, float]:
        return self.requests, self.cache_hits, self.seconds


def to_event(ev: dict):
    if ev["op"] == "clear":
        return ClearFilter(ev["attr"])
    return SetFilter(ev["attr"], values=tuple(ev["values"]), lo=ev["lo"], hi=ev["hi"],
                     source=ev["source"])


def route(stats) -> str:
    """How a re-rendered viz was served, from its ``ExecStats``."""
    if stats.bin_cube_hits:
        return "cube"
    if stats.prefetch_hits:
        return "prefetch"
    if stats.plan_hits + stats.plan_traces == 0 and stats.messages_computed == 0:
        return "dedup"
    return "store" if stats.messages_computed == 0 else "computed"


@dataclasses.dataclass
class System:
    data: dict
    treant: Treant
    server: TreantServer
    handles: list


def build(cfg: dict, seed: int, sessions: int, scale: float = 1.0) -> System:
    """Tables from the seed, codes on the device, every session opened."""
    data = catalog_mod.tables(cfg, seed, scale)
    cat = catalog_mod.catalog(cfg, data)
    policy = getattr(predictive, cfg["think_time_policy"])()
    treant = Treant(cat, ring=sr.SUM, jt=jt_from_catalog(cat), mesh=False, policy=policy)
    for rel in cfg["relations"]:
        if "rows" in rel:
            r = cat.get(rel["name"])
            jax.block_until_ready([cat.dev_flat_codes(r, (a,))[0] for a in r.attrs])
    server = TreantServer(treant)
    spec = catalog_mod.dashboard(cfg)
    handles = [server.open_session(spec, name=f"analyst{i}") for i in range(sessions)]
    treant.store.block_until_ready()
    return System(data, treant, server, handles)


@dataclasses.dataclass
class Done:
    """One event of the window, as the analyst saw it."""

    seat: int
    due: float
    submitted: float
    done: float
    failed: bool
    filters: dict        # the analyst's filters after the event
    screen: dict         # viz -> Factor on the analyst's screen after the event
    routes: dict         # viz -> route, for the vizzes this event re-rendered


@dataclasses.dataclass
class Seat:
    """One analyst at a dashboard: generator, filters and screen."""

    handle: object
    gen: generator.Analyst
    filters: dict = dataclasses.field(default_factory=dict)
    screen: dict = dataclasses.field(default_factory=dict)
    step: generator.Step | None = None
    due: float = 0.0
    inflight: tuple | None = None  # (program event, submit time)
    events: int = 0


class ServingLoop:
    """Closed-loop analysts against one server, on the host clock."""

    def __init__(self, system: System, mix: dict):
        self.system = system
        self.server = system.server
        self.mix = mix
        self.seats: list[Seat] = []
        self.step_s: list[float] = []  # each step, its events ready
        self.idle_s: list[float] = []  # each idle() call
        self.errors: list[str] = []
        self.trace = None  # (start time, stop time, directory) on the host clock
        self.traced: tuple[float, float] | None = None  # when it really ran

    def attach(self, gens: list[generator.Analyst]) -> None:
        if not self.seats:
            self.seats = [Seat(h, g) for h, g in zip(self.system.handles, gens)]
        for seat, gen in zip(self.seats, gens):
            seat.gen, seat.step, seat.events = gen, None, 0

    def _submit(self, seat: Seat, now: float) -> None:
        with TraceAnnotation("bench.generate"):
            ev = to_event(seat.step.event)
        with TraceAnnotation("serve.submit"):
            seat.handle.submit(ev)
        seat.inflight = (ev, now)

    def _step(self, record: list | None) -> None:
        """One micro-batch; completes every event it served."""
        busy = [s for s in self.seats if s.inflight is not None]
        t = clock()
        with TraceAnnotation("serve.step"):
            try:
                self.server.step()
                crashed = False
            except Exception as e:  # a raising batch fails its events, the run goes on
                crashed = True
                self.errors.append(repr(e)[:500])
        served = []
        for seat in busy:
            res = seat.handle.last_result
            if not crashed and res is not None and res.event is seat.inflight[0]:
                served.append((seat, res))
        with TraceAnnotation("bench.read"):
            jax.block_until_ready([r.factor.field for _, res in served
                                   for r in res.results.values()])
        now = clock()
        self.step_s.append(now - t)
        ok = {id(seat) for seat, _ in served}
        results = {id(seat): res for seat, res in served}
        for seat in busy:
            if self.server.queue_depth and id(seat) not in ok:
                continue  # still queued behind a full batch
            failed = id(seat) not in ok
            ev = seat.step.event
            if ev["op"] == "clear":
                seat.filters.pop(ev["attr"], None)
            else:
                seat.filters[ev["attr"]] = ev
            routes = {}
            if not failed:
                for viz, r in results[id(seat)].results.items():
                    seat.screen[viz] = r.factor
                    routes[viz] = route(r.stats)
            if record is not None:
                record.append(Done(self.seats.index(seat), seat.due, seat.inflight[1], now,
                                   failed, dict(seat.filters), dict(seat.screen), routes))
            seat.inflight, seat.step = None, None
            seat.events += 1
            self._advance(seat, now)

    def _advance(self, seat: Seat, now: float, first: float | None = None) -> None:
        seat.step = seat.gen.next()
        seat.due = now + (seat.step.delay_s if first is None else first)

    def _start(self, t0: float, spread: float) -> None:
        n = len(self.seats)
        for i, seat in enumerate(self.seats):
            # first events spread over ``spread`` seconds
            self._advance(seat, t0, first=spread * (i + 0.5) / n)

    def _run(self, t_end: float, cap: int | None, record: list | None) -> None:
        """Serve due events, think when none is due, sleep otherwise; stop
        at ``t_end`` or once every analyst has sent ``cap`` events, and
        then once the events due before it are done."""
        server, scheduler = self.server, self.system.treant.scheduler
        idle_more = False
        while True:
            now = clock()
            self._trace_tick(now)
            for seat in self.seats:
                if (seat.inflight is None and seat.due <= min(now, t_end)
                        and (cap is None or seat.events < cap)):
                    self._submit(seat, now)
            if server.queue_depth:
                self._step(record)
                idle_more = True
                continue
            waiting = [s.due for s in self.seats if cap is None or s.events < cap]
            if now >= t_end or not waiting:
                return
            nxt = min(waiting)
            if idle_more:
                t = clock()
                with TraceAnnotation("serve.idle"):
                    idle_more = server.idle() > 0 or scheduler.pending() > 0
                self.idle_s.append(clock() - t)
            elif nxt > now:
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_end, self._next_trace_tick(nxt)) - now))

    def warm(self, sweeps: list[generator.Sweep], gens: list[generator.Analyst],
             events: int) -> None:
        """Set-up: the sweeps, every analyst in step, then the mix's own
        traffic from ``gens`` until every analyst has sent ``events``
        events: a fixed amount of work."""
        self.attach(sweeps)
        self._start(clock(), 0.0)
        self._run(float("inf"), sweeps[0].length, None)
        self.attach(gens)
        self._start(clock(), float(self.mix["think_mean_s"]))
        self._run(float("inf"), events, None)

    def window(self, seconds: float, trace: tuple | None = None
               ) -> tuple[list[Done], float, float]:
        """Measure for ``seconds``; every event due before the close is
        then served too.  ``trace`` is ``(start, stop, directory)``, in
        seconds from the window's start, for the profiler.  Returns the
        events, the start and the close."""
        t0 = clock()
        if trace is not None:
            self.trace = (t0 + trace[0], t0 + trace[1], trace[2])
        self._start(t0, float(self.mix["think_mean_s"]))
        record: list[Done] = []
        self._run(t0 + seconds, None, record)
        self._trace_tick(float("inf"))
        return record, t0, t0 + seconds

    # -- profiler ----------------------------------------------------------------
    def _next_trace_tick(self, t: float) -> float:
        if self.trace is None or self.traced is not None and self.traced[1]:
            return t
        return min(t, self.trace[0] if self.traced is None else self.trace[1])

    def _trace_tick(self, now: float) -> None:
        if self.trace is None:
            return
        start, stop, path = self.trace
        if self.traced is None and now >= start and now < float("inf"):
            jax.profiler.start_trace(path)
            self.traced = (clock(), 0.0)
        elif self.traced is not None and not self.traced[1] and now >= stop:
            t = clock()
            jax.profiler.stop_trace()
            self.traced = (self.traced[0], t)
