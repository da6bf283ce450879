"""The load driver: the only module of the benchmark that calls the program.

It drives ``SortServeEngine.begin()`` -> ``SortSession.feed`` / ``poll`` /
``drain`` from one thread, the served path from request to decoded
response, and records for every request its due time and the instant its
response reached the driver.  It also wraps each backend's ``run`` (the
call that copies the tile in, runs the executor and blocks in
``np.asarray``) with a host timer, and, in a traced run, opens
``jax.profiler.TraceAnnotation`` spans named after what the host is doing,
so idle stretches on the device can be put down to them.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

__all__ = ["Driver", "Record", "TileRecord", "build_engine"]

clock = time.perf_counter


@dataclass
class Record:
    """One request: when it was due and when its answer came back."""

    req: object
    due: float
    client: int = -1
    t_fed: float | None = None
    t_done: float | None = None
    resp: object = None


@dataclass
class TileRecord:
    backend: str
    op: str
    rows: int                      # the tile's shape, padding included
    n: int
    k: int | None
    lengths: tuple                 # each request's own payload length
    traced: bool


@dataclass
class Counters:
    """Host time inside the engine's entry points and inside backend runs,
    over the measured window only."""

    engine_s: float = 0.0
    backend_s: float = 0.0
    tiles: list = field(default_factory=list)
    longest: dict = field(default_factory=dict)   # label -> longest call, s

    def note(self, label: str, dt: float) -> None:
        self.longest[label] = max(self.longest.get(label, 0.0), dt)


def build_engine(fields: dict):
    """The engine a configuration file's ``engine`` block describes."""
    from repro.sortserve import EngineConfig, SortServeEngine

    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in fields.items()}
    return SortServeEngine(EngineConfig(**kw))


class Driver:
    """One engine, one driver thread, one measured window at a time."""

    def __init__(self, engine):
        self.engine = engine
        self.counting = False          # inside the measured window
        self.traced = False            # the profiler is recording
        self.counters = Counters()
        self._annotation = None
        for be in engine.backends:
            be.run = self._timed(be.run, be.name)

    # ------------------------------------------------------------ spans
    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def _engine_call(self, name: str):
        t0 = clock()
        with self.span(name):
            yield
        if self.counting:
            dt = clock() - t0
            self.counters.engine_s += dt
            self.counters.note(name, dt)

    def _timed(self, run, name: str):
        label = f"backend.run:{name}"

        def timed(tile):
            t0 = clock()
            with self.span(label):
                result = run(tile)
            dt = clock() - t0
            if self.counting:
                b, n = tile.data.shape
                self.counters.backend_s += dt
                self.counters.note(label, dt)
                self.counters.tiles.append(TileRecord(
                    name, tile.op, b, n, tile.k,
                    tuple(req.n for req, _ in tile.entries), self.traced))
            return result
        return timed

    # ------------------------------------------------------- the profiler
    def start_trace(self, logdir: str) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # it would trace every call
        jax.profiler.start_trace(logdir, profiler_options=options)
        self.traced = True
        self._annotation = jax.profiler.TraceAnnotation("window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        import jax
        if not self.traced:
            return
        self._annotation.__exit__(None, None, None)
        self.traced = False
        jax.profiler.stop_trace()

    # ------------------------------------------------------------ loops
    def _request(self, traffic, i: int):
        from repro.sortserve import SortRequest

        op, payload, k = traffic.request(i)
        return SortRequest(op=op, payload=payload, k=k)

    def _sleep_until(self, t: float) -> None:
        dt = t - clock()
        if dt > 0:
            with self.span("wait"):
                time.sleep(dt)
            if self.counting:
                self.counters.note("wait overshoot", clock() - t)

    def closed_loop(self, traffic, *, seconds: float | None = None,
                    requests: int | None = None, on_tick=None):
        """``traffic.clients`` callers, each due again the instant its
        answer arrives.  Runs for ``seconds`` or until ``requests`` have
        been sent, then drains what is still open.  Returns the records
        and the loop's start instant."""
        sess = self.engine.begin(max_age_s=traffic.bucket_age_s)
        t0 = clock()
        t_end = None if seconds is None else t0 + seconds
        ready = deque((t0, c) for c in range(traffic.clients))
        open_: dict[int, Record] = {}
        records: list[Record] = []
        i = traffic.cursor
        first = i
        while True:
            now = clock()
            if (t_end is not None and now >= t_end) or (
                    requests is not None and i - first >= requests):
                break
            if on_tick is not None:
                on_tick(now)
            if ready:
                due, client = ready.popleft()
                if traffic.drawn(i):
                    req = self._request(traffic, i)
                else:
                    with self.span("generate"):
                        req = self._request(traffic, i)
                i += 1
                rec = Record(req, due, client, t_fed=clock())
                open_[req.request_id] = rec
                records.append(rec)
                with self._engine_call("feed"):
                    out = sess.feed([req])
            else:
                # every caller waits in an open bucket: let it age out
                deadline = sess.next_deadline()
                if deadline is not None:
                    self._sleep_until(deadline)
                with self._engine_call("poll"):
                    out = sess.poll()
            t = clock()
            for resp in out:
                rec = open_.pop(resp.request_id)
                rec.t_done, rec.resp = t, resp
                ready.append((t, rec.client))
        traffic.cursor = i
        self.counting = False
        with self.span("drain"):
            out = sess.drain()
        t = clock()
        for resp in out:
            rec = open_.pop(resp.request_id)
            rec.t_done, rec.resp = t, resp
        return records, t0

    def open_loop(self, traffic, *, seconds: float | None = None,
                  steps: int | None = None, paced: bool = True,
                  on_tick=None):
        """Steps fed whole (``feed(rows, flush=traffic.flush)``) at their
        due instants: every step due before ``seconds`` (or the first
        ``steps``).  A step's rows are made before its due instant; open
        buckets are polled as they age out.  Then drains what is still
        open.  Returns the records and the first step's due instant."""
        sess = self.engine.begin(max_age_s=traffic.bucket_age_s)
        t0 = None                       # the first step is due once made
        open_: dict[int, Record] = {}
        records: list[Record] = []

        def answered(out) -> None:
            t = clock()
            for resp in out:
                rec = open_.pop(resp.request_id)
                rec.t_done, rec.resp = t, resp

        j, i = traffic.cursor
        first, off0 = j, traffic.step(j)[0]
        while True:
            due_off, rows = traffic.step(j)
            due_off -= off0
            if (seconds is not None and due_off >= seconds) or (
                    steps is not None and j - first >= steps):
                break
            with self.span("generate"):
                reqs = [self._request(traffic, i + r) for r in range(rows)]
            i += rows
            if t0 is None:
                t0 = clock()
            due = t0 + due_off if paced else clock()
            if on_tick is not None:
                on_tick(clock())
            recs = [Record(req, due, j) for req in reqs]
            while True:                 # buckets that age out before it
                deadline = sess.next_deadline()
                if deadline is None or deadline >= due:
                    break
                self._sleep_until(deadline)
                with self._engine_call("poll"):
                    answered(sess.poll())
            self._sleep_until(due)
            fed = clock()
            for rec in recs:
                rec.t_fed = fed
                open_[rec.req.request_id] = rec
            records.extend(recs)
            with self._engine_call("feed"):
                out = sess.feed(reqs, flush=traffic.flush)
            answered(out)
            j += 1
        traffic.cursor = (j, i)
        self.counting = False
        with self.span("drain"):
            answered(sess.drain())
        return records, t0

    def run(self, traffic, **kw):
        loop = (self.closed_loop if traffic.loop == "closed"
                else self.open_loop)
        return loop(traffic, **kw)

    def exec_misses(self) -> int:
        """Compiles so far: executor-cache misses plus persistent-cache
        misses (the program's own counters)."""
        ec = self.engine.telemetry()["executor_cache"]
        return int(ec["misses"]) + int(ec["persistent_misses"])
