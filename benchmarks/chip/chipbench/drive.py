"""Drive the program's serving engine through a window and record, on the
host clock, what each request saw.

The harness calls only ``ServingEngine.add_request`` and
``ServingEngine.step``, each inside a ``jax.profiler.TraceAnnotation``
(``admit``, ``step``, ``await_arrival``, ``token_cb``), so that a traced
run can say what the host was doing in every gap of the device.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax

Annot = jax.profiler.TraceAnnotation


class Tracked:
    """One request as the harness sees it."""

    def __init__(self, tr, due: float, stream: bool):
        self.tr = tr
        self.due = due
        self.stream = stream
        self.req = None
        self.submit = None
        self.token_times: list[float] = []
        self.prefill_events: list[list] = []   # [time, first_row, end_row]
        self._prefilled = 0
        self.error: Optional[str] = None

    def on_token(self, tok) -> None:
        with Annot("token_cb"):
            self.token_times.append(time.perf_counter())

    @property
    def done(self) -> bool:
        return self.error is not None or (
            self.req is not None and len(self.req.output_tokens) >= self.tr.max_new_tokens)

    def poll(self, now: float) -> None:
        """Credit prompt rows whose prefill finished, and (without a
        streaming callback) output tokens that reached the host."""
        r = self.req
        if r is None:
            return
        rows = r.prompt_len if r.output_tokens else r.prefill_done
        if rows > self._prefilled:
            self.prefill_events.append([now, self._prefilled, rows])
            self._prefilled = rows
        if not self.stream:
            self.token_times.extend([now] * (len(r.output_tokens) - len(self.token_times)))

    def record(self) -> dict:
        """What the metric readers see of this request."""
        return {"due": self.due,
                "prompt_len": int(len(self.tr.prompt)),
                "token_times": list(self.token_times),
                "prefill_events": list(self.prefill_events)}


def submit(engine, t: Tracked) -> None:
    with Annot("admit"):
        t.submit = time.perf_counter()
        try:
            t.req = engine.add_request(
                t.tr.prompt, t.tr.max_new_tokens,
                on_token=t.on_token if t.stream else None)
        except ValueError as e:        # refused: counts as missing
            t.error = repr(e)


class StepLog:
    """Per-step host times and what each step dispatched, for the traced
    slice's kernel accounting."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: list[dict] = []

    def step(self, active: list[Tracked]) -> None:
        m = self.engine.metrics
        before = (m.decode_steps, m.chunk_steps, m.defrag_count)
        n_out = {id(t): len(t.req.output_tokens) for t in active if t.req is not None}
        t0 = time.perf_counter()
        with Annot("step"):
            self.engine.step()
        t1 = time.perf_counter()
        oneshot, decode_keys = [], []
        for t in active:
            r = t.req
            if r is None:
                continue
            was, now = n_out.get(id(t), 0), len(r.output_tokens)
            first = was == 0 and now > 0
            # a one-shot admission prefills the whole prompt and emits the
            # first token within the step; a chunked one advances
            # prefill_done over several steps
            if first and r.prefill_done == 0:
                oneshot.append(int(r.prompt_len))
            # a lane the decode step served emitted token now-1 from the
            # one before it, attending prompt_len + now - 1 keys
            if now - was >= (2 if first else 1):
                decode_keys.append(int(r.prompt_len) + now - 1)
        self.steps.append({"t0": t0, "t1": t1,
                           "decode": m.decode_steps - before[0],
                           "chunks": m.chunk_steps - before[1],
                           "defrags": m.defrag_count - before[2],
                           "oneshot_prompts": oneshot,
                           "decode_keys": decode_keys})


def drive_open_loop(engine, requests, window_s: float, fill_s: float = 0.0,
                    drain_limit_s: float = 60.0,
                    on_window: Optional[Callable] = None):
    """Open loop: each request is submitted when it falls due, whatever
    the engine is doing; the window opens ``fill_s`` seconds after the
    loop starts (the requests due before it fill the engine, unmeasured),
    and arrivals keep flowing after it closes until every request due in
    the window has finished (or ``drain_limit_s`` has passed).
    ``on_window(phase, now)`` is called with ``"open"`` and the window's
    open at the start, and after every step."""
    log = StepLog(engine)
    clock = time.perf_counter
    t0 = clock() + fill_s
    close = t0 + window_s
    tracked: list[Tracked] = []
    active: list[Tracked] = []
    i, n = 0, len(requests)
    if on_window:
        on_window("open", t0)
    while True:
        now = clock()
        while i < n and t0 + requests[i].due_s <= now:
            t = Tracked(requests[i], t0 + requests[i].due_s, requests[i].stream)
            submit(engine, t)
            tracked.append(t)
            active.append(t)
            i += 1
        if now >= close:
            due_open = [t for t in tracked if t.due < close and not t.done]
            if not due_open or now > close + drain_limit_s:
                break
        if engine.has_work:
            log.step(active)
            now = clock()
            for t in active:
                t.poll(now)
            active = [t for t in active if not t.done]
            if on_window:
                on_window("step", now)
        elif i < n:
            with Annot("await_arrival"):
                time.sleep(max(0.0, t0 + requests[i].due_s - clock()))
        else:
            break
    end = clock()
    return tracked, (t0, close), end, log.steps


def finish_for_check(engine, tracked, tokens: int, limit_s: float) -> None:
    """After the run's end, with no new arrivals: step the engine until
    the finished requests hold ``tokens`` served tokens (what the check
    samples from), nothing is left in flight, or ``limit_s`` has passed.
    Nothing here is timed; the metrics were read before."""
    stop = time.perf_counter() + limit_s

    def served() -> int:
        return sum(len(t.req.output_tokens) for t in tracked
                   if t.error is None and t.req is not None and t.done)

    while engine.has_work and served() < tokens and time.perf_counter() < stop:
        engine.step()


class Backlog:
    """Offline batch: the harness holds the backlog and keeps
    ``keep_waiting`` requests queued in the engine, so every lane that
    frees is refilled at once and the window can close without the engine
    holding the rest of the batch."""

    def __init__(self, engine, requests, keep_waiting: int = 1):
        self.engine = engine
        self.requests = requests
        self.keep_waiting = keep_waiting
        self.next = 0
        self.feeding = True
        self.tracked: list[Tracked] = []
        self.active: list[Tracked] = []
        self.log = StepLog(engine)

    def top_up(self) -> None:
        sched = self.engine.scheduler
        while (self.feeding and self.next < len(self.requests)
               and len(sched.waiting) < self.keep_waiting):
            tr = self.requests[self.next]
            t = Tracked(tr, time.perf_counter(), tr.stream)
            submit(self.engine, t)
            self.tracked.append(t)
            self.active.append(t)
            self.next += 1

    def step(self) -> float:
        self.log.step(self.active)
        now = time.perf_counter()
        for t in self.active:
            t.poll(now)
        self.active = [t for t in self.active if not t.done]
        self.top_up()
        return now

    def fill(self, n_lanes: int) -> None:
        """Step until the engine is saturated (set-up: the window opens on
        a full engine): every lane busy, or the next request held back by
        the page pool with no prefill in flight."""
        sched = self.engine.scheduler
        self.top_up()
        while True:
            busy = len(sched.running) + len(sched.chunking)
            admitted = self.engine.metrics.prefill_dispatches
            self.step()
            if len(sched.running) + len(sched.chunking) >= n_lanes:
                return
            if (sched.waiting and not sched.chunking and busy > 0
                    and self.engine.metrics.prefill_dispatches == admitted):
                return

    def drive(self, window_s: float, drain_limit_s: float = 60.0,
              on_window: Optional[Callable] = None):
        """Measure ``window_s`` seconds, then stop feeding and let the
        requests in flight finish."""
        clock = time.perf_counter
        self.log.steps.clear()
        t0 = clock()
        close = t0 + window_s
        if on_window:
            on_window("open", t0)
        while True:
            now = clock()
            if now >= close:
                self.feeding = False
                if not self.engine.has_work or now > close + drain_limit_s:
                    break
            now = self.step()
            if on_window:
                on_window("step", now)
        return self.tracked, (t0, close), clock(), self.log.steps
