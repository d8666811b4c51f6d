"""The open-loop driver around ``MicrobatchScheduler.submit`` / ``flush``.

A generator thread submits each request at its due time, with ``t_enq``
preset to that due time, so latency runs from when the request was due,
however late the generator ran. The calling thread is the serving thread:
it calls ``flush`` in a loop and waits for the next submission when the
queue is empty. Two threads are safe here: with admission control and
packing off (the configuration's serve settings), ``submit`` only appends
to the scheduler's deque, ``flush`` is the deque's only consumer, and a
CPython deque's append and popleft are atomic.

The window closes ``seconds`` after it opens. Requests still queued or in
flight then are drained: they are answered and their latency is kept,
for up to ``drain_s`` more seconds.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass

import numpy as np

from chipbench.trace import WINDOW_SPAN


@dataclass
class Window:
    responses: dict             # uid -> Response
    opened: float               # perf_counter when the window opened
    seconds: float
    lateness: np.ndarray        # [n] seconds each submit ran behind its due


def serve_until_answered(sched, n: int, done: threading.Event,
                         wake: threading.Event, responses: dict,
                         deadline: float) -> None:
    clock = time.perf_counter
    while True:
        wake.clear()
        for r in sched.flush():
            responses[r.uid] = r
        if done.is_set() and (len(responses) >= n or clock() > deadline):
            return
        if not sched.queue:
            wake.wait(0.002)


def open_loop(sched, request_of, due: np.ndarray, seconds: float, *,
              drain_s: float = 60.0, annotate: bool = False) -> Window:
    """Serve ``len(due)`` requests; ``request_of(i, t_due)`` builds the
    i-th ``Request``."""
    clock = time.perf_counter
    n = len(due)
    responses: dict = {}
    lateness = np.zeros(n)
    wake, done = threading.Event(), threading.Event()
    errors: list = []
    opened = clock() + 0.05
    due_abs = opened + np.asarray(due, np.float64)

    def generate():
        try:
            wait = opened - clock()
            if wait > 0:
                time.sleep(wait)
            with _annotation() if annotate else contextlib.nullcontext():
                for i in range(n):
                    wait = due_abs[i] - clock()
                    if wait > 0:
                        time.sleep(wait)
                    lateness[i] = clock() - due_abs[i]
                    sched.submit(request_of(i, float(due_abs[i])))
                    wake.set()
                rest = opened + seconds - clock()
                if rest > 0:
                    time.sleep(rest)
        except BaseException as e:      # re-raised in the serving thread
            errors.append(e)
        finally:
            done.set()
            wake.set()

    gen = threading.Thread(target=generate, name="chipbench-generator")
    gen.start()
    try:
        serve_until_answered(sched, n, done, wake, responses,
                             opened + seconds + drain_s)
    finally:
        gen.join(timeout=seconds + drain_s + 60.0)
    if errors:
        raise errors[0]
    return Window(responses, opened, seconds, lateness)


def _annotation():
    import jax
    return jax.profiler.TraceAnnotation(WINDOW_SPAN)
