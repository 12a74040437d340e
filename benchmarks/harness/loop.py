"""The benchmark's own training loop: warm steps that ``correct`` reads,
then the timed window.

The window keeps ``IN_FLIGHT`` steps dispatched ahead of the one it waits
for: after dispatching step i it blocks on the loss of step i-2, so the
device never waits while the host reads its clock, and the time each step
completed is known.  Every call into a layer is wrapped in a host span
(``jax.profiler.TraceAnnotation``) that a traced run finds again on the
device's clock; the same spans' durations are summed on the host clock for
``input_wait_ms`` and ``dispatch_ms``.
"""

from __future__ import annotations

import collections
import gc
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

import jax

from . import trace

IN_FLIGHT = 2


def annotate(kind: str):
    return jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + kind)


@dataclass
class Window:
    steps: int = 0
    seconds: float = 0.0
    completions: List[float] = field(default_factory=list)
    wait_s: List[float] = field(default_factory=list)
    dispatch_s: List[float] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)


def step_intervals_ms(completions: List[float]) -> List[float]:
    """The interval, in ms, between the completions of consecutive
    steps."""
    return [(b - a) * 1e3 for a, b in zip(completions, completions[1:])]


def drive(step: Callable, state, feed: Iterator, xy: Callable,
          seconds: float, trace_dir: Optional[str] = None):
    """Run the timed window.  Returns ``(state, Window)``.  With
    ``trace_dir`` the window runs under ``jax.profiler``."""
    w = Window()
    pending = collections.deque()
    handles = []
    clock = time.perf_counter
    gc.collect()
    gc.disable()  # a collection in the window is a stall nobody configured
    try:
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            # the loop's spans are level 1; the Python tracer and the
            # runtime's per-transfer events only make the host slow
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with annotate(trace.WINDOW_SPAN):
                t0 = clock()
                while clock() - t0 < seconds:
                    with annotate("next_batch"):
                        a = clock()
                        arrays = next(feed)
                        w.wait_s.append(clock() - a)
                    x, y = xy(arrays)
                    with annotate("dispatch"):
                        a = clock()
                        state, loss = step(state, x, y)
                        w.dispatch_s.append(clock() - a)
                    pending.append(loss)
                    handles.append(loss)
                    if len(pending) > IN_FLIGHT:
                        with annotate("loss_fetch"):
                            pending.popleft().block_until_ready()
                            w.completions.append(clock())
                with annotate("loss_fetch"):
                    while pending:
                        pending.popleft().block_until_ready()
                        w.completions.append(clock())
                w.seconds = clock() - t0
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    finally:
        gc.enable()
    w.steps = len(handles)
    w.losses = [float(x) for x in jax.device_get(handles)]
    return state, w
