"""Tool bodies, timed from the benchmark's side of the tool boundary.

Every configuration's tool factory wraps its body in :func:`timed`.  The
wrapper records the body's own start and end (host clock) and, while the
harness traces, names the body in the profiler's trace as
``tool:<step>`` so that device gaps can be put down to the tool that the
host was running.  The records are what ``outside_body_ms`` subtracts
from an invocation's engine-side duration.

The recorder is process-wide because the engine builds the tools from a
StreamFlow document and hands them no benchmark object; the harness
clears it before each window.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax


@dataclass(frozen=True)
class Body:
    step: str
    tag: Tuple[int, ...]
    start: float
    end: float


class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self._bodies: List[Body] = []      # lock: _lock
        self.annotate = False

    def add(self, body: Body):
        with self._lock:
            self._bodies.append(body)

    def take(self) -> List[Body]:
        """Every body recorded since the last take, in recording order."""
        with self._lock:
            out, self._bodies = self._bodies, []
        return out


RECORDER = Recorder()


def timed(step: str, fn: Callable[[Dict, dict], Dict]
          ) -> Callable[[Dict, dict], Dict]:
    """``fn`` as a tool body that records its own duration.

    The body ends when its outputs are ready on the device: JAX returns
    before the device has finished, and the device work is the tool's,
    not the engine's.  The outputs are returned as they are, device
    arrays included; moving them is the engine's data plane."""
    def run(inputs: Dict, ctx) -> Dict:
        return jax.block_until_ready(fn(inputs, ctx))

    def body(inputs: Dict, ctx) -> Dict:
        tag = tuple((ctx or {}).get("tag", ()))
        t0 = time.time()
        if RECORDER.annotate:
            with jax.profiler.TraceAnnotation(f"tool:{step}"):
                out = run(inputs, ctx)
        else:
            out = run(inputs, ctx)
        RECORDER.add(Body(step, tag, t0, time.time()))
        return out
    return body
