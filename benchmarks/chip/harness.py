"""What a cell's driver (``kinds/<kind>.py``) is given and gives back."""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time
from dataclasses import dataclass, field

from benchmarks.chip import trace as trace_mod


@dataclass
class Check:
    """One number compared with the plain reference, and its limit: the
    run is correct only where ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit     # False for NaN


@dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float                  # perf_counter at process start
    _tracedir: str | None = None
    longest: dict = field(default_factory=dict)   # span → longest s
    gc_pauses: list = field(default_factory=list)  # (generation, s)

    @property
    def seed32(self) -> int:
        """The seed as the program's 31-bit seeds take it."""
        return self.seed % (2 ** 31 - 1)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: in the trace of a ``--trace 1`` run, and its
        longest pass on the host's clock in :attr:`longest`."""
        t0 = time.perf_counter()
        if self.trace:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        dt = time.perf_counter() - t0
        if dt > self.longest.get(name, 0.0):
            self.longest[name] = dt

    @contextlib.contextmanager
    def window(self):
        """The measured window: traced (under the span ``window``) when
        tracing is on. Python's collections of garbage inside it are
        timed into :attr:`gc_pauses`."""
        started = []

        def watch(phase, info):
            if phase == "start":
                started.append(time.perf_counter())
            elif started:
                self.gc_pauses.append(
                    (info["generation"], time.perf_counter() - started.pop()))

        gc.callbacks.append(watch)
        try:
            if not self.trace:
                yield
                return
            import jax

            self._tracedir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._tracedir)
            try:
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    yield
            finally:
                jax.profiler.stop_trace()
        finally:
            gc.callbacks.remove(watch)

    def host_report(self) -> str:
        """One line on the host's stalls in the window: the longest pass
        of each span, and the collections of garbage."""
        spans = ", ".join(f"{k} {v:.4f}" for k, v in sorted(
            self.longest.items()))
        worst = max(self.gc_pauses, key=lambda g: g[1], default=(None, 0.0))
        return (f"host longest span s: {spans}; gc collections "
                f"{len(self.gc_pauses)}, longest {worst[1]:.4f} s "
                f"(generation {worst[0]})")

    def read_trace(self):
        """The reduced trace of the window (None when not traced); the
        trace files are deleted once read."""
        if self._tracedir is None:
            return None
        try:
            ids = {d.id for d in self.devices}
            return trace_mod.load(trace_mod.find_xplane(self._tracedir), ids)
        finally:
            shutil.rmtree(self._tracedir, ignore_errors=True)
            self._tracedir = None


@dataclass
class Outcome:
    end_to_end: dict                # metric name → value
    setup_s: float
    attempted: int
    failed: int
    checks: list                    # [Check]
    memory_peak_bytes: int | None
    counters: dict = field(default_factory=dict)   # for the metric readers
    trace: object = None            # trace.Trace of the window

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0
