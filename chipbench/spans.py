"""Host spans and counters around the calls the served path makes.

The benchmark times layers from outside the program: it replaces module
attributes that the sweep path looks up at call time with wrappers that
count calls, add up host seconds and, while a trace is on, mark the
call as a named span in the profiler's trace.  ``run_packed`` is also
where the timed path's answers are captured: the packed inputs handed to
the device program and the final cell state it returned.
"""

from __future__ import annotations

import functools
import importlib
import inspect
import time
from contextlib import ExitStack, nullcontext

import numpy as np

MODULE = "repro.accel.sweep_exec"
SYNTH, RUN = "synthesize_cell", "run_packed"
SPAN_PREFIX = "chipbench."


class Recorder:
    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.calls = {SYNTH: 0, RUN: 0}
        self.seconds = {SYNTH: 0.0, RUN: 0.0}
        self.rows_steps = 0
        self.captured: list[tuple[dict, dict]] = []
        self.missing: list[str] = []
        self._stack: ExitStack | None = None

    def span(self, name: str):
        if not self.tracing:
            return nullcontext()
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(SPAN_PREFIX + name)

    def reset(self) -> None:
        """Forget what the warm-up recorded."""
        self.calls = dict.fromkeys(self.calls, 0)
        self.seconds = dict.fromkeys(self.seconds, 0.0)
        self.rows_steps = 0
        self.captured = []

    def _timed(self, name, fn, *args, **kwargs):
        with self.span(name):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.seconds[name] += time.perf_counter() - t0
                self.calls[name] += 1

    def _wrap_synth(self, fn):
        @functools.wraps(fn)
        def synthesize_cell(*args, **kwargs):
            return self._timed(SYNTH, fn, *args, **kwargs)
        return synthesize_cell

    def _wrap_run(self, fn):
        t_default = inspect.signature(fn).parameters.get("t_chunk")
        t_default = None if t_default is None else t_default.default

        @functools.wraps(fn)
        def run_packed(params, *args, **kwargs):
            t_chunk = kwargs.get("t_chunk", t_default)
            rows = int(np.asarray(params["arrival"]).shape[0])
            chunks = [0]
            outer = kwargs.get("on_chunk")

            def on_chunk(*a):
                chunks[0] += 1
                if outer is not None:
                    outer(*a)

            kwargs["on_chunk"] = on_chunk
            final = self._timed(RUN, fn, params, *args, **kwargs)
            if isinstance(t_chunk, int):
                self.rows_steps += rows * t_chunk * chunks[0]
            self.captured.append((params, final))
            return final
        return run_packed

    def __enter__(self):
        mod = importlib.import_module(MODULE)
        self._stack = ExitStack()
        for name, wrap in ((SYNTH, self._wrap_synth), (RUN, self._wrap_run)):
            orig = getattr(mod, name, None)
            if orig is None:
                self.missing.append(f"{MODULE}.{name}")
                continue
            setattr(mod, name, wrap(orig))
            self._stack.callback(setattr, mod, name, orig)
        return self

    def __exit__(self, *exc):
        self._stack.close()
        return False
