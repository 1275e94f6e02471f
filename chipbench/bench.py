"""One run of one benchmark cell.

``run()`` does, in order:

1. finds the cell in ``BENCHMARK.json``, its configuration file, its
   traffic mix (``mixes/<traffic>.json``), its limits
   (``limits/<cell>.json``) and the readers of its per-layer metrics
   (``readers/<metric>.py``), all by name;
2. checks the device: a TPU listed in ``peaks.json``, as many chips as
   the cell asks for;
3. set-up: one warm-up matrix of the cell's own shape on seeds outside
   the window, so every program the window runs is compiled (or loaded
   from the persistent cache) before it;
4. the window: a closed loop of fresh matrices through
   ``repro.scenarios.worker.run_worker(compute="accel")`` with the
   command line's defaults, each into a private store in a temporary
   directory, its results read back from the store; the window ends
   with the first matrix that finishes at or after ``seconds``;
5. the reference over every cell of the window, and the verdict.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import judge
import spans
import trace_reduce
import traffic

#: ``python -m repro.scenarios worker --compute accel``'s defaults.
WORKER_DEFAULTS = {"ttl": 30.0, "renew_every": None, "timeout": 600.0,
                   "max_retries": 2, "retry_backoff": 0.5, "poll": 0.5}
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """The run cannot measure: no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Catalog:
    """Everything of a cell, found by name under a benchmark root."""

    def __init__(self, root: Path, workload: str):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        entry = configs[self.cell["config"]]
        self.config = json.loads((self.root / entry["file"]).read_text())
        home = self.root / self.bench["paths"][0]
        self.mix = json.loads(
            (home / "mixes" / f"{self.cell['traffic']}.json").read_text())
        traffic.check_mix(self.mix)
        self.limits = json.loads(
            (home / "limits" / f"{workload}.json").read_text())
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in self.bench["per_layer"]
                          if workload in m.get("workloads", [workload])]
        self.readers = {m["name"]: home / "readers" / f"{m['name']}.py"
                        for m in self.per_layer}
        self.peaks = json.loads((home / "peaks.json").read_text())

    def reader(self, name: str):
        path = self.readers[name]
        spec = importlib.util.spec_from_file_location(
            "chipbench_reader_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def device_info(cat: Catalog, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    chips = int(cat.cell["chips"])
    if require_chip:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is {dev.platform!r}")
        if len(devices) < chips:
            raise BenchError(f"{len(devices)} chip(s), the cell needs {chips}")
        if dev.device_kind not in cat.peaks:
            raise BenchError(f"device kind {dev.device_kind!r} is not in "
                             "peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts programs lowered and compiled while it is open."""

    def __enter__(self):
        import jax.monitoring

        self.counts = dict.fromkeys(COMPILE_EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def run_matrix(cat: Catalog, seed: int, phase: int, index: int) -> tuple:
    """Submit one matrix, wait for it, read its results back."""
    from repro.scenarios.store import open_store
    from repro.scenarios.worker import run_worker

    sweep = traffic.sweep_for(cat.config, cat.mix, seed, phase, index,
                              name=cat.cell["name"])
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        store = str(Path(tmp) / "matrix.jsonl")
        run_worker(sweep, store, compute="accel",
                   accel_batch=int(cat.mix.get("accel_batch", 32)),
                   **WORKER_DEFAULTS)
        stored = open_store(store).load()
    cells = sweep.expand()
    records = {cid: stored.get((cid, spec.spec_hash())) for cid, spec in cells}
    ident = {cid: traffic.cell_of_spec(spec) for cid, spec in cells}
    return records, ident


def window_rate(cells: int, window_s: float) -> float:
    """Cells completed and correct per second of the whole window."""
    return cells / window_s


def window_done(elapsed: float, seconds: float) -> bool:
    """The window ends with the first matrix that finishes at or after
    ``seconds``."""
    return elapsed >= seconds


def window(cat: Catalog, rec: spans.Recorder, seed: int, seconds: float):
    """The measured window: fresh matrices until one finishes at or after
    ``seconds``.  Returns ([(index, records, identities)], seconds)."""
    matrices = []
    t0 = time.perf_counter()
    with rec.span("window"):
        while True:
            with rec.span("driver"):
                records, ident = run_matrix(cat, seed, traffic.WINDOW,
                                            len(matrices))
            matrices.append((len(matrices), records, ident))
            if window_done(time.perf_counter() - t0, seconds):
                break
    return matrices, time.perf_counter() - t0


def verdict(cat: Catalog, rec: spans.Recorder, seed: int, matrices) -> dict:
    """The reference over every cell of the window, and the judgement."""
    expected, records = [], {}
    for index, recs, ident in matrices:
        cells = traffic.matrix_cells(cat.mix, seed, traffic.WINDOW, index)
        by_ident = {traffic.cell_identity(c): c for c in cells}
        if set(by_ident) != set(ident.values()):
            raise BenchError("the program's sweep and the mix's cells differ")
        refs = judge.reference_cells(
            cat.config, [by_ident[ident[cid]] for cid in ident])
        expected += list(zip(ident, refs))
        records.update(recs)
    out = judge.judge(expected, records, rec.captured, cat.limits)
    out["attempted"] = len(expected)
    return out


def per_layer(cat: Catalog, rec: spans.Recorder, red: dict, cells: int,
              window_s: float) -> dict:
    obs = {"cells": cells, "window_s": window_s,
           "host": {k: {"calls": rec.calls[k], "seconds": rec.seconds[k]}
                    for k in rec.calls},
           "rows_steps": rec.rows_steps, "trace": red}
    out = {}
    for m in cat.per_layer:
        value = cat.reader(m["name"])(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float | None = None, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cat = Catalog(root, workload)
    device = device_info(cat, require_chip)
    rec = spans.Recorder(tracing=trace)
    with CompileCounter() as counter, rec, \
            tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        if trace and rec.missing:
            raise BenchError(f"the program has no {rec.missing}")
        run_matrix(cat, seed, traffic.WARMUP, 0)
        setup_s = time.perf_counter() - t_start
        log(f"setup_s: {setup_s:.3f}")
        rec.reset()
        before = counter.snapshot()
        if trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        matrices, window_s = window(cat, rec, seed, seconds)
        if trace:
            jax.profiler.stop_trace()
        after = counter.snapshot()
        compiles = {k: after[k] - before[k] for k in COMPILE_EVENTS}
        log(f"compilations in window: {sum(compiles.values())} {compiles}")
        device["memory_peak_bytes"] = memory_peak(int(cat.cell["chips"]))
        red = None
        if trace:
            path = next(Path(tdir).rglob("*.xplane.pb"))
            red = trace_reduce.reduce_events(
                trace_reduce.load_events(str(path)))

    t_ref = time.perf_counter()
    v = verdict(cat, rec, seed, matrices)
    log(f"reference: {v['attempted']} cells in "
        f"{time.perf_counter() - t_ref:.3f} s")
    log(f"window: {len(matrices)} matrices, {v['attempted']} cells, "
        f"{window_s:.3f} s")
    good = v["attempted"] - v["failed"]
    result = {"correct": v["correct"], "attempted": v["attempted"],
              "failed": v["failed"], "metrics": {}, "device": device}
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["metrics"] = per_layer(cat, rec, red, good, window_s)
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = {"cells_per_s": window_rate(good, window_s),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cat.end_to_end}
    for line in judge.check_lines(v["numbers"], cat.limits):
        log(line)
    result["checks"] = judge.check_json(v["numbers"], cat.limits)
    return result


def json_line(result: dict) -> str:
    return json.dumps(result, allow_nan=False)
