"""What every cell's runner shares: the device check, the compile cache and
the compile counter, diagnostics, the profiler window, per-layer
readers and the result line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager

from . import spec

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class NoDevice(Exception):
    """No chip of the kind and count the cell needs."""


def peaks_for(kind: str) -> dict:
    with open(os.path.join(spec.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise NoDevice(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


class Context:
    def __init__(self, args, cell, t_start: float):
        self.args, self.cell, self.t_start = args, cell, t_start
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.events: list = []      # (phase, kind, name, seconds)
        self.phase = "setup"
        self._closers: list = []
        self.device = None
        self.dev_info: dict = {}
        self.window_trace = None
        self.peaks = None

    # ------------------------------------------------------ diagnostics
    def elapsed(self) -> float:
        return time.perf_counter() - self.t_start

    def emit(self, phase: str, **fields) -> None:
        row = {"phase": phase, "t_s": round(self.elapsed(), 3), **fields}
        print(json.dumps(row, default=str), file=sys.stderr, flush=True)

    def on_close(self, fn) -> None:
        self._closers.append(fn)

    def close(self) -> None:
        while self._closers:
            self._closers.pop()()

    # ----------------------------------------------------------- device
    def open_device(self):
        """JAX on the chip: a TPU, as many as the cell asks for, the
        persistent compile cache at a fixed path inside the checkout
        (or where JAX_COMPILATION_CACHE_DIR says), the compile counter
        and the program's spans on only for a traced run."""
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(spec.ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # Op locations name only the innermost frame, so that a program's
        # cache key does not move with the harness's own line numbers.
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        try:
            devs = jax.devices()
        except RuntimeError as exc:
            raise NoDevice(f"JAX found no device: {exc}") from None
        dev = devs[0]
        if dev.platform != "tpu":
            raise NoDevice(f"no TPU: JAX found {dev.platform!r}; "
                           "there is no CPU fallback")
        if len(devs) < self.cell.chips:
            raise NoDevice(f"the cell needs {self.cell.chips} chips, "
                           f"JAX sees {len(devs)}")
        self.peaks = peaks_for(dev.device_kind)
        self.device = dev
        self.dev_info = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": self.cell.chips}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        from lighthouse_tpu.common import tracing

        tracing.set_enabled(self.trace)
        self.emit("device", **self.dev_info, visible=len(devs),
                  compile_cache=jax.config.jax_compilation_cache_dir)
        return dev

    def _on_event(self, event: str, seconds: float, **kw) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind:
            self.events.append((self.phase, kind,
                                str(kw.get("fun_name", "?")), seconds))

    def compile_summary(self, phase: str) -> dict:
        out: dict = {}
        for ph, kind, name, s in self.events:
            if ph != phase:
                continue
            row = out.setdefault(kind, {"n": 0, "seconds": 0.0, "longest": []})
            row["n"] += 1
            row["seconds"] += s
            row["longest"].append((name, round(s, 3)))
        for row in out.values():
            row["seconds"] = round(row["seconds"], 3)
            row["longest"] = sorted(row["longest"], key=lambda r: -r[1])[:6]
        return out

    def window_compiles(self) -> int:
        return sum(1 for ph, kind, _, _ in self.events
                   if ph == "window" and kind in ("trace", "lower", "compile"))

    def memory_peak(self) -> int | None:
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    # ----------------------------------------------------------- window
    @contextmanager
    def window(self):
        """The measured window: nothing may compile inside it. What
        set-up left alive (lowering's millions of objects) is collected
        and frozen first, so that the window's own collections scan only
        the window's objects."""
        import gc

        gc.collect()
        gc.freeze()
        gc.enable()
        self.phase = "window"
        try:
            with self.span("bench/window"):
                yield
        finally:
            self.phase = "after"

    @contextmanager
    def traced(self):
        """A profiler trace of the calls inside (a traced run only);
        ``self.window_trace`` then holds its reduction."""
        import jax

        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with self.span("bench/traced"):
                yield
        finally:
            jax.profiler.stop_trace()
            from . import trace as tr

            self.window_trace = tr.reduce_file(tr.find_xplane(tmp))
            shutil.rmtree(tmp, ignore_errors=True)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    # ----------------------------------------------------------- result
    def result(self, *, correct: bool, attempted: int, failed: int,
               end_to_end: dict, facts: dict, checks: dict) -> dict:
        """The result line. ``end_to_end`` maps metric names to values;
        ``facts`` is what the per-layer readers read."""
        metrics = {}
        if self.trace:
            facts = dict(facts, trace=self.window_trace, peaks=self.peaks)
            for m in self.cell.per_layer:
                value = m.reader.read(facts)
                if value is not None:
                    metrics[m.name] = {"value": value, "unit": m.unit}
        else:
            for m in self.cell.end_to_end:
                if m.name in end_to_end:
                    metrics[m.name] = {"value": end_to_end[m.name],
                                       "unit": m.unit}
        device = dict(self.dev_info,
                      memory_peak_bytes=facts.get("memory_peak_bytes"))
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed), "metrics": metrics, "device": device}
        if self.trace and self.window_trace is not None:
            t = self.window_trace
            device["busy_s"] = t["busy_s"]
            device["window_s"] = t["window_s"]
            out["breakdown"] = {"device_ops": t["top_ops"],
                                "idle_gaps": t["idle_gaps"]}
        out["checks"] = checks
        return out

    @staticmethod
    def print_checks(result: dict) -> None:
        for name, row in result["checks"].items():
            print(f"check {name} {row['value']} limit {row['limit']}",
                  file=sys.stderr, flush=True)
