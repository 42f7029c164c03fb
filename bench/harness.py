"""One run of one cell: set-up, the measured window, the check, the metrics.

The configuration's deployment kind names the driver,
``bench/drivers/<kind>.py`` (a corpus job's closed loop, the web service's
open loop), and the traffic file names the generator of its inputs,
``bench/generators/<name>.py``. Drivers only hand the program generated
inputs; everything they compare against comes from :mod:`bench.reference`.
Of the configuration the harness reads only ``deployment`` and ``checks``;
what else a run has to say comes from the driver's window (``info`` lines).
"""

from __future__ import annotations

import json
import os
import shutil
import time
from collections import deque
from pathlib import Path

from . import spec
from .trace_reduce import WINDOW, reduce_trace

OUT = spec.BENCH / "out"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# --------------------------------------------------------------------------
# Process and device
# --------------------------------------------------------------------------


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
    uptime = float(Path("/proc/uptime").read_text().split()[0])
    return uptime - start / ticks


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform!r} device(s)")
    return devices[:n]


class CompileEvents:
    """Counts XLA compilations and persistent-cache loads while armed."""

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.backend = self.hits = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
            self.compile_s += secs

    def arm(self):
        self.backend = self.hits = 0
        self.compile_s = 0.0
        self.armed = True

    def disarm(self) -> dict:
        self.armed = False
        return {"compiles": self.backend - self.hits, "cache_loads": self.hits,
                "seconds": self.compile_s}


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------


def _enlarge_span_ring(obs, size: int = 1 << 17) -> None:
    tr = obs.tracer
    with tr._lock:
        tr._spans = deque(tr._spans, maxlen=size)


def _spans_in(obs, t0: float, t1: float) -> list:
    return [s.to_json() | {"t_end": s.t_end}
            for s in obs.tracer.recent_spans(limit=1 << 17)
            if s.t_start >= t0 and s.t_start <= t1]


def _start_profiler(path: Path) -> None:
    import jax

    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)


def persistent_cache(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def _xplane(path: Path) -> Path:
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"no trace written under {path}")
    return found[-1]


def execute(name: str, seed: int, seconds: float, trace: bool, *,
            control: bool = False, require_chip: bool = True,
            root: Path = spec.ROOT, out_root: Path = OUT,
            overrides=None) -> dict:
    """One run of cell ``name``. -> {"result": result line, "info": lines}.
    Outputs go to ``out_root/<name>``. ``overrides(cell)`` may edit the
    loaded cell in place (tests only)."""
    c = spec.cell(name, root)
    if overrides is not None:
        overrides(c)
    n_chips = c["workload"]["chips"]
    devices = require_chips(n_chips) if require_chip else None

    import jax

    from repro import obs
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices or jax.devices()[:n_chips]
    events = CompileEvents()
    _enlarge_span_ring(obs)
    out = Path(out_root) / name
    out.mkdir(parents=True, exist_ok=True)
    driver = spec.driver(c["config"]["deployment"]["kind"], Path(c["bench"]))(
        c, seed, seconds, out)
    info = []

    events.arm()
    driver.setup()
    setup_s = process_age_s()
    setup_compiles = events.disarm()
    trace_dir = out / "trace"
    # Inside the window a shape met for the first time compiles on every
    # run: with the persistent cache on it would load from whatever earlier
    # runs in this checkout left there, and the cell would drift with them.
    persistent_cache(False)
    if trace:
        obs.configure(xla_annotations=True)
        _start_profiler(trace_dir)
    events.arm()
    with jax.profiler.TraceAnnotation(WINDOW):
        w = driver.window()
    compiles = events.disarm()
    persistent_cache(True)
    if trace:
        jax.profiler.stop_trace()
        obs.configure(xla_annotations=False)
    spans = _spans_in(obs, w["t0"], w["t_end"])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    driver.close()
    t_check = time.perf_counter()
    compared, check_info = driver.check(control)
    check_s = time.perf_counter() - t_check

    red = reduce_trace(_xplane(trace_dir)) if trace else None
    ctx = {
        "window": w, "setup_s": setup_s, "spans": spans,
        "compile_events": compiles, "trace": red, "config": c["config"],
        "traffic": c["traffic"], "seconds": seconds,
        "device_kind": devices[0].device_kind,
    }
    metrics = {}
    for m in (c["per_layer"] if trace else c["end_to_end"]):
        v = spec.reader(m["name"], Path(c["bench"]))(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    limits = c["config"]["checks"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in compared.items()}
    correct = all(v <= limits[k] for k, v in compared.items())
    correct = correct and w["failed"] == 0
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = red["breakdown"]
    result["checks"] = checks

    info += [
        f"device: {d0.platform} {d0.device_kind} x {len(devices)}",
        f"setup: {setup_s:.3f} s (process start to the first timed request); "
        f"{setup_compiles['compiles']} XLA compiles, "
        f"{setup_compiles['cache_loads']} persistent-cache loads, "
        f"{setup_compiles['seconds']:.3f} s",
        f"window: {w['t_end'] - w['t0']:.3f} s; compiles inside it: "
        f"{compiles['compiles']} XLA compiles, {compiles['cache_loads']} "
        f"persistent-cache loads, {compiles['seconds']:.3f} s",
        f"work: attempted {w['attempted']}, succeeded "
        f"{w['attempted'] - w['failed']}, failed {w['failed']}",
        *w.get("info", ()),
    ]
    if "generator_late_s" in w:
        g = w["generator_late_s"]
        info.append(f"open loop: {len(w['requests'])} requests sent; "
                    f"generator late by median {g['median'] * 1e3:.3f} ms, "
                    f"max {g['max'] * 1e3:.3f} ms; scheduler {w['scheduler']}")
    if "in_window_generation_s" in w:
        info.append(f"closed loop: {len(w['shards'])} shards, "
                    f"{w['residues']} residues; corpus generated inside the "
                    f"window: {w['in_window_generation_s']:.3f} s")
    info.append(f"check: {check_info}, {check_s:.3f} s")
    info += [f"compared {k}: {v['value']} (limit {v['limit']})"
             for k, v in checks.items()]
    (out / "last_run.json").write_text(json.dumps(
        {"result": result, "info": info,
         "window": {k: v for k, v in w.items()
                    if k not in ("requests", "shards")}},
        indent=1, default=str))
    return {"result": result, "info": info, "window": w}
