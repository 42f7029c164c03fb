"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Figure mapping:
  fig4    bench_construction          (fingerprints + hashing ablation)
  bank    bench_construction.run_bank (batched bank closure vs per-pattern
          loop @ P=4/16/64, writes BENCH_construction.json)
  fig5    bench_parallel_construction (parallel vs best sequential)
  fig6    bench_matching              (chunk-parallel matching scaling)
  census  bench_census                (PROSITE DFA -> SFA growth, §IV)
  kernels bench_kernels               (fingerprint pipeline micro)
  roofline bench_roofline             (LM dry-run cells, beyond-paper)
  multipattern bench_multipattern     (batched bank vs per-pattern loop, §IV)
  engine  bench_multipattern.run_engine_modes (auto vs forced Scanner modes,
          also writes BENCH_engine.json)
  speculative bench_speculative       (speculative vs enumeration in the
          blowup regime, writes BENCH_speculative.json)
  service bench_service               (cold vs warm start through the
          artifact store; coalesced vs sequential submits; writes
          BENCH_service.json)

``--smoke`` caps sizes/iterations (see benchmarks/_config.py) so CI can run
the whole harness as a smoke job without burning minutes on full figures.
``--profile`` wraps each module in ``jax.profiler.trace`` and writes one
trace directory per module under ``BENCH_traces/`` (the profiling harness:
open in TensorBoard/Perfetto to see where a bench's wall time went; the
bench-smoke CI job uploads the smoke-size traces as an artifact), turns on
``obs.configure(xla_annotations=True)`` so engine/construction spans land
on the same timeline, and writes a machine-readable per-module summary
(status, wall seconds, trace path) to ``BENCH_traces/summary.json``.

Every sweep also records each module's *metric footprint*: the delta of the
process-wide ``repro.obs`` registry snapshot across the module's run,
appended as one JSONL record per module to ``BENCH_metrics.jsonl`` next to
the BENCH JSONs (uploaded as a CI artifact) — construction rounds, cache
hit/miss counts, speculative repair totals per benchmark, correlating the
BENCH timings with what the code actually did. The log survives across
sweeps (so local before/after comparisons keep history) but is trimmed to
the newest ``METRICS_KEEP`` records at sweep start — it never grows
without bound.

``--serve-telemetry [PORT]`` additionally runs the sweep behind a live
:class:`repro.scanservice.TelemetryServer` (``PORT`` 0 = ephemeral) and
self-scrapes ``GET /metrics`` over real HTTP after every module,
re-parsing the exposition text with ``obs.parse_prometheus`` — a scrape
that fails to parse fails the sweep, which is exactly the guarantee the
CI bench-smoke job wants: the endpoint Prometheus would poll is validated
mid-sweep, under the same process load as the benchmarks themselves.
A benchmark module that fails to *import* (missing optional dep, broken
bench) is skipped with a warning — it costs its own suites, never the sweep.
But a sweep where **every** module failed to import ran nothing at all:
that exits 2, so CI's bench-smoke job cannot silently go green with zero
benchmarks run. Suites that import but *fail at runtime* exit 1. Either
way the sweep ends with a per-module summary table (status + wall time),
so a long CI log still answers "what ran, what broke, what was slow" at
a glance.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

#: Newest metric-footprint records kept in BENCH_metrics.jsonl across
#: sweeps (one record per module per sweep, so ~20 sweeps of history).
METRICS_KEEP = 200

#: (module, suite function names) — resolved one by one so an unimportable
#: module skips with a warning instead of aborting the whole sweep.
SUITES = [
    ("bench_construction", ("run", "run_bank")),
    ("bench_parallel_construction", ("run", "run_jax_engine")),
    ("bench_matching", ("run", "run_sfa_size_ladder")),
    ("bench_census", ("run", "run_synthetic_ladder")),
    ("bench_kernels", ("run",)),
    ("bench_roofline", ("run",)),
    ("bench_multipattern", ("run", "run_engine_modes")),
    ("bench_speculative", ("run",)),
    ("bench_service", ("run", "run_coalesced")),
]


def _resolve_suites() -> tuple:
    """-> ([(module name, callables)], skipped module names). Import errors
    warn and skip — the *caller* decides whether anything at all resolved."""
    modules = []
    skipped = []
    for mod_name, fn_names in SUITES:
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
        except Exception:
            skipped.append(mod_name)
            print(f"WARNING: skipping benchmarks.{mod_name} "
                  "(import failed):", file=sys.stderr)
            traceback.print_exc()
            continue
        modules.append((mod_name, [getattr(mod, fn) for fn in fn_names]))
    return modules, skipped


def _trim_metrics_log(path, keep: int = METRICS_KEEP) -> None:
    """Truncate the JSONL metrics log to its newest ``keep`` records.
    Torn or non-JSON lines (a killed sweep's last append) are dropped."""
    from repro.obs.aggregate import read_records

    if not path.exists():
        return
    records = read_records(path)
    if len(records) <= keep:
        return
    from repro import obs

    tmp = path.with_suffix(".jsonl.tmp")
    tmp.unlink(missing_ok=True)
    obs.write_jsonl(tmp, records[-keep:])
    tmp.replace(path)


def _scrape_metrics(url: str):
    """GET ``url``/metrics over real HTTP and re-parse the exposition
    text. -> parsed snapshot dict; raises on HTTP or parse failure."""
    from urllib.request import urlopen

    from repro import obs

    with urlopen(f"{url}/metrics", timeout=10) as resp:
        if resp.status != 200:
            raise RuntimeError(f"/metrics returned HTTP {resp.status}")
        text = resp.read().decode("utf-8")
    return obs.parse_prometheus(text)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down sizes/iterations (CI smoke job)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap each bench module in jax.profiler.trace, "
                         "writing one trace directory per module under "
                         "BENCH_traces/ (open with TensorBoard or Perfetto)")
    ap.add_argument("--serve-telemetry", nargs="?", const=0, default=None,
                    type=int, metavar="PORT",
                    help="serve /metrics over HTTP for the sweep's duration "
                         "(PORT omitted or 0 = ephemeral) and self-scrape + "
                         "parse it after every module; a scrape that fails "
                         "to parse fails the sweep")
    args = ap.parse_args()

    from pathlib import Path

    from benchmarks import _config
    from repro import obs
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        _config.set_smoke(True)

    repo_root = Path(__file__).resolve().parents[1]
    metrics_path = repo_root / "BENCH_metrics.jsonl"
    _trim_metrics_log(metrics_path)   # bounded history, not a fresh unlink

    telemetry = None
    if args.serve_telemetry is not None:
        from repro.scanservice import TelemetryServer

        telemetry = TelemetryServer(port=args.serve_telemetry).start()
        print(f"telemetry: serving {telemetry.url}/metrics", file=sys.stderr)

    trace_root = None
    if args.profile:
        trace_root = repo_root / "BENCH_traces"
        trace_root.mkdir(exist_ok=True)
        # Bridge obs spans onto the XLA profiler's host timeline so the
        # engine/construction spans show up inside each module's trace.
        obs.configure(xla_annotations=True)

    modules, skipped = _resolve_suites()
    if not modules:
        print(f"ERROR: all {len(skipped)} benchmark modules failed to "
              "import; no benchmarks were run", file=sys.stderr)
        sys.exit(2)

    print("name,us_per_call,derived")

    def emit(name: str, us: float, derived: str = "") -> None:
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()

    summary = [(name, "SKIPPED (import)", 0.0) for name in skipped]
    failures = 0
    for mod_name, suites in modules:
        status = "ok"
        before = obs.snapshot()
        t0 = time.perf_counter()

        def run_suites():
            nonlocal failures, status
            for suite in suites:
                try:
                    suite(emit)
                except Exception:  # keep the harness going; report at the end
                    failures += 1
                    status = "FAILED"
                    traceback.print_exc()

        if trace_root is not None:
            import jax

            # One trace directory per module: a whole-sweep trace would be
            # unreadably long, and a failed module still leaves the others'
            # traces intact.
            with jax.profiler.trace(str(trace_root / mod_name)):
                run_suites()
        else:
            run_suites()
        wall = time.perf_counter() - t0
        if telemetry is not None:
            # Mid-sweep scrape over real HTTP: the exposition text the
            # endpoint serves under benchmark load must stay parseable.
            try:
                _scrape_metrics(telemetry.url)
            except Exception:
                failures += 1
                status = "FAILED (scrape)"
                traceback.print_exc()
        summary.append((mod_name, status, wall))
        # The module's metric footprint: what the registry counted while it
        # ran.
        obs.write_jsonl(metrics_path, [obs.snapshot_record(
            obs.snapshot_delta(before, obs.snapshot()), label=mod_name,
        )])

    width = max(len(name) for name, _, _ in summary)
    print("\n== sweep summary ==")
    for name, status, wall in sorted(summary, key=lambda r: -r[2]):
        print(f"{name:<{width}}  {status:<16} {wall:8.1f}s")
    sys.stdout.flush()
    if trace_root is not None:
        import json

        # Machine-readable sweep outcome next to the traces: what ran, how
        # long, where its trace went — the profiling run's index file.
        (trace_root / "summary.json").write_text(json.dumps({
            "smoke": _config.SMOKE,
            "modules": [
                {"module": name, "status": status, "wall_s": wall,
                 "trace": (str(trace_root / name)
                           if (trace_root / name).is_dir() else None)}
                for name, status, wall in summary
            ],
        }, indent=1))
    if telemetry is not None:
        telemetry.close()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
