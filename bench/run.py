"""One run of one benchmark cell of the deadline-scheduled TPC-H session path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/``), its traffic mix
(``bench/traffic/``) and its metrics (``bench/metrics/<name>.py``, each a
``read(run)`` function) are found by name through ``BENCHMARK.json``.  The
run makes its data from ``--seed``, calibrates the program's cost models,
warms up, measures for ``--seconds`` of the rounds' own time, checks every
answer due in the window against the plain reference (round by round, with
the clock stopped between rounds), and prints one JSON object as the last
line of standard output.  ``--trace 1`` profiles the measured window and
reports the per-layer metrics instead of the end-to-end ones.

It needs a TPU: on any other platform it prints the platform and exits
non-zero with no result.  ``--rehearse`` is the one exception, a CPU
rehearsal at a thousandth of the scale (Pallas interpreter; XLA on a mesh)
that prints no metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

REHEARSAL_SCALE = 0.001


@dataclasses.dataclass
class RunView:
    """What a metric reader reads."""

    lo: float                 # measured window on the perf_counter clock
    hi: float
    setup_s: float
    rec: object               # proxy.Recorder
    windows: List[object]     # drive.Window, due in the measured window
    chips: int
    device_kind: str
    busy: Optional[List[list]] = None   # per chip: disjoint busy intervals


def rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize()


class CompileClock:
    """Compilations JAX reports, and their seconds (a persistent cache hit
    counts with its retrieval time)."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.count += 1


class GcClock:
    """Python's GC passes, as ``gc.callbacks`` reports them:
    ``(start, seconds, generation)``."""

    def __init__(self):
        self.passes, self._start = [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.passes.append((self._start, time.perf_counter() - self._start,
                                info["generation"]))
            self._start = None


def minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def print_host(gc_clock: GcClock, faults: int, rss: tuple,
               live: List[tuple]) -> None:
    """Minor page faults of the rounds and the checks between them,
    resident bytes at the window's start and end, peak RSS, and the GC
    passes of the measured time: a host stall becomes a reading.  A
    sandboxed host may not count page faults (``ru_minflt`` reads 0); the
    resident bytes show the heap's growth there."""
    inside = [p for p in gc_clock.passes
              if any(s <= p[0] < e for s, e in live)]
    gens = [sum(1 for p in inside if p[2] == g) for g in (0, 1, 2)]
    maxrss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(f"host in the window: minor_faults={faults} "
          f"rss_bytes={rss[0]}->{rss[1]} max_rss_bytes={maxrss_kb * 1024} "
          f"gc_seconds={sum(p[1] for p in inside)} gc_passes_by_gen={gens} "
          f"longest_gc_s={max((p[1] for p in inside), default=0.0)}",
          file=sys.stderr)


def print_stalls(rec, lo: float, hi: float) -> None:
    """Where the serving loop lost time in [lo, hi]: the latest calls and
    the longest span of each name."""
    late = sorted(((d, t - lo) for d, t in zip(rec.lateness, rec.late_at)
                   if lo <= t <= hi), reverse=True)[:3]
    print(f"latest calls (late_s, at_s): {late}", file=sys.stderr)
    for name in ("batch", "decide", "finalize"):
        longest = max(((e - s, s - lo) for n, s, e in rec.spans
                       if n == name and lo <= s <= hi), default=(0.0, 0.0))
        print(f"longest {name} (s, at_s): {longest}", file=sys.stderr)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny scale; prints no metric")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the traffic's ticks per second (knee sweep)")
    return ap.parse_args(argv)


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, view: RunView):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(view)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    spec, cell, config, traffic = load_cell(args.workload)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device}", file=sys.stderr)
    if args.rehearse != (device["platform"] != "tpu"):
        print(f"bench: needs a TPU (or --rehearse on a CPU); jax found "
              f"platform {device['platform']!r}", file=sys.stderr)
        return 2
    if device["count"] < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips; jax found "
              f"{device['count']}", file=sys.stderr)
        return 2

    import device_trace
    import drive
    import readings
    import reference
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if args.rehearse:
        scale = REHEARSAL_SCALE
        backend = "xla" if config["shard_across"] > 1 else "interpret"
    else:
        scale, backend = 1.0, "pallas"
    run = drive.Cell(config, traffic, args.seed, scale, backend, args.rate)
    ref_s = time.perf_counter()
    check = reference.Check(reference.Reference(config, run.pool, scale))
    ref_s = time.perf_counter() - ref_s
    print(f"reference set-up, left out of setup_s: seconds={ref_s}",
          file=sys.stderr)
    run.calibrate()
    run.warm_up()
    gc_clock = GcClock()
    profile = (device_trace.Profile(f"{args.workload}-{args.seed}")
               if args.trace else None)
    if profile:
        profile.start()
    c_sec, c_num = clock.seconds, clock.count
    gc.callbacks.append(gc_clock)
    faults, rss = minor_faults(), rss_bytes()
    lo, hi = run.measure(args.seconds, check)
    faults, rss = minor_faults() - faults, (rss, rss_bytes())
    gc.callbacks.remove(gc_clock)
    if profile:
        profile.stop()
    print(f"compiles in the window: count={clock.count - c_num} "
          f"seconds={clock.seconds - c_sec}", file=sys.stderr)
    used = devices[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)
    late = run.rec.lateness
    print(f"pacing: late calls={len(late)} max_late_s={max(late, default=0.0)}",
          file=sys.stderr)
    print_stalls(run.rec, lo, hi)

    view = RunView(lo=lo, hi=hi, setup_s=lo - T_START - ref_s, rec=run.rec,
                   windows=[w for w in run.windows if lo <= w.close <= hi],
                   chips=cell["chips"], device_kind=device["kind"])
    live = readings.measured(view)
    print_host(gc_clock, faults, rss, live)
    result = {}
    if profile:
        ops, layout = profile.read()
        device_trace.print_layout(layout)
        view.busy = [device_trace.union(ops.get(d.id, [])) for d in used]
        device["busy_s"] = sum(device_trace.covered_in(b, live)
                               for b in view.busy) / len(used)
        device["window_s"] = sum(e - s for s, e in live)
        result["breakdown"] = device_trace.breakdown(ops, run.rec.spans, live)

    metrics = {}
    for m in cell_metrics(spec, args.workload, bool(args.trace)):
        value = read_metric(m["name"], view)
        if value is None:
            continue
        if args.rehearse:
            print(f"rehearsal reading (CPU, not a device metric): "
                  f"{m['name']}={value}", file=sys.stderr)
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    failed = sum(1 for w in view.windows if w.emitted is None)
    n_late = sum(1 for w in view.windows
                 if w.emitted is not None and w.emitted > w.deadline)
    print(f"deadlines: answered late={n_late} never={failed} "
          f"of {len(view.windows)}", file=sys.stderr)
    limits, numbers = config["limits"], check.numbers
    correct = reference.verdict(numbers, limits) and bool(view.windows)
    out = {"correct": correct, "attempted": len(view.windows), "failed": failed,
           "metrics": metrics, "device": device, **result}
    if args.rehearse:
        out["rehearsal"] = True
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in numbers.items()}
    for k, v in numbers.items():
        print(f"check {k}: value={v} limit={limits[k]}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
