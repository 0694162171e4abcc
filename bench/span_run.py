"""One run of one benchmark cell with the program's own tracer on
(``repro.tracing``): where the host's time goes, by program span.

    python3 bench/span_run.py --workload <cell> --seed <n> --seconds <s> [--profile 0|1]

The cell is set up, warmed and measured as ``run.py`` does it, with
``repro.tracing`` enabled over the measured window.  With ``--profile 1``
(the default) the profiler traces the window too, and the last line of
standard output is a JSON object with the program-span readings: per batch
``policy_ms``, ``observe_ms``, ``concat_ms``, ``extract_ms``,
``transfer_ms``, ``dispatch_ms``, ``spill_ms`` and ``executor_batch_ms``;
``merge_ms`` per window finalized; ``scope_share`` (per device scope, e.g.
``segagg.pad``: % of device time inside ``executor.batch`` spans in ops of
that scope; a scope no op carries is left out); the proxy's ``batch_ms``
and ``decide_ms`` beside them; ``idle_by_span``; the longest own time of
each span name, less the proxy's pacing waits (host stalls); the cell's
own per-layer metrics; ``correct`` and the checks.  With ``--profile 0``
only the tracer is on, and the line holds the end-to-end metrics: what
tracing costs when on, against ``run.py --trace 0`` on the same seed.
Like ``run.py`` it needs a TPU, save for ``--rehearse`` (CPU, a
thousandth of the scale).
"""
from __future__ import annotations

import argparse
import json
import sys

import device_trace
import run  # bench/run.py: puts src/ on the path


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def readings(spans, view, ops, scopes) -> dict:
    """The program-span readings of one traced window."""
    import program_spans as ps
    import readings as rd

    lo, hi = view.lo, view.hi
    n = len(rd.batches(view))
    out = {}
    for key, name in (("policy_ms", "policy.decide"),
                      ("observe_ms", "session.observe"),
                      ("concat_ms", "prep.concat"),
                      ("extract_ms", "prep.extract"),
                      ("transfer_ms", "transfer"),
                      ("dispatch_ms", "kernel.dispatch"),
                      ("spill_ms", "spill"),
                      ("executor_batch_ms", "executor.batch")):
        out[key] = ps.per_count_ms(spans, name, n, lo, hi)
    finals = len(ps.spans_named(spans, "executor.finalize", lo, hi))
    out["merge_ms"] = ps.per_count_ms(spans, "finalize.merge", finals, lo, hi)
    out["batch_ms"] = 1e3 * sum(e - s for s, e in rd.spans(view, "batch")) / max(n, 1)
    out["decide_ms"] = 1e3 * view.rec.span_seconds("decide", lo, hi) / max(n, 1)
    if ops is not None:
        inside = sorted((s[1], s[2]) for s in
                        ps.spans_named(spans, "executor.batch", lo, hi))
        out["scope_share"] = {}
        for scope in ps.SCOPES:
            shares = [ps.scope_share(ops[d], scopes.get(d, []), scope, inside)
                      for d in ops]
            shares = [x for x in shares if x is not None]
            if shares and any(x > 0 for x in shares):
                out["scope_share"][scope] = sum(shares) / len(shares)
        busy = device_trace.union([iv for evs in ops.values() for iv in evs])
        out["idle_by_span"] = ps.idle_by_span(busy, spans, view.rec.spans, lo, hi)
    waits = device_trace.union(rd.spans(view, "wait"))
    out["longest_own_s"] = ps.longest_self(spans, lo, hi, waits)
    return out


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    spec, cell, config, traffic = run.load_cell(args.workload)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse != (platform != "tpu"):
        print(f"span_run: needs a TPU (or --rehearse on a CPU); jax found "
              f"platform {platform!r}", file=sys.stderr)
        return 2
    import drive
    import program_spans
    import reference
    from repro import tracing
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.rehearse:
        scale = run.REHEARSAL_SCALE
        backend = "xla" if config["shard_across"] > 1 else "interpret"
    else:
        scale, backend = 1.0, "pallas"
    cell_run = drive.Cell(config, traffic, args.seed, scale, backend)
    check = reference.Check(reference.Reference(config, cell_run.pool, scale))
    cell_run.calibrate()
    cell_run.warm_up()
    profile = (program_spans.ScopedProfile(f"spans-{args.workload}-{args.seed}")
               if args.profile else None)
    tracing.drain()
    tracing.enable()
    if profile:
        profile.start()
    lo, hi = cell_run.measure(args.seconds, check)
    if profile:
        profile.stop()
    tracing.disable()
    spans = [(s.name, s.start, s.end, s.id, s.parent, s.request)
             for s in tracing.drain()]
    late = cell_run.rec.lateness
    print(f"pacing: late calls={len(late)} max_late_s={max(late, default=0.0)}",
          file=sys.stderr)
    run.print_stalls(cell_run.rec, lo, hi)
    used = devices[:cell["chips"]]
    view = run.RunView(lo=lo, hi=hi, setup_s=lo - run.T_START,
                       rec=cell_run.rec,
                       windows=[w for w in cell_run.windows if lo <= w.close <= hi],
                       chips=cell["chips"], device_kind=devices[0].device_kind)
    ops = scopes = None
    if profile:
        ops, _ = profile.read()
        ops = {d.id: ops.get(d.id, []) for d in used}
        scopes = profile.scopes
        view.busy = [device_trace.union(ops[d.id]) for d in used]
    found = readings(spans, view, ops, scopes)
    for m in run.cell_metrics(spec, args.workload, bool(args.profile)):
        value = run.read_metric(m["name"], view)
        if value is not None:
            found[m["name"]] = value
    for name, (own, at) in found["longest_own_s"].items():
        print(f"longest own time of {name} (s, at_s): ({own}, {at})",
              file=sys.stderr)
    if "idle_by_span" in found:
        print(f"idle_by_span: {found['idle_by_span']}", file=sys.stderr)
    out = {"workload": args.workload, "seed": args.seed,
           "profile": args.profile, "device": devices[0].device_kind,
           "spans": len(spans),
           # A CPU rehearsal's times are no device readings.
           ("rehearsal_readings" if args.rehearse else "readings"): found}
    out["correct"] = (reference.verdict(check.numbers, config["limits"])
                      and bool(view.windows))
    out["checks"] = check.numbers
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
