"""Roofline reports.

1. Dry-run table (deliverable g): reads benchmarks/results/dryrun/*.json
   (written by repro.launch.dryrun) and emits the per-(arch x shape x mesh)
   table: three roofline terms, dominant bottleneck, MODEL_FLOPS/HLO_FLOPS
   usefulness ratio, memory fit.

2. segagg kernel report (PR 8): reads the committed
   benchmarks/results/kernels.json (written by benchmarks.bench_kernels),
   probes the machine's achievable copy bandwidth and matmul FLOP rate, and
   reports achieved-vs-roofline fractions per (backend, shape) through
   ``repro.dist.KernelRooflineManager`` — how close each dispatched segagg
   backend runs to the roof the host demonstrably sustains.  Results land
   in results/segagg_roofline.json.
"""
from __future__ import annotations

import glob
import json
import pathlib
import time

from .common import RESULTS, Timer, emit, write_result

DRYRUN = RESULTS / "dryrun"


def load_cells():
    cells = []
    for fn in sorted(glob.glob(str(DRYRUN / "*.json"))):
        cells.append(json.loads(pathlib.Path(fn).read_text()))
    return cells


def markdown_table(cells, mesh="single") -> str:
    rows = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant | "
        "useful_flops | mfu_bound | mem/chip | fits |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for c in cells:
        if c.get("mesh") != mesh:
            continue
        if c["status"] == "skipped":
            rows.append(f"| {c['arch']} | {c['shape']} | — | — | — | "
                        f"skipped | — | — | — | — |")
            continue
        if c["status"] != "ok":
            rows.append(f"| {c['arch']} | {c['shape']} | ERROR: "
                        f"{c.get('error','')[:40]} | | | | | | | |")
            continue
        r = c["roofline"]
        m = c["memory"]
        rows.append(
            f"| {c['arch']} | {c['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {c['useful_flops_ratio']:.2f} | "
            f"{c['mfu_bound']:.3f} | "
            f"{m['peak_bytes_per_chip']/2**30:.2f} GiB | "
            f"{'Y' if m['fits_hbm'] else 'N'} |")
    return "\n".join(rows)


def measure_machine_spec():
    """Achievable peaks of THIS host: copy bandwidth (read+write bytes of a
    jnp copy) and f32 matmul FLOP rate.  Measured, not datasheet — so the
    segagg achieved fractions compare against a roof the machine has
    actually demonstrated."""
    import jax
    import jax.numpy as jnp

    from repro.dist import MachineSpec

    copy = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((64 * 2**20 // 4,), jnp.float32)   # 64 MiB
    jax.block_until_ready(copy(x))
    t0 = time.perf_counter()
    for _ in range(5):
        x = copy(x)
    jax.block_until_ready(x)
    bw = 5 * 2 * x.size * 4 / (time.perf_counter() - t0)

    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(mm(a))
    t0 = time.perf_counter()
    for _ in range(5):
        out = mm(a)
    jax.block_until_ready(out)
    flops = 5 * 2 * 1024**3 / (time.perf_counter() - t0)
    return MachineSpec(peak_flops=flops, peak_bw=bw)


def mesh_spec(spec):
    """The MESH roof this process should report against: the per-device
    spec aggregated over the visible devices.  Forced-host CPU "devices"
    all share one socket — the measured host rate already IS the aggregate
    — so only real accelerator meshes scale the roof."""
    import jax

    n = jax.device_count()
    if n <= 1 or jax.default_backend() not in ("tpu", "gpu"):
        return spec
    return spec.scaled(n)


def segagg_report():
    """Achieved-vs-roofline rows for every timed segagg/pane_segagg bench
    entry; returns (report dict, summary line) or (None, reason).

    Reports BOTH roofs: the single-device achieved fraction per row, and
    the mesh-aggregate spec (``MachineSpec.scaled`` over the visible
    devices) a sharded run is measured against."""
    from repro.dist import KernelRooflineManager

    kernels_path = RESULTS / "kernels.json"
    if not kernels_path.exists():
        return None, "results/kernels.json missing (run benchmarks.bench_kernels)"
    data = json.loads(kernels_path.read_text())
    spec = measure_machine_spec()
    mspec = mesh_spec(spec)
    mng = KernelRooflineManager(spec)
    mesh_mng = KernelRooflineManager(mspec)
    rows = []
    for r in data.get("rows", ()):
        if r.get("kernel") not in ("segagg", "pane_segagg") or "flops" not in r:
            continue
        info = {"flops": r["flops"], "bytes": r["bytes"],
                "seconds": r["us"] / 1e6}
        roof = mng.get_roofline(info)
        if mspec is not spec:
            roof["mesh_achieved_frac"] = \
                mesh_mng.get_roofline(info)["achieved_frac"]
        rows.append({k: r[k] for k in
                     ("kernel", "backend", "formulation", "n", "groups")
                     if k in r} | roof)
    best = {}
    for r in rows:
        key = (r["kernel"], r["n"], r["groups"])
        if key not in best or r["achieved_frac"] > best[key]["achieved_frac"]:
            best[key] = r
    report = {
        "spec": {"peak_flops": spec.peak_flops, "peak_bw": spec.peak_bw,
                 "source": spec.source, "devices": spec.devices},
        "mesh_spec": {"peak_flops": mspec.peak_flops, "peak_bw": mspec.peak_bw,
                      "source": mspec.source, "devices": mspec.devices},
        "rows": rows,
        "best_per_shape": {
            f"{k[0]}@{k[1]}x{k[2]}":
                {"backend": v["backend"], "achieved_frac": v["achieved_frac"]}
            for k, v in best.items()},
    }
    line = "; ".join(
        f"{k}:{v['backend']}@{v['achieved_frac']:.2f}"
        for k, v in sorted(report["best_per_shape"].items()))
    return report, line


def main() -> None:
    with Timer() as t:
        cells = load_cells()
    ok = [c for c in cells if c["status"] == "ok"]
    skipped = [c for c in cells if c["status"] == "skipped"]
    errors = [c for c in cells if c["status"] not in ("ok", "skipped")]
    fits = sum(1 for c in ok if c["memory"]["fits_hbm"])
    dominant = {}
    for c in ok:
        dominant[c["roofline"]["dominant"]] = \
            dominant.get(c["roofline"]["dominant"], 0) + 1
    write_result("roofline_summary", {
        "num_ok": len(ok), "num_skipped": len(skipped),
        "num_errors": len(errors), "fits": fits, "dominant": dominant,
        "table_single": markdown_table(cells, "single"),
        "table_multi": markdown_table(cells, "multi"),
    })
    emit("roofline_dryrun", t.seconds * 1e6 / max(len(cells), 1),
         f"cells ok={len(ok)} skipped={len(skipped)} errors={len(errors)} "
         f"fits_hbm={fits}/{len(ok)} dominant={dominant}")

    with Timer() as t2:
        report, line = segagg_report()
    if report is None:
        emit("roofline_segagg", 0, f"skipped: {line}")
    else:
        write_result("segagg_roofline", report)
        emit("roofline_segagg", t2.seconds * 1e6, line)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
