"""Benchmark driver: one section per paper table/figure + the roofline
report. ``PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke]
[--compare BASELINE.json]``.

Sections:
  fig4  rate-distortion curves (PSNR vs bitrate), SZ + ZFP, Nyx + HACC
  fig5  power-spectrum pk-ratio gate at the best-fit configs
  fig6  FoF halo mass-function / count-ratio gate
  fig7-10  throughput: stage breakdown, rate scaling
  serving  continuous-batching load generator: Poisson arrivals, none vs
        blockfloat8 KV, equal-pool-bytes concurrency (>=1.8x gate)
  vd    §V-D guideline end-to-end (best-fit configs + overall CR)
  roofline  per (arch x shape x mesh) terms from the dry-run artifacts

Every run writes a machine-readable MB/s record so the perf trajectory is
tracked across PRs: only full-size runs write the committed
``BENCH_throughput.json``; ``--smoke`` and ``--fast`` write the untracked
``BENCH_throughput.<mode>.json`` so small-n numbers never overwrite — or
get compared against — the canonical full-run record.

``--compare BASELINE.json`` prints per-section deltas of the current
record (the one just produced, or ``--current PATH`` / the committed
record when no benchmarks ran) against a prior ``BENCH_throughput*.json``
and **exits nonzero on any >20% regression** — throughput keys must not
drop, wall keys must not grow.  Compare like modes against like (smoke vs
smoke): n differs across modes, so cross-mode deltas are meaningless.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Optional

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_throughput.json"

# ------------------------------------------------------------- compare ----

# direction inference from key names: which way is "better"?
_HIGHER_SUFFIXES = ("_mbs", "_mbps", "_gbps", "_x", "ratio", "_savings",
                    "tokens_per_s")
_HIGHER_SUBSTRINGS = ("throughput", "speedup", "reduction", "goodput")
_LOWER_SUFFIXES = ("_s",)
_LOWER_SUBSTRINGS = ("wall", "blip")
# noise floor for lower-better (timing) keys: sub-millisecond baselines
# are timer jitter, not signal
_MIN_TIMING_BASE_S = 1e-3


def key_direction(key: str) -> Optional[str]:
    """'higher' | 'lower' | None (informational — counts, configs, n)."""
    k = key.rsplit(".", 1)[-1].lower()
    if k.endswith(_HIGHER_SUFFIXES) or any(s in k for s in _HIGHER_SUBSTRINGS):
        return "higher"
    if k.endswith(_LOWER_SUFFIXES) or any(s in k for s in _LOWER_SUBSTRINGS):
        return "lower"
    return None


def flatten_bench(obj, prefix: str = "") -> dict:
    """Nested record -> {'section.path.key': float}.  List entries are
    labeled by their identifying field (compressor/config/kernel/name)
    when present, else by index, so baselines stay aligned across runs."""
    out: dict[str, float] = {}
    if isinstance(obj, dict):
        for k in sorted(obj):
            out.update(flatten_bench(obj[k], f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(obj, list):
        for i, item in enumerate(obj):
            label = str(i)
            if isinstance(item, dict):
                for idk in ("compressor", "config", "kernel", "name", "arch"):
                    if idk in item:
                        label = str(item[idk]).replace(" ", "_")
                        break
            out.update(flatten_bench(item, f"{prefix}[{label}]"))
    elif isinstance(obj, bool):
        pass  # flags are config, not measurements
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    return out


def compare_records(base: dict, cur: dict, threshold: float = 0.20
                    ) -> tuple[list[str], list[str]]:
    """Per-section deltas of ``cur`` vs ``base``.  Returns
    ``(report_lines, regressions)`` — a regression is a directional key
    moving the wrong way by more than ``threshold``."""
    lines: list[str] = []
    regressions: list[str] = []
    if base.get("mode") != cur.get("mode"):
        lines.append(f"WARNING: comparing mode={cur.get('mode')!r} against "
                     f"baseline mode={base.get('mode')!r} — n differs, "
                     "deltas below are not apples-to-apples")
    fb, fc = flatten_bench(base), flatten_bench(cur)
    # a section living in only one record (e.g. `serving` landed after the
    # baseline was cut) is a schema drift warning, never a regression —
    # there is nothing to compare it against
    sec_b = {k.split(".")[0].split("[")[0] for k in fb}
    sec_c = {k.split(".")[0].split("[")[0] for k in fc}
    for s in sorted(sec_b - sec_c):
        lines.append(f"WARNING: section '{s}' only in baseline — "
                     "absent from the current record, skipping")
    for s in sorted(sec_c - sec_b):
        lines.append(f"WARNING: section '{s}' only in current record — "
                     "no baseline to compare, skipping")
    shared = sorted(set(fb) & set(fc))
    by_section: dict[str, list] = {}
    for key in shared:
        d = key_direction(key)
        if d is None:
            continue
        b, c = fb[key], fc[key]
        if b <= 0 or (d == "lower" and b < _MIN_TIMING_BASE_S):
            continue
        delta = (c - b) / abs(b)
        regressed = (delta < -threshold) if d == "higher" else (delta > threshold)
        by_section.setdefault(key.split(".")[0], []).append(
            (key, b, c, delta, d, regressed))
        if regressed:
            arrow = "dropped" if d == "higher" else "grew"
            regressions.append(f"{key}: {b:.6g} -> {c:.6g} "
                               f"({arrow} {abs(delta) * 100:.1f}%, "
                               f"threshold {threshold * 100:.0f}%)")
    for section in sorted(by_section):
        rows = by_section[section]
        worst = max(rows, key=lambda r: (abs(r[3]) if r[5] else 0, abs(r[3])))
        lines.append(f"[{section}] {len(rows)} keys compared; worst: "
                     f"{worst[0].split('.', 1)[-1]} "
                     f"{worst[1]:.6g} -> {worst[2]:.6g} ({worst[3]:+.1%})")
        for key, b, c, delta, d, regressed in rows:
            if regressed:
                lines.append(f"  REGRESSION {key}: {b:.6g} -> {c:.6g} "
                             f"({delta:+.1%}, {d}-is-better)")
    if not shared:
        lines.append("no shared numeric keys — wrong baseline file?")
        regressions.append("baseline and current records share no keys")
    return lines, regressions


def _section(title: str):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")


def run_throughput(n: int, vs_bitrate_n: int, smoke: bool = False,
                   mode: str = "full") -> dict:
    """Figs 7-10 + the packer microbench; returns the json-serializable
    record written by :func:`write_bench_json`."""
    from benchmarks import serving_load, throughput

    record = {
        "schema": "bench_throughput/v1",
        "mode": "smoke" if smoke else mode,
        "n": n,
        "measured_breakdown": throughput.measured_breakdown(n=n),
        "zfp_stage_breakdown": throughput.zfp_stage_breakdown(n=n),
        "packer": throughput.packer_microbench(n=1 << 18 if smoke else 1 << 22),
        "dist": throughput.dist_wire_bytes(n=1 << 18 if smoke else 1 << 22),
        "insitu": throughput.insitu_snapshot(n=n),
        "snapshot_dispatch": throughput.snapshot_dispatch(
            n_leaves=60 if smoke else 200, iters=2 if smoke else 5),
        "snapshot_overlap": throughput.snapshot_overlap(
            snaps=2 if smoke else 3),
        "serving": serving_load.bench_section(smoke=smoke),
    }
    if not smoke:
        record["throughput_vs_bitrate"] = throughput.throughput_vs_bitrate(n=vs_bitrate_n)
    return record


def write_bench_json(record: dict) -> None:
    mode = record.get("mode", "full")
    path = BENCH_JSON if mode == "full" else BENCH_JSON.with_suffix(f".{mode}.json")
    path.write_text(json.dumps(record, indent=2, sort_keys=True) + "\n")
    print(f"\nwrote {path}")


def _do_compare(args, record: Optional[dict]) -> int:
    base = json.loads(Path(args.compare).read_text())
    if record is None:
        cur_path = Path(args.current) if args.current else BENCH_JSON
        record = json.loads(cur_path.read_text())
    _section(f"Compare vs baseline {args.compare}")
    lines, regressions = compare_records(base, record, args.threshold)
    for ln in lines:
        print(ln)
    if regressions:
        print(f"\n{len(regressions)} regression(s) beyond "
              f"{args.threshold:.0%}:")
        for r in regressions:
            print("  " + r)
        return 1
    print(f"\nno regressions beyond {args.threshold:.0%}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced n")
    ap.add_argument("--smoke", action="store_true",
                    help="throughput sections only, minimal n")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="print per-section deltas vs a prior "
                         "BENCH_throughput*.json and exit nonzero on any "
                         "regression beyond --threshold.  With --smoke/"
                         "--fast the just-produced record is compared; "
                         "alone, --current (default: the committed "
                         "BENCH_throughput.json) is compared without "
                         "re-running anything")
    ap.add_argument("--current", default=None, metavar="RECORD.json",
                    help="with --compare and no benchmark run: the record "
                         "to compare against the baseline")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="regression threshold as a fraction (default 0.20)")
    args = ap.parse_args(argv)
    fast, smoke = args.fast, args.smoke
    from repro import compile_cache

    compile_cache.enable()

    if args.compare is not None and not (fast or smoke):
        return _do_compare(args, None)  # compare-only: no benchmark run

    n = 32 if (fast or smoke) else 64
    t0 = time.time()

    if smoke:
        _section("Throughput smoke (measured CPU)")
        record = run_throughput(n=n, vs_bitrate_n=0, smoke=True)
        for r in record["measured_breakdown"]:
            print(r)
        for r in record["zfp_stage_breakdown"]:
            print(r)
        print(record["packer"])
        print("dist:", record["dist"])
        print("insitu:", record["insitu"])
        print("snapshot_dispatch:", record["snapshot_dispatch"])
        print("snapshot_overlap:", record["snapshot_overlap"])
        for r in record["serving"]["load"]:
            print("serving:", r)
        print("serving equal-bytes:", record["serving"]["equal_bytes"])
        fd = record["serving"]["fault_drill"]
        print(f"serving fault-drill: goodput_ratio={fd['goodput_ratio']:.3f} "
              f"(clean={fd['clean']['goodput']:.3f}, "
              f"killed={fd['killed']['goodput']:.3f}, "
              f"redispatched={fd['killed']['redispatched']})")
        write_bench_json(record)
        print(f"\nsmoke benchmarks complete in {time.time() - t0:.1f}s")
        if args.compare is not None:
            return _do_compare(args, record)
        return 0

    from benchmarks import (guideline_bench, halo_finder, power_spectrum,
                            rate_distortion, roofline)

    _section("Fig 4 — rate-distortion (PSNR vs bitrate)")
    print("table,compressor,field,config,bitrate,psnr_db,ratio")
    for t, c, f, cfg, br, ps, ra in rate_distortion.run(n=n):
        print(f"{t},{c},{f},{cfg},{br:.3f},{ps:.2f},{ra:.2f}")

    _section("Fig 5 — power-spectrum pk-ratio gate (1 +/- 1%)")
    rows, overall = power_spectrum.run(n=n)
    print("field,compressor,ratio,pk_gate_pass,worst_pk_dev")
    for field, name, ratio, ok, dev in rows:
        print(f"{field},{name},{ratio:.2f},{ok},{dev:.4f}")
    for name, cr in overall.items():
        print(f"OVERALL,{name},{cr:.2f},,")

    _section("Fig 6 — FoF halo finder gate")
    hrows = halo_finder.run(grid=32 if fast else 48)
    cols = list(hrows[0])
    print(",".join(cols))
    for r in hrows:
        print(",".join(str(r[c]) for c in cols))

    _section("Figs 7-10 — throughput (measured CPU)")
    record = run_throughput(n=n, vs_bitrate_n=32 if fast else 48,
                            mode="fast" if fast else "full")
    for r in record["measured_breakdown"]:
        print(r)
    for r in record["zfp_stage_breakdown"]:
        print(r)
    for r in record["throughput_vs_bitrate"]:
        print(r)
    print(record["packer"])
    print("dist:", record["dist"])
    print("insitu:", record["insitu"])
    print("snapshot_dispatch:", record["snapshot_dispatch"])
    print("snapshot_overlap:", record["snapshot_overlap"])
    for r in record["serving"]["load"]:
        print("serving:", r)
    print("serving equal-bytes:", record["serving"]["equal_bytes"])
    fd = record["serving"]["fault_drill"]
    print(f"serving fault-drill: goodput_ratio={fd['goodput_ratio']:.3f} "
          f"(clean={fd['clean']['goodput']:.3f}, "
          f"killed={fd['killed']['goodput']:.3f}, "
          f"redispatched={fd['killed']['redispatched']})")
    write_bench_json(record)

    _section("§V-D — optimization guideline (best-fit configs)")
    res = guideline_bench.run(n=n)
    for name, d in res.items():
        print(f"{name}: overall best-fit CR = {d['overall']:.2f}x")
        for f, (cfg, cr, ok) in d["per_field"].items():
            print(f"   {f}: {cfg} -> {cr}x (gate={'pass' if ok else 'FALLBACK'})")

    _section("Roofline — per (arch x shape x mesh) from dry-run artifacts")
    roofline.main()

    print(f"\nbenchmarks complete in {time.time() - t0:.1f}s")
    if args.compare is not None:
        return _do_compare(args, record)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
