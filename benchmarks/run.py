"""Benchmark driver — one entry per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV summary at the end (us_per_call =
benchmark wall time; derived = the benchmark's headline metric), and exits
non-zero if any registered benchmark raised — a failing benchmark must not
pass silently in CI.

Each benchmark also writes a machine-readable ``BENCH_<slug>.json`` to
``--out-dir`` with its headline-metric dict, the exact config it ran under,
the git revision, and wall time — so CI runs leave comparable artifacts
instead of only scrollback. ``--smoke`` shrinks every workload for a
minutes-not-hours CI pass; the artifact records which mode produced it.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def main() -> None:
    import benchmarks.chain_compare as chain_compare
    import benchmarks.kv_utilization as kv_utilization
    import benchmarks.orca_scheduling as orca_scheduling
    import benchmarks.serving_fig9 as serving_fig9
    import benchmarks.serving_fig10 as serving_fig10
    import benchmarks.chunked_prefill_sweep as chunked_prefill_sweep
    import benchmarks.disagg_sweep as disagg_sweep
    import benchmarks.prefix_cache_sweep as prefix_cache_sweep
    import benchmarks.mla_sweep as mla_sweep
    import benchmarks.roofline_report as roofline_report
    import benchmarks.router_sweep as router_sweep
    import benchmarks.swap_sweep as swap_sweep
    import benchmarks.zero_copy_sweep as zero_copy_sweep
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description="run all paper benchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink every workload for a fast CI pass")
    ap.add_argument("--out-dir", default="bench_out", metavar="DIR",
                    help="where BENCH_<slug>.json artifacts land "
                         "(default: bench_out)")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benchmarks whose slug contains SUBSTR")
    args = ap.parse_args()
    enable_compile_cache()
    os.makedirs(args.out_dir, exist_ok=True)
    rev = git_rev()

    csv_rows = []
    failures = []

    def bench(slug, title, fn, config, derive, metrics):
        """Run one benchmark: stdout table, CSV row, BENCH_<slug>.json."""
        if args.only and args.only not in slug:
            return None
        print(f"\n=== {title} " + "=" * max(0, 60 - len(title)))
        t0 = time.monotonic()
        try:
            out = fn(**config)
        except Exception:
            # record and continue: the remaining benchmarks still run, but
            # the driver exits non-zero at the end
            traceback.print_exc()
            failures.append(slug)
            csv_rows.append((slug, (time.monotonic() - t0) * 1e6, "FAILED"))
            return None
        wall_s = time.monotonic() - t0
        try:
            derived = derive(out)
        except Exception:  # pragma: no cover - derived metric best-effort
            traceback.print_exc()
            derived = "n/a"
        try:
            metric_dict = metrics(out)
        except Exception:  # pragma: no cover - same best-effort policy
            traceback.print_exc()
            metric_dict = {"error": "metric extraction failed"}
        artifact = {
            "name": slug,
            "title": title,
            "metrics": metric_dict,
            "config": dict(config, smoke=args.smoke),
            "git_rev": rev,
            "wall_s": round(wall_s, 4),
        }
        path = os.path.join(args.out_dir, f"BENCH_{slug}.json")
        with open(path, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True, default=str)
        csv_rows.append((slug, wall_s * 1e6, derived))
        return out

    smoke = args.smoke

    bench("chain_compare", "chain_nsga2_vs_dijkstra (paper §II.B.5)",
          chain_compare.run,
          {"n_fleets": 3 if smoke else 6},
          lambda out: f"hv_ratio={out[1]['hv_ga']/max(out[1]['hv_base'],1e-9):.2f}x",
          lambda out: {"hv_ga": out[1]["hv_ga"], "hv_base": out[1]["hv_base"],
                       "hv_ratio": out[1]["hv_ga"]
                       / max(out[1]["hv_base"], 1e-9)})

    bench("serving_fig9", "serving_fig9_paged_vs_orca",
          serving_fig9.run,
          {"n_requests": 80 if smoke else 300},
          lambda out: "latency_curves=%d" % sum(len(v) for v in out.values()),
          lambda out: {
              f"{dist}_sustainable_{sysname}": max(
                  (r["rate"] for r in rows if r[sysname] <= 0.040),
                  default=0.0)
              for dist, rows in out.items()
              for sysname in ("vLLM-paged", "orca-max")})

    bench("kv_utilization", "kv_utilization (§III.C 20.4-38.2%)",
          kv_utilization.run, {},
          lambda out: f"orca_max={out['orca-max']:.1%},paged={out['vLLM-paged']:.1%}",
          lambda out: dict(out))

    bench("serving_fig10", "serving_fig10_distkv",
          serving_fig10.run,
          {"n_requests": 60 if smoke else 200},
          lambda out: "max_gain=%.2fx" % max(r["gain"] for r in out),
          lambda out: {"max_gain": max(r["gain"] for r in out),
                       "n_points": len(out)})

    bench("chunked_prefill_sweep",
          "chunked_prefill_sweep (stall-free mixed batching)",
          chunked_prefill_sweep.run,
          {"n_requests": 60 if smoke else 220},
          chunked_prefill_sweep.headline,
          lambda rows: {
              "p99_tbt_gain_vs_monolithic":
                  next(r for r in rows if r["workload"] == "mixed-long"
                       and r["policy"] == "monolithic")["p99_tbt"]
                  / max(next(r for r in rows if r["workload"] == "mixed-long"
                             and r["policy"] == "decode_first")["p99_tbt"],
                        1e-12),
              "decode_first_p99_tbt_s":
                  next(r for r in rows if r["workload"] == "mixed-long"
                       and r["policy"] == "decode_first")["p99_tbt"]})

    bench("disagg_sweep",
          "disagg_sweep (prefill/decode disaggregation frontier)",
          disagg_sweep.run,
          {"n_requests": 80 if smoke else 200,
           "rates": disagg_sweep.SMOKE_RATES if smoke
           else disagg_sweep.RATES},
          disagg_sweep.headline,
          lambda rows: {
              "p99_tbt": {f"{r['system']}@{r['rate']:g}": r["p99_tbt"]
                          for r in rows},
              "throughput": {f"{r['system']}@{r['rate']:g}": r["throughput"]
                             for r in rows},
              "handoffs_leased": sum(r.get("handoffs_leased", 0)
                                     for r in rows
                                     if r["system"] == "disagg-2p2d"),
              "handoffs_migrated": sum(r.get("handoffs_migrated", 0)
                                       for r in rows
                                       if r["system"] == "disagg-2p2d")})

    bench("swap_sweep", "swap_sweep (swap-to-host vs recompute crossover)",
          swap_sweep.run,
          # the two operating points are already CI-sized; the PCIe swap
          # lane calibration is pinned here so the artifact records it
          {"pcie_gbps": 256.0, "t_swap_fixed": 2e-5},
          swap_sweep.headline,
          lambda rows: {
              "long_throughput": {
                  r["system"]: r["throughput"] for r in rows
                  if r["point"] == "long" and "throughput" in r},
              "long_p99_norm_lat": {
                  r["system"]: r["p99_norm_lat"] for r in rows
                  if r["point"] == "long" and "p99_norm_lat" in r},
              "short_throughput": {
                  r["system"]: r["throughput"] for r in rows
                  if r["point"] == "short" and "throughput" in r},
              "reprefill_ok": not next(
                  r for r in rows if r["system"] == "proof"
              )["reprefill_problems"]})

    bench("mla_sweep", "mla_sweep (latent-KV paging vs GQA at fixed HBM)",
          mla_sweep.run,
          # the two layout points are already CI-sized; the HBM KV budget
          # is pinned here so the artifact records it
          {"hbm_budget": mla_sweep.HBM_KV_BUDGET},
          mla_sweep.headline,
          lambda rows: {
              "bytes_per_token": {r["layout"]: r["bytes_per_token"]
                                  for r in rows},
              "compression_ratio":
                  next(r for r in rows if r["layout"] == "gqa")
                  ["bytes_per_token"]
                  / next(r for r in rows if r["layout"] == "mla")
                  ["bytes_per_token"],
              "throughput": {r["layout"]: r["throughput"] for r in rows},
              "p99_norm_lat": {r["layout"]: r["p99_norm_lat"]
                               for r in rows},
              "achievable_batch": {r["layout"]: r["achievable_batch"]
                                   for r in rows},
              "completed": {r["layout"]: r["completed"] for r in rows}})

    bench("prefix_cache_sweep", "prefix_cache_sweep (radix KV reuse)",
          prefix_cache_sweep.run,
          {"n_requests": 50 if smoke else 150},
          lambda out: "shared_speedup=%.3fx,hit=%.0f%%" % (
              out[0]["speedup"], 100 * out[0]["hit_rate"]),
          lambda out: {"shared_speedup": out[0]["speedup"],
                       "hit_rate": out[0]["hit_rate"]})

    bench("router_sweep", "router_sweep (cluster placement policies)",
          router_sweep.run,
          {"n_requests": 60 if smoke else 160},
          router_sweep.headline,
          lambda rows: {
              "affinity_hit_rate":
                  next(r for r in rows if r["workload"] == "shared-prefix"
                       and r["policy"] == "prefix_affinity"
                       and not r["share"])["hit_rate"],
              "round_robin_hit_rate":
                  next(r for r in rows if r["workload"] == "shared-prefix"
                       and r["policy"] == "round_robin"
                       and not r["share"])["hit_rate"]})

    bench("zero_copy_sweep",
          "zero_copy_sweep (copy vs borrowed-rBlock prefix serving)",
          zero_copy_sweep.run,
          {"n_requests": 60 if smoke else 160,
           "out_lens": (16, 96) if smoke else (16, 96, 256)},
          zero_copy_sweep.headline,
          lambda rows: {
              "net_ms": {f"{r['mode']}@{r['out_len']}": r["net_ms"]
                         for r in rows},
              "borrowed_pages": sum(r["borrowed_pages"] for r in rows)})

    bench("orca_scheduling", "orca_iteration_vs_batch",
          orca_scheduling.run,
          {"n_requests": 60 if smoke else 300},
          lambda out: "batch/iter=%.1fx" % max(
              r["batch_lat"] / r["iter_lat"] for r in out),
          lambda out: {"max_batch_over_iter_latency": max(
              r["batch_lat"] / r["iter_lat"] for r in out)})

    bench("roofline_report", "roofline_report (dry-run artifacts)",
          roofline_report.run, {},
          lambda out: "rows=%d" % len(out),
          lambda out: {"rows": len(out)})

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.0f},{derived}")
    print(f"\nartifacts: {args.out_dir}/BENCH_*.json (rev {rev})")

    if failures:
        print(f"\nFAILED benchmarks: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
