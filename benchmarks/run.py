"""Benchmark harness: one module per paper table/figure (DESIGN.md §7)
plus the roofline report over the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--quiet]

Emits the repo-root perf-trajectory files BENCH_encode.json,
BENCH_checkpoint.json, BENCH_repair.json, BENCH_cluster.json,
BENCH_store.json, BENCH_codes.json and BENCH_shard.json, and prints
``name,us_per_call,derived`` CSV rows at
the end.  Unknown files under results/ (superseded artifacts, benches
missing from KNOWN_RESULTS) fail the run before any sweep starts.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks import (bench_checkpoint, bench_cluster, bench_codes,
                        bench_drills, bench_encode_throughput,
                        bench_field_size, bench_pipeline,
                        bench_regeneration, bench_repair_bandwidth,
                        bench_serve, bench_shard, bench_store, roofline)
from repro.exec.compile_cache import enable_compile_cache

OUT = pathlib.Path(__file__).resolve().parent / "results"
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Every file benchmarks/ is allowed to leave under results/.  A result
# file not in this set is either a superseded artifact that should have
# been deleted (the field_scaling.json case) or a new bench that forgot
# to register here — both fail the run loudly instead of silently
# shipping stale JSON.
KNOWN_RESULTS = {"checkpoint", "cluster", "codes", "drills",
                 "encode_throughput", "field_size", "pipeline",
                 "regeneration", "repair_bandwidth", "roofline", "serve",
                 "shard", "store"}


def check_results_dir() -> None:
    unknown = sorted(p.name for p in OUT.glob("*.json")
                     if p.stem not in KNOWN_RESULTS)
    if unknown:
        raise SystemExit(
            f"benchmarks/results/ contains unknown result file(s): "
            f"{unknown}.  Delete superseded artifacts or register new "
            f"benches in benchmarks.run.KNOWN_RESULTS.")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller sweeps")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-row prints (CI smoke mode)")
    args = ap.parse_args()
    enable_compile_cache()
    quiet = args.quiet
    OUT.mkdir(exist_ok=True)
    check_results_dir()
    csv_rows = [("name", "us_per_call", "derived")]

    # the regeneration timing section runs FIRST: its fused-vs-unfused
    # ratio is the most contention-sensitive number in the suite (the fused
    # path parallelizes, the unfused path is dispatch-bound), so it gets
    # the freshest CPU budget on throttled/burstable hosts
    print("== paper §IV: regeneration complexity =====================")
    # the 45 s sampling window spreads the paired fused/unfused rounds
    # across shared-host capacity oscillations (see _timeit_pair)
    rows_regen = bench_regeneration.run(
        ks=(2, 4) if args.fast else (2, 4, 8),
        block_symbols=(1 << 14 if args.fast else 1 << 18), quiet=quiet,
        sample_window_s=(0.0 if args.fast else 45.0))
    (OUT / "regeneration.json").write_text(json.dumps(rows_regen, indent=1))
    csv_rows.append(("regeneration",
                     f"{rows_regen[-1]['t_embedded_s']*1e6:.0f}",
                     f"fused_vs_unfused={rows_regen[-1]['speedup_fused_vs_unfused']}x;"
                     f"speedup_vs_solve={rows_regen[-1]['speedup']}"))

    print("== paper §IV eq.(7): repair bandwidth =====================")
    t0 = time.perf_counter()
    rows_bw = bench_repair_bandwidth.run(
        file_bytes=(1 << 18 if args.fast else 1 << 20),
        ks=(2, 3, 4) if args.fast else (2, 3, 4, 8), quiet=quiet)
    (OUT / "repair_bandwidth.json").write_text(json.dumps(rows_bw, indent=1))
    # repair-side perf trajectory, tracked like encode/checkpoint: the
    # fused-engine regeneration rows plus the measured repair bandwidth
    (REPO_ROOT / "BENCH_repair.json").write_text(json.dumps(
        {"regeneration": rows_regen, "repair_bandwidth": rows_bw}, indent=1))
    csv_rows.append(("repair_bandwidth",
                     f"{(time.perf_counter()-t0)*1e6/len(rows_bw):.0f}",
                     f"saving_vs_ec={rows_bw[-1]['saving_vs_ec']:.3f}"))

    print("== paper §IV-A: field size requirement ====================")
    t0 = time.perf_counter()
    rows = bench_field_size.run(ks=(2, 3) if args.fast else (2, 3, 4, 5),
                                quiet=quiet)
    # the scaling-limit sweep lives INSIDE field_size.json (it used to be
    # a separate field_scaling.json, now superseded — KNOWN_RESULTS
    # rejects the old file if it reappears)
    scaling = None if args.fast else bench_field_size.scaling_limit(quiet=quiet)
    (OUT / "field_size.json").write_text(json.dumps(
        {"rows": rows, "scaling_limit": scaling}, indent=1))
    csv_rows.append(("field_size",
                     f"{(time.perf_counter()-t0)*1e6/len(rows):.0f}",
                     f"min_field_k2={rows[0]['min_field']}"))

    print("== paper §IV: encode throughput (dispatch backends) =======")
    t0 = time.perf_counter()
    # stream >= 2^14 symbols: below that, per-call dispatch overhead
    # dominates and the MB/s trajectory numbers are meaningless
    rows = bench_encode_throughput.run(
        ks=(2, 8),
        stream_symbols=(1 << 14 if args.fast else 1 << 16), quiet=quiet)
    (OUT / "encode_throughput.json").write_text(json.dumps(rows, indent=1))
    (REPO_ROOT / "BENCH_encode.json").write_text(json.dumps(rows, indent=1))
    csv_rows.append(("encode_throughput",
                     f"{rows[-1]['circulant_s']*1e6:.0f}",
                     f"circulant_mbps={rows[-1]['circulant_mbps']};"
                     f"vs_interpret={rows[-1].get('speedup_vs_interpret')}x"))

    print("== checkpoint pipeline: save/restore throughput ===========")
    t0 = time.perf_counter()
    rows = bench_checkpoint.run(
        ks=(4,) if args.fast else (4, 8),
        state_mb=(1.0 if args.fast else 4.0), quiet=quiet)
    (OUT / "checkpoint.json").write_text(json.dumps(rows, indent=1))
    (REPO_ROOT / "BENCH_checkpoint.json").write_text(json.dumps(rows, indent=1))
    csv_rows.append(("checkpoint",
                     f"{rows[-1]['save_s']*1e6:.0f}",
                     f"save_mbps={rows[-1]['save_mbps']};regen_frac="
                     f"{rows[-1]['restore']['regenerate']['frac_of_stored']}"))

    print("== cluster scenarios: repair traffic + degraded reads =====")
    t0 = time.perf_counter()
    rows = bench_cluster.run(
        ks=(4,) if args.fast else (4, 8),
        block_symbols=(1 << 13 if args.fast else 1 << 16), quiet=quiet)
    (OUT / "cluster.json").write_text(json.dumps(rows, indent=1))
    (REPO_ROOT / "BENCH_cluster.json").write_text(json.dumps(rows, indent=1))
    worst_ratio = max(
        (s["repair_ratio_vs_rs"] for r in rows for s in r["scenarios"]
         if s["repair_ratio_vs_rs"] is not None), default=None)
    csv_rows.append(("cluster",
                     f"{(time.perf_counter()-t0)*1e6/len(rows):.0f}",
                     f"worst_repair_ratio={worst_ratio};deg_read_ms="
                     f"{rows[-1]['degraded_read_latency']['steady_s']*1e3:.2f}"))

    print("== object store: put/get, degraded reads, repair drain ====")
    t0 = time.perf_counter()
    rows = bench_store.run(
        ks=(4,) if args.fast else (4, 8),
        stripe_symbols=(1 << 10 if args.fast else 1 << 12),
        n_objects=(4 if args.fast else 8),
        object_bytes=(1 << 17 if args.fast else 1 << 20), quiet=quiet)
    (OUT / "store.json").write_text(json.dumps(rows, indent=1))
    (REPO_ROOT / "BENCH_store.json").write_text(json.dumps(rows, indent=1))
    csv_rows.append(("store",
                     f"{(time.perf_counter()-t0)*1e6/len(rows):.0f}",
                     f"put_mbps={rows[-1]['put_mbps']};"
                     f"drain_ratio_vs_rs={rows[-1]['drain'][0]['ratio_vs_rs']}"))

    print("== code families: frontier + conversion + roofline =========")
    t0 = time.perf_counter()
    # the pm-beats-RS / bit-exact-conversion / zero-orphan gates are in
    # rec["assertions"]; codes-smoke re-checks the emitted artifact
    rec = bench_codes.run(fast=args.fast, quiet=quiet)
    (OUT / "codes.json").write_text(json.dumps(rec, indent=1))
    (REPO_ROOT / "BENCH_codes.json").write_text(json.dumps(rec, indent=1))
    assert rec["all_passed"], f"codes assertions failed: {rec['assertions']}"
    best = min(rec["frontier"], key=lambda r: r["repair_ratio_vs_rs"])
    csv_rows.append(("codes",
                     f"{(time.perf_counter()-t0)*1e6:.0f}",
                     f"best_repair_vs_rs={best['repair_ratio_vs_rs']};"
                     f"convert_mbps={rec['conversion']['mbps']};"
                     f"orphans={rec['conversion']['orphans']}"))

    print("== crash consistency: drills + zero-stall checkpointing ===")
    t0 = time.perf_counter()
    rec = bench_drills.run(fast=args.fast, quiet=quiet)
    (OUT / "drills.json").write_text(json.dumps(rec, indent=1))
    (REPO_ROOT / "BENCH_drills.json").write_text(json.dumps(rec, indent=1))
    assert rec["all_bit_exact"] and rec["all_passed"], \
        f"drill failure: {rec['drills']['results']}"
    csv_rows.append(("drills",
                     f"{(time.perf_counter()-t0)*1e6:.0f}",
                     f"all_passed={rec['all_passed']};wb_overhead_ratio="
                     f"{rec['checkpoint_overhead']['wb_vs_stw_overhead_ratio']}"))

    print("== robust serving: hedged reads + quarantine + shedding ===")
    t0 = time.perf_counter()
    # every robustness claim is asserted inside the bench itself
    rec = bench_serve.run(fast=args.fast, quiet=quiet)
    (OUT / "serve.json").write_text(json.dumps(rec, indent=1))
    (REPO_ROOT / "BENCH_serve.json").write_text(json.dumps(rec, indent=1))
    csv_rows.append(("serve",
                     f"{(time.perf_counter()-t0)*1e6:.0f}",
                     f"req_per_s={rec['healthy']['req_per_s']};"
                     f"p99_cut={rec['hedge_ab']['p99_cut']};"
                     f"shed={rec['overload']['shed']}"))

    print("== exec layer: plan cache + overlapped pipeline ===========")
    t0 = time.perf_counter()
    # raises on any steady-state recompile — the bench IS the CI gate
    rec = bench_pipeline.run(fast=args.fast, quiet=quiet)
    (OUT / "pipeline.json").write_text(json.dumps(rec, indent=1))
    csv_rows.append(("pipeline",
                     f"{(time.perf_counter()-t0)*1e6:.0f}",
                     f"ckpt_speedup={rec['restore']['speedup_vs_serial']}x;"
                     f"steady_recompiles="
                     f"{rec['recompiles']['planned_steady_compiles']}"))

    print("== mesh sharding: multi-device encode/repair scaling ======")
    t0 = time.perf_counter()
    # parity, zero steady-state recompiles, and (given >= 4 cores) the
    # 2x 4-device scaling claim are all asserted inside the bench
    rec = bench_shard.run(fast=args.fast, quiet=quiet)
    (OUT / "shard.json").write_text(json.dumps(rec, indent=1))
    (REPO_ROOT / "BENCH_shard.json").write_text(json.dumps(rec, indent=1))
    csv_rows.append(("shard",
                     f"{(time.perf_counter()-t0)*1e6:.0f}",
                     f"enc_speedup_4dev={rec['encode_speedup_4dev']}x;"
                     f"asserted={rec['scaling_asserted']};"
                     f"steady_recompiles={rec['steady_recompiles']}"))

    print("== roofline (dry-run artifacts) ===========================")
    t0 = time.perf_counter()
    rows = roofline.run(quiet=quiet)
    if rows:
        (OUT / "roofline.json").write_text(json.dumps(rows, indent=1))
        worst = min(rows, key=lambda r: r["projected_mfu"])
        csv_rows.append(("roofline",
                         f"{(time.perf_counter()-t0)*1e6/len(rows):.0f}",
                         f"cells={len(rows)};worst_mfu={worst['projected_mfu']:.3f}"))
    else:
        print("  (no dry-run artifacts found — run repro.launch.dryrun --all)")

    print()
    for row in csv_rows:
        print(",".join(str(x) for x in row))


if __name__ == "__main__":
    main()
