"""Generate the §Dry-run and §Roofline tables of EXPERIMENTS.md from the
dry-run artifacts, and the one-table ``BENCH_*.json`` summary the CI
bench-smoke job prints.  Run after (re-)running repro.launch.dryrun:

    PYTHONPATH=src python -m benchmarks.report > /tmp/tables.md
    PYTHONPATH=src python -m benchmarks.report --bench   # BENCH_* summary
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks import roofline

RESULTS = pathlib.Path(__file__).resolve().parent / "dryrun_results"
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def fmt_bytes(b):
    return f"{b/2**30:.2f}"


def dryrun_table(mesh: str) -> str:
    rows = []
    for f in sorted(RESULTS.glob("*.json")):
        rec = json.loads(f.read_text())
        if rec.get("mesh") != mesh or "error" in rec:
            continue
        rows.append(rec)
    out = [f"#### Mesh `{mesh}` ({rows[0]['n_devices'] if rows else '?'} chips)",
           "",
           "| arch | shape | kind | params | args GiB/dev | temp GiB/dev | HLO GFLOP/dev | wire GiB/dev | AR/AG/RS/A2A/CP execs | compile s |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        d = r["dynamic"]["collectives"]
        execs = "/".join(str(d[k]["count"]) for k in
                         ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute"))
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['kind']} | "
            f"{r['n_params']/1e9:.2f}B | {fmt_bytes(r['memory']['argument_bytes'])} | "
            f"{fmt_bytes(r['memory']['temp_bytes'])} | "
            f"{r['dynamic']['flops']/1e9:.0f} | "
            f"{roofline.wire_bytes(r)/2**30:.2f} | {execs} | {r['compile_s']} |")
    return "\n".join(out)


def roofline_table(mesh: str) -> str:
    rows = [r for r in roofline.load_all() if r["mesh"] == mesh]
    out = ["| arch | shape | compute s | memory s | collective s | bottleneck | 6ND/HLO | proj. MFU | next lever |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.2e} | "
            f"{r['t_memory_s']:.2e} | {r['t_collective_s']:.2e} | "
            f"**{r['bottleneck']}** | {r['useful_flops_ratio']:.2f} | "
            f"{r['projected_mfu']:.1%} | {roofline.hint(r)} |")
    return "\n".join(out)


# ------------------------------------------------- BENCH_*.json summary
def _bench_headline(stem: str, rec) -> str:
    """One-line headline per trajectory file; unknown shapes degrade to a
    key listing instead of crashing the CI summary."""
    try:
        if stem == "BENCH_encode":
            r = rec[-1]
            return (f"k={r['k']} circulant {r['circulant_mbps']} MB/s "
                    f"({r.get('speedup_vs_interpret', '?')}x vs interpret)")
        if stem == "BENCH_checkpoint":
            r = rec[-1]
            return (f"k={r['k']} save {r['save_mbps']} MB/s, regenerate "
                    f"reads {r['restore']['regenerate']['frac_of_stored']} "
                    f"of stored")
        if stem == "BENCH_repair":
            r = rec["regeneration"][-1]
            bw = rec["repair_bandwidth"][-1]
            return (f"k={r['k']} fused {r['speedup_fused_vs_unfused']}x vs "
                    f"unfused; bandwidth saving vs EC "
                    f"{bw['saving_vs_ec']:.3f}")
        if stem == "BENCH_cluster":
            ratios = [s["repair_ratio_vs_rs"] for r in rec
                      for s in r["scenarios"]
                      if s["repair_ratio_vs_rs"] is not None]
            worst = max(ratios) if ratios else "n/a (no repair bytes)"
            lat = rec[-1]["degraded_read_latency"]["steady_s"]
            return (f"worst repair ratio vs RS {worst}; degraded read "
                    f"{lat * 1e3:.2f} ms steady")
        if stem == "BENCH_pipeline":
            rc = rec["recompiles"]
            return (f"k={rec['k']} mixed-size stream: store "
                    f"{rec['store']['speedup_vs_serial']}x / ckpt "
                    f"{rec['restore']['speedup_vs_serial']}x vs pre-plan "
                    f"serial; steady recompiles "
                    f"{rc['planned_steady_compiles']} (warmup "
                    f"{rc['planned_warmup_compiles']}); get p99 "
                    f"{rec['store']['get_latency_s']['p99']*1e3:.1f} ms")
        if stem == "BENCH_drills":
            oh = rec["checkpoint_overhead"]
            worst = max(r["resume_s"] for r in rec["time_to_resume"]["rows"])
            return (f"{len(rec['drills']['results'])} drills "
                    f"bit_exact={rec['all_bit_exact']} "
                    f"orphans={rec['orphans_total']}; write-behind ckpt "
                    f"+{oh['write_behind']['overhead_pct']}% vs stop-world "
                    f"+{oh['stop_world']['overhead_pct']}%; worst resume "
                    f"{worst*1e3:.0f} ms")
        if stem == "BENCH_serve":
            h = rec["healthy"]
            ab = rec["hedge_ab"]
            return (f"{h['req_per_s']} req/s healthy, p99 "
                    f"{h['latency']['p99_s']*1e3:.2f} ms; hedging cuts "
                    f"straggler p99 {ab['p99_cut']:.0%}; degraded failed="
                    f"{rec['degraded']['failed']}, corrupt served="
                    f"{rec['corrupt_storm']['corrupt_served']}, shed="
                    f"{rec['overload']['shed']} (typed)")
        if stem == "BENCH_shard":
            e4 = next((r for r in rec["encode"] if r["mesh"] == 4), None)
            bar = ("asserted" if rec["scaling_asserted"]
                   else f"skipped: {rec.get('scaling_skip_reason')}")
            if e4 is None:
                return f"no 4-device mesh ({bar})"
            return (f"4-device encode {e4['mbps']} MB/s "
                    f"({e4['speedup_vs_1dev']}x vs 1-device, 2x bar {bar}); "
                    f"parity_ok={rec['parity_ok']}, steady recompiles "
                    f"{rec['steady_recompiles']}")
        if stem == "BENCH_store":
            r = rec[-1]
            d = r["drain"][0]
            return (f"k={r['k']} put {r['put_mbps']} / get {r['get_mbps']} "
                    f"MB/s; drain {d['ticks']} ticks @ "
                    f"{d['budget_symbols_per_tick']} sym/tick, ratio_vs_rs "
                    f"{d['ratio_vs_rs']}")
        if stem == "BENCH_codes":
            fr = rec["frontier"]
            best = min(fr, key=lambda r: r["repair_ratio_vs_rs"])
            cv = rec["conversion"]
            return (f"{len(fr)} classes on frontier, best repair vs RS "
                    f"{best['repair_ratio_vs_rs']:.3f} "
                    f"({best['family']} n{best['n']}k{best['k']}"
                    f"d{best['d']}); convert {cv['mbps']} MB/s "
                    f"bit_exact={cv['bit_exact']} orphans={cv['orphans']}")
    except (KeyError, IndexError, TypeError) as e:
        return f"(unreadable: {type(e).__name__}: {e})"
    keys = list(rec) if isinstance(rec, dict) else f"{len(rec)} rows"
    return f"(unregistered trajectory file: {keys})"


def _bench_gap(stem: str, rec) -> str:
    """The overlap/roofline column (DESIGN.md §16.3): how close each
    hot path runs to its machine bound, so the trajectory of the gap is
    visible across PRs.  Files without the signal show a dash."""
    try:
        if stem == "BENCH_pipeline":
            ov = rec["overlap"]
            return (f"overlap {ov['overlap_speedup']}x, "
                    f"{ov['overlap_efficiency']:.0%} of bound "
                    f"({ov['host_parallelism']} CPU)")
        if stem == "BENCH_codes":
            fr = rec["frontier"]
            enc = max(r["roofline_frac_of_memcpy"] for r in fr)
            rep = max(r["repair_roofline_frac_of_memcpy"] for r in fr)
            dec = max(r["decode_roofline_frac_of_memcpy"] for r in fr)
            return (f"roofline enc {enc:.1%} / repair {rep:.1%} / "
                    f"decode {dec:.1%} of memcpy")
        if stem == "BENCH_repair":
            r = rec["regeneration"][-1]
            return f"fused repair {r['roofline_frac_of_memcpy']:.1%} of memcpy"
    except (KeyError, IndexError, TypeError):
        pass
    return "—"


# Every trajectory file the fast sweep is expected to produce; a missing
# one gets an explicit skip row instead of silently vanishing from the
# table (a CI summary that shrinks should be loud about why).
EXPECTED_BENCH = ("BENCH_encode", "BENCH_checkpoint", "BENCH_repair",
                  "BENCH_cluster", "BENCH_pipeline", "BENCH_drills",
                  "BENCH_serve", "BENCH_shard", "BENCH_store",
                  "BENCH_codes")


def bench_table() -> str:
    """Markdown summary of every repo-root BENCH_*.json — the one table
    the CI bench-smoke job prints after the fast sweep.  Expected files
    that are absent get a skip-with-notice row; unexpected extras are
    still summarized."""
    out = ["| trajectory file | headline | overlap / roofline |",
           "|---|---|---|"]
    files = sorted(REPO_ROOT.glob("BENCH_*.json"))
    if not files:
        return "(no repo-root BENCH_*.json found — run benchmarks.run first)"
    present = {f.stem for f in files}
    for f in files:
        rec = json.loads(f.read_text())
        out.append(f"| `{f.name}` | {_bench_headline(f.stem, rec)} | "
                   f"{_bench_gap(f.stem, rec)} |")
    for stem in EXPECTED_BENCH:
        if stem not in present:
            out.append(f"| `{stem}.json` | (missing — run "
                       f"`PYTHONPATH=src python -m benchmarks.run --fast`) | "
                       f"— |")
    return "\n".join(out)


def refresh_dynamics():
    """Recompute every artifact's `dynamic` block from its stored .hlo.gz —
    lets analyzer improvements apply without recompiling 66 cells."""
    import gzip
    import sys as _sys
    _sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))
    from repro.launch import hlo_stats
    n = 0
    for f in sorted(RESULTS.glob("*.json")):
        hlo = f.with_suffix(".hlo.gz")
        if not hlo.exists():
            continue
        rec = json.loads(f.read_text())
        with gzip.open(hlo, "rt") as fh:
            dyn = hlo_stats.analyze(fh.read())
        rec["dynamic"] = {"flops": dyn["flops"], "hbm_bytes": dyn["hbm_bytes"],
                          "collectives": dyn["collectives"]}
        f.write_text(json.dumps(rec, indent=2))
        n += 1
    print(f"refreshed {n} artifacts")


def main():
    if "--refresh" in sys.argv:
        refresh_dynamics()
        return
    if "--bench" in sys.argv:
        print("### Benchmark trajectory (repo-root BENCH_*.json)\n")
        print(bench_table())
        return
    print("<!-- generated by benchmarks/report.py -->")
    print("\n### Dry-run ledger\n")
    print(dryrun_table("pod16x16"))
    print()
    print(dryrun_table("pod2x16x16"))
    print("\n### Roofline (single pod, 256 chips)\n")
    print(roofline_table("pod16x16"))
    print("\n### Roofline (multi-pod, 512 chips)\n")
    print(roofline_table("pod2x16x16"))


if __name__ == "__main__":
    main()
