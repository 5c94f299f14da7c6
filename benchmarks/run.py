# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows.  Mapping to the paper:
#   bench_serving        — Fig 7(a)  TCG vs TDG serving throughput, plus
#                          repro.serve engine rows (tok/s, p50/p95 under
#                          an open-loop arrival trace)
#   bench_sync_training  — Fig 7(b,c) sync PPO: holistic GMI vs dedicated
#   bench_lgr            — Table 7   LGR (MRR/HAR) vs MPR baseline
#   bench_mcc            — Table 8   multi-channel vs uni-channel sharing
#   bench_num_env        — Fig 10    throughput/memory vs num_env
#   bench_async          — Fig 11    async PPS / TTOP
#   bench_selection      — Alg 2     profiling-based GMI search
#   bench_backend        — Fig 8     backend isolation comparison
#   bench_reward         — Fig 9     reward accumulation over time
#   bench_kernels        — Pallas kernels (interpret-mode correctness cost)
#   bench_calibration    — Table-2 bandwidth calibration (synthetic
#                          recovery; rides in the lgr suite)
#   bench_faults         — fault-recovery cost (GMI kill / engine fail /
#                          checkpoint round-trip) + goodput retention
#   bench_disagg         — disaggregated prefill/decode serving: migrated
#                          vs local path, tok/s per role, migrate-vs-local
#                          crossover from measured Table-2 terms
#   roofline             — §Roofline terms from the dry-run artifacts
#
# Every invocation starts with the repro.analysis static pre-flight
# (python -m repro.analysis --strict): a tree with findings — tracked
# bytecode included — exits 1 before any suite runs, so it can never
# re-baseline a BENCH json.
#
# ``--quick`` runs only the perf-trajectory tier (bench_mcc + bench_kernels
# + bench_lgr + bench_serving + bench_faults + bench_disagg +
# bench_num_env, interpret mode on CPU),
# writes BENCH_*.json
# artifacts so
# future PRs have before/after numbers to diff against, and FAILS (exit 1)
# when any row regresses more than REGRESSION_FACTOR against the committed
# baseline — the perf trajectory is enforced, not advisory.  Re-baselining
# on a different machine: BENCH_ALLOW_REGRESSION=1 python -m benchmarks.run
# --quick.
import json
import os
import sys
import traceback

REGRESSION_FACTOR = 2.0

# allow both `python -m benchmarks.run` and `python benchmarks/run.py`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _parse_rows(rows):
    out = [dict(zip(("name", "us_per_call", "derived"), r.split(",", 2)))
           for r in rows]
    for r in out:
        r["us_per_call"] = float(r["us_per_call"])
    return out


def _dump_rows(path: str, suite: str, rows) -> None:
    payload = {"suite": suite, "rows": _parse_rows(rows)}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path}", file=sys.stderr)


def _check_regressions(path: str, rows, strict: bool = False) -> tuple:
    """Compare fresh rows against the committed baseline.

    Returns ``(regressions, missing)``: a timing row more than
    REGRESSION_FACTOR slower is a regression; ratio rows (us_per_call ==
    0) and rows new to this baseline are skipped.  ``missing`` lists
    baseline rows ABSENT from the fresh run — a deleted or renamed bench
    would otherwise hide its regression forever, because rewriting the
    baseline silently drops the old row.  Missing rows are warnings by
    default and additionally folded into ``regressions`` (i.e. failures)
    when ``strict``."""
    if not os.path.exists(path):
        return [], []
    with open(path) as f:
        base = {r["name"]: r["us_per_call"] for r in json.load(f)["rows"]}
    fresh = {r["name"]: r for r in _parse_rows(rows)}
    regs = []
    for r in fresh.values():
        old = base.get(r["name"], 0.0)
        if old > 0.0 and r["us_per_call"] > REGRESSION_FACTOR * old:
            regs.append(f"{r['name']}: {r['us_per_call']:.1f}us vs "
                        f"baseline {old:.1f}us "
                        f"({r['us_per_call'] / old:.2f}x > "
                        f"{REGRESSION_FACTOR}x)")
    missing = sorted(n for n in base if n not in fresh)
    # BENCH_PAGED_BASELINE=1: one-run escape hatch for the paged-serving
    # row reshuffle (serving_paged_*/serving_stall_*/disagg_page_* rows
    # replacing or joining older names) — strict missing-row failures
    # downgrade to warnings so the re-baseline run can rewrite the JSON
    if strict and not os.environ.get("BENCH_PAGED_BASELINE"):
        regs.extend(f"{n}: baseline row missing from this run (deleted "
                    f"or renamed bench? an intentional paged-serving row "
                    f"rename re-baselines with BENCH_PAGED_BASELINE=1)"
                    for n in missing)
    return regs, missing


def _analysis_findings(root: str) -> list:
    """Static-analysis pre-flight (``python -m repro.analysis``): the
    full rule battery, including the tracked-bytecode hygiene check that
    used to live here as a private ``git ls-files`` filter.  A violating
    tree can never run the suites, so it can never re-baseline a BENCH
    json."""
    from repro.analysis import run_analysis
    from repro.analysis.__main__ import DEFAULT_PATHS
    paths = [os.path.join(root, d) for d in DEFAULT_PATHS
             if os.path.isdir(os.path.join(root, d))]
    return run_analysis(paths, root=root)


def main() -> None:
    from benchmarks import (bench_async, bench_backend, bench_calibration,
                            bench_disagg, bench_faults, bench_kernels,
                            bench_lgr, bench_mcc, bench_num_env,
                            bench_reward, bench_selection, bench_serving,
                            bench_sync_training, roofline)
    from benchmarks.common import ROWS, emit

    findings = _analysis_findings(_ROOT)
    if findings:
        print("# STATIC ANALYSIS FINDINGS (python -m repro.analysis "
              "--strict; fix them or annotate `# repro: allow(<rule>)`):",
              file=sys.stderr)
        for f in findings:
            print(f"#   {f.format()}", file=sys.stderr)
        raise SystemExit(1)

    def lgr_suite():
        # calibration rows ride in the lgr suite: both land in
        # BENCH_lgr.json under the same regression gate
        bench_lgr.run()
        bench_calibration.run()

    def disagg_suite():
        # migrated-vs-local rows + the paged-wire rows (per-page migrate
        # cost, partial-migration crossover, shared-prefix bytes saved);
        # one BENCH_disagg.json under the same gate
        bench_disagg.run()
        bench_disagg.run_paged()

    def serving_suite():
        # Fig 7(a) TCG/TDG rows + the repro.serve continuous-batching
        # engine rows (tok/s, p50/p95 under an open-loop arrival trace);
        # both land in BENCH_serving.json under the regression gate
        bench_serving.run()
        bench_serving.run_engine()
        # paged-cache rows: paged tok/s + p50/p95, admitted concurrency
        # at a fixed cache budget (asserted > dense), decode-stall with
        # vs without chunked prefill (asserted smaller)
        bench_serving.run_paged()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    suites = [
        ("serving", serving_suite),
        ("sync_training", bench_sync_training.run),
        ("lgr", lgr_suite),
        ("mcc", bench_mcc.run),
        ("num_env", bench_num_env.run),
        ("async", bench_async.run),
        ("selection", bench_selection.run),
        ("backend", bench_backend.run),
        ("reward", bench_reward.run),
        ("kernels", bench_kernels.run),
        ("faults", bench_faults.run),
        ("disagg", disagg_suite),
        ("roofline", roofline.run),
    ]
    flags = {"--quick", "--strict"}
    args = [a for a in sys.argv[1:] if a not in flags]
    quick = "--quick" in sys.argv[1:]
    # strict: a baseline row missing from the fresh run (deleted/renamed
    # bench) is a gate FAILURE instead of a warning
    strict = "--strict" in sys.argv[1:] \
        or bool(os.environ.get("BENCH_STRICT"))
    only = args[0].split(",") if args else None
    if quick and only is None:
        only = ["mcc", "kernels", "lgr", "serving", "faults", "disagg",
                "num_env"]
        # an explicit selection wins; --quick then only adds the JSON
        # artifacts
    allow_regression = bool(os.environ.get("BENCH_ALLOW_REGRESSION"))
    failed = []
    regressions = []
    for name, fn in suites:
        if only and name not in only:
            continue
        start = len(ROWS)
        ok = True
        try:
            fn()
        except Exception as e:
            ok = False
            failed.append(name)
            emit(f"{name}_SUITE_FAILED", 0.0, repr(e)[:120])
            traceback.print_exc(file=sys.stderr)
        if quick and ok:
            path = f"BENCH_{name}.json"
            regs, missing = _check_regressions(path, ROWS[start:],
                                               strict=strict)
            for m in missing:
                print(f"# WARNING: {name}: baseline row {m!r} absent "
                      f"from this run — deleting/renaming a bench hides "
                      f"its regression (run with --strict to fail)",
                      file=sys.stderr)
            if regs and not allow_regression:
                # keep the last good baseline so the next run still has
                # something honest to diff against
                regressions.extend(regs)
                print(f"# NOT rewriting {path} (regressions)",
                      file=sys.stderr)
            else:
                # never clobber the last good baseline with a partial run
                _dump_rows(path, name, ROWS[start:])
    if regressions:
        print("# PERF REGRESSIONS (>"
              f"{REGRESSION_FACTOR}x vs committed baseline; "
              "set BENCH_ALLOW_REGRESSION=1 to re-baseline):",
              file=sys.stderr)
        for r in regressions:
            print(f"#   {r}", file=sys.stderr)
    if failed:
        print(f"# FAILED SUITES: {failed}", file=sys.stderr)
    if failed or regressions:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
