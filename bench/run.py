"""Benchmark harness: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data.  The cell names a configuration (``bench/configs/<name>
.json``: the model and env as run) and a traffic mix (``bench/traffic/
<name>.json``: the algorithm's sizes and the driver that runs it,
``bench/drivers/<driver>.py``).  Limits of the correctness check are in
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration, traffic mix,
driver or metric is new files plus new ``BENCHMARK.json`` entries.

A run: set-up builds the driver's program from the seed and drives it
through its first steps (compiling every shape the window uses), then the
window calls the driver's unit (an iteration, a round) back to back for
``--seconds``, both ends behind ``block_until_ready``.  With ``--trace 1``
the profiler records the first ``trace_seconds`` (traffic file) of the
window and the run reports the per-layer metrics; otherwise the
end-to-end ones.  After the window the program's state is freed and the
plain reference replays the first steps from the seed; the numbers
compared, each beside its limit, are the last lines on stderr and the
``checks`` key of the result, the last line on stdout.

Exits 2 and prints no result when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse                                          # noqa: E402
import contextlib                                        # noqa: E402
import gc                                                # noqa: E402
import importlib                                         # noqa: E402
import json                                              # noqa: E402
import math                                              # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402
from pathlib import Path                                 # noqa: E402
from types import SimpleNamespace                        # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the program's own fixed cache path (launch/compile_cache.py), inside the
# checkout: only the first run of a cell there compiles
CACHE_DIR = ROOT / ".jax_cache"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------- the cell --
def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return SimpleNamespace(
        name=name, chips=cell["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json")
            .read_text()),
        limits=json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer)


def chip_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def enable_cache():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however quick to compile: the eager A3C trainer's
    # small op-by-op programs would otherwise compile in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------------ a run --
def judge(numbers: dict, limits: dict):
    """Each number the limits file names against its limit; a named number
    that is missing or not finite fails.  Numbers the file does not name
    are not compared (``PERF.md`` says why for each)."""
    checks = {k: {"value": numbers.get(k, math.nan), "limit": lim}
              for k, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             process_start: float = PROCESS_START, driver=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line's dict.
    ``driver`` substitutes the driver class (the fault tests use it)."""
    import jax

    from benchlib import training_check
    from benchlib.compile_meter import CompileMeter
    from benchlib.trace import WINDOW_SPAN, find_xplane, reduce_trace

    traffic = cell.traffic
    mod = importlib.import_module(f"drivers.{traffic['driver']}")
    meter = CompileMeter()
    span = jax.profiler.TraceAnnotation
    with span("bench.setup"):
        drv = (driver or mod.Driver)(cell.config, traffic, seed)
        drv.first_steps(traffic["first_steps"])
    log(f"setup: {meter.compiles} backend compiles in {meter.seconds!r} s, "
        f"{meter.hits} persistent-cache hits ({CACHE_DIR})")
    unit = f"bench.{mod.UNIT}"
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    traced = None
    window = contextlib.ExitStack()

    t0 = time.perf_counter()
    setup_s = t0 - process_start
    compiles0, samples0, units0 = meter.compiles, drv.trained_samples(), \
        drv.units
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
        window.enter_context(span(WINDOW_SPAN))
    traced_from = time.perf_counter()
    while True:
        with span(unit):
            drv.step()
        now = time.perf_counter()
        if trace and traced is None and (
                now - t0 >= traffic["trace_seconds"] or now - t0 >= seconds):
            with span("bench.sync"):
                drv.sync()
            window.close()
            traced = {"seconds": time.perf_counter() - traced_from,
                      "units": drv.units - units0,
                      "samples": drv.trained_samples() - samples0}
            jax.profiler.stop_trace()
        if now - t0 >= seconds:
            break
    drv.sync()
    t1 = time.perf_counter()
    window_compiles = meter.compiles - compiles0
    samples = drv.trained_samples() - samples0
    units = drv.units - units0
    log(f"window: {units} {mod.UNIT}s, {samples} trained samples in "
        f"{t1 - t0!r} s; {window_compiles} backend compiles inside")

    with span("bench.finish"):
        numbers = drv.end_window()
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    attempted, failed = drv.attempted, drv.failed
    first = drv.first
    drv.release()
    gc.collect()
    t = time.perf_counter()
    numbers.update(training_check.numbers(first, drv.reference()))
    log(f"reference: {time.perf_counter() - t!r} s")
    correct, checks = judge(numbers, cell.limits["numbers"])

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct and failed == 0, "attempted": attempted,
              "failed": failed}
    if trace:
        from benchlib.peaks import peaks_for
        red = reduce_trace(find_xplane(trace_dir.name), chips=len(devices))
        trace_dir.cleanup()
        ctx = SimpleNamespace(
            reduction=red, traced=traced, chips=len(devices),
            peaks=peaks_for(d0.device_kind),
            flops_per_sample=drv.flops_per_sample,
            kernel_shapes=drv.kernel_shapes,
            window={"seconds": t1 - t0, "units": units, "samples": samples,
                    "compiles": window_compiles})
        read = {m["name"]: (importlib.import_module(f"metrics.{m['name']}")
                            .read(ctx), m["unit"]) for m in cell.per_layer}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.top_gaps()}
    else:
        read = {m["name"]: (
            {"train_samples_per_s": samples / (t1 - t0),
             "setup_s": setup_s}[m["name"]], m["unit"])
            for m in cell.end_to_end}
    result["metrics"] = {k: {"value": v, "unit": u}
                         for k, (v, u) in read.items() if v is not None}
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    enable_cache()
    try:
        devices = chip_devices(cell.chips)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
