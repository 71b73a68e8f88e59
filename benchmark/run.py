#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (see the builder's
contract, quoted in PERF.md). Without a TPU that holds the cell's chips the
run exits non-zero and prints no result: there is no CPU continuation.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TRACE_SECONDS = 4.0                # a traced run profiles this much


def log(msg: str) -> None:
    print(f"{time.perf_counter() - T_START:7.1f}s {msg}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    from benchmark import cells, device, xplane
    manifest = cells.load_manifest()
    cell = cells.load_cell(args.workload, manifest)
    chips = cell["cell"]["chips"]
    dev = device.require_tpu(chips)

    import jax
    # keep every program in the persistent cache, not only the slow ones:
    # a serving run launches some hundreds of small ones (PR 21)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import paddle_tpu              # noqa: F401  (points jax at the cache dir)
    from paddle_tpu.framework.device import compile_cache_dir
    log(f"[run] {args.workload} seed={args.seed} seconds={args.seconds:g} "
        f"trace={args.trace} on {dev}; compile cache {compile_cache_dir()}")

    meter = device.CompileMeter()
    tracedir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    tracer = xplane.Tracer(tracedir, TRACE_SECONDS) if args.trace else None
    try:
        run = cell["job"].run(cell["config"], cell["traffic"], seed=args.seed,
                              seconds=args.seconds, chips=chips,
                              tracer=tracer, log=log, on_open=meter.mark)
        trace = tracer.summary() if tracer else None
    finally:
        if tracedir:
            shutil.rmtree(tracedir, ignore_errors=True)
    run["e2e"]["setup_s"] = run["t_open"] - T_START
    run["counters"]["compiles_in_window"] = meter.count - meter.marked
    run.update(trace=trace, config=cell["config"], traffic=cell["traffic"],
               peaks=device.peaks(dev["kind"]))
    log(f"[run] set-up {run['e2e']['setup_s']:.1f}s, {meter.count} programs "
        f"compiled or loaded ({meter.seconds:.1f}s), "
        f"{run['counters']['compiles_in_window']} of them inside the window")

    dev["memory_peak_bytes"] = device.memory_peak_bytes(chips)
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": {}, "device": dev}
    if args.trace:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = xplane.breakdown(trace)
        for name in cell["per_layer"]:
            got = cells.read_layer_metric(name, run)
            if got is not None:
                line["metrics"][name] = got
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        for name in cell["end_to_end"]:
            line["metrics"][name] = {"value": run["e2e"][name],
                                     "unit": units[name]}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
