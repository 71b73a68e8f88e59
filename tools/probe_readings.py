#!/usr/bin/env python3
"""The two readings a probe tolerance is set between, on the chip:

    python3 tools/probe_readings.py --workload trinity-large.mixed-queue --seed N
    python3 tools/probe_readings.py --workload joyai-flash.long-decode --seed N
    python3 tools/probe_readings.py --workload lfm2-24b.busy-chat --seed N

(any cell whose job has ``build_server``, ``probe_engine``,
``probed_positions``, ``compare_probe`` and ``LOGITS_TOL``: ``serve_arch``,
``serve_latent``, ``serve_conv``)

1. what the engine gives: its logits at the probed positions against the
   plain float32 reference (the comparison that decides a run's ``correct``);
2. what the reference itself gives when every matrix is first rounded to the
   nearest precision below the one the configuration states
   (``--mantissa-bits``: 3, a scaled float8 e4m3, under bfloat16's 7),
   against the reference at the stated one.
   This one has to come out as NOT correct by the cell's limit.

Both go through ``compare_probe``, so each reads ``correct`` true or false
as a run of the cell would. Prints one JSON line. Meant for the chip: the published widths are far too
large for a CPU run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def readings(config: dict, traffic: dict, job, seed: int,
             mantissa_bits: int = 3) -> dict:
    import numpy as np
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    with tempfile.TemporaryDirectory(prefix="probe_readings_") as workdir:
        server = job.build_server(config, seed, workdir)
        try:
            tsm = server.engine.target
            probe = job.probe_engine(server, config, traffic, seed)
            engine = compared(job, tsm, config, probe)
            # the control: the reference's own rows, its matrices rounded,
            # handed to the comparison in the engine's place
            low_stats = {}
            low = ref.logits(ref.weights_of(tsm), probe["tokens"],
                             rows=job.probed_positions(probe),
                             engine_routes=probe["routes"], stats=low_stats,
                             rounding=mantissa_bits)
            control = compared(job, tsm, config,
                               dict(probe, rows=list(np.asarray(low))))
        finally:
            server.close()
    return {"seed": seed, "limit": job.LOGITS_TOL, "engine": engine,
            "mantissa_bits": mantissa_bits, "rounded_reference": control,
            "rounded_reference_route": low_stats}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="trinity-large.mixed-queue")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mantissa-bits", type=int, default=3)
    args = ap.parse_args()

    from benchmark import cells
    cell = cells.load_cell(args.workload)
    import paddle_tpu  # noqa: F401  (points jax at the compile cache)
    print(json.dumps({"workload": args.workload, **readings(
        cell["config"], cell["traffic"], cell["job"], args.seed,
        args.mantissa_bits)}), flush=True)


def compared(job, tsm, config, probe) -> dict:
    """What ``compare_probe``, the comparison that decides a run's
    ``correct``, says of ``probe``."""
    stats = {}
    try:
        job.compare_probe(tsm, config, probe, stats=stats)
        fault = None
    except AssertionError as e:
        fault = str(e)[:200]
    return {"correct": fault is None, "fault": fault, **stats}


if __name__ == "__main__":
    main()
