#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and traffic mix, and the metrics it reports
are found by name from ``BENCHMARK.json``.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared with the reference beside its limit).
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, os.path.join(REPO, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("stale_views",), default=None,
                    help="run the cell with the stated guarantee broken "
                         "(the comparison must then fail)")
    ap.add_argument("--save-events", default=None,
                    help="with --trace 1: write the flattened trace here")
    args = ap.parse_args(argv)

    from bench.lib import registry
    bm = registry.load_benchmark()
    cell = registry.workload(bm, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from bench.lib.cell import run_cell
    run, out = run_cell(cell["name"], registry.config(cell["config"]),
                        registry.traffic(cell["traffic"]), args.seed,
                        args.seconds, bool(args.trace), T_START,
                        control=args.control, save_events=args.save_events)
    metrics = registry.read_metrics(
        registry.metrics_for(bm, cell["name"], bool(args.trace)), run)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
