"""Tiny versions of the benchmark's cells, for CPU tests."""
import time

from bench.lib import registry
from bench.lib.cell import run_cell

READ_CELL = "snb_s1.read_point"
WRITE_CELL = "finbench_s1.transfer_write"


def tiny(cell: str, traffic=None):
    """The cell's configuration and traffic at a size a test can hold:
    node counts cut, three of the seven reads, few clients, short warm-up."""
    bm = registry.load_benchmark()
    w = registry.workload(bm, cell)
    cfg = registry.config(w["config"])
    tr = registry.traffic(w["traffic"])
    scale = 0.01 if w["config"] == "snb_s1" else 0.03
    for k in cfg["reduced"]:
        cfg[k] = max(int(cfg[k] * scale), 8)
    tr.update(readers=min(tr["readers"], 12), reads=[0, 2, 4],
              schedule_len=64, check_share=0.25, pool=min(tr["pool"], 1),
              warm_rows=[8])
    tr["warm"] = {"min_steps": 4, "quiet_steps": 4, "max_s": 60}
    tr.update(traffic or {})
    return cfg, tr


def run(cell: str, seed: int = 1234567890123, seconds: float = 1.0,
        control=None, traffic=None):
    cfg, tr = tiny(cell, traffic)
    if cell == WRITE_CELL:
        seconds = max(seconds, 3.0)
    return run_cell(cell, cfg, tr, seed, seconds, False, time.perf_counter(),
                    control=control)
