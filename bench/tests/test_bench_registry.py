"""The harness finds configurations, traffic mixes and metric readers by
file name, so a new one is picked up without editing a file."""
import json
import os
import shutil

import numpy as np

from bench.lib import registry
from bench.lib.cell import Run


def _empty_run():
    return Run(cell="x", setup_s=1.0, window_s=2.0,
               read_lat_s=np.asarray([0.1, 0.2, 0.3]), writes_acked=0,
               steps={"read": (0.3, 3)}, read_tickets=3, read_windows=3,
               fences=0, compiles_in_window=0, views_build_s=0.5, warm_s=0.2)


def test_every_name_in_benchmark_json_has_its_file():
    bm = registry.load_benchmark()
    for w in bm["workloads"]:
        cfg = registry.config(w["config"])
        assert cfg["name"] == w["config"]
        assert registry.traffic(w["traffic"])["readers"] >= 0
        for trace in (False, True):
            for m in registry.metrics_for(bm, w["name"], trace):
                assert callable(registry.metric_reader(m["name"]))
    for c in bm["configs"]:
        assert os.path.exists(os.path.join(registry.REPO, c["file"]))


def test_metrics_of_a_cell_follow_workloads_keys():
    bm = registry.load_benchmark()
    e2e = {m["name"] for m in registry.metrics_for(
        bm, "snb_s1.read_point", False)}
    assert e2e == {"read_qps", "read_p50_ms", "read_p95_ms", "setup_s"}
    layer = {m["name"] for m in registry.metrics_for(
        bm, "finbench_s1.transfer_write", True)}
    assert "maint.fence_step_ms" in layer and "serve.window_ms.read" not in layer
    got = registry.read_metrics(registry.metrics_for(
        bm, "snb_s1.read_point", False), _empty_run())
    assert got["read_qps"] == {"value": 1.5, "unit": "reads/s"}
    assert got["read_p50_ms"]["value"] == 200.0


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(registry.BENCH / "configs", root / "configs")
    shutil.copytree(registry.BENCH / "traffic", root / "traffic")
    shutil.copytree(registry.BENCH / "metrics", root / "metrics")
    shutil.copytree(registry.BENCH / "generators", root / "generators")
    (root / "generators" / "new_gen.py").write_text(
        "def generate(seed, n, **_):\n    return [seed] * n\n")
    assert registry.generator("new_gen", root)(7, n=2, other=1) == [7, 7]
    (root / "configs" / "new_cfg.json").write_text(json.dumps({"name": "n"}))
    (root / "traffic" / "new_mix.json").write_text(json.dumps({"readers": 3}))
    (root / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return run.window_s * 10\n")
    assert registry.config("new_cfg", root)["name"] == "n"
    assert registry.traffic("new_mix", root)["readers"] == 3
    entries = [{"name": "new.metric", "unit": "s"},
               {"name": "new.metric.read", "unit": "s"},
               {"name": "write_ack_ms", "unit": "ms"}]
    # a reader that finds nothing (no writes) leaves its metric out, and a
    # quantity split by cell falls back to the quantity's reader
    assert registry.read_metrics(entries, _empty_run(), root) == {
        "new.metric": {"value": 20.0, "unit": "s"},
        "new.metric.read": {"value": 20.0, "unit": "s"}}
