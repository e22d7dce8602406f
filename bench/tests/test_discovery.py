"""A cell, its driver and a per-layer metric are found by the names in
``BENCHMARK.json``: adding them takes new files and entries, and no edit
to a file the harness already has."""

import json

import harness

DRIVER = '''
import time


def make(ctx):
    return Dummy(ctx)


class Dummy:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self):
        self.n = self.ctx.cell.traffic["work"]

    def window(self, seconds):
        t0 = time.perf_counter()
        done = 0
        while time.perf_counter() - t0 < seconds:
            with self.ctx.spans.span("step"):
                done += self.n
            self.ctx.count("items", self.n)
        return {"attempted": done, "failed": 0,
                "metrics": {"items_per_s": done / seconds}}

    def telemetry(self):
        return {}

    def release(self):
        pass

    def check(self, precisions):
        return {"program": {"dummy_gap": 0.0},
                **{p: {"dummy_gap": 1.0} for p in precisions[1:]}}
'''

METRIC = '''
def read(run):
    n = run.counters.get("items")
    return None if not n else float(n)
'''


def _tree(tmp_path):
    bench = tmp_path / "bench"
    for sub, name, text in [
            ("configs", "dummy-config.json", json.dumps({"model": {}})),
            ("workloads", "dummy-traffic.json",
             json.dumps({"driver": "dummy_driver", "work": 3})),
            ("drivers", "dummy_driver.py", DRIVER),
            ("metrics", "items.counted.py", METRIC),
            ("limits", "dummy-cell.json", json.dumps({"dummy_gap": 0.5}))]:
        (bench / sub).mkdir(parents=True, exist_ok=True)
        (bench / sub / name).write_text(text)
    spec = {
        "workloads": [{"name": "dummy-cell", "config": "dummy-config",
                       "traffic": "dummy-traffic", "chips": 1,
                       "why": "a test"}],
        "end_to_end": [{"name": "items_per_s", "unit": "items/s",
                        "better": "higher", "bound": 0.1,
                        "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "items.counted", "unit": "items",
                       "better": "higher", "source": "program_counter",
                       "layer": "dummy", "moves": "items_per_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def test_new_cell_driver_and_metric_found_by_name(tmp_path):
    bench = _tree(tmp_path)
    cell = harness.Cell.find("dummy-cell", bench=bench)
    assert cell.traffic["driver"] == "dummy_driver"
    assert [m["name"] for m in cell.per_layer] == ["items.counted"]

    out = harness.run_cell(cell, 7, 0.05, False, require_tpu=False,
                           control=True, log=lambda m: None)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"items_per_s", "setup_s"}
    assert out["control"] == {"dummy_gap": 1.0}

    traced = harness.run_cell(cell, 7, 0.05, True, require_tpu=False,
                              out_dir=tmp_path / "out", log=lambda m: None)
    assert traced["metrics"]["items.counted"]["value"] > 0
    assert traced["checks"] == {"dummy_gap": {"value": 0.0, "limit": 0.5}}
