"""The whole run of every cell, in-process on the CPU at a tiny size,
with the harness's look for a chip passed by the test alone."""
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

from conftest import ROOT, cpu_chip

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in BENCH["workloads"] if c["chips"] == 1]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(root, cell, seconds="2", trace="0", seed="4000000017"):
    from bench import run

    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", seed, "--seconds",
                       seconds, "--trace", trace], root=root,
                      require=cpu_chip)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_prints_the_contract_line(tiny_root, cell):
    line = run_cell(tiny_root, cell)
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def test_a_traced_run_reports_the_device_window(tiny_root):
    line = run_cell(tiny_root, "rmnist-km.refresh", trace="1")
    assert line["correct"] is True
    assert line["device"]["window_s"] > 0 and "busy_s" in line["device"]
    # what the CPU's trace cannot hold (device ops) is left out, the
    # program's spans and counters are read
    assert "ingest.wave_ms" in line["metrics"]
    assert "round.lloyd_iters" in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_perturbed_served_model_is_not_correct(tiny_root, monkeypatch):
    from bench import generator

    output = generator.Federation.output

    def perturbed(self, rounds):
        out = output(self, rounds)
        for rnd in out["rounds"]:
            if rnd["models"] is not None:
                rnd["models"] = {k: v + 1e-3
                                 for k, v in rnd["models"].items()}
        return out

    monkeypatch.setattr(generator.Federation, "output", perturbed)
    line = run_cell(tiny_root, "rmnist-km.refresh")
    assert line["correct"] is False
    assert line["checks"]["model_err"]["value"] > 5e-4


def test_models_are_kept_for_a_bounded_seeded_sample_of_rounds(tiny_root,
                                                               monkeypatch):
    """Every round's labels are compared; the models of at most
    ``MODEL_SAMPLE`` rounds, drawn from the seed, are held."""
    from bench import generator

    monkeypatch.setattr(generator, "MODEL_SAMPLE", 2)
    line = run_cell(tiny_root, "rmnist-km.refresh")
    assert line["correct"] is True
    assert line["checks"]["rounds_checked"]["value"] > 2
    assert line["checks"]["model_rounds_checked"]["value"] <= 2


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmnist-km.refresh",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def _add_cell(root, name, traffic, metric=None):
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": name, "config": "rmnist-mlp-km",
                               "traffic": traffic, "chips": 1,
                               "why": "added by a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rmnist-km.refresh" in m.get("workloads", []):
            m["workloads"].append(name)
    if metric:
        bench["per_layer"].append(dict(metric, workloads=[name]))
    json.dump(bench, open(path, "w"))


def test_new_cell_mix_and_metric_are_found_by_name(tiny_root):
    """A cell, a mix of an existing driver and a per-layer metric added
    as new files and new entries, with no existing file edited, are
    picked up: the mix is a data file alone."""
    traffic = os.path.join(tiny_root, "bench", "traffic")
    mix = json.load(open(os.path.join(traffic, "refresh.json")))
    mix["wave_frac"] = 0.2
    json.dump(mix, open(os.path.join(traffic, "refresh-big.json"), "w"))
    with open(os.path.join(tiny_root, "bench", "metrics",
                           "ingest.waves.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run.obs['histograms']['session.ingest.ms']"
                "['count']\n")
    _add_cell(tiny_root, "rmnist-km.refresh-big", "refresh-big", {
        "name": "ingest.waves", "unit": "waves", "better": "higher",
        "source": "program_span", "layer": "session",
        "moves": "round_s"})
    line = run_cell(tiny_root, "rmnist-km.refresh-big", trace="1")
    assert line["correct"] is True
    assert line["metrics"]["ingest.waves"]["value"] > 0
    assert np.isfinite(line["metrics"]["ingest.wave_ms"]["value"])


def test_a_new_traffic_driver_is_found_by_name(tiny_root):
    """A new kind of traffic is a new driver file and a mix naming it."""
    traffic = os.path.join(tiny_root, "bench", "traffic")
    with open(os.path.join(traffic, "refresh_one.py"), "w") as f:
        f.write(
            "import os\n"
            "from bench import generator\n"
            "HERE = os.path.dirname(__file__)\n"
            "refresh = generator.driver(HERE.rsplit('/bench/', 1)[0],\n"
            "                           'refresh')\n"
            "\n"
            "def make(config, mix, seed):\n"
            "    open(os.path.join(HERE, 'made'), 'w').close()\n"
            "    mix = dict(mix, draws=1)\n"
            "    return refresh.Refresh(generator.Federation(config, seed,\n"
            "                                                mix), mix)\n")
    mix = json.load(open(os.path.join(traffic, "refresh.json")))
    mix["driver"] = "refresh_one"
    json.dump(mix, open(os.path.join(traffic, "refresh-one.json"), "w"))
    _add_cell(tiny_root, "rmnist-km.refresh-one", "refresh-one")
    line = run_cell(tiny_root, "rmnist-km.refresh-one")
    assert os.path.exists(os.path.join(traffic, "made"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"round_s", "setup_s"}
