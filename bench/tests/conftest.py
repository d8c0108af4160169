"""Helpers of the benchmark's own tests: a copy of the benchmark at a
size the CPU holds, and a stand-in for the harness's look for a chip.

Run them with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_UPLOAD = {"W1": [24, 8], "b1": [8], "W2": [8, 3], "b2": [3]}
TINY_CLIENTS = 80


def shrink(config: dict) -> dict:
    """The configuration at a size the CPU holds: fewer clients, narrower
    uploads; everything else as committed."""
    config = dict(config)
    config["federation"] = dict(config["federation"], clients=TINY_CLIENTS)
    config["upload"] = dict(TINY_UPLOAD)
    return config


def make_tiny_root(path) -> str:
    """A benchmark tree whose configurations are shrunk; the cells,
    mixes, metrics, readers and traffic drivers are the committed
    ones."""
    path = str(path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"),
                    os.path.join(path, "bench", "metrics"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for entry in bench["configs"]:
        dst = os.path.join(path, entry["file"])
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = shrink(json.load(f))
        with open(dst, "w") as f:
            json.dump(config, f)
    os.makedirs(os.path.join(path, "bench", "traffic"))
    for name in os.listdir(os.path.join(ROOT, "bench", "traffic")):
        if name.endswith((".py", ".json")):
            shutil.copy(os.path.join(ROOT, "bench", "traffic", name),
                        os.path.join(path, "bench", "traffic", name))
    return path


def cpu_chip(jax, chips):
    """The harness's look for a chip, passed on the CPU."""
    return {"platform": jax.devices()[0].platform,
            "kind": "TPU v5 lite", "count": chips}


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
