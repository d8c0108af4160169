"""The four-chip cell at a tiny size on four virtual CPU devices, each
run in a fresh interpreter (the device count is fixed when JAX starts),
with the harness's look for a chip passed by the test alone."""
import json
import math
import os
import subprocess
import sys
import textwrap

from conftest import ROOT, TINY_CLIENTS, TINY_UPLOAD

CELL = "rmnist4800-km.refresh"
HERE = os.path.dirname(os.path.abspath(__file__))


def on_four_devices(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([HERE, ROOT,
                                           os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=HERE)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


MEASURE = """
    import json
    import tempfile
    from conftest import cpu_chip, make_tiny_root
    from bench import run

    root = make_tiny_root(tempfile.mkdtemp())

    def measure(trace):
        args = run.parse(["--workload", "%s", "--seed", "3123456789",
                          "--seconds", "2", "--trace", trace])
        return run.measure(args, root=root, require=cpu_chip)
""" % CELL


def test_the_mesh_cell_runs_correct_and_reports_its_metrics():
    out = on_four_devices(MEASURE + """
    plain, traced = measure("0"), measure("1")
    print(json.dumps({"plain": plain["line"], "traced": traced["line"],
                      "notes": plain["notes"]}))
    """)
    plain, traced = out["plain"], out["traced"]
    assert plain["correct"] is True, plain["checks"]
    assert traced["correct"] is True, traced["checks"]
    assert set(plain["metrics"]) == {"round_s", "setup_s"}
    assert plain["metrics"]["round_s"]["value"] > 0
    assert plain["device"]["count"] == 4
    assert len(out["notes"]["memory_peak_bytes_per_chip"]) == 4
    assert out["notes"]["compiles_in_window"] == 0
    wave = 0.1 * TINY_CLIENTS * 4 * sum(
        math.prod(shape) for shape in TINY_UPLOAD.values())
    metrics = traced["metrics"]
    assert abs(metrics["ingest.device_mb_per_wave"]["value"]
               - 4 * wave / 1e6) < 1e-9
    assert metrics["round.mesh_mb"]["value"] > 0
    assert "ingest.wave_ms" in metrics and "round.lloyd_iters" in metrics


def test_one_shard_left_out_of_the_mean_is_not_correct():
    """The mean over every client row except the last shard's (a
    quarter of the clients on four devices)."""
    out = on_four_devices(MEASURE + """
    import jax
    import jax.numpy as jnp
    from repro.core.engine import aggregate

    def three_shards(params, labels, onehot, counts, aggregator):
        rows = onehot.shape[0]
        kept = onehot * (jnp.arange(rows) < rows - rows // 4)[:, None]
        n = jnp.maximum(kept.sum(0), 1.0)[:, None]

        def back(leaf):
            flat = leaf.reshape(leaf.shape[0], -1)
            means = jnp.dot(kept.T, flat, precision="highest") / n
            return jnp.dot(onehot, means,
                           precision="highest").reshape(leaf.shape)

        return jax.tree_util.tree_map(back, params)

    aggregate.cluster_aggregate_tree = three_shards
    print(json.dumps(measure("0")["line"]))
    """)
    assert out["correct"] is False
    assert out["checks"]["model_err"]["value"] > \
        out["checks"]["model_err"]["limit"]
