"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs[].file``, under ``bench/configs/``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names the module
``bench/traffic/<driver>.py`` that offers it).  Per-layer metrics are read by
``bench/metrics/<metric>.py``, each a ``read(run)`` that returns a number
or ``None`` when the run holds nothing for it to read.  All of them are
found by name: a new cell, mix or metric is a new file and a new entry.

The run builds the federation from ``--seed`` on the device, loads and
finalizes it, warms every shape its traffic uses (all of that is
``setup_s``), measures for ``--seconds``, and then checks what the timed
path produced against the plain reference (``bench/reference.py``).
With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries the per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.
The run exits non-zero, printing no result, unless JAX finds a TPU with
published peaks and as many chips as the cell asks for.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


# ------------------------------------------------------------ the cell


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(root: str, workload: str):
    """(benchmark, cell, configuration, mix) of a workload, by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = named(bench["workloads"], workload, "workload")
    entry = named(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(root, entry["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic",
                                 cell["traffic"] + ".json"))
    return bench, cell, config, mix


def listed(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def end_to_end(bench: dict, cell: dict) -> list:
    return [m for m in bench["end_to_end"] if listed(m, cell)]


def per_layer(bench: dict, cell: dict) -> list:
    moved = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if m["moves"] in moved and listed(m, cell)]


def reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ------------------------------------------------------------ the chip


def require_chips(jax, chips: int) -> dict:
    """Device description of the chips this cell runs on, or Refused."""
    from bench import costs

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, found platform {dev.platform!r} "
                      f"({dev.device_kind})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, found "
                      f"{len(devices)}")
    try:
        costs.peaks(dev.device_kind)
    except KeyError as exc:
        raise Refused(str(exc)) from None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


class RunView:
    """What a per-layer reader may read: the program's spans and
    counters over the window (``obs``), the reduced device trace
    (``trace``), the rounds the window installed, the cell and its
    configuration, and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# ------------------------------------------------------------ one run


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(args, *, root: str = ROOT, require=require_chips,
            control: bool = False) -> dict:
    """One run: the result line, notes for standard error and the
    checks.  With ``control`` it also holds the numbers that the control
    (the reference one precision step below, put in the program's place)
    reads on the same uploads and rounds."""
    bench, cell, config, mix = load_cell(root, args.workload)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout, set before JAX is imported; the program takes it from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    import jax

    device = require(jax, int(cell["chips"]))
    t_device = time.perf_counter() - _T0
    from repro import obs, runtime

    cache = runtime.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *a, **k: compiles.append(1)
        if event == BACKEND_COMPILE else None)

    from bench import costs, generator, reference, trace as tr

    driver = generator.make(root, config, mix, args.seed)
    driver.setup()
    fed = driver.fed
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if tdir:
        # host spans and device ops; the Python tracer would record
        # every Python call of the load generator
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
    obs.reset()
    n_compiles = len(compiles)
    setup_s = time.perf_counter() - _T0
    t_window = time.perf_counter()
    result = driver.window(args.seconds)
    in_window = len(compiles) - n_compiles
    reduced = None
    if tdir:
        window_s = time.perf_counter() - t_window
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = tr.reduce(paths[0], [str(d.id) for d in fed.devices])
        reduced["window_s"] = window_s
        shutil.rmtree(tdir, ignore_errors=True)
    snap = obs.snapshot()
    device["memory_peak_bytes"] = fed.memory_peak()
    output = driver.output()
    ref_inputs = fed.reference_inputs()
    view = RunView(obs=snap, trace=reduced, rounds=output["rounds"],
                   cell=cell, config=config, mix=mix,
                   peak=costs.peaks(device["kind"]), seconds=args.seconds)
    fed.close()
    del driver, fed
    gc.collect()

    numbers = reference.compare(output, ref_inputs)
    checks = reference.checks(numbers, config["limits"])
    correct = all(ok for *_, ok in checks) and not result["errors"]

    if args.trace:
        metrics = {}
        for m in per_layer(bench, cell):
            value = reader(root, m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in end_to_end(bench, cell)}
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if reduced is not None:
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, _, limit, _ in checks}
    notes = dict(result["notes"], compile_cache=cache,
                 compiles_in_window=in_window, setup_s=setup_s,
                 start_to_device_s=t_device,
                 errors=result["errors"], numbers=numbers)
    run = {"line": line, "notes": notes, "checks": checks}
    if control:
        run["control"] = reference.compare(output, ref_inputs, control=True)
    return run


def main(argv=None, **kw) -> int:
    args = parse(argv)
    try:
        run = measure(args, **kw)
    except Refused as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr, flush=True)
        return 2
    notes = run["notes"]
    print(f"compiles inside the window: {notes.pop('compiles_in_window')}",
          file=sys.stderr)
    print("notes " + json.dumps(notes, default=str), file=sys.stderr)
    for name, value, op, limit, ok in run["checks"]:
        print(f"check {name} {value!r} {op} {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(run["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
