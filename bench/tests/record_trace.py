"""Record the chip trace that ``test_trace.py`` checks the reduction
against, with what the program was asked to run inside it.

    python3 bench/tests/record_trace.py     # on a TPU; writes
                                            # bench/tests/data/chip.xplane.pb
                                            # and chip.json

The session is ``rmnist-mlp-km``'s at full size: 2,400 keyed clients
uploading the 159,010-float MLP, sketched to 64 floats, finalized with
``kmeans-device`` (k=4) and served by a ``RouteServer`` (max_batch=64).
Every program is run once before the trace.  Inside it: one keyed ingest
wave of 240 clients, one warm ``refinalize(background=True)``, then 64
route requests sent one at a time through ``submit(params=...)``, so
that each is flushed alone.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

WAVE = 240
ROUTES = 64


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from bench import population
    from repro.core.engine import AggregationSession
    from repro.serving import RouteServer

    with open(os.path.join(ROOT, "bench", "configs",
                           "rmnist-mlp-km.json")) as f:
        config = json.load(f)
    clients = int(config["federation"]["clients"])
    _, draw = population.make_draw(config, 1, 0)
    server = RouteServer(AggregationSession(clients, sketch_dim=64, seed=1),
                         max_batch=64)

    def ingest(lo):
        server.ingest({k: v[lo:lo + WAVE] for k, v in draw.items()},
                      client_ids=range(lo, lo + WAVE))

    def client(i):
        return {k: v[i] for k, v in draw.items()}

    for lo in range(0, clients, WAVE):
        ingest(lo)
    server.finalize(algorithm="kmeans-device", k=4)
    # every program of the traced window, once
    ingest(0)
    server.refinalize(background=True).result(60)
    server.start()
    server.submit(params=client(0)).result(60)

    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.ingest"):
        ingest(WAVE)
    with jax.profiler.TraceAnnotation("bench.refinalize"):
        server.refinalize(background=True).result(60)
    for i in range(ROUTES):
        with jax.profiler.TraceAnnotation("bench.submit"):
            server.submit(params=client(i)).result(60)
    jax.profiler.stop_trace()
    server.stop(drain=True)
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "chip.xplane.pb"))
    with open(os.path.join(out, "chip.json"), "w") as f:
        json.dump({"recorded": "bench/tests/record_trace.py: an "
                   "rmnist-mlp-km session of 2,400 keyed clients, one "
                   "ingest wave of 240, one warm refinalize, 64 route "
                   "requests through submit(params=...) one at a time",
                   "ingest_waves": 1, "route_batches": ROUTES,
                   "route_kernel_rows": 8,
                   "lloyd_kernel_rows": -(-clients // 256) * 256,
                   "clusters": 4,
                   "device_kind": jax.devices()[0].device_kind}, f,
                  indent=1)
    print(json.dumps({"xplane_bytes": os.path.getsize(path)}))
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
