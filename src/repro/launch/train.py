"""Federated training driver over the LM-scale method registry.

Runs any registered ``FederatedMethod`` (``core.federated_methods``) on
a clustered LM federation: ODCL's one-shot protocol (local training, ONE
clustered aggregation round, optional personalized fine-tuning), the
iterative IFCA baseline, global FedAvg, or local-only — selected with
``--method``; new methods registered via ``register_federated_method``
appear in the flag automatically.

Production: launch one process per host with the production mesh and
``--arch <id>``; this container (CPU, 1 device) runs the same driver
with ``--reduced`` for the end-to-end example.

  # Algorithm 1, host clustering (ODCL-KM++):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --clients 8 --clusters 2 --local-steps 100

  # same protocol, the whole round jitted on-device (add --restarts /
  # --batch-m for multi-restart or minibatch Lloyd at huge C):
  PYTHONPATH=src python -m repro.launch.train --reduced \
      --method odcl --engine device --algo kmeans++ --restarts 4

  # ODCL-CC on-device: K-free convex clustering in the jitted round
  PYTHONPATH=src python -m repro.launch.train --reduced \
      --method odcl --engine device --algo convex

  # the iterative baseline the paper compares against (R rounds);
  # --ifca-carry-opt carries per-cluster Adam moments across rounds
  PYTHONPATH=src python -m repro.launch.train --reduced \
      --method ifca --rounds 5 --local-steps 10 --warmup-steps 40 \
      --ifca-carry-opt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs, runtime
from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.core.federated import evaluate_per_client, init_federation
from repro.core.federated_methods import (
    build_federated_method,
    cluster_agreement,
    list_federated_methods,
)
from repro.core.clustering import list_algorithms
from repro.core.engine.aggregators import list_aggregators
from repro.data import ClusteredTokenStream, make_lm_batch_iterator
from repro.launch.steps import make_eval_batch
from repro.optim import AdamWConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family variant")
    ap.add_argument("--method", default="odcl",
                    choices=list(list_federated_methods()),
                    help="registered FederatedMethod to run")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=100)
    ap.add_argument("--post-steps", type=int, default=20,
                    help="continued local steps after aggregation (odcl)")
    ap.add_argument("--rounds", type=int, default=5,
                    help="communication rounds (ifca / fedavg)")
    ap.add_argument("--warmup-steps", type=int, default=0,
                    help="pure local steps before the round loop (ifca)")
    ap.add_argument("--ifca-assign", choices=("loss", "sketch"),
                    default="loss", dest="assign",
                    help="IFCA cluster-estimate rule")
    ap.add_argument("--ifca-carry-opt", action="store_true",
                    dest="carry_opt_state",
                    help="FedOpt-style IFCA: carry per-cluster Adam "
                         "moments across rounds (averaged server-side "
                         "with the parameters) instead of re-initializing "
                         "every client's optimizer each round")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--algo", default="kmeans++",
                    choices=list(list_algorithms()),
                    help="admissible clustering algorithm; with --engine "
                         "device the Lloyd names map onto kmeans-device "
                         "and convex/clusterpath onto their -device twins")
    ap.add_argument("--engine", choices=("host", "device"), default="host",
                    help="device = run the whole one-shot round jitted "
                         "on-device (engine.one_shot_aggregate_device)")
    ap.add_argument("--restarts", type=int, default=1,
                    help="multi-restart Lloyd for the device kmeans "
                         "family: vmap this many inits and keep the "
                         "best-inertia clustering")
    ap.add_argument("--batch-m", type=int, default=None,
                    help="minibatch Lloyd: sample this many sketch rows "
                         "per iteration (device kmeans family; >= C runs "
                         "full Lloyd bit-exactly)")
    ap.add_argument("--sketch-dim", type=int, default=128)
    ap.add_argument("--aggregator", default="mean",
                    choices=list(list_aggregators()),
                    help="per-cluster step-3 reduction (odcl / ifca round "
                         "averaging): mean, or a robust registry variant")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write every obs span/event (engine round, "
                         "per-round comm) of this run as JSONL")
    args = ap.parse_args(argv)
    runtime.use_compile_cache()
    if args.trace:
        obs.add_sink(obs.JsonlSink(args.trace))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(max_vocab=256)
    print(f"arch={cfg.name} d_model={cfg.d_model} L={cfg.n_layers} "
          f"vocab={cfg.vocab_size} clients={args.clients} "
          f"true_clusters={args.clusters} method={args.method}")

    stream = ClusteredTokenStream(
        n_clients=args.clients, n_clusters=args.clusters,
        vocab_size=cfg.vocab_size, seed=args.seed)
    batches = make_lm_batch_iterator(
        stream, clients_per_batch=list(range(args.clients)),
        per_client_batch=args.batch, seq_len=args.seq_len)

    def batch_iter():
        for toks, labels in batches:
            yield {"tokens": toks, "labels": labels}

    it = batch_iter()
    opt = AdamWConfig(lr=args.lr, weight_decay=0.0)
    state = init_federation(jax.random.PRNGKey(args.seed), cfg, args.clients)

    algo_options = {}
    if args.restarts > 1:
        algo_options["restarts"] = args.restarts
    if args.batch_m is not None:
        algo_options["batch_m"] = args.batch_m
    if algo_options and (args.engine != "device"
                         or args.algo.startswith(("convex", "clusterpath"))):
        # the registry adapters swallow unknown options, so say it loudly
        # rather than let the knobs silently no-op
        print(f"[warn] {sorted(algo_options)} only apply to the device "
              f"kmeans family; ignored for --engine {args.engine} "
              f"--algo {args.algo}")
        algo_options = {}

    # one flat kwargs superset — build_federated_method keeps only the
    # fields the chosen method declares (registry stays ladder-free)
    method = build_federated_method(
        args.method, algorithm=args.algo, k=args.clusters,
        engine=args.engine, sketch_dim=args.sketch_dim,
        algo_options=algo_options or None,
        local_steps=args.local_steps, post_steps=args.post_steps,
        rounds=args.rounds, warmup_steps=args.warmup_steps,
        assign=args.assign, carry_opt_state=args.carry_opt_state,
        aggregator=args.aggregator, opt=opt, seed=args.seed)

    t0 = time.time()
    res = method.run(jax.random.PRNGKey(args.seed), state, cfg, it)
    elapsed = time.time() - t0
    for r in res.round_metrics:
        print(f"[{method.name}] {r}")
    agreement = cluster_agreement(res.labels, stream.true_labels)
    print(f"[{method.name}] {elapsed:.1f}s  rounds={res.comm_rounds:g} "
          f"comm={res.comm_bytes / 1e6:.2f}MB  K'={res.n_clusters} "
          f"cluster purity={agreement:.3f} labels={res.labels.tolist()}")

    eval_batch = make_eval_batch(stream, n_clients=args.clients,
                                 batch=args.batch, seq_len=args.seq_len)
    final_eval = evaluate_per_client(res.state, cfg, eval_batch)
    print(f"[eval] per-client loss {final_eval.mean():.4f} "
          f"(min {final_eval.min():.4f} max {final_eval.max():.4f})")

    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, res.state.step, res.state.params)
        print(f"[ckpt] saved {path}")
    return res.state, res.labels


if __name__ == "__main__":
    main()
