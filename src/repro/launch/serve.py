"""Batched serving driver: prefill + autoregressive generation with the
KV-cache/recurrent-state serving path (per-cluster personalized models
from a federated checkpoint, or a fresh init).

Two ways to pick the served model from a stacked federated checkpoint:

  * ``--client i`` — the raw per-client slice (legacy behaviour);
  * ``--route-by-sketch`` — the paper's own serving rule: rebuild the
    cluster structure from the checkpoint through a streaming
    ``AggregationSession`` (ingest the stacked parameters, finalize the
    registered clustering over their sketches), route the requested
    client's sketch to its nearest recovered cluster, and serve that
    cluster's *averaged* model — step 4 of Algorithm 1 at serving time,
    which also handles clients the training run never saw.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16

  PYTHONPATH=src python -m repro.launch.serve --reduced --ckpt-dir ckpts \
      --route-by-sketch --clusters 2 --client 3

``--server`` upgrades --route-by-sketch into the concurrent serving
frontend: instead of one route for one client, the rebuilt session goes
behind a ``RouteServer`` and every checkpointed client's sketch is
routed by concurrent caller threads through the cross-caller batcher:

  PYTHONPATH=src python -m repro.launch.serve --reduced --ckpt-dir ckpts \
      --route-by-sketch --server --server-callers 4 --clusters 2
"""
from __future__ import annotations

import argparse
import time

from repro import runtime

runtime.apply_env_presets()  # REPRO_PLATFORM etc. — before jax loads

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.checkpoint import latest_step, restore_checkpoint  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.transformer import (  # noqa: E402
    abstract_params,
    decode_step,
    init_decode_cache,
    prefill_with_cache,
)


def generate(params, cfg, prompts, gen: int, *, temperature: float = 0.0,
             seed: int = 0):
    """prompts (b, s) int32 -> (b, s+gen) tokens + timing stats."""
    b, s = prompts.shape
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, t: prefill_with_cache(p, cfg, {"tokens": t},
                                        capacity=s + gen))(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    key = jax.random.PRNGKey(seed)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(gen - 1):
        lg, cache = step(params, cache, tok)
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, lg[:, -1] / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    tokens = jnp.concatenate([prompts] + out, axis=1)
    return tokens, {"prefill_s": t_prefill, "decode_s": t_decode,
                    "tok_per_s": b * (gen - 1) / max(t_decode, 1e-9)}


def route_from_checkpoint(stacked, cfg, client: int, *, algorithm: str,
                          clusters: int, sketch_dim: int, seed: int = 0):
    """Cluster a stacked federated checkpoint and pick the served model
    by sketch routing.  Returns (cluster model pytree, cluster id, info).
    """
    from repro.core.engine.session import AggregationSession

    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    session = AggregationSession(n, sketch_dim=sketch_dim, cfg=cfg,
                                 seed=seed)
    session.ingest(stacked)
    _, labels, info = session.finalize(algorithm=algorithm, k=clusters,
                                       engine="device")
    client_params = jax.tree_util.tree_map(lambda l: l[client], stacked)
    cid = session.route(params=client_params)
    if not 0 <= cid < session.n_clusters:
        # belt over cluster_model's own IndexError: a routed id outside
        # the recovered range means the session state is corrupt, and a
        # serving driver should say so rather than wrap around
        raise SystemExit(f"routed cluster id {cid} out of range for "
                         f"{session.n_clusters} recovered clusters")
    return session.cluster_model(cid), cid, {"labels": labels, **info}


def serve_routes(stacked, cfg, *, algorithm: str, clusters: int,
                 sketch_dim: int, callers: int, duration_s: float,
                 seed: int = 0) -> dict:
    """``--server``: rebuild the cluster structure from a stacked
    checkpoint exactly like ``route_from_checkpoint``, then put the
    session behind a ``RouteServer`` and route every checkpointed
    client's sketch from concurrent caller threads through the
    cross-caller batcher.  Returns a small report dict."""
    from repro.core.engine.session import AggregationSession
    from repro.serving.loadgen import closed_loop, warm_route_buckets
    from repro.serving.server import RouteServer

    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    session = AggregationSession(n, sketch_dim=sketch_dim, cfg=cfg,
                                 seed=seed)
    session.ingest(stacked)
    session.finalize(algorithm=algorithm, k=clusters, engine="device")
    probes = np.asarray(session.sketch_params(stacked))
    max_batch = min(32, max(1, n))
    warm_route_buckets(session, probes[0], max_batch)
    with RouteServer(session, max_batch=max_batch, max_wait_ms=0.5) as srv:
        # every checkpointed client once, through the batched path —
        # the routed ids are the serving-time cluster assignment
        routed = [srv.route(p, timeout=30.0) for p in probes]
        stats = closed_loop(srv, probes, callers=callers,
                            duration_s=duration_s, batched=True)
    counts = np.bincount(routed, minlength=session.n_clusters)
    return {
        "clients": n,
        "n_clusters": session.n_clusters,
        "routed": routed,
        "cluster_sizes": counts.tolist(),
        "callers": callers,
        **stats,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--client", type=int, default=0,
                    help="which client to serve from a stacked federated "
                         "checkpoint (its raw slice, or — with "
                         "--route-by-sketch — its routed cluster model)")
    ap.add_argument("--route-by-sketch", action="store_true",
                    help="rebuild the cluster structure from the stacked "
                         "checkpoint (AggregationSession) and serve the "
                         "cluster model the client's sketch routes to")
    ap.add_argument("--clusters", type=int, default=2,
                    help="k for the routing clustering (--route-by-sketch)")
    ap.add_argument("--route-algorithm", default="kmeans-device",
                    help="registered clustering for --route-by-sketch")
    ap.add_argument("--route-sketch-dim", type=int, default=64)
    ap.add_argument("--server", action="store_true",
                    help="concurrent serving mode: rebuild the cluster "
                         "structure (like --route-by-sketch) and route "
                         "ALL clients through a RouteServer with "
                         "concurrent caller threads; without --ckpt-dir "
                         "a synthetic stacked checkpoint is generated")
    ap.add_argument("--server-callers", type=int, default=4,
                    help="closed-loop caller threads for --server")
    ap.add_argument("--server-duration", type=float, default=2.0,
                    help="seconds of closed-loop load for --server")
    ap.add_argument("--server-clients", type=int, default=16,
                    help="synthetic stacked-checkpoint size when --server "
                         "runs without --ckpt-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write every obs span/event (routing, finalize) "
                         "of this serve run as JSONL")
    args = ap.parse_args(argv)
    runtime.use_compile_cache()
    if args.trace:
        obs.add_sink(obs.JsonlSink(args.trace))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(max_vocab=256)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")

    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)

    if args.server:
        if args.ckpt_dir:
            step = latest_step(args.ckpt_dir)
            if step is None:
                raise SystemExit(f"no checkpoints found in {args.ckpt_dir}")
            stacked = restore_checkpoint(args.ckpt_dir, step, params)
            leading = jax.tree_util.tree_leaves(stacked)[0].shape
            if leading == jax.tree_util.tree_leaves(params)[0].shape:
                raise SystemExit("--server needs a stacked federated "
                                 "checkpoint (leading client axis); this "
                                 "one is a single model")
            stacked = jax.tree_util.tree_map(
                lambda l, r: jnp.asarray(l, r.dtype), stacked, params)
            src = f"checkpoint step {step} ({leading[0]} clients)"
        else:
            # no checkpoint: a synthetic stacked federated checkpoint —
            # per-cluster offsets + small per-client noise, so routing
            # has real structure to recover
            n, k = args.server_clients, args.clusters
            group = jnp.arange(n) % k
            leaves, treedef = jax.tree_util.tree_flatten(params)
            stacked_leaves = []
            for i, leaf in enumerate(leaves):
                k1, k2 = jax.random.split(jax.random.fold_in(key, i + 1))
                offs = jax.random.normal(k1, (k,) + leaf.shape, leaf.dtype)
                noise = 0.05 * jax.random.normal(
                    k2, (n,) + leaf.shape, leaf.dtype)
                stacked_leaves.append(leaf[None] + offs[group] + noise)
            stacked = jax.tree_util.tree_unflatten(treedef, stacked_leaves)
            src = f"{n} synthetic clients"
        report = serve_routes(
            stacked, cfg, algorithm=args.route_algorithm,
            clusters=args.clusters, sketch_dim=args.route_sketch_dim,
            callers=args.server_callers, duration_s=args.server_duration,
            seed=args.seed)
        print(f"[server] {src}: K'={report['n_clusters']} "
              f"cluster sizes {report['cluster_sizes']}")
        print(f"[server] {report['callers']} callers  "
              f"{report['qps']:.0f} routes/s  "
              f"p50={report['route_p50_ms']:.2f}ms "
              f"p99={report['route_p99_ms']:.2f}ms  "
              f"errors={report['n_errors']} timeouts={report['timeouts']}")
        return report

    if args.ckpt_dir:
        step = latest_step(args.ckpt_dir)
        if step is None:
            raise SystemExit(f"no checkpoints found in {args.ckpt_dir}")
        stacked = restore_checkpoint(args.ckpt_dir, step, params)
        leading = jax.tree_util.tree_leaves(stacked)[0].shape
        is_stacked = leading != jax.tree_util.tree_leaves(params)[0].shape
        if args.route_by_sketch:
            if not is_stacked:
                raise SystemExit("--route-by-sketch needs a stacked "
                                 "federated checkpoint (leading client "
                                 "axis); this one is a single model")
            n = leading[0]
            if not 0 <= args.client < n:
                raise SystemExit(f"client index {args.client} out of range "
                                 f"for {n} checkpointed clients")
            stacked = jax.tree_util.tree_map(
                lambda l, r: jnp.asarray(l, r.dtype), stacked, params)
            params, cid, info = route_from_checkpoint(
                stacked, cfg, args.client, algorithm=args.route_algorithm,
                clusters=args.clusters, sketch_dim=args.route_sketch_dim,
                seed=args.seed)
            print(f"[ckpt] restored step {step}; client {args.client} "
                  f"routed to cluster {cid}/{info['n_clusters']} "
                  f"(labels {info['labels'].tolist()})")
            h = obs.snapshot()["histograms"].get("session.route.ms")
            if h and h.get("count"):
                print(f"[route] {h['count']} request(s), "
                      f"p50={h['p50']:.3f}ms max={h['max']:.3f}ms")
        else:
            def select(restored, ref):
                # federated checkpoints stack params along a leading
                # client axis; single-model checkpoints restore as-is
                if restored.shape == ref.shape:
                    return jnp.asarray(restored, ref.dtype)
                if restored.shape[1:] != ref.shape or \
                        not 0 <= args.client < restored.shape[0]:
                    raise SystemExit(
                        f"checkpoint leaf {restored.shape} does not match "
                        f"model {ref.shape} (client index {args.client})")
                return jnp.asarray(restored[args.client], ref.dtype)

            params = jax.tree_util.tree_map(select, stacked, params)
            print(f"[ckpt] restored step {step} (client {args.client})")

    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    tokens, stats = generate(params, cfg, prompts, args.gen,
                             temperature=args.temperature, seed=args.seed)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill {stats['prefill_s']*1e3:.1f}ms  "
          f"decode {stats['decode_s']*1e3:.1f}ms  "
          f"throughput {stats['tok_per_s']:.1f} tok/s")
    print("sample row:", np.asarray(tokens[0, -args.gen:]).tolist())
    return tokens


if __name__ == "__main__":
    main()
