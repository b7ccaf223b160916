"""Serving CLI: continuous-batching engine over the paged KV cache.

Serves dense or SPA/OBSPA-pruned models — the point of structured pruning
is that the pruned model is a *plain smaller model*: the serving path is
unchanged, it just compiles to fewer FLOPs (see DESIGN.md §8).

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 16 --prompt-len 32 --gen 32 \
      --max-seqs 8 --block-size 16 --chunk-size 32 --prefill-budget 64 \
      [--no-prefix-caching] [--prune-ratio 0.5] [--temperature 0.8] \
      [--spec-k 4 --draft-ratio 0.5] [--mesh 4x1]

``--mesh DxM`` (or ``auto``) serves over a (data, model) device mesh:
request slots go data-parallel, the paged KV pools tensor-parallel over
kv_heads, and the jitted steps run as one sharded SPMD program with the
paged-attention kernel shard_mapped per device (DESIGN.md §10).  Multi-
device CPU smoke: XLA_FLAGS=--xla_force_host_platform_device_count=4.

Prefill is chunked through ``paged_prefill_step`` (``--chunk-size`` tokens
per step per slot, ``--prefill-budget`` tokens per step across slots;
``--chunk-size 0`` restores token-by-token prefill), and requests sharing
a prompt prefix alias full KV blocks via refcounted prefix caching unless
``--no-prefix-caching``.

``--spec-k K`` turns on lossless self-speculative decoding: the served
model is SPA-pruned at ``--draft-ratio`` into a draft that proposes K
tokens per cycle, verified in one multi-token target pass (outputs stay
distribution-identical; see DESIGN.md §9).  SSM/hybrid families are
capability-gated back to dense-only decode.

``--metrics`` prints the serving telemetry after the run — per-phase
p50/p99 step timings, pool gauges and the full Prometheus-format metric
dump — and ``--trace-out PATH`` writes a Chrome-trace JSON of the run
(step phases as duration slices, requests as async spans, pool
occupancy as counter tracks) loadable in https://ui.perfetto.dev or
chrome://tracing (repro.obs; DESIGN.md §12).

``--audit-level {off,alloc,full}`` turns on runtime invariant auditing
(allocator / full cache conservation checked every ``--audit-interval``
steps, with quarantine-and-recover on violation) and ``--degrade``
enables the load-shedding ladder — both from DESIGN.md §14.

On SIGTERM/SIGINT the server drains gracefully: it stops admitting,
finishes in-flight requests, and — with ``--snapshot-out PATH`` — writes
an engine snapshot whose waiting queue a fresh process can resume
byte-identically via ``--restore PATH`` (which rebuilds the engine from
the snapshot's own ServeConfig; CLI engine flags are ignored).
``--drain-timeout S`` bounds any drain: stragglers past the deadline are
force-preempted back to the waiting queue instead of blocking shutdown.

``--replicas N`` serves behind a fault-tolerant :class:`Cluster` of N
engine replicas (DESIGN.md §15): requests route to the least-loaded
alive replica, replica death fails its requests over onto survivors via
snapshot/block handoff (byte-identical at temperature 0), and SIGHUP
triggers a rolling restart of every replica in turn with zero failed
requests.

``--prefill-replicas N`` disaggregates the cluster (DESIGN.md §16): N
dedicated prefill-role replicas take every new prompt, and on final-
chunk completion each sequence's KV+scale blocks migrate byte-exactly
to the least-loaded of the ``--replicas`` decode-role replicas, so
long prompts stop stealing decode steps from latency-sensitive
requests.

``generate`` (sequential, token-by-token) is kept as the correctness
oracle the engine is tested against (tests/test_serve.py).
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.data.synthetic import batches
from repro.models import build


def generate(model, params, prompt: jax.Array, gen_len: int,
             max_len: int | None = None):
    """Sequential greedy generation (reference implementation).

    prompt (B, P) int32 -> (B, P+gen_len).  The contiguous-cache,
    single-position decode loop the paged engine must match token-for-token.
    """
    B, P = prompt.shape
    max_len = max_len or (P + gen_len)
    cache = model.init_cache(batch=B, max_len=max_len)
    step = jax.jit(model.decode_step)
    logits = None
    for t in range(P):
        logits, cache = step(params, cache, prompt[:, t], jnp.int32(t))
    toks = [jnp.argmax(logits, -1)]
    for t in range(P, P + gen_len - 1):
        logits, cache = step(params, cache, toks[-1], jnp.int32(t))
        toks.append(jnp.argmax(logits, -1))
    return jnp.concatenate([prompt, jnp.stack(toks, 1)], axis=1)


def build_engine(cfg, model, params, args, draft_model=None,
                 draft_params=None, telemetry=None, role="mixed"):
    from repro.launch.mesh import parse_mesh
    from repro.serve import Engine, ServeConfig
    mesh = parse_mesh(args.mesh) if args.mesh else None
    # K tokens of headroom: speculative reservation (num_cached + K + 1)
    # must stay within per-seq capacity or tail cycles degrade to plain
    # decode (DESIGN.md §9)
    return Engine(model, params, ServeConfig(
        role=role,
        max_seqs=args.max_seqs, block_size=args.block_size,
        max_len=args.max_len or (args.prompt_len + args.gen + args.spec_k),
        num_blocks=args.num_blocks, seed=args.seed,
        chunk_size=args.chunk_size, prefill_budget=args.prefill_budget,
        prefix_caching=not args.no_prefix_caching,
        spec_k=args.spec_k, spec_ema=args.spec_ema,
        draft_cache_dtype=args.draft_cache_dtype,
        cache_dtype=args.cache_dtype, async_step=args.async_step,
        audit_level=getattr(args, "audit_level", "off"),
        audit_interval=getattr(args, "audit_interval", 1),
        degrade=getattr(args, "degrade", False),
        drain_timeout_s=getattr(args, "drain_timeout", 0.0)),
        draft_model=draft_model, draft_params=draft_params, mesh=mesh,
        telemetry=telemetry)


def _serve_replicated(engines, args, toks, lens, stop, telemetry):
    """Replicated serving (DESIGN.md §15): N health-checked engine
    replicas behind a Cluster router.  SIGHUP triggers a rolling
    restart (drain + backlog re-homing + snapshot round-trip per
    replica, zero failed requests); SIGTERM/SIGINT drain all replicas
    and exit."""
    from repro.serve import Cluster, ClusterConfig
    cluster = Cluster(engines, ClusterConfig(
        drain_timeout_s=args.drain_timeout or 30.0), telemetry=telemetry)
    hup: dict[str, int] = {}
    signal.signal(signal.SIGHUP,
                  lambda signum, frame: hup.setdefault("hup", signum))
    t0 = time.time()
    for i in range(args.requests):
        cluster.submit([int(t) for t in toks[i, :lens[i]]],
                       max_new_tokens=args.gen,
                       temperature=args.temperature)
    n_pre = getattr(args, "prefill_replicas", 0)
    if n_pre:
        print(f"cluster ready ({n_pre} prefill + {args.replicas} decode "
              f"replicas)", flush=True)
    else:
        print(f"cluster ready ({args.replicas} replicas)", flush=True)
    while True:
        out, stats = cluster.run(
            stop_when=lambda: "sig" in stop or "hup" in hup)
        if "hup" in hup and "sig" not in stop:
            hup.clear()
            print("SIGHUP: rolling restart", flush=True)
            cluster.rolling_restart()
            continue
        break
    if "sig" in stop:
        print(f"signal {stop['sig']}: draining replicas", flush=True)
        out.update(cluster.drain_all(args.drain_timeout))
    dt = time.time() - t0
    n_new = sum(len(r.tokens) for r in out.values())
    print(f"served {len(out)} requests / {n_new} new tokens in {dt:.2f}s "
          f"(incl. compile)")
    print(f"cluster: {stats['ticks']:.0f} ticks | "
          f"{stats['steps']:.0f} engine steps | "
          f"{stats['alive']:.0f}/{stats['replicas']:.0f} alive | "
          f"failovers {stats['failovers']:.0f} | "
          f"migrated blocks {stats['migrated_blocks']:.0f} | "
          f"disagg migrations {stats['disagg_migrations']:.0f}")
    if out:
        first = out[min(out)]
        print("sample token ids:", first.tokens[:16])
    if args.trace_out:
        from repro.obs import write_chrome
        write_chrome(telemetry.trace, args.trace_out)
        print(f"chrome trace -> {args.trace_out} "
              f"(one phase track per replica)")


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags; ``build_engine`` reads the parsed
    namespace (chip_smoke.py builds its engines through both)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool blocks (0 = worst-case sized)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prefill chunk tokens (0 = token-by-token)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prefill tokens per engine step (0 = no cap)")
    ap.add_argument("--no-prefix-caching", action="store_true",
                    help="disable shared-prefix block aliasing")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prune-ratio", type=float, default=0.0)
    ap.add_argument("--obspa", action="store_true",
                    help="prune with OBSPA (data-free) instead of SPA-L1")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft tokens per cycle (0 = off)")
    ap.add_argument("--draft-ratio", type=float, default=0.5,
                    help="SPA prune ratio for the speculative draft")
    ap.add_argument("--spec-ema", type=float, default=0.0,
                    help="dynamic speculative K: EMA coefficient of the "
                         "per-slot acceptance rate (0 = fixed K)")
    ap.add_argument("--draft-cache-dtype", default="",
                    help="draft KV pool dtype, e.g. bfloat16 "
                         "(default: model dtype)")
    ap.add_argument("--cache-dtype", default="",
                    help="target KV pool dtype: float32/bfloat16 cast; "
                         "int8/fp8_e4m3 quantize with fused kernel "
                         "dequant (default: model dtype)")
    ap.add_argument("--async-step", action="store_true",
                    help="double-buffered engine steps: plan/dispatch "
                         "step N+1 while step N's device work is in "
                         "flight (DESIGN.md §13; outputs stay "
                         "byte-identical at temperature 0)")
    ap.add_argument("--mesh", default="",
                    help="serving mesh 'DxM' (data x model) or 'auto'; "
                         "empty = single-device engine")
    ap.add_argument("--metrics", action="store_true",
                    help="enable serving telemetry and print phase "
                         "timings + Prometheus metrics after the run")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON of the run "
                         "(load in https://ui.perfetto.dev)")
    ap.add_argument("--audit-level", default="off",
                    choices=("off", "alloc", "full"),
                    help="runtime invariant auditing after each step "
                         "(alloc: allocator conservation; full: cache "
                         "tables + prefix index too; DESIGN.md §14)")
    ap.add_argument("--audit-interval", type=int, default=1,
                    help="audit every N steps (amortizes full audits)")
    ap.add_argument("--degrade", action="store_true",
                    help="graceful degradation under pool pressure: "
                         "shed aged waiting requests, clamp spec K, "
                         "pause prefix-cache admission")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve behind a fault-tolerant Cluster of N "
                         "engine replicas: health-checked routing, "
                         "failover via snapshot/block handoff, and "
                         "SIGHUP-triggered rolling restarts "
                         "(DESIGN.md §15)")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="disaggregated serving (DESIGN.md §16): N "
                         "dedicated prefill-role replicas in front of "
                         "--replicas decode-role replicas; prompts "
                         "prefill on the prefill tier and migrate "
                         "their KV blocks to the decode tier on final-"
                         "chunk completion (0 = colocated)")
    ap.add_argument("--drain-timeout", type=float, default=0.0,
                    help="drain() deadline in seconds: running requests "
                         "past it are force-preempted to the waiting "
                         "queue (0 = unbounded)")
    ap.add_argument("--snapshot-out", default="",
                    help="write an engine snapshot here after a "
                         "SIGTERM/SIGINT drain (resume via --restore)")
    ap.add_argument("--restore", default="",
                    help="restore engine state from a snapshot file and "
                         "resume its waiting queue (engine flags come "
                         "from the snapshot, not the CLI)")
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only; no decode path")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.prune_ratio:
        if args.obspa:
            from repro.core.obspa import obspa_prune
            calib = batches(cfg, "datafree", 4, 4, args.prompt_len,
                            seed=5, with_targets=False)
            pr = obspa_prune(model, params, args.prune_ratio, calib,
                             calib_mode="datafree")
        else:
            from repro.core.pruner import prune_model
            pr = prune_model(model, params, args.prune_ratio)
        model, params = build(pr.cfg), pr.params
        print(f"serving pruned model: {pr.cfg.name}")

    draft_model = draft_params = None
    if args.spec_k > 0:
        from repro.core.pruner import prune_model
        dr = prune_model(model, params, args.draft_ratio, criterion="l1")
        draft_model, draft_params = build(dr.cfg), dr.params
        print(f"speculative draft: {dr.cfg.name} "
              f"({dr.cfg.param_count()} params, K={args.spec_k})")

    # variable-length prompts: realistic continuous-batching traffic
    toks = batches(cfg, "id", 1, args.requests, args.prompt_len,
                   with_targets=False)[0]["tokens"]
    lens = [max(4, args.prompt_len - (i % 4) * (args.prompt_len // 8))
            for i in range(args.requests)]

    telemetry = None
    if args.metrics or args.trace_out:
        from repro.obs import Telemetry
        telemetry = Telemetry(enabled=True)
    if args.restore:
        from repro.launch.mesh import parse_mesh
        from repro.serve import load_snapshot, restore_engine
        snap = load_snapshot(args.restore)
        engine = restore_engine(
            snap, model, params, draft_model=draft_model,
            draft_params=draft_params,
            mesh=parse_mesh(args.mesh) if args.mesh else None,
            telemetry=telemetry)
        print(f"restored snapshot {args.restore}: "
              f"{len(engine.scheduler.waiting)} waiting / "
              f"{len(engine.scheduler.running)} running requests")
    else:
        engine = build_engine(cfg, model, params, args, draft_model,
                              draft_params, telemetry=telemetry)
    if engine.mesh is not None:
        print(f"serving mesh: "
              f"{dict(zip(engine.mesh.axis_names, engine.mesh.devices.shape))}"
              f" | slots per data shard: "
              f"{args.max_seqs // engine.scheduler.data_shards}")
    if args.spec_k > 0 and not engine.spec_active:
        print("speculative decoding gated off for this family "
              "(recurrent state cannot be rewound; DESIGN.md §9)")
    # graceful shutdown: a signal flips the flag; run() notices between
    # steps, then we drain (finish in-flight, refuse admissions) and
    # optionally snapshot — the handler itself does no engine work, so a
    # signal mid-step is safe
    stop: dict[str, int] = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda signum, frame: stop.setdefault(
            "sig", signum))

    if args.replicas > 1 or args.prefill_replicas > 0:
        if args.prefill_replicas > 0:
            # disaggregated tiers (DESIGN.md §16): N prefill-role
            # replicas feed --replicas decode-role replicas; the
            # pre-built mixed engine is not part of the cluster
            roles = ["prefill"] * args.prefill_replicas + \
                ["decode"] * args.replicas
            engines = [build_engine(cfg, model, params, args, draft_model,
                                    draft_params, telemetry=None,
                                    role=role)
                       for role in roles]
        else:
            engines = [engine] + [
                build_engine(cfg, model, params, args, draft_model,
                             draft_params, telemetry=None)
                for _ in range(args.replicas - 1)]
        _serve_replicated(engines, args, toks, lens, stop, telemetry)
        return

    t0 = time.time()
    if not args.restore:
        for i in range(args.requests):
            engine.add_request([int(t) for t in toks[i, :lens[i]]],
                               max_new_tokens=args.gen,
                               temperature=args.temperature)
    print("engine ready", flush=True)    # subprocess tests wait for this
    out, stats = engine.run(stop_when=lambda: "sig" in stop)
    if "sig" in stop:
        print(f"signal {stop['sig']}: draining "
              f"({len(engine.scheduler.running)} in flight, "
              f"{len(engine.scheduler.waiting)} waiting)", flush=True)
        out.update(engine.drain())
        if args.snapshot_out:
            from repro.serve import save_snapshot
            save_snapshot(engine, args.snapshot_out)
            print(f"snapshot -> {args.snapshot_out} "
                  f"({len(engine.scheduler.waiting)} waiting requests "
                  f"resumable via --restore)", flush=True)
    dt = time.time() - t0
    n_new = sum(len(r.tokens) for r in out.values())
    print(f"served {len(out)} requests / {n_new} new tokens in {dt:.2f}s "
          f"(incl. compile)")
    if not out:
        return
    print(f"decode {stats['decode_tok_per_s']:.1f} tok/s | "
          f"prefill+decode {stats['total_tok_per_s']:.1f} tok/s | "
          f"{stats['steps']:.0f} steps | "
          f"{stats['prefill_chunks']:.0f} prefill chunks | "
          f"mean ttft {stats['mean_ttft_s'] * 1e3:.1f}ms")
    if engine.mesh is not None:
        n_dev = int(engine.mesh.devices.size)
        print(f"per-device decode "
              f"{stats['decode_tok_per_s'] / n_dev:.1f} tok/s "
              f"({n_dev} devices)")
    if engine.spec_active:
        print(f"speculative: {stats['spec_cycles']:.0f} cycles | "
              f"acceptance {stats['spec_acceptance']:.1%} "
              f"({stats['spec_accepted']:.0f}/{stats['spec_proposed']:.0f})")
    rb = ("faults_injected", "recoveries", "requests_shed",
          "audit_violations", "callback_errors")
    if any(stats.get(k) for k in rb):
        print("robustness: " + " | ".join(
            f"{k} {stats[k]:.0f}" for k in rb if stats.get(k)))
    first = out[min(out)]
    print("sample token ids:", first.tokens[:16])

    if args.metrics:
        from repro.obs import prometheus_text
        reg = telemetry.registry
        print("\n-- step phases (per-step wall, us) --")
        for name in ("step", "plan", "overlap", "prefill_dispatch",
                     "decode_dispatch", "sync", "fold"):
            h = reg.histograms.get("phase/" + name)
            if h is None:
                continue
            s = h.summary()
            print(f"{name:18s} p50 {s['p50'] * 1e6:9.1f}  "
                  f"p99 {s['p99'] * 1e6:9.1f}  "
                  f"mean {s['mean'] * 1e6:9.1f}  n={s['count']}")
        step_h = reg.histograms.get("phase/step")
        sync_h = reg.histograms.get("phase/sync")
        if step_h is not None and step_h.total > 0 and sync_h is not None:
            print(f"host bubble fraction "
                  f"{sync_h.total / step_h.total:.3f} "
                  f"(phase sync / phase step wall)")
        lat = [(out[r].queue_wait_s, out[r].preempt_stall_s, out[r].tpot_s)
               for r in out]
        print(f"mean queue wait {np.mean([x[0] for x in lat]) * 1e3:.2f}ms | "
              f"mean preempt stall {np.mean([x[1] for x in lat]) * 1e3:.2f}ms"
              f" | mean tpot {np.mean([x[2] for x in lat]) * 1e3:.2f}ms")
        print("\n-- prometheus --")
        print(prometheus_text(reg))
    if args.trace_out:
        from repro.obs import write_chrome
        write_chrome(telemetry.trace, args.trace_out)
        print(f"chrome trace -> {args.trace_out} "
              f"(load in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
