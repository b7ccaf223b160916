#!/usr/bin/env python3
"""Chip smoke test: serve full-width Qwen3-1.7B, dense and SPA-pruned, on
a TPU through the compiled Pallas kernels.

    python3 chip_smoke.py              # one chip: phases a-e
    python3 chip_smoke.py --chips 4    # four chips: sharded serving only

Everything runs in this one process, through the functions the serving
CLI uses (``launch/serve.py``: ``build_parser``, ``build_engine``;
``core.pruner.prune_model``).  Weights are random, from ``--seed``.

  a. The first JAX device must be a TPU, else exit 1 with no result line.
  b. The paged-attention kernel against ``kernels/paged_attention/ref.py``
     (f32 at "highest" matmul precision) at the model's attention shapes:
     decode and chunked prefill over bf16 pools, decode over an int8 pool.
  c. Serve 16 requests (prompts of 128-1,024 tokens, half sharing a
     256-token system prefix, 32 new tokens each) on the dense model with
     chunked prefill and prefix caching.  Every request must finish by
     length with 32 tokens, the decode and prefill step programs must hold
     compiled kernels (``tpu_custom_call``), and each request's first
     token must score within ``LOGIT_TOL`` of the best logit of a
     full-sequence forward of its prompt (XLA attention, no paging).
  d. Prune the model 50% with SPA-L1 and serve the same requests under the
     same checks.
  e. The OBSPA sweep (in-block kernel + panel GEMMs) on one full-width MLP
     matrix against ``sweep_oracle`` (float64 numpy).

``--chips 4`` runs the same requests through a one-chip engine, a ``4x1``
(data-parallel) engine and a ``1x4`` (KV heads over the model axis)
engine, checks that pools and parameters span the four devices, and
holds every engine's first tokens to the same reference-logit check.

Each phase prints its wall time as set-up time: it includes compilation
and is not a performance metric.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
# ServeConfig through the serving CLI's own flags: 8 slots x 2,048 tokens
SERVE_FLAGS = ("--max-seqs", "8", "--block-size", "16", "--max-len", "2048",
               "--chunk-size", "256", "--gen", "32")
N_REQUESTS = 16
PROMPT_LENS = (128, 1024)
PREFIX_LEN = 256             # shared system prefix: 16 full blocks
PRUNE_RATIO = 0.5

# max |kernel - reference| over attention outputs rounded to bf16 (values
# of order 0.1-1; one bf16 ulp there is <= 4e-3)
KERNEL_TOL = 1e-2
# best reference logit minus the reference logit of the engine's first
# token.  The logits of this random init spread with std ~1 over 151,936
# tokens, so the runner-up sits ~0.2 below the best; bf16 rounding moves
# logits by a few hundredths.  A correct engine lands at 0 or on a near
# tie, a wrong attention on a token far below the best.
LOGIT_TOL = 0.25
# OBSPA: max error relative to max |oracle| (f32 sweep vs float64 oracle)
OBSPA_TOL = 1e-3
OBSPA_ROWS = 256             # oracle rows: the sweep is row-independent


class SmokeFailure(Exception):
    """A phase's output was wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}] start", flush=True)
    t0 = time.time()
    yield
    print(f"[{name}] set-up time {time.time() - t0:.1f} s (wall clock, "
          f"includes compilation; not a performance metric)", flush=True)


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform}")
    check(len(devs) >= chips, f"{chips} chips wanted, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# b. paged-attention kernel vs reference
# ---------------------------------------------------------------------------

def _kernel_custom_calls(fn, *args) -> int:
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def kernel_parity(cfg, *, max_seqs: int, block_size: int, max_len: int,
                  chunk: int, seed: int) -> dict:
    """Kernel against reference on random pools at ``cfg``'s attention
    shapes.  Returns max abs errors and the kernel's tpu_custom_call count
    per case (0 where the kernel is interpreted, off the TPU)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import (
        paged_attention, paged_attention_reference, paged_prefill_attention,
        paged_prefill_attention_reference, pool_dtype, quantize)
    from repro.models.layers import dtype_of

    B, H, KH = max_seqs, cfg.n_heads, cfg.n_kv_heads
    D, DV, bs = cfg.head_dim_, cfg.v_head_dim_, block_size
    NB = max_len // bs
    P = B * NB + 1
    dt = dtype_of(cfg.dtype)
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dt)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    k, v = rand(P, KH, bs, D), rand(P, KH, bs, DV)
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    lens = jnp.asarray(rng.integers(1, NB * bs + 1, B), jnp.int32)
    q = rand(B, H, D)

    def err(a, b):
        return float(jnp.max(jnp.abs(f32(a) - f32(b))))

    out: dict[str, float | int] = {}
    dec = functools.partial(paged_attention, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_reference(f32(q), f32(k), f32(v), tables, lens)
    out["decode_err"] = err(dec(q, k, v, tables, lens), ref)
    out["decode_calls"] = _kernel_custom_calls(dec, q, k, v, tables, lens)

    qc = rand(B, chunk, H, D)
    starts = jnp.asarray(rng.integers(0, NB * bs - chunk + 1, B), jnp.int32)
    valid = rng.integers(1, chunk + 1, B)
    ends = starts + jnp.asarray(valid, jnp.int32)
    pre = functools.partial(paged_prefill_attention, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_prefill_attention_reference(f32(qc), f32(k), f32(v),
                                                tables, starts, ends)
    got = pre(qc, k, v, tables, starts, ends)
    live = np.arange(chunk)[None, :] < valid[:, None]   # rows past valid
    diff = jnp.abs(f32(got) - f32(ref))                 # are don't-care
    out["prefill_err"] = float(jnp.max(jnp.where(
        jnp.asarray(live)[:, :, None, None], diff, 0.0)))
    out["prefill_calls"] = _kernel_custom_calls(pre, qc, k, v, tables,
                                                starts, ends)

    qk, sk = quantize(f32(k), pool_dtype("int8"))
    qv, sv = quantize(f32(v), pool_dtype("int8"))
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_reference(f32(q), qk, qv, tables, lens,
                                        k_scale=sk, v_scale=sv)
    out["int8_decode_err"] = err(
        dec(q, qk, qv, tables, lens, k_scale=sk, v_scale=sv), ref)
    out["int8_decode_calls"] = _kernel_custom_calls(
        lambda *a: dec(*a[:5], k_scale=a[5], v_scale=a[6]),
        q, qk, qv, tables, lens, sk, sv)
    for case in ("decode", "prefill", "int8_decode"):
        check(out[case + "_err"] <= KERNEL_TOL,
              f"{case}: kernel vs reference {out[case + '_err']:.3g} > "
              f"{KERNEL_TOL}")
    return out


# ---------------------------------------------------------------------------
# c/d. serving
# ---------------------------------------------------------------------------

def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS,
                 lens: tuple[int, int] = PROMPT_LENS,
                 prefix: int = PREFIX_LEN) -> list[list[int]]:
    """Prompt lengths spread evenly over ``lens`` in a shuffled order;
    every even request opens with one shared ``prefix``-token system
    prompt (and so is at least one token longer)."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, prefix).tolist()
    lengths = rng.permutation(np.linspace(*lens, n).astype(int))
    prompts = []
    for i, length in enumerate(lengths):
        head = system if i % 2 == 0 else []
        length = max(int(length), len(head) + 1)
        prompts.append(head + rng.integers(0, vocab,
                                           length - len(head)).tolist())
    return prompts


def serve(model, params, prompts, flags, mesh: str = ""):
    """Serve ``prompts`` on an engine built by the serving CLI's own
    functions; every request must finish by length with ``--gen`` new
    tokens.  Returns (engine, generated tokens in prompt order, stats)."""
    from repro.launch.serve import build_engine, build_parser
    argv = ["--arch", model.cfg.name, *flags] + (["--mesh", mesh]
                                                 if mesh else [])
    args = build_parser().parse_args(argv)
    eng = build_engine(model.cfg, model, params, args)
    rids = [eng.add_request(p, max_new_tokens=args.gen) for p in prompts]
    out, stats = eng.run()
    check(sorted(out) == sorted(rids),
          f"{len(out)} of {len(rids)} requests finished")
    for rid in rids:
        r = out[rid]
        check(r.finish_reason == "length" and len(r.tokens) == args.gen,
              f"request {rid}: {r.finish_reason!r} after {len(r.tokens)} "
              f"of {args.gen} tokens")
    stats["prefix_hits"] = eng.cache_host.prefix_hits
    return eng, [out[r].tokens for r in rids], stats


def step_kernel_calls(eng) -> dict[str, int]:
    """tpu_custom_call count in the engine's lowered decode and prefill
    step programs.  A Pallas kernel compiled for the TPU lowers to one; an
    interpreted kernel or the jnp reference to plain HLO."""
    import jax
    import jax.numpy as jnp
    B, C, NB = eng.cfg.max_seqs, eng.cfg.chunk_size, eng.cfg.blocks_per_seq

    def sds(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    temps, active = sds(B, dtype=jnp.float32), sds(B, dtype=jnp.bool_)
    step = eng._step_fn.lower(eng.params, eng.cache, sds(B), sds(B),
                              sds(B, NB), temps, active, eng._key)
    pre = eng._prefill_fn.lower(eng.params, eng.cache, sds(B, C), sds(B, C),
                                sds(B), sds(B, NB), sds(B), temps, eng._key)
    return {"step": step.as_text().count("tpu_custom_call"),
            "prefill": pre.as_text().count("tpu_custom_call")}


def reference_logits(model, params, prompts) -> np.ndarray:
    """Last-position logits of each prompt from a full-sequence forward
    (XLA attention, no paging, no kernel), f32 (n, V).  Prompts are
    right-padded to one shape; causal attention keeps padding out of the
    positions read."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf
    cfg = model.cfg
    L = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), L), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = np.asarray([len(p) - 1 for p in prompts], np.int32)

    @jax.jit
    def last_logits(params, toks, last):
        h, _ = tf.forward(params, cfg, {"tokens": toks})
        h = jnp.take_along_axis(h, last[:, None, None], axis=1)
        return tf.logits_from_hidden(params, cfg, h)[:, 0].astype(jnp.float32)

    return np.asarray(last_logits(params, jnp.asarray(toks),
                                  jnp.asarray(last)))


def logit_gaps(ref: np.ndarray, first_tokens) -> np.ndarray:
    """Best reference logit minus the reference logit of each request's
    first generated token; checked against ``LOGIT_TOL``."""
    check(bool(np.isfinite(ref).all()), "reference logits not finite")
    gaps = ref.max(-1) - ref[np.arange(len(ref)), first_tokens]
    check(bool((gaps <= LOGIT_TOL).all()),
          f"first tokens off the reference: gaps {np.round(gaps, 3)} "
          f"(limit {LOGIT_TOL})")
    return gaps


def serve_phase(model, params, prompts, flags) -> tuple[list, dict]:
    """Phases c/d: serve, check the step programs and the first tokens.
    Returns (tokens, report)."""
    eng, tokens, stats = serve(model, params, prompts, flags)
    calls = step_kernel_calls(eng)
    del eng
    gc.collect()        # the engine's jitted methods cycle back to it;
                        # free its KV pool before the reference forward
    gaps = logit_gaps(reference_logits(model, params, prompts),
                      [t[0] for t in tokens])
    report = {"steps": stats["steps"], "prefill_chunks":
              stats["prefill_chunks"], "prefix_hits": stats["prefix_hits"],
              "kernel_calls": calls, "max_logit_gap": float(gaps.max()),
              "first_token_is_ref_argmax": int((gaps == 0).sum())}
    check(stats["prefix_hits"] > 0, "no prefix-cache hit on shared prefixes")
    return tokens, report


# ---------------------------------------------------------------------------
# e. OBSPA sweep
# ---------------------------------------------------------------------------

def obspa_phase(params, seed: int) -> dict:
    """OBSPA on layer 0's MLP up-projection: W (d_ff, d_model) with the
    inverse Hessian of random calibration inputs, half the input columns
    pruned.  Returns relative errors against the float64 oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.obspa_update import obspa_sweep, sweep_oracle
    W = jnp.asarray(params["layers"]["mlp"]["w_up"][0], jnp.float32).T
    R, K = W.shape
    X = jax.random.normal(jax.random.PRNGKey(seed), (4 * K, K), jnp.float32)
    H = jnp.matmul(X.T, X, precision=jax.lax.Precision.HIGHEST) / (4 * K)
    H = np.asarray(H, np.float64) + 0.01 * np.eye(K)
    Hinv = np.linalg.inv(H).astype(np.float32)
    mask = np.random.default_rng(seed).random(K) < 0.5
    out = np.asarray(obspa_sweep(W, Hinv, mask))
    rows = min(OBSPA_ROWS, R)
    gold = sweep_oracle(np.asarray(W[:rows]), Hinv, mask)
    scale = float(np.abs(gold).max())
    rep = {"shape": [R, K], "pruned_columns": int(mask.sum()),
           "rel_err": float(np.abs(out[:rows] - gold).max()) / scale,
           "pruned_residual": float(np.abs(out[:, mask]).max()) / scale}
    check(rep["rel_err"] <= OBSPA_TOL,
          f"OBSPA vs oracle {rep['rel_err']:.3g} > {OBSPA_TOL}")
    check(rep["pruned_residual"] <= OBSPA_TOL,
          f"pruned columns not zeroed: {rep['pruned_residual']:.3g}")
    return rep


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(model, params, prompts, flags, meshes=("4x1", "1x4")
               ) -> dict:
    """The same requests through a one-chip engine and each mesh engine.
    Pools and parameters must span every device of the mesh; each
    engine's first tokens are held to the reference-logit check.  Token
    agreement with the one-chip engine is reported, not required: the
    sharded programs reduce in another order, and a bf16 near-tie that
    flips changes the rest of that request."""
    import jax
    ref = reference_logits(model, params, prompts)
    eng, base, _ = serve(model, params, prompts, flags)
    del eng
    gc.collect()
    rep = {"one_chip": {"max_logit_gap": float(
        logit_gaps(ref, [t[0] for t in base]).max())}}
    n = len(jax.devices())
    for mesh in meshes:
        eng, toks, _ = serve(model, params, prompts, flags, mesh=mesh)
        k = eng.cache["k"]
        leaf = jax.tree.leaves(eng.params)[0]
        spans = {"pool_devices": len(k.sharding.device_set),
                 "param_devices": len(leaf.sharding.device_set),
                 "pool_shard": list(k.sharding.shard_shape(k.shape)),
                 "pool": list(k.shape)}
        mode = eng.shard_mode
        del eng
        gc.collect()
        check(spans["pool_devices"] == n and spans["param_devices"] == n,
              f"{mesh}: spans {spans}, want {n} devices")
        gaps = logit_gaps(ref, [t[0] for t in toks])
        same = sum(a == b for ta, tb in zip(toks, base)
                   for a, b in zip(ta, tb))
        rep[mesh] = {"mode": mode, **spans,
                     "max_logit_gap": float(gaps.max()),
                     "first_tokens_equal": sum(
                         t[0] == b[0] for t, b in zip(toks, base)),
                     "tokens_equal": same,
                     "tokens": sum(len(t) for t in toks)}
    return rep


# ---------------------------------------------------------------------------

def compile_cache_counter() -> dict[str, int]:
    """Count persistent compilation-cache hits and misses from here on."""
    import jax
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def run(chips: int, seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.core.pruner import prune_model
    from repro.models import build

    cfg = get_config(ARCH)
    with phase("init"):
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        print(f"{cfg.name}: {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, "
              f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f} "
              f"B params in {cfg.dtype}", flush=True)
    prompts = make_prompts(cfg.vocab_size, seed)
    print(f"prompts: {len(prompts)}, {min(map(len, prompts))}-"
          f"{max(map(len, prompts))} tokens, {PREFIX_LEN}-token shared "
          f"prefix on every other one", flush=True)
    if chips == 4:
        with phase("4 chips"):
            rep = mesh_phase(model, params, prompts, SERVE_FLAGS)
            print("meshes:", json.dumps(rep), flush=True)
        return

    from repro.launch.serve import build_parser
    sa = build_parser().parse_args(["--arch", ARCH, *SERVE_FLAGS])
    with phase("b kernel"):
        rep = kernel_parity(cfg, max_seqs=sa.max_seqs,
                            block_size=sa.block_size, max_len=sa.max_len,
                            chunk=sa.chunk_size, seed=seed)
        print("kernel vs reference:", json.dumps(rep), flush=True)
        for case in ("decode", "prefill", "int8_decode"):
            check(rep[case + "_calls"] > 0, f"{case}: no compiled kernel")
    with phase("c dense"):
        _, rep = serve_phase(model, params, prompts, SERVE_FLAGS)
        print("dense:", json.dumps(rep), flush=True)
        check(min(rep["kernel_calls"].values()) > 0,
              "dense step programs hold no compiled kernel")
    mlp0 = {"layers": {"mlp": {"w_up": params["layers"]["mlp"]["w_up"][:1]}}}
    with phase("d pruned"):
        pr = prune_model(model, params, PRUNE_RATIO)
        del params
        pmodel = build(pr.cfg)
        c = pr.cfg
        print(f"pruned {PRUNE_RATIO:.0%} SPA-L1: {c.n_heads}/{c.n_kv_heads} "
              f"heads, head_dim {c.head_dim_}/{c.v_head_dim_}, d_ff "
              f"{c.d_ff}", flush=True)
        _, rep = serve_phase(pmodel, pr.params, prompts, SERVE_FLAGS)
        print("pruned:", json.dumps(rep), flush=True)
        check(min(rep["kernel_calls"].values()) > 0,
              "pruned step programs hold no compiled kernel")
    with phase("e obspa"):
        rep = obspa_phase(mlp0, seed)
        print("obspa:", json.dumps(rep), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: sharded serving on four chips, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.time()
    try:
        with phase("a device"):
            dev = device_info(args.chips)
            print(f"device: {dev['kind']} x{dev['count']}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    counts = compile_cache_counter()
    try:
        run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile cache {cache_dir or os.environ['JAX_COMPILATION_CACHE_DIR']}"
          f": {counts['hits']} hits, {counts['misses']} misses; total "
          f"set-up time {time.time() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
