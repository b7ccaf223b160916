"""Continuous-batching inference engine over the paged KV/SSM cache.

Jit-compiled device functions serve every in-flight request:

  - a batched *decode* step of fixed shape (max_seqs,): slots in decode
    feed their last sample; slots that are idle or mid-prefill ride along
    inactive (zeroed table row -> null-block writes; recurrent state
    gated by the ``active`` mask);
  - a *prefill* step of fixed shape (max_seqs, chunk_size): every slot
    with a planned chunk pushes its known tokens through
    ``forward``-style attention in ONE device call per step, scattering
    K/V straight into its pool blocks (idle rows write the null block) —
    O(P/chunk) engine steps per P-token prompt instead of the O(P)
    token-by-token warmup, which is what collapses time-to-first-token
    (benchmarks/serving.py);
  - with speculative decoding on (``spec_k > 0`` plus a draft model), a
    *draft* loop of K pruned-model decode steps fused into one call and a
    *verify* step of fixed shape (max_seqs, K+1) that scores every
    drafted position with the dense target in a single multi-token pass
    (``paged_verify_step``), accepting drafts by exact match (greedy) or
    rejection sampling (temperature) so outputs remain
    distribution-identical to the dense-only engine (DESIGN.md §9).

One engine step may mix all of these (continuous batching): the
scheduler plans prefill chunks and speculative cycles under a shared
per-step token budget so decode latency stays bounded while prompts
stream in.  ``chunk_size=0`` restores the legacy token-by-token prefill
exactly; ``spec_k=0`` the dense-only decode.

Self-speculative decoding is the pruning loop closed: the SPA/OBSPA-
pruned model shares the dense model's vocabulary, so it is a free draft.
Draft and target each own a device block *pool*, but share one host-side
allocator/block-table — both write a sequence's KV at the same pool
coordinates, so admission, growth, COW and preemption stay single-
sourced.  Rejected drafts roll back by cursor (``PagedCache.truncate``);
recurrent SSM/conv state cannot be rewound that way, so SSM/hybrid
families are capability-gated back to dense-only decode.

Prefix caching (``prefix_caching``, attention-only families) aliases
cached full blocks into new requests' tables; the scheduler hands back
copy-on-write (src, dst) pool copies which the engine runs on device
(on both pools in spec mode) before the step.

Quantized KV pools (``cache_dtype="int8"``/``"fp8_e4m3"``, DESIGN.md
§11): the pools store 1-byte elements plus per-(token, kv-head) f32
scale pools that share the KV pools' block addressing — ``_scatter_kv``
quantizes on write, the paged-attention kernel fuses the dequant into
its score and value products, and the engine's only added duty is COWing the scale pools
alongside k/v.  Host bookkeeping is unchanged, so scheduler behavior is
byte-identical across cache dtypes; ~3.8x more history fits per HBM
byte vs f32 (benchmarks/serving.py --cache-dtype).

Host<->device traffic is one batched transfer per step: every sampled
token, acceptance count and prefill logit the host needs is fetched in a
single ``jax.device_get`` (``stats["host_syncs"]``; asserted in
tests/test_serve_spec.py).

Observability (``Engine(..., telemetry=...)``; DESIGN.md §12): a
``repro.obs.Telemetry`` handle records per-step phase timers (plan /
prefill dispatch / decode-or-spec dispatch / the one device_get sync /
host fold), per-request lifecycle spans (submit → admit → first chunk →
first token → preempt/resume → finish) and per-step pool gauges.  All
instrumentation is host-side wall clock around the existing calls —
never inside jitted code, never touching the RNG — so metrics-on and
metrics-off engine outputs are byte-identical (tests/test_obs.py), and
the disabled default costs one attribute check per hook.  The engine's
run counters are registry-backed; ``run()`` stats are a diff of two
registry snapshots.

Sharded serving (``Engine(..., mesh=...)``; DESIGN.md §10): the same
engine runs over a (data, model) device mesh — request slots
data-parallel, paged pools tensor-parallel over kv_heads, all host
bookkeeping (allocator, tables, prefix index, scheduler) still global
and single-sourced.  Pure-DP attention meshes run every step under
shard_map with per-device pool replicas (zero-collective steady
decode); everything else goes through sharding-constrained jit with the
paged-attention kernel shard_mapped per device.  Outputs are
byte-identical to the single-device engine at temperature 0
(tests/test_serve_sharded.py).
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import math
import time
from typing import Any, Iterable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import tree_shardings, use_rules
from repro.kernels.paged_attention import CACHE_DTYPES, is_quantized
from repro.obs import DEFAULT_TIME_BUCKETS, NULL_CTX, Telemetry
from repro.serve.faults import CrashError, FaultError, FaultInjector
from repro.serve.kv_cache import OutOfBlocks, PagedCache
from repro.serve.scheduler import FCFSScheduler, Request, RequestState

# engine run counters, registry-backed (repro.obs): the keys double as
# the delta-stat names Engine.run() reports, so stats stay a pure diff
# of two registry snapshots instead of hand-rolled `x0` locals
_RUN_COUNTERS = ("steps", "decode_tokens", "prefill_tokens",
                 "prefill_chunks", "cow_copies", "host_syncs",
                 "spec_cycles", "spec_proposed", "spec_accepted",
                 # fault-tolerance layer (DESIGN.md §14)
                 "faults_injected", "recoveries", "requests_shed",
                 "audit_violations", "callback_errors",
                 # cluster failover / block migration (DESIGN.md §15)
                 "migrated_blocks",
                 # intra-mesh cross-shard aliasing (DESIGN.md §16):
                 # refused cross-shard prefix matches vs replica copies
                 # executed to make the alias legal
                 "alias_refusals", "shard_moves")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seqs: int = 8                 # decode slots = max batch per step
    block_size: int = 16              # tokens per KV block
    max_len: int = 512                # per-sequence token capacity
    num_blocks: int = 0               # 0 -> pool sized for worst case
    seed: int = 0
    chunk_size: int = 32              # prefill chunk; 0/1 -> token-by-token
    prefill_budget: int = 0           # max prefill tokens/step (0 = no cap)
    prefix_caching: bool = True       # share full blocks across prefixes
    spec_k: int = 0                   # draft tokens per speculative cycle
    spec_ema: float = 0.0             # >0: dynamic K, EMA coefficient of
                                      # the per-slot acceptance rate
    draft_cache_dtype: str = ""       # "" = draft pool in the model dtype;
                                      # e.g. "bfloat16" narrows the draft
                                      # KV pool (lossless under verify)
    cache_dtype: str = ""             # target KV pool dtype: "" = model
                                      # dtype; "float32"/"bfloat16" cast;
                                      # "int8"/"fp8_e4m3" quantize with
                                      # per-write scale pools and fused
                                      # kernel dequant (DESIGN.md §11)
    async_step: bool = False          # run()/stream() drive step_async():
                                      # double-buffered submit/reconcile
                                      # pipeline (DESIGN.md §13); outputs
                                      # stay byte-identical at temp 0
    donate_pools: str = "auto"        # donate KV pools into the jitted
                                      # steps ("always"/"never"); "auto"
                                      # donates except for async_step on
                                      # the CPU backend: XLA:CPU acquires
                                      # donated buffers synchronously at
                                      # dispatch (the call blocks for the
                                      # whole step compute), which would
                                      # serialize the pipeline, so async
                                      # CPU trades the aliasing for an
                                      # extra pool copy (DESIGN.md §13)
    max_waiting: int = 0              # backpressure: add_request raises
                                      # EngineOverloaded once this many
                                      # requests wait (0 = unbounded)
    audit_level: str = "off"          # runtime invariant auditing
                                      # (DESIGN.md §14): "off" | "alloc"
                                      # (allocator conservation) | "full"
                                      # (the PagedCache.check() oracle);
                                      # a violation quarantines into the
                                      # recover path instead of serving
                                      # from corrupt state
    audit_interval: int = 1           # audit every N engine steps
    degrade: bool = False             # graceful-degradation ladder under
                                      # sustained pool pressure: shed
                                      # aged waiting requests, clamp
                                      # speculative K to 1, pause
                                      # prefix-cache admission
    shed_queue_age_s: float = 0.5     # degraded: shed waiting requests
                                      # older than this (finish_reason
                                      # "shed" — a retriable rejection)
    pressure_threshold: float = 0.125 # pressured when available blocks
                                      # fall below this pool fraction
                                      # (or the waiting queue is full)
    pressure_window: int = 3          # consecutive pressured (calm)
                                      # steps to engage (disengage)
    drain_timeout_s: float = 0.0      # drain() deadline: running
                                      # requests still unfinished after
                                      # this many seconds are force-
                                      # preempted into the waiting queue
                                      # (waiting-with-prefix, snapshot-
                                      # able) so a straggler cannot
                                      # stall a rolling restart
                                      # (0 = unbounded)
    role: str = "mixed"               # disaggregated serving (DESIGN.md
                                      # §16): "mixed" plans everything;
                                      # "prefill" plans prefill chunks
                                      # only and parks decode-phase
                                      # sequences for cluster migration;
                                      # "decode" plans normally (it can
                                      # recompute-prefill on fallback) —
                                      # the Cluster keeps new prompts
                                      # off it
    migrate_on_alias: bool = True     # DP mode: migrate blocks across
                                      # shard replicas to serve cross-
                                      # shard prefix aliases (False =
                                      # PR 4's conservative refusal,
                                      # counted in alias_refusals)

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_len // self.block_size)

    def pool_blocks(self) -> int:
        if self.num_blocks:
            return self.num_blocks
        # worst case every slot full, +1 for the reserved null block
        return self.max_seqs * self.blocks_per_seq + 1


class EngineOverloaded(RuntimeError):
    """Backpressure-aware admission (ServeConfig.max_waiting): the
    waiting queue is full (or the engine is draining), so ``add_request``
    refuses instead of growing host state without bound.  Callers shed
    load or retry later."""


class AuditViolation(RuntimeError):
    """A runtime invariant audit (ServeConfig.audit_level) failed AND the
    recovery rebuild could not restore a consistent state — the engine
    refuses to keep serving from memory it cannot trust.  The recoverable
    case never raises: it is counted (``audit_violations``,
    ``recoveries``) and serving continues (DESIGN.md §14)."""


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    prompt: tuple[int, ...]
    tokens: list[int]                 # generated tokens
    preemptions: int
    steps: int                        # engine steps, first admission -> finish
    ttft_s: float = 0.0               # submission -> first sampled token
    queue_wait_s: float = 0.0         # submission -> first admission
    preempt_stall_s: float = 0.0      # total wall spent evicted, preempt
                                      # -> re-admission, summed over evictions
    tpot_s: float = 0.0               # mean per-token latency after the
                                      # first token (0 for 1-token requests)
    spec_proposed: int = 0            # draft tokens offered to verification
    spec_accepted: int = 0            # draft tokens the target accepted
    finish_reason: str = "length"     # stop | length | cancelled |
                                      # deadline | shed (load shedding) |
                                      # error (callback raise / fault)


@dataclasses.dataclass
class SequenceHandoff:
    """One request's portable state for failover / migration (DESIGN.md
    §15): everything a byte-compatible engine needs to resume the
    request without recompute — the request state (slot-independent),
    its latency wall clocks, and (for requests that were running on an
    attention-family single-device engine) the committed hash chain plus
    the raw pool bytes of its KV(+scale) blocks, gathered block-wise
    from the source pools.  ``key`` is the exporter's ``handoff_key()``;
    an adopter whose key differs falls back to waiting-with-recompute,
    which is still byte-identical at temperature 0 (the recompute-
    preemption contract).  Host-only transport: ``on_token``/``deadline``
    ride along in-process but are not serializable."""
    state: RequestState
    clocks: dict[str, float]
    key: tuple = ()
    num_cached: int = 0               # tokens the pool bytes cover
    draft_cached: int = 0             # tokens the draft pool bytes cover
    chain: list[int] = dataclasses.field(default_factory=list)
    pools: dict[str, Any] | None = None        # (L, n_blocks, ...) bytes
    draft_pools: dict[str, Any] | None = None
    on_token: Any = None
    deadline: float | None = None


# latency wall clocks that ride a handoff (name -> the engine's per-rid
# dict attribute), so TTFT / queue-wait / preempt-stall accounting
# survives re-homing onto another replica
_HANDOFF_CLOCKS = (("submit", "_submit_wall"), ("first_tok",
                   "_first_tok_wall"), ("last_tok", "_last_tok_wall"),
                   ("queue_wait", "_queue_wait"),
                   ("preempt", "_preempt_wall"),
                   ("preempt_stall", "_preempt_stall"))

# pool entries that ride block migration (the same set _cow_impl copies:
# KV plus the per-(token, head) scale pools sharing block addressing)
_POOL_KEYS = ("k", "v", "k_scale", "v_scale")


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unreconciled engine step: the async pipeline's
    in-flight record (DESIGN.md §13).

    Holds the plan, the device output arrays awaiting the step's single
    ``device_get``, and the fold metadata captured *at submit time* —
    which rows sample a token (``emit``), where each sampling request's
    token lives in the fetch arrays (``src``, the next step's
    device-side token feed), and rows a later reconcile cancelled
    (mispredicted finishes) whose samples must be discarded.  ``folded``
    marks that ``_predict_fold`` already advanced the host cursors, so
    ``_reconcile`` only materializes token values."""
    plan: Any
    running: list[RequestState]
    fetch: dict[str, Any] = dataclasses.field(default_factory=dict)
    pre_rows: list[tuple[RequestState, int]] = \
        dataclasses.field(default_factory=list)      # sampled prefill rows
    decode_rows: list[tuple[RequestState, int, bool]] = \
        dataclasses.field(default_factory=list)      # (state, slot, emit)
    spec_meta: list[tuple[RequestState, int, int]] = \
        dataclasses.field(default_factory=list)
    src: dict[int, tuple[str, int]] = \
        dataclasses.field(default_factory=dict)      # rid -> (array, slot)
    cancelled: set[int] = dataclasses.field(default_factory=set)
    folded: bool = False


class Engine:
    # extra host-sync attempts before a step is aborted (DESIGN.md §14):
    # the fetched device arrays stay alive across attempts, so a retried
    # fetch is byte-identical to the one that failed
    _sync_retries = 2

    def __init__(self, model, params, cfg: ServeConfig | None = None,
                 draft_model=None, draft_params=None, mesh=None,
                 telemetry: Telemetry | None = None,
                 faults: FaultInjector | None = None):
        if not model.cfg.has_decode:
            raise ValueError(f"{model.cfg.name} has no decode path")
        if model.cfg.family == "vlm":
            raise ValueError("vlm serving needs patch prefill (not supported)")
        self.model = model
        self.params = params
        self.cfg = cfg or ServeConfig()
        # fault injection (repro.serve.faults; DESIGN.md §14): None keeps
        # every hook behind one attribute check.  A plain attribute, not
        # reset() state, so tests can attach/detach an injector mid-life.
        self.faults = faults
        if self.cfg.audit_level not in ("off", "alloc", "full"):
            raise ValueError(f"audit_level {self.cfg.audit_level!r} "
                             f"not in ('off', 'alloc', 'full')")
        if self.cfg.audit_interval < 1:
            raise ValueError("audit_interval must be >= 1")
        # --- observability (repro.obs; DESIGN.md §12) ---------------------
        # Host-side only: phase timers, lifecycle spans and pool gauges
        # never touch the jitted paths, the device arrays, or the RNG, so
        # enabling telemetry cannot change engine outputs (tests/test_obs).
        # The default disabled handle is a no-op (one attr check per hook);
        # the registry's run counters are always live — they replaced
        # equally-cheap attribute increments and back run()'s stats.
        self.obs = telemetry if telemetry is not None else \
            Telemetry(enabled=False)
        # --- mesh-aware serving (DESIGN.md §10) ---------------------------
        # With a (data, model) mesh the engine becomes one sharded SPMD
        # program: block pools + head-sharded params go tensor-parallel
        # over `model` (kv_heads), request slots data-parallel over
        # `data`; block tables and all host bookkeeping stay global.
        # mesh=None is byte-for-byte the single-device engine.
        #
        # Two sharded modes:
        #   "dp"    — pure data-parallel mesh (model axis 1), attention
        #             family, slots divide the data axis: every step runs
        #             under shard_map with a *device-local* pool replica
        #             per data shard.  A shard's replica is authoritative
        #             for its own slots' blocks only — decode AND prefill
        #             both write shard-locally, so the prefix index is
        #             home-shard gated (PagedCache.data_shards).  Zero
        #             collectives in steady decode and prefill — devices
        #             run fully concurrently.
        #   "gspmd" — anything else (tensor parallelism, recurrent
        #             families, non-dividing slot counts): sharding-
        #             constrained jit; GSPMD keeps the pools globally
        #             consistent with per-layer update collectives.
        self.mesh = mesh
        self.rules = None
        self._data_shards = 1
        self.shard_mode = "none"
        if mesh is not None:
            from repro.launch.mesh import serve_rules
            self.rules = serve_rules(model.cfg, mesh)
            bspec = self.rules.spec(("serve_batch",),
                                    shape=(self.cfg.max_seqs,))[0]
            names = () if bspec is None else (
                (bspec,) if isinstance(bspec, str) else tuple(bspec))
            self._data_shards = math.prod(mesh.shape[a] for a in names) \
                if names else 1
            self.shard_mode = "gspmd"
            if (self._data_shards > 1 and mesh.shape.get("model", 1) == 1
                    and model.cfg.family != "ssm" and not model.cfg.hybrid):
                self.shard_mode = "dp"
        for field in ("cache_dtype", "draft_cache_dtype"):
            if getattr(self.cfg, field) not in CACHE_DTYPES:
                raise ValueError(f"{field} {getattr(self.cfg, field)!r} "
                                 f"not in {CACHE_DTYPES}")
        if self.cfg.donate_pools not in ("auto", "always", "never"):
            raise ValueError(f"donate_pools {self.cfg.donate_pools!r} "
                             f"not in ('auto', 'always', 'never')")
        if self.cfg.role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"role {self.cfg.role!r} "
                             f"not in ('mixed', 'prefill', 'decode')")
        self._donate_pools = {
            "auto": not (self.cfg.async_step
                         and jax.default_backend() == "cpu"),
            "always": True, "never": False}[self.cfg.donate_pools]
        self.cache = model.init_paged_cache(
            num_blocks=self.cfg.pool_blocks(),
            block_size=self.cfg.block_size,
            max_seqs=self.cfg.max_seqs,
            dtype=self.cfg.cache_dtype or None)
        if mesh is not None:
            self._params_sh = tree_shardings(mesh, self.rules,
                                             model.param_axes(), params)
            self._cache_sh = tree_shardings(
                mesh, self.rules,
                model.paged_cache_axes(
                    quantized=is_quantized(self.cfg.cache_dtype)),
                self.cache)
            self.params = jax.device_put(params, self._params_sh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        self._step_fn = self._make_fn(self._step_impl, "step", (1,))
        self._prefill_fn = self._make_fn(self._prefill_impl, "prefill", (1,))
        self._cow_fn = self._make_fn(self._cow_impl, "cow", (0,))
        # prefix caching needs the cached blocks to fully determine the
        # model state they stand for; recurrent SSM/conv state is per-slot
        # and not reconstructable from aliased KV blocks
        self._prefix_ok = (self.cfg.prefix_caching
                           and model.cfg.family != "ssm"
                           and not model.cfg.hybrid)
        # speculative decoding capability gate: rejected drafts roll back
        # by dropping KV cursor positions; recurrent SSM/conv state has no
        # such rewind, so SSM/hybrid fall back to dense-only decode
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.spec_active = (self.cfg.spec_k > 0 and draft_model is not None
                            and model.cfg.family != "ssm"
                            and not model.cfg.hybrid)
        if self.spec_active:
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError("draft/target vocabularies differ")
            self.draft_cache = draft_model.init_paged_cache(
                num_blocks=self.cfg.pool_blocks(),
                block_size=self.cfg.block_size,
                max_seqs=self.cfg.max_seqs,
                dtype=self.cfg.draft_cache_dtype or None)
            if mesh is not None:
                self._draft_params_sh = tree_shardings(
                    mesh, self.rules, draft_model.param_axes(), draft_params)
                self._draft_cache_sh = tree_shardings(
                    mesh, self.rules,
                    draft_model.paged_cache_axes(
                        quantized=is_quantized(self.cfg.draft_cache_dtype)),
                    self.draft_cache)
                self.draft_params = jax.device_put(draft_params,
                                                   self._draft_params_sh)
                self.draft_cache = jax.device_put(self.draft_cache,
                                                  self._draft_cache_sh)
            self._draft_fn = self._make_fn(self._draft_impl, "draft", (1,))
            self._verify_fn = self._make_fn(self._verify_impl, "verify", (1,))
            self._draft_prefill_fn = self._make_fn(
                self._draft_prefill_impl, "draft_prefill", (1,))
        self.reset()

    def _make_fn(self, impl, which: str, donate: tuple[int, ...]):
        """Jit one device step.  "dp" mode wraps the impl in shard_map
        first: per-device pool replicas (specs P() with check_vma=False —
        replicas legitimately diverge on foreign slots' blocks) and every
        slot-batched operand — decode rows AND prefill chunks — split
        over `data`, so each shard computes and writes only its own
        slots' blocks.  A block's KV therefore exists only on its home
        shard, which is why the PagedCache prefix index is home-shard
        gated in this mode.  The sampling key is folded with the shard
        index so shards draw distinct noise at temperature > 0 (greedy
        byte parity is key-independent)."""
        if self.shard_mode == "dp":
            if which in ("step", "prefill", "draft", "verify"):
                inner = impl

                def impl(*args, _inner=inner):
                    *rest, key = args
                    key = jax.random.fold_in(
                        key, jax.lax.axis_index("data"))
                    return _inner(*rest, key)
            in_specs, out_specs = self._dp_specs(which)
            impl = jax.shard_map(impl, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)
        return jax.jit(impl,
                       donate_argnums=donate if self._donate_pools else (),
                       **self._jit_shardings(which))

    def _dp_specs(self, which: str):
        d, r = P("data"), P()
        dt = P("data", None)
        dv = P("data", None, None)
        if which == "step":
            return (r, r, d, d, dt, d, d, r), (d, r)
        if which == "prefill":
            return (r, r, dt, dt, d, dt, d, d, r), (d, r)
        if which == "cow":
            return (r, r, r), r
        if which == "draft":
            return (r, r, dt, d, d, dt, d, d, r), (dt, dv, r)
        if which == "verify":
            return (r, r, d, dt, dv, d, d, dt, d, d, d, r), (dt, d, r)
        if which == "draft_prefill":
            return (r, r, dt, dt, d, dt, d), r
        raise ValueError(which)

    # ----- sharded-jit plumbing -----
    def _sh(self, *axes: str | None, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.rules.spec(axes, shape=shape))

    def _jit_shardings(self, which: str) -> dict:
        """in/out_shardings for the jitted steps on the serving mesh.

        Slot-batched operands split over the data axis; block tables are
        sharded with their slots but replicated over model (every tensor
        shard addresses the same pool blocks); PRNG keys and the B=1
        prefill chunk replicate; params and pools keep their placement
        (donated pools must round-trip with an identical sharding or XLA
        cannot alias the buffers).  mesh=None -> plain jit.
        """
        if self.mesh is None:
            return {}
        B, NB = self.cfg.max_seqs, self.cfg.blocks_per_seq
        K = max(self.cfg.spec_k, 1)
        C = max(self.cfg.chunk_size, 1)
        V = self.model.cfg.vocab_size
        b1 = self._sh("serve_batch", shape=(B,))
        bK = self._sh("serve_batch", None, shape=(B, K))
        bC = self._sh("serve_batch", None, shape=(B, C))
        bKV = self._sh("serve_batch", None, None, shape=(B, K, V))
        bt = self._sh("serve_batch", None, shape=(B, NB))
        r = self._sh()                      # replicated (keys, scalars)
        if which == "step":
            return dict(
                in_shardings=(self._params_sh, self._cache_sh,
                              b1, b1, bt, b1, b1, r),
                out_shardings=(b1, self._cache_sh))
        if which == "prefill":
            return dict(
                in_shardings=(self._params_sh, self._cache_sh,
                              bC, bC, b1, bt, b1, b1, r),
                out_shardings=(b1, self._cache_sh))
        if which == "cow":
            return dict(in_shardings=(self._cache_sh, r, r),
                        out_shardings=self._cache_sh)
        if which == "draft":
            return dict(
                in_shardings=(self._draft_params_sh, self._draft_cache_sh,
                              bK, b1, b1, bt, b1, b1, r),
                out_shardings=(bK, bKV, self._draft_cache_sh))
        if which == "verify":
            return dict(
                in_shardings=(self._params_sh, self._cache_sh,
                              b1, bK, bKV, b1, b1, bt, b1, b1, b1, r),
                out_shardings=(bK, b1, self._cache_sh))
        if which == "draft_prefill":
            return dict(
                in_shardings=(self._draft_params_sh, self._draft_cache_sh,
                              bC, bC, b1, bt, b1),
                out_shardings=self._draft_cache_sh)
        raise ValueError(which)

    def _trace_ctx(self):
        """Sharding context for device calls in "gspmd" mode: installs the
        serve rules + mesh (both the module-level context ``constrain``
        and the kernel's shard_map wrap read, and jax's mesh context
        manager that ``with_sharding_constraint`` needs) at trace time.

        "dp" mode deliberately installs nothing: the step itself is the
        shard_map — inside it every device runs plain single-device code
        (constrain must no-op, and the kernel must not nest another
        shard_map)."""
        if self.mesh is None or self.shard_mode == "dp":
            return contextlib.nullcontext()
        stack = contextlib.ExitStack()
        stack.enter_context(use_rules(self.rules, mesh=self.mesh))
        stack.enter_context(self.mesh)
        return stack

    def reset(self) -> None:
        """Clear all request/allocator state; keep params, pools, and the
        compiled step (stale pool contents are dead: reads are gated by
        per-slot positions and SSM state re-zeroes at position 0)."""
        # per-device pool replicas ("dp") restrict prefix aliasing to a
        # block's home shard and balance slot placement; "gspmd" pools
        # are globally consistent, so they keep the global index and the
        # legacy placement (data_shards=1)
        self.cache_host = PagedCache(
            max_seqs=self.cfg.max_seqs,
            num_blocks=self.cfg.pool_blocks(),
            block_size=self.cfg.block_size,
            max_blocks_per_seq=self.cfg.blocks_per_seq,
            prefix_caching=self._prefix_ok,
            data_shards=self._data_shards if self.shard_mode == "dp" else 1,
            migrate_on_alias=(self.shard_mode == "dp"
                              and self.cfg.migrate_on_alias))
        self.scheduler = FCFSScheduler(self.cache_host)
        self._key = jax.random.PRNGKey(self.cfg.seed)
        self._rid = 0
        self._c = {k: self.obs.registry.counter("serve/" + k)
                   for k in _RUN_COUNTERS}
        for c in self._c.values():
            c.reset()
        self._admit_step: dict[int, int] = {}
        self._finish_step: dict[int, int] = {}
        # per-request wall clocks (request-lifecycle spans + the latency
        # fields on FinishedRequest; all host-side)
        self._submit_wall: dict[int, float] = {}
        self._first_tok_wall: dict[int, float] = {}
        self._last_tok_wall: dict[int, float] = {}
        self._queue_wait: dict[int, float] = {}
        self._preempt_wall: dict[int, float] = {}
        self._preempt_stall: dict[int, float] = {}
        self._chunked: set[int] = set()   # rids whose first chunk is logged
        # async pipeline + serving front-end state (DESIGN.md §13)
        self._pending: _Inflight | None = None
        self._on_token: dict[int, Any] = {}    # rid -> streaming callback
        self._deadline: dict[int, float] = {}  # rid -> absolute wall time
        self._drained = 0    # scheduler.finished entries already reported
        # fault-tolerance / degradation state (DESIGN.md §14)
        self._tick = 0                  # monotonic hook tick: hold expiry
        self._fault_held: list[tuple[int, list[int]]] = []
        self._draining = False          # drain(): no new admissions
        self._degraded = False          # degradation ladder engaged
        self._pressure_run = 0
        self._calm_run = 0

    # back-compat accessors: these were plain attributes before the
    # registry existed and are still read by tests/benchmarks
    @property
    def _steps(self) -> int:
        return self._c["steps"].value

    @property
    def _cow_copies(self) -> int:
        return self._c["cow_copies"].value

    @property
    def _host_syncs(self) -> int:
        return self._c["host_syncs"].value

    # ----- jitted steps -----
    def _sample(self, logits, temps, key):
        greedy = jnp.argmax(logits, axis=-1)
        temps_safe = jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(key, logits / temps_safe, axis=-1)
        return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    def _step_impl(self, params, cache, tokens, positions, block_tables,
                   temps, active, key):
        logits, cache = self.model.paged_decode_step(
            params, cache, tokens, positions, block_tables, active)
        return self._sample(logits, temps, key), cache

    def _prefill_impl(self, params, cache, tokens, positions, slots,
                      block_tables, valid, temps, key):
        logits, cache = self.model.paged_prefill_step(
            params, cache, tokens, positions, slots, block_tables, valid)
        return self._sample(logits, temps, key), cache

    def _draft_prefill_impl(self, params, cache, tokens, positions, slots,
                            block_tables, valid):
        """Spec mode: the draft pool needs the prompt's KV too (the draft
        attends over its own history); logits are discarded."""
        _, cache = self.draft_model.paged_prefill_step(
            params, cache, tokens, positions, slots, block_tables, valid)
        return cache

    def _cow_impl(self, cache, src, dst):
        # scale pools COW in lockstep with their KV pools: a copied block
        # is meaningless without the scales its bytes were written under
        for name in ("k", "v", "k_scale", "v_scale"):
            if name in cache:
                cache[name] = cache[name].at[:, dst].set(cache[name][:, src])
        return cache

    def _dist(self, logits, temps):
        """The distribution ``_sample`` actually samples from: softmax at
        temperature, a one-hot argmax at 0 (so the rejection-sampling
        identity also covers greedy exact-match acceptance)."""
        lf = logits.astype(jnp.float32)
        t = jnp.maximum(temps, 1e-6)[..., None]
        soft = jax.nn.softmax(lf / t, axis=-1)
        hard = jax.nn.one_hot(jnp.argmax(lf, -1), lf.shape[-1],
                              dtype=jnp.float32)
        return jnp.where(temps[..., None] > 0, soft, hard)

    def _draft_impl(self, params, cache, forced, known_len, start_pos,
                    block_tables, active, temps, key):
        """K pruned-model decode steps fused into one device call.

        forced (B, K): known tokens to feed first — normally just the
        last sampled token (known_len == 1), plus catch-up tokens when
        the draft pool lags the target's cursor (the full-acceptance KV
        gap, DESIGN.md §9).  Step i feeds ``forced[:, i]`` while
        i < known_len, else its own previous sample; every step writes
        draft KV at ``start_pos + i``.  Returns the K candidate tokens
        (right-aligned from the step that consumed the last known token;
        positions past ``K - known_len + 1`` are padding the verify mask
        discards), their proposal distributions q (B, K, V), and cache.
        """
        B, K = forced.shape
        prev = forced[:, 0]
        cands, qs = [], []
        for i in range(K):
            tok = jnp.where(jnp.int32(i) < known_len, forced[:, i], prev)
            logits, cache = self.draft_model.paged_decode_step(
                params, cache, tok, start_pos + jnp.int32(i), block_tables,
                active)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, temps, sub)
            cands.append(nxt)
            qs.append(self._dist(logits, temps))
            prev = nxt
        cand = jnp.stack(cands, 1)                          # (B, K)
        q = jnp.stack(qs, 1)                                # (B, K, V)
        # candidates begin at the step that fed the last known token
        idx = jnp.clip((known_len - 1)[:, None] + jnp.arange(K)[None],
                       0, K - 1)
        cand = jnp.take_along_axis(cand, idx, axis=1)
        q = jnp.take_along_axis(q, idx[..., None], axis=1)
        return cand, q, cache

    def _verify_impl(self, params, cache, base_tok, cand, qprobs,
                     positions0, slots, block_tables, valid, ncand, temps,
                     key):
        """One multi-token target pass over ``[base token, drafts]``, then
        exact speculative acceptance.

        The K verify rows feed ``[base, c_1 .. c_{K-1}]``: row j's logits
        are the target's distribution for sequence position
        ``positions0 + j + 1`` — exactly what a token-by-token decode
        would have sampled from — and score candidate c_{j+1}.  (The last
        candidate's own KV is not written this cycle; if accepted it
        becomes the next cycle's base row.  No "bonus" token is emitted
        on full acceptance — emitting it would leave the draft pool one
        position behind, halving the next cycle's candidates; deferring
        it to the next verify row 0 samples from the identical target
        distribution, so losslessness is untouched.)

        Candidate j is accepted with probability min(1, p(c)/q(c))
        (greedy: p and q are one-hots, so this is exact match); the first
        rejection resamples from norm(max(p - q, 0)) (Leviathan et
        al.-style, so outputs stay distribution-identical to the
        dense-only engine).  Rows with ``ncand == 0`` are plain decodes
        riding the verify batch: they emit row 0's target sample.

        Returns (out_tokens (B, K): accepted drafts then the replacement
        or plain-decode sample, n_acc (B,), cache).
        """
        B, K = cand.shape
        tokens = jnp.concatenate([base_tok[:, None], cand[:, :K - 1]],
                                 axis=1)                    # (B, K)
        positions = positions0[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
        logits, cache = self.model.paged_verify_step(
            params, cache, tokens, positions, slots, block_tables, valid)
        p = self._dist(logits, temps[:, None])              # (B, K, V)

        pc = jnp.take_along_axis(p, cand[..., None], -1)[..., 0]
        qc = jnp.take_along_axis(qprobs, cand[..., None], -1)[..., 0]
        k_acc, k_res, k_plain = jax.random.split(key, 3)
        u = jax.random.uniform(k_acc, (B, K))
        real = jnp.arange(K)[None] < ncand[:, None]
        ok = (u < pc / jnp.maximum(qc, 1e-30)) & real
        n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

        # residual distribution at the first rejected position; for plain
        # rows (ncand == 0) q is never consulted — row 0's plain target
        # sample is emitted instead
        res = jnp.maximum(p - qprobs, 0.0)
        res = res / jnp.maximum(res.sum(-1, keepdims=True), 1e-30)
        rep = jnp.where(
            temps[:, None] > 0,
            jax.random.categorical(k_res, jnp.log(res + 1e-30), axis=-1),
            jnp.argmax(p, -1)).astype(jnp.int32)            # (B, K)
        plain = self._sample(logits[:, 0], temps, k_plain)
        rep_at = jnp.take_along_axis(
            rep, jnp.clip(n_acc, 0, K - 1)[:, None], 1)[:, 0]
        fill = jnp.where(ncand == 0, plain, rep_at)
        j = jnp.arange(K, dtype=jnp.int32)[None]
        out = jnp.where(j < n_acc[:, None], cand,
                        jnp.where(j == n_acc[:, None], fill[:, None], 0))
        return out, n_acc, cache

    # ----- public API -----
    def add_request(self, prompt: Iterable[int], max_new_tokens: int = 32,
                    temperature: float = 0.0,
                    stop_tokens: Iterable[int] = (),
                    on_token=None, deadline_s: float | None = None) -> int:
        """Queue one request; returns its rid.

        ``on_token(token, done)`` streams every sampled token as the
        step that produced it folds (async mode: one step after
        dispatch); a tokenless finish (cancellation, deadline) calls it
        once with ``(None, True)``.  ``deadline_s`` is a wall-clock
        budget from submission — the request is cancelled (finish_reason
        "deadline") at the first step boundary past it, admitted or not.
        Raises EngineOverloaded when ``max_waiting`` requests already
        wait (backpressure), ValueError on degenerate requests (empty
        prompt, non-positive max_new_tokens, prompt+budget beyond
        capacity)."""
        if self._draining:
            raise EngineOverloaded(
                "engine is draining; retry on another instance")
        if self.cfg.max_waiting and \
                len(self.scheduler.waiting) >= self.cfg.max_waiting:
            raise EngineOverloaded(
                f"waiting queue full ({self.cfg.max_waiting}); "
                f"shed load or retry")
        rid = self._rid
        self.scheduler.add(Request(     # validates; raises before any
            rid=rid, prompt=tuple(int(t) for t in prompt),   # state lands
            max_new_tokens=max_new_tokens, temperature=temperature,
            stop_tokens=tuple(stop_tokens)))
        self._rid += 1
        now = time.time()
        self._submit_wall[rid] = now
        self.obs.event("submit", rid)
        if on_token is not None:
            self._on_token[rid] = on_token
        if deadline_s is not None:
            self._deadline[rid] = now + deadline_s
        return rid

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Cancel a request by rid; True if it was still live.  Waiting
        requests finish immediately; running ones retire at the next
        scheduling round (their blocks free there), and any sample of
        theirs still in flight is discarded at reconcile."""
        self._deadline.pop(rid, None)
        for s in self.scheduler.running:
            if s.req.rid == rid and not s.done:
                self._finish_early(s, reason)
                return True
        for s in self.scheduler.waiting:
            if s.req.rid == rid:
                self._finish_early(s, reason)
                self.scheduler.drop_waiting(s)
                return True
        return False

    def _finish_early(self, s: RequestState, reason: str) -> None:
        s.stopped = True
        s.finish_reason = reason
        rid = s.req.rid
        self._finish_step[rid] = self._steps
        self.obs.event("finish", rid, reason=reason)
        self._emit_cb(s, None, True)

    def _expire_deadlines(self) -> None:
        if not self._deadline:
            return
        now = time.time()
        for rid, t in list(self._deadline.items()):
            if now >= t:
                self.cancel(rid, reason="deadline")

    @property
    def pending_step(self) -> bool:
        """True while a dispatched step awaits reconciliation — async
        drivers must keep stepping until both the queue and this drain."""
        return self._pending is not None

    def stream(self, prompt: Iterable[int], max_new_tokens: int = 32,
               temperature: float = 0.0, stop_tokens: Iterable[int] = (),
               deadline_s: float | None = None):
        """Generate one request's tokens as a plain iterator, driving
        the engine between yields (``step_async`` when
        ``cfg.async_step``).  Other queued requests ride the same steps
        — continuous batching is unaffected."""
        buf: list[tuple[int | None, bool]] = []
        self.add_request(prompt, max_new_tokens=max_new_tokens,
                         temperature=temperature, stop_tokens=stop_tokens,
                         on_token=lambda t, d: buf.append((t, d)),
                         deadline_s=deadline_s)
        step = self.step_async if self.cfg.async_step else self.step
        while True:
            while buf:
                tok, done = buf.pop(0)
                if tok is not None:
                    yield tok
                if done:
                    return
            if not (self.scheduler.has_work or self.pending_step):
                return
            step()

    def _append_sample(self, s: RequestState, tok: int) -> None:
        self._c["decode_tokens"].inc()
        rid = s.req.rid
        now = time.time()
        if not s.generated:
            self._first_tok_wall[rid] = now
            self.obs.event("first_token", rid)
            if rid in self._submit_wall:
                self.obs.observe("latency/ttft_s",
                                 now - self._submit_wall[rid],
                                 buckets=DEFAULT_TIME_BUCKETS)
        elif rid in self._last_tok_wall:
            # streaming cares about the inter-token distribution, not
            # just the TPOT mean run() reports
            self.obs.observe("latency/itl_s",
                             now - self._last_tok_wall[rid],
                             buckets=DEFAULT_TIME_BUCKETS)
        self._last_tok_wall[rid] = now
        s.generated.append(tok)
        if tok in s.req.stop_tokens:
            s.stopped = True
            s.finish_reason = "stop"
        if s.done:
            if not s.finish_reason:
                s.finish_reason = "length"
            self._finish_step[rid] = self._steps + 1
            self.obs.event("finish", rid, reason=s.finish_reason)
        self._emit_cb(s, tok, s.done)

    def _emit_cb(self, s: RequestState, tok: int | None, done: bool
                 ) -> None:
        """Deliver one streaming callback, hardened: user code that
        raises cancels only its own request (finish_reason "error",
        counted in ``callback_errors``) — it can never unwind the step
        fold or poison async reconciliation.  The caller's ordinary
        ``if s.done: _cancel_inflight`` path then rolls back any row
        already dispatched for the request."""
        rid = s.req.rid
        cb = self._on_token.get(rid)
        if cb is None:
            return
        if done:
            del self._on_token[rid]
        try:
            if self.faults is not None and \
                    self.faults.fire("callback_error", self._steps,
                                     rid=rid) is not None:
                self._c["faults_injected"].inc()
                raise FaultError(f"injected on_token exception (rid {rid})")
            cb(tok, done)
        except Exception:
            self._c["callback_errors"].inc()
            self._on_token.pop(rid, None)
            if not done:            # _finish_early re-enters _emit_cb,
                self._finish_early(s, "error")   # cb is already popped
                try:
                    cb(None, True)  # best-effort end-of-stream notice so
                except Exception:   # a consumer blocked on the stream
                    pass            # still observes termination

    def _fetch(self, tree):
        """The step's single device->host synchronization point: one
        batched transfer of every value the host needs this step."""
        self._c["host_syncs"].inc()
        if self.faults is not None and \
                self.faults.fire("sync_error", self._steps) is not None:
            self._c["faults_injected"].inc()
            raise FaultError("injected device-sync error")
        return jax.device_get(tree)

    def _phase(self, name: str):
        """Step-phase timer (no-op context when telemetry is disabled)."""
        if not self.obs.enabled:
            return NULL_CTX
        return self.obs.phase(name, self._steps)

    def _note_transitions(self, plan) -> None:
        """Queue-transition bookkeeping for this scheduling round:
        lifecycle span events plus the queue-wait / preemption-stall
        wall clocks surfaced on FinishedRequest.  Host wall time only —
        cheap enough to run unconditionally (one time.time() when any
        transition happened)."""
        if not (plan.admitted or plan.preempted):
            return
        now = time.time()
        for s in plan.preempted:
            self._preempt_wall[s.req.rid] = now
            self.obs.event("preempt", s.req.rid)
        for s in plan.admitted:
            rid = s.req.rid
            t0 = self._preempt_wall.pop(rid, None)
            if t0 is not None:                # back from eviction
                self._preempt_stall[rid] = \
                    self._preempt_stall.get(rid, 0.0) + (now - t0)
                self.obs.event("resume", rid)
            else:
                self._queue_wait.setdefault(
                    rid, now - self._submit_wall.get(rid, now))
                self.obs.event("admit", rid)

    def _sample_gauges(self) -> None:
        """Per-step pool occupancy + prefix-index gauges (telemetry only;
        recorded both as registry gauges and trace counter samples)."""
        a = self.cache_host.allocator
        self.obs.sample("pool", {
            "free": a.num_free, "live": a.num_live, "cached": a.num_cached,
            "held": a.num_held, "evictions": a.total_evictions,
            "cow_copies": self._cow_copies,
            "degraded": 1.0 if self._degraded else 0.0})
        c = self.cache_host
        if c.prefix_caching:
            self.obs.sample("prefix", {
                "lookups": c.prefix_lookups, "hits": c.prefix_hits,
                "hit_rate": c.prefix_hits / max(c.prefix_lookups, 1)})
        # host bubble fraction: the share of step wall spent blocked in
        # the device_get — the async pipeline's before/after number
        # (sync engine ~= device time / step; overlap shrinks it)
        hists = self.obs.registry.histograms
        step_h = hists.get("phase/step")
        if step_h is not None and step_h.total > 0:
            sync_h = hists.get("phase/sync")
            self.obs.sample("engine", {
                "bubble_fraction": (sync_h.total / step_h.total)
                if sync_h is not None else 0.0})

    def step(self) -> list[RequestState]:
        """One lockstep engine step: schedule, run prefill chunks + the
        decode (or draft/verify) batch, fetch the results in one
        transfer, fold them back.  Any async-pipelined step still in
        flight reconciles first, so mixed ``step``/``step_async``
        driving stays safe."""
        with self._trace_ctx():
            with self._phase("step"):
                self._fault_tick()
                self._expire_deadlines()
                self._degrade_tick()
                # audit BEFORE dispatch: corruption is caught before the
                # next step's plan/kernels consume it, so recovery can
                # still rebuild without a corrupt-table step having
                # committed wrong tokens (DESIGN.md §14)
                self._audit_maybe()
                if self._pending is not None:
                    rec, self._pending = self._pending, None
                    self._reconcile(rec)
                rec = self._submit_step()
                if rec is not None:
                    self._reconcile(rec)
                self._idle_release_holds()
            if self.obs.enabled:
                self._sample_gauges()
            return rec.running if rec is not None else []

    def step_async(self) -> list[RequestState]:
        """One double-buffered engine step (DESIGN.md §13): while the
        previous step's device work is in flight, predict its host fold
        (decode growth is deterministic; only sampled *values* are
        unknown), plan and dispatch the next step from that predicted
        state — feeding still-unfetched tokens device-to-device — then
        reconcile the previous step on its (now overlapped) sync.  Falls
        back to lockstep when prediction is unsafe: speculative decode
        or possible preemption (``_can_overlap``).  Returns the set the
        *submitted* step runs; its tokens fold one call later."""
        with self._trace_ctx():
            with self._phase("step"):
                out = self._step_async_host()
                self._idle_release_holds()
            if self.obs.enabled:
                self._sample_gauges()
            return out

    def _step_async_host(self) -> list[RequestState]:
        self._fault_tick()
        self._expire_deadlines()
        self._degrade_tick()
        self._audit_maybe()             # pre-dispatch, as in step()
        prev, self._pending = self._pending, None
        if prev is not None and self._can_overlap(prev):
            # the overlap phase measures exactly the host work hidden
            # under the in-flight device step (the de-bubbled time)
            with self._phase("overlap"):
                self._predict_fold(prev)
                rec = self._submit_step(prev=prev)
            self._reconcile(prev, newer=rec)
            self._pending = rec
            return rec.running if rec is not None else []
        if prev is not None:              # lockstep fall-back: resolve
            self._reconcile(prev)         # the true state, then plan
        rec = self._submit_step()
        self._pending = rec
        return rec.running if rec is not None else []

    def _can_overlap(self, rec: _Inflight) -> bool:
        """Conservative gate for planning on predicted state, evaluated
        *before* the predicted plan mutates anything.  Overlap needs (a)
        no speculative decode — accepted-draft growth is variable, so
        the next plan depends on the unfetched acceptance counts — and
        (b) a proof the predicted scheduling round cannot preempt: every
        running slot's next-position growth must be backable from the
        free+evictable pool (preemption would re-prefill from ``seq``,
        which cannot include in-flight token values).  Admission, COW
        and retirement are all prediction-safe and stay overlapped."""
        if self.spec_active:
            return False
        cache = self.cache_host
        will_advance = {s.req.rid for s, _, _ in rec.decode_rows}
        need = 0
        for s in self.scheduler.running:
            nc = s.num_cached + (1 if s.req.rid in will_advance else 0)
            need += cache.blocks_needed(s.slot, nc + 1)
        return need <= cache.allocator.num_available

    def _predict_fold(self, rec: _Inflight) -> None:
        """Advance host cursors for a dispatched-but-unfetched step: the
        device KV writes are deterministic and have (logically) happened,
        so ``num_cached`` grows now; the sampled token *values* are still
        in flight and tracked as ``pending`` until reconcile materializes
        them.  Rows cancelled by an earlier reconcile (mispredicted
        finish) are skipped entirely — their growth never existed."""
        rec.folded = True
        for s, _ in rec.pre_rows:
            if s.req.rid not in rec.cancelled:
                s.pending += 1
        for s, _, emit in rec.decode_rows:
            if s.req.rid in rec.cancelled:
                continue
            s.num_cached += 1
            if emit:
                s.pending += 1
            else:                         # still streaming known tokens
                self._c["prefill_tokens"].inc()

    def _submit_step(self, prev: _Inflight | None = None
                     ) -> _Inflight | None:
        """The step's host half: schedule, run COW copies, dispatch the
        prefill and decode (or draft/verify) device calls.  Everything
        here is async — no host<->device synchronization.  With ``prev``
        (async overlap), decode rows whose next token is still in flight
        read it straight from ``prev``'s device output arrays."""
        spec_k = self.cfg.spec_k if self.spec_active else 0
        # degradation ladder: clamp the *planned* K to 1 under pressure
        # (cheapest cycles, least speculative pool reservation); the
        # compiled device shapes stay (B, cfg.spec_k) by construction
        plan_spec_k = 1 if (spec_k > 1 and self._degraded) else spec_k
        with self._phase("plan"):
            while True:
                try:
                    plan = self.scheduler.plan_step(
                        self.cfg.chunk_size, self.cfg.prefill_budget,
                        plan_spec_k, self.cfg.spec_ema,
                        allow_admission=not self._draining,
                        prefill_only=self.cfg.role == "prefill")
                    break
                except OutOfBlocks:
                    # a lone running request outgrew the pool — recover
                    # instead of crashing the engine (DESIGN.md §14)
                    if not self._unjam():
                        raise
        refusals = self.cache_host.alias_refusals
        if refusals > self._c["alias_refusals"].value:
            self._c["alias_refusals"].inc(
                refusals - self._c["alias_refusals"].value)
        self._note_transitions(plan)
        if prev is not None:
            # _can_overlap proved the pool could back every growth
            assert not plan.preempted, \
                "overlap gate let a preemption through"
        running = plan.decode + [s for s, _ in plan.prefill]
        for s in running:
            self._admit_step.setdefault(s.req.rid, self._steps)
        if not running:
            return None

        # intra-mesh block migration (DESIGN.md §16) must precede the
        # COW copies and dispatch: a cross-shard alias admitted by this
        # plan is only readable on its new home once the replica copy
        # lands, and COW sources must be local to the writing shard.
        # (Reading pool buffers here implicitly syncs an overlapped
        # in-flight step — migration trades one bubble for recompute.)
        moves = self.cache_host.drain_moves()
        if moves:
            with self._phase("migrate"):
                t0 = time.perf_counter()
                self.cache = self._apply_moves(self.cache, moves)
                if self.spec_active:
                    self.draft_cache = self._apply_moves(
                        self.draft_cache, moves)
                self._c["shard_moves"].inc(len(moves))
                self.obs.observe("migrate/intra_mesh_s",
                                 time.perf_counter() - t0,
                                 buckets=DEFAULT_TIME_BUCKETS)

        for src, dst in plan.copies:          # copy-on-write pool copies
            self.cache = self._cow_fn(self.cache, np.int32(src),
                                      np.int32(dst))
            if spec_k:
                self.draft_cache = self._cow_fn(
                    self.draft_cache, np.int32(src), np.int32(dst))
            self._c["cow_copies"].inc()

        rec = _Inflight(plan=plan, running=running)

        if plan.prefill:
            sampled: list[RequestState] = []
            with self._phase("prefill_dispatch"):
                self._dispatch_prefill(plan, spec_k, rec.fetch, sampled)
            rec.pre_rows = [(s, s.slot) for s in sampled]

        if plan.decode:
            with self._phase("decode_dispatch"):   # plain, or draft+verify
                self._dispatch_decode(plan, spec_k, rec.fetch,
                                      rec.spec_meta, prev)
            if not (spec_k and plan.spec):
                # fold metadata, captured before anything moves: emit is
                # sync-fold's "model just saw the last known token" test
                rec.decode_rows = [(s, s.slot,
                                    s.num_cached == s.seq_len - 1)
                                   for s in plan.decode]
        for s, slot, emit in rec.decode_rows:
            if emit:
                rec.src[s.req.rid] = ("dec", slot)
        for s, slot in rec.pre_rows:
            rec.src[s.req.rid] = ("pre", slot)
        return rec

    def _reconcile(self, rec: _Inflight, newer: _Inflight | None = None
                   ) -> None:
        """The step's sync half: the ONE ``device_get``, then fold the
        fetched values into request state.  For a predict-folded record
        only token values materialize (``pending`` drains); otherwise
        this is the classic lockstep fold.  A token that finishes its
        request mid-pipeline (stop token, or a cancel that landed while
        the step flew) cancels the request's row in the ``newer``
        in-flight record — the misprediction rollback."""
        with self._phase("sync"):             # the ONE device_get per step
            vals: dict | None = {}
            if rec.fetch:
                vals = None
                for attempt in range(1 + self._sync_retries):
                    try:
                        vals = self._fetch(rec.fetch)
                        break
                    except FaultError:
                        continue
                if vals is not None and attempt:
                    # transient sync failure, retried clean: the device
                    # arrays are still alive, so the refetch reads the
                    # identical values
                    self._c["recoveries"].inc()
        if vals is None:                      # persistent sync failure
            self._abort_step(rec, newer)
            return

        with self._phase("fold"):
            for s, slot in rec.pre_rows:
                if s.req.rid in rec.cancelled:
                    continue                  # predict skipped it entirely
                if rec.folded:
                    s.pending -= 1
                if s.stopped:                 # cancelled mid-flight: the
                    continue                  # sample is discarded
                self._append_sample(s, int(vals["pre"][slot]))
                if s.done:
                    self._cancel_inflight(s, newer)

            if "out" in vals:                 # spec cycles are lockstep:
                self._fold_spec(rec.plan, vals["out"], vals["acc"],
                                rec.spec_meta)
            else:
                for s, slot, emit in rec.decode_rows:
                    if s.req.rid in rec.cancelled:
                        continue
                    if not rec.folded:
                        s.num_cached += 1
                        if not emit:          # still streaming known tokens
                            self._c["prefill_tokens"].inc()
                            continue
                    else:
                        if not emit:
                            continue          # counted at predict time
                        s.pending -= 1
                    if s.stopped:
                        continue
                    self._append_sample(s, int(vals["dec"][slot]))
                    if s.done:
                        self._cancel_inflight(s, newer)

            self._c["steps"].inc()
            self.scheduler.commit_progress()  # register newly-full blocks
            # commit_progress hashes s.seq[:num_cached], which clamps to
            # *known* tokens — blocks holding a pending token's KV only
            # register once its value materializes

    def _cancel_inflight(self, s: RequestState, rec: _Inflight | None
                         ) -> None:
        """Misprediction rollback: ``s`` just finished at reconcile, but
        the next step was already planned and dispatched from the
        predicted still-running state.  Discard its row in that record
        (the in-flight sample never folds; ``_predict_fold`` skips its
        growth) and hand back the blocks the predicted plan over-
        reserved — the same ``PagedCache.truncate`` rollback speculative
        decode uses; the slot's in-flight garbage KV write lands in a
        freed block that is re-written before any gated read."""
        rid = s.req.rid
        if rec is None or rid not in rec.src or rid in rec.cancelled:
            return
        rec.cancelled.add(rid)
        if s.slot >= 0:
            self.cache_host.truncate(s.slot, s.num_cached)

    # ----- fault tolerance (DESIGN.md §14) -----
    def _fault_tick(self) -> None:
        """Per-step fault hook: release expired injected holds, then let
        the injector fire the step-scoped kinds (crash / slow_step /
        alloc_hold).  One list check + one attribute check when idle."""
        self._tick += 1
        if self._fault_held:
            a = self.cache_host.allocator
            keep = []
            for rel, blocks in self._fault_held:
                if self._tick >= rel:
                    a.unhold(blocks)
                else:
                    keep.append((rel, blocks))
            self._fault_held = keep
        if self.faults is None:
            return
        f = self.faults.fire("crash", self._steps)
        if f is not None:
            self._c["faults_injected"].inc()
            raise CrashError(f"injected crash at step {self._steps}")
        f = self.faults.fire("slow_step", self._steps)
        if f is not None:
            self._c["faults_injected"].inc()
            time.sleep(f.delay_s)
        f = self.faults.fire("alloc_hold", self._steps)
        if f is not None:
            self._c["faults_injected"].inc()
            a = self.cache_host.allocator
            n = f.blocks or max(1, a.num_available // 2)
            held = a.hold(n)
            if held:
                self._fault_held.append(
                    (self._tick + max(1, f.hold_steps), held))

    def _idle_release_holds(self) -> None:
        """Injected holds simulate pool pressure DURING serving; when a
        step leaves the engine idle (no work, nothing in flight) the
        pressure is moot and outstanding holds are handed back — a hold
        outliving the last request would read as a real block leak."""
        if self._fault_held and not self.scheduler.has_work \
                and self._pending is None:
            a = self.cache_host.allocator
            for _, blocks in self._fault_held:
                a.unhold(blocks)
            self._fault_held = []

    def _abort_step(self, rec: _Inflight, newer: _Inflight | None) -> None:
        """A step's host sync failed past every retry.  Recovery splits
        on pipeline position:

        - *lockstep* (not predict-folded): no host cursor moved and the
          device KV writes are idempotent, so the step simply never
          happened.  Sampled-prefill rows rewind their cursors to re-feed
          the last prompt token; speculative reservations are handed
          back.  The redone step is byte-identical at temperature 0
          (greedy sampling is key-independent; at temperature > 0 the
          redo legitimately re-draws).
        - *folded* (async overlap): the next step already consumed this
          step's device outputs, and the lost sample values cannot be
          recovered — the rows that were waiting on them fail cleanly
          (finish_reason "error", rolled out of the newer record), while
          every non-emitting row keeps its deterministic growth."""
        self._c["recoveries"].inc()
        if not rec.folded:
            for s, _, _ in rec.spec_meta:
                if not s.done and s.slot >= 0:
                    self.cache_host.truncate(s.slot, s.num_cached + 1)
            for s, _ in rec.pre_rows:
                if s.slot >= 0:
                    s.num_cached = min(s.num_cached, s.seq_len - 1)
                    s.draft_cached = min(s.draft_cached,
                                         max(s.num_cached, 0))
            return
        for s, _ in rec.pre_rows:
            if s.req.rid in rec.cancelled:
                continue
            s.pending -= 1
            if not s.stopped:
                self._finish_early(s, "error")
            self._cancel_inflight(s, newer)
        for s, _, emit in rec.decode_rows:
            if s.req.rid in rec.cancelled or not emit:
                continue
            s.pending -= 1
            if not s.stopped:
                self._finish_early(s, "error")
            self._cancel_inflight(s, newer)
        self._c["steps"].inc()

    def _audit_maybe(self) -> None:
        """Runtime invariant auditing (ServeConfig.audit_level): run the
        property-test conservation oracle as a production defense.  On a
        violation, quarantine into the recover path instead of silently
        serving from corrupt state.  "off" costs one string compare."""
        lvl = self.cfg.audit_level
        if lvl == "off":
            return
        if self._steps % self.cfg.audit_interval:
            return
        try:
            with self._phase("audit"):
                if lvl == "alloc":
                    self.cache_host.allocator.check()
                else:
                    self.cache_host.check()
        except AssertionError as e:
            self._c["audit_violations"].inc()
            try:
                self._recover()
            except AssertionError:
                raise AuditViolation(
                    f"invariant audit failed and recovery did not "
                    f"converge: {e}") from e

    def _recover(self) -> None:
        """Quarantine-and-recover (DESIGN.md §14): rebuild every derived
        host structure from the authoritative per-slot ownership, fail
        the requests whose bookkeeping cannot be trusted, and resume.

        The in-flight async step (if any) is discarded — its fetch
        metadata may describe the corrupt state — and predicted growth
        rolls back to known tokens; device KV for those positions is
        rewritten idempotently when the requests re-plan."""
        self._c["recoveries"].inc()
        self._pending = None
        cache, sched = self.cache_host, self.scheduler
        for s in list(sched.running) + list(sched.waiting):
            s.pending = 0
        cache.rebuild()
        seen: dict[int, RequestState] = {}
        for s in sorted(list(sched.running), key=lambda r: r.req.rid):
            dup = not (0 <= s.slot < cache.max_seqs) or s.slot in seen
            if dup:
                # an invalid or contested slot: the request's blocks are
                # not distinguishable from its neighbor's — fail without
                # releasing (the slot's owner keeps it)
                self._fail_running(s, "error", release=False)
                continue
            seen[s.slot] = s
            cap = len(cache._owned[s.slot]) * cache.block_size
            tgt = max(0, min(s.num_cached, len(s.seq) - 1))
            if tgt > cap:
                # ownership cannot back the KV the cursor claims — the
                # history is gone, fail cleanly and free what's left
                self._fail_running(s, "error", release=True)
                continue
            s.num_cached = tgt
            s.draft_cached = min(s.draft_cached, tgt)
        # the free-slot stack is derived state too: recompute from the
        # surviving running set (descending, preserving LIFO admission)
        used = {s.slot for s in sched.running}
        sched._free_slots = [sl for sl in range(cache.max_seqs - 1, -1, -1)
                             if sl not in used]
        cache.check()                   # recovery must converge

    def _fail_running(self, s: RequestState, reason: str,
                      release: bool = True) -> None:
        """Fail one running request outside a scheduling round: finish
        it, move it straight to the finished list, optionally release its
        slot's blocks (recovery recomputes the free-slot stack itself)."""
        self._finish_early(s, reason)
        self.scheduler.running.remove(s)
        self.scheduler.finished.append(s)
        if release and 0 <= s.slot < self.cache_host.max_seqs:
            self.cache_host.release(s.slot)
        s.slot = -1

    def _unjam(self) -> bool:
        """``plan_step`` hit OutOfBlocks growing a lone running request.
        Release emergency resources instead of crashing the engine:
        injected holds go back first; failing that, the youngest running
        request fails cleanly ("error").  Returns False when nothing is
        left to give — the caller re-raises."""
        self._c["recoveries"].inc()
        if self._fault_held:
            a = self.cache_host.allocator
            for _, blocks in self._fault_held:
                a.unhold(blocks)
            self._fault_held = []
            return True
        live = [s for s in self.scheduler.running if not s.done]
        if not live:
            return False
        victim = max(live, key=lambda s: s.req.rid)
        self._finish_early(victim, "error")
        return True

    def _degrade_tick(self) -> None:
        """Graceful degradation under sustained pool pressure (DESIGN.md
        §14).  Pressure = available blocks below ``pressure_threshold``
        of the pool, or a full waiting queue; ``pressure_window``
        consecutive pressured (calm) steps engage (disengage) the
        ladder: shed waiting requests older than ``shed_queue_age_s``
        (finish_reason "shed" — a retriable rejection), clamp the
        planned speculative K to 1, and pause prefix-cache admission."""
        if not self.cfg.degrade:
            return
        a = self.cache_host.allocator
        usable = max(a.num_blocks - 1, 1)
        pressured = (a.num_available < self.cfg.pressure_threshold * usable
                     or (self.cfg.max_waiting > 0 and
                         len(self.scheduler.waiting) >=
                         self.cfg.max_waiting))
        if pressured:
            self._pressure_run += 1
            self._calm_run = 0
        else:
            self._calm_run += 1
            self._pressure_run = 0
        if not self._degraded and \
                self._pressure_run >= self.cfg.pressure_window:
            self._degraded = True
        elif self._degraded and self._calm_run >= self.cfg.pressure_window:
            self._degraded = False
        self.cache_host.admission_paused = self._degraded
        if self._degraded and self.cfg.shed_queue_age_s > 0 \
                and self.scheduler.waiting:
            now = time.time()
            for s in [w for w in self.scheduler.waiting if not w.done]:
                born = self._submit_wall.get(s.req.rid, now)
                if now - born > self.cfg.shed_queue_age_s:
                    self._c["requests_shed"].inc()
                    self._finish_early(s, "shed")
                    self.scheduler.drop_waiting(s)

    def drain(self, timeout_s: float | None = None
              ) -> dict[int, FinishedRequest]:
        """Graceful shutdown: stop admitting waiting requests, run every
        already-admitted request to completion (reconciling any in-flight
        async step), and return the drained records.  Waiting requests
        stay queued — a snapshot taken after ``drain()`` preserves them
        for a restored engine to serve.  ``add_request`` raises
        EngineOverloaded while draining; ``reset()`` clears the state.

        ``timeout_s`` (default ``cfg.drain_timeout_s``; 0 = unbounded)
        deadlines the drain: requests still running when it expires are
        force-preempted into the waiting queue as waiting-with-prefix
        (prompt + generated tokens ride along for recompute on
        re-admission), so one hung or long-tailed request cannot stall a
        rolling restart forever.  Nothing is failed — the preempted
        requests survive into the snapshot / backlog re-homing."""
        if timeout_s is None:
            timeout_s = self.cfg.drain_timeout_s
        deadline = time.time() + timeout_s if timeout_s > 0 else None
        self._draining = True
        step = self.step_async if self.cfg.async_step else self.step
        while self.scheduler.running or self.pending_step:
            if deadline is not None and time.time() >= deadline:
                self._force_preempt_running()
                break
            step()
        return self.pop_finished()

    def _force_preempt_running(self) -> None:
        """Drain-deadline enforcement: reconcile any in-flight step, then
        preempt every unfinished running request back to the waiting
        queue — exactly the recompute preemption pool pressure applies,
        so the requests stay byte-identically resumable.  Oldest requests
        end up at the queue's head (FCFS is preserved)."""
        if self._pending is not None:
            rec, self._pending = self._pending, None
            self._reconcile(rec)
        self.scheduler.retire_finished()
        now = time.time()
        for s in sorted(self.scheduler.running,
                        key=lambda r: r.req.rid, reverse=True):
            self.scheduler._preempt(s)
            self._preempt_wall[s.req.rid] = now
            self.obs.event("preempt", s.req.rid)
        self._idle_release_holds()

    def snapshot(self):
        """Serialize full host state + device pools (repro.serve.snapshot;
        DESIGN.md §14).  Any in-flight async step is reconciled first so
        the captured state has no pending tokens."""
        from repro.serve import snapshot as _snap
        if self._pending is not None:
            rec, self._pending = self._pending, None
            self._reconcile(rec)
        return _snap.capture(self)

    def restore(self, snap) -> None:
        """Restore a snapshot produced by a config-identical engine; the
        restored engine resumes byte-identically (DESIGN.md §14)."""
        from repro.serve import snapshot as _snap
        _snap.restore_into(self, snap)

    # ----- failover handoff / adoption (DESIGN.md §15) -----
    def handoff_key(self) -> tuple:
        """Byte-compatibility fingerprint for migrated pool blocks: two
        engines whose keys match write bit-identical KV(+scale) bytes at
        the same block coordinates, so exported blocks can scatter
        straight into the adopter's pools.  A mismatch (different model
        tier, block size, or pool dtype) downgrades adoption to
        waiting-with-recompute."""
        return (self.model.cfg.name, self.model.cfg.vocab_size,
                self.cfg.block_size, self.cfg.cache_dtype,
                self.draft_model.cfg.name if self.spec_active else "",
                self.cfg.draft_cache_dtype if self.spec_active else "")

    @property
    def can_handoff_blocks(self) -> bool:
        """Block-byte migration is gated to single-device attention
        engines: per-shard DP pool replicas hold a block's bytes only on
        its home shard (a host gather would read other shards' garbage),
        and SSM/hybrid recurrent state is per-slot, not per-block, so it
        cannot ride the block transport.  Gated-off engines still hand
        requests off — as waiting-with-recompute."""
        return (self.mesh is None and self.model.cfg.family != "ssm"
                and not self.model.cfg.hybrid)

    def discard_inflight(self) -> None:
        """Forget a dispatched-but-unreconciled step *without* its device
        fetch — failover salvage for a replica declared dead, where the
        in-flight sample values are treated as lost.  Predicted growth
        rolls back to known tokens (the same clamp ``_recover`` applies),
        leaving the host state quiescent and exportable."""
        self._pending = None
        for s in list(self.scheduler.running) + list(self.scheduler.waiting):
            s.pending = 0
            s.num_cached = max(0, min(s.num_cached, len(s.seq) - 1))
            s.draft_cached = min(s.draft_cached, max(s.num_cached, 0))

    def decode_ready(self) -> list[int]:
        """Rids whose prefill is complete (phase flipped to decode) —
        on a prefill-role engine these are parked by ``prefill_only``
        planning and wait for the cluster to migrate them to a decode
        replica (DESIGN.md §16).  The first token is already sampled
        (the final chunk's sampled prefill), so a done request never
        shows up here — it retires locally instead."""
        return [s.req.rid for s in self.scheduler.running
                if s.phase == "decode" and not s.done]

    def export_request(self, rid: int, remove: bool = False
                       ) -> SequenceHandoff:
        """Export one live (running or waiting) request as a
        :class:`SequenceHandoff`.  Running requests on a block-handoff-
        capable engine carry their KV(+scale) pool bytes — one batched
        ``device_get`` over the slot's blocks — plus the committed hash
        chain, so a byte-compatible adopter resumes decode without
        recompute and can re-register the prefix in its own index.
        ``remove=True`` also retires the request here (releasing its
        slot), for live migration off a draining engine."""
        if self._pending is not None:
            rec, self._pending = self._pending, None
            self._reconcile(rec)
        src = next((s for s in self.scheduler.running if s.req.rid == rid),
                   None)
        from_running = src is not None
        if src is None:
            src = next((s for s in self.scheduler.waiting
                        if s.req.rid == rid), None)
        if src is None:
            raise KeyError(f"rid {rid} is not live")
        st = copy.deepcopy(src)
        st.pending = 0
        st.num_cached = max(0, min(st.num_cached, len(st.seq) - 1))
        st.draft_cached = min(st.draft_cached, st.num_cached)
        clocks = {name: getattr(self, attr)[rid]
                  for name, attr in _HANDOFF_CLOCKS
                  if rid in getattr(self, attr)}
        h = SequenceHandoff(state=st, clocks=clocks,
                            key=self.handoff_key(),
                            on_token=self._on_token.get(rid),
                            deadline=self._deadline.get(rid))
        if from_running and self.can_handoff_blocks and st.num_cached > 0:
            blocks, chain = self.cache_host.export_slot(src.slot,
                                                        st.num_cached)
            h.num_cached = st.num_cached
            h.chain = chain
            h.pools = self._gather_blocks(self.cache, blocks)
            if self.spec_active and st.draft_cached > 0:
                nd = self.cache_host.blocks_for(st.draft_cached)
                h.draft_pools = self._gather_blocks(self.draft_cache,
                                                    blocks[:nd])
                h.draft_cached = st.draft_cached
        st.slot = -1
        self.obs.event("export", rid)
        if remove:
            if from_running:
                self.scheduler._release(src)
            else:
                self.scheduler.waiting.remove(src)
            self._forget_rid(rid)
        return h

    def export_backlog(self, remove: bool = False) -> list[SequenceHandoff]:
        """Export every waiting (not yet admitted, unfinished) request in
        queue order — the dead/draining replica's backlog the cluster
        re-homes onto survivors."""
        rids = [s.req.rid for s in self.scheduler.waiting if not s.done]
        return [self.export_request(rid, remove=remove) for rid in rids]

    def adopt(self, h: SequenceHandoff) -> int:
        """Adopt a handed-off request under a fresh local rid (returned).
        When the handoff carries pool bytes, the engine is byte-
        compatible (``handoff_key``), and a free slot + pool room exist,
        the blocks import directly (``PagedCache.import_slot``) and the
        request resumes decode with zero recompute; otherwise it joins
        the waiting queue and re-prefills its known prefix — either way
        the token stream is byte-identical at temperature 0.  Raises
        ValueError if the request cannot fit this engine at all."""
        st = copy.deepcopy(h.state)
        req = st.req
        if len(req.prompt) + req.max_new_tokens > self.cache_host.max_len:
            raise ValueError(
                f"adopt: prompt+max_new "
                f"{len(req.prompt) + req.max_new_tokens} exceeds per-seq "
                f"capacity {self.cache_host.max_len}")
        worst = self.cache_host.blocks_for(
            len(req.prompt) + req.max_new_tokens)
        if worst > self.cache_host.allocator.num_blocks - 1:
            raise ValueError(f"adopt: needs up to {worst} blocks but the "
                             f"pool has "
                             f"{self.cache_host.allocator.num_blocks - 1}")
        rid = self._rid
        self._rid += 1
        st.req = dataclasses.replace(req, rid=rid)
        st.slot = -1
        st.pending = 0
        self._submit_wall[rid] = h.clocks.get("submit", time.time())
        for name, attr in _HANDOFF_CLOCKS:
            if name != "submit" and name in h.clocks:
                getattr(self, attr)[rid] = h.clocks[name]
        if h.on_token is not None:
            self._on_token[rid] = h.on_token
        if h.deadline is not None:
            self._deadline[rid] = h.deadline
        self.obs.event("adopt", rid)
        if not self._adopt_blocks(st, h):
            st.num_cached = 0
            st.draft_cached = 0
            self.scheduler.adopt_waiting(st)
        return rid

    def _adopt_blocks(self, st: RequestState, h: SequenceHandoff) -> bool:
        """Seat an adopted request straight into a slot with its migrated
        pool bytes.  False (nothing mutated) when the handoff carries no
        blocks, keys mismatch, no slot is free, or the pool lacks room —
        the caller falls back to waiting-with-recompute."""
        if (h.pools is None or h.key != self.handoff_key()
                or not self.can_handoff_blocks
                or not self.scheduler._free_slots):
            return False
        cache, sched = self.cache_host, self.scheduler
        slot = sched._pick_slot()
        n = next(iter(h.pools.values())).shape[1]
        try:
            dst = cache.import_slot(slot, n, h.chain,
                                    n_tokens=st.seq_len + 1)
        except OutOfBlocks:
            return False
        st.num_cached = h.num_cached
        sched.adopt_running(st, slot)
        self.cache = self._scatter_blocks(self.cache, h.pools, dst)
        moved = n
        if self.spec_active and h.draft_pools is not None \
                and h.draft_cached > 0:
            nd = next(iter(h.draft_pools.values())).shape[1]
            self.draft_cache = self._scatter_blocks(
                self.draft_cache, h.draft_pools, dst[:nd])
            st.draft_cached = h.draft_cached
            moved += nd
        else:
            st.draft_cached = 0
        self._c["migrated_blocks"].inc(moved)
        self._admit_step.setdefault(st.req.rid, self._steps)
        return True

    def _gather_blocks(self, pools, blocks: list[int]) -> dict:
        """Host-side bytes of ``blocks`` from each pool entry that uses
        block addressing — one batched transfer (blocks are pool axis 1,
        matching ``_cow_impl``)."""
        idx = np.asarray(blocks, np.int32)
        return jax.device_get({name: pools[name][:, idx]
                               for name in _POOL_KEYS if name in pools})

    def _scatter_blocks(self, pools, vals: dict, blocks: list[int]):
        """Write migrated block bytes into this engine's pools at the
        freshly-imported block ids (eager `.at[].set`; the arrays feed
        the next jitted step like any other pool update)."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        out = dict(pools)
        for name, v in vals.items():
            if name in out:
                out[name] = out[name].at[:, idx].set(jnp.asarray(v))
        return out

    def _apply_moves(self, pools, moves: list[tuple[int, int, int]]):
        """Intra-mesh block migration (DESIGN.md §16): copy block bytes
        between per-device pool *replicas* so a cross-shard prefix alias
        reads valid KV on its new home shard.  DP pools are replicated
        NamedShardings whose per-device buffers legitimately diverge
        (each device is authoritative for its own slots' blocks), so
        this is host-mediated buffer surgery: pick each device's buffer
        out of ``addressable_shards``, copy the source shard's bytes for
        the moved blocks onto the destination device, and rebuild the
        array from the per-device buffers.  Scale pools ride along via
        ``_POOL_KEYS``.  Moves are grouped per (src, dst) pair in first-
        occurrence order, which preserves chained re-homes (a block
        moved A->B then B->C sources B's already-updated buffer)."""
        devs = list(self.mesh.devices.flat)   # data-axis order (model=1)
        grouped: dict[tuple[int, int], list[int]] = {}
        for b, src, dst in moves:
            grouped.setdefault((src, dst), []).append(b)
        out = dict(pools)
        for name in _POOL_KEYS:
            if name not in out:
                continue
            arr = out[name]
            shards = arr.addressable_shards
            per = {s.device: s.data for s in shards}
            for (src, dst), blocks in grouped.items():
                idx = jnp.asarray(np.asarray(blocks, np.int32))
                payload = jax.device_put(per[devs[src]][:, idx],
                                         devs[dst])
                per[devs[dst]] = per[devs[dst]].at[:, idx].set(payload)
            out[name] = jax.make_array_from_single_device_arrays(
                arr.shape, arr.sharding, [per[s.device] for s in shards])
        return out

    def _dispatch_decode(self, plan, spec_k, fetch, spec_meta, prev=None):
        """Build the fixed-shape decode batch and launch either the plain
        decode step or the speculative draft/verify cycle.  Under async
        overlap, rows with a pending token splice it in from the previous
        step's device arrays (``jnp.where`` on device — the token value
        never round-trips through the host)."""
        B = self.cfg.max_seqs
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        active = np.zeros((B,), bool)
        feed = {"dec": np.zeros((B,), bool), "pre": np.zeros((B,), bool)}
        for s in plan.decode:
            if s.pending:
                src, pslot = prev.src[s.req.rid]
                assert pslot == s.slot     # no preemption while pending
                feed[src][s.slot] = True
            else:
                tokens[s.slot] = s.next_token
            positions[s.slot] = s.num_cached
            temps[s.slot] = s.req.temperature
            active[s.slot] = True
        # inactive slots write into the null block, not their tables
        tables = np.where(active[:, None], self.cache_host.tables, 0)

        if spec_k and plan.spec:
            fetch["out"], fetch["acc"] = self._spec_decode(
                plan, tokens, positions, temps, active, tables,
                spec_meta)
        else:
            tok = jnp.asarray(tokens)
            for name, mask in feed.items():
                if mask.any():
                    tok = jnp.where(jnp.asarray(mask), prev.fetch[name],
                                    tok)
            self._key, sub = jax.random.split(self._key)
            nxt, self.cache = self._step_fn(
                self.params, self.cache, tok,
                jnp.asarray(positions), jnp.asarray(tables),
                jnp.asarray(temps), jnp.asarray(active), sub)
            fetch["dec"] = nxt

    def _dispatch_prefill(self, plan, spec_k, fetch, sampled_prefills):
        """Every planned chunk rides ONE fixed-shape (max_seqs, C) call —
        one launch per step instead of a per-slot python loop, and under
        sharded-DP each data shard prefills its own slots concurrently.
        Rows with valid == 0 are idle: K/V writes land in the null block,
        recurrent state is write-gated."""
        B, C = self.cfg.max_seqs, self.cfg.chunk_size
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros((B, C), np.int32)
        valid = np.zeros((B,), np.int32)
        ptemps = np.zeros((B,), np.float32)
        pref_active = np.zeros((B,), bool)
        for s, n in plan.prefill:
            seq = s.seq
            toks[s.slot, :n] = seq[s.num_cached:s.num_cached + n]
            pos[s.slot] = s.num_cached + np.arange(C, dtype=np.int32)
            valid[s.slot] = n
            ptemps[s.slot] = s.req.temperature
            pref_active[s.slot] = True
        ptables = np.where(pref_active[:, None],
                           self.cache_host.tables, 0)
        args = (jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(np.arange(B, dtype=np.int32)),
                jnp.asarray(ptables), jnp.asarray(valid))
        self._key, sub = jax.random.split(self._key)
        nxt, self.cache = self._prefill_fn(
            self.params, self.cache, *args, jnp.asarray(ptemps), sub)
        if spec_k:                        # keep the draft pool in step
            self.draft_cache = self._draft_prefill_fn(
                self.draft_params, self.draft_cache, *args)
        for s, n in plan.prefill:
            if self.obs.enabled and s.req.rid not in self._chunked:
                self._chunked.add(s.req.rid)
                self.obs.event("first_chunk", s.req.rid)
            if spec_k:
                s.draft_cached = s.num_cached + n
            covered_last = s.num_cached + n == s.seq_len
            s.num_cached += n
            self._c["prefill_chunks"].inc()
            self._c["prefill_tokens"].inc(n - (1 if covered_last else 0))
            if covered_last:              # chunk saw the last known token
                sampled_prefills.append(s)
        if sampled_prefills:
            fetch["pre"] = nxt

    def _spec_decode(self, plan, tokens, positions, temps, active, tables,
                     spec_meta):
        """Device calls for one speculative cycle: the fused K-step draft
        loop, then the single multi-token verify.  Returns the device
        arrays (out_tokens, n_acc) for the step's batched fetch."""
        B, K = self.cfg.max_seqs, self.cfg.spec_k
        forced = np.zeros((B, K), np.int32)
        known_len = np.ones((B,), np.int32)
        start_pos = positions.copy()
        draft_active = np.zeros((B,), bool)
        valid = active.astype(np.int32)       # plain decode rows: 1 row
        ncand = np.zeros((B,), np.int32)
        for s in plan.spec:
            seq = s.seq
            gap = s.num_cached - s.draft_cached
            kl = min(gap + 1, K)
            forced[s.slot, :kl] = seq[s.draft_cached:s.draft_cached + kl]
            known_len[s.slot] = kl
            start_pos[s.slot] = s.draft_cached
            draft_active[s.slot] = True
            # dynamic K (spec_ema > 0): the scheduler planned (and block-
            # reserved) k_s <= K candidates for this slot; the device
            # shapes stay (B, K) — surplus draft positions land in the
            # null block and the verify mask discards them
            k_s = s.spec_k_plan or K
            m = max(0, k_s - gap)             # candidates this cycle
            ncand[s.slot] = m
            valid[s.slot] = max(1, m)         # verify rows consumed
            spec_meta.append((s, m, K))

        self._key, k_draft, k_verify = jax.random.split(self._key, 3)
        cand, qprobs, self.draft_cache = self._draft_fn(
            self.draft_params, self.draft_cache, jnp.asarray(forced),
            jnp.asarray(known_len), jnp.asarray(start_pos),
            jnp.asarray(tables), jnp.asarray(draft_active),
            jnp.asarray(temps), k_draft)
        out, n_acc, self.cache = self._verify_fn(
            self.params, self.cache, jnp.asarray(tokens), cand, qprobs,
            jnp.asarray(positions), jnp.asarray(
                np.arange(B, dtype=np.int32)),
            jnp.asarray(tables), jnp.asarray(valid), jnp.asarray(ncand),
            jnp.asarray(temps), k_verify)
        self._c["spec_cycles"].inc()
        return out, n_acc

    def _fold_spec(self, plan, out, n_acc, spec_meta):
        """Fold one speculative cycle back into request state: append the
        accepted tokens + the replacement/bonus token, advance cursors,
        roll rejected KV positions back in the host block tables."""
        drafted = {s.req.rid: (n_cand, k) for s, n_cand, k in spec_meta}
        for s in plan.decode:
            a = int(n_acc[s.slot])
            n_cand, k = drafted.get(s.req.rid, (0, 0))
            assert a <= n_cand
            was_decode = s.num_cached == s.seq_len - 1
            if not was_decode:                # legacy token-by-token prefill
                s.num_cached += 1
                self._c["prefill_tokens"].inc()
                continue
            draft_start = s.draft_cached
            # the a accepted drafts, plus the rejection replacement (or
            # the plain-decode sample); full acceptance emits exactly a —
            # the would-be bonus arrives as the next cycle's row 0
            emit = a + (1 if (a < n_cand or n_cand == 0) else 0)
            for j in range(emit):
                s.num_cached += 1
                self._append_sample(s, int(out[s.slot, j]))
                if s.done:
                    break
            if k:
                s.draft_cached = min(draft_start + k, s.num_cached)
                s.spec_proposed += n_cand
                s.spec_accepted += a
                self._c["spec_proposed"].inc(n_cand)
                self._c["spec_accepted"].inc(a)
                if n_cand:
                    # acceptance histograms (telemetry only): accepted
                    # drafts per cycle in [0, K], and the cycle's rate
                    self.obs.observe(
                        "spec/accepted_per_cycle", a,
                        buckets=tuple(float(i)
                                      for i in range(self.cfg.spec_k + 1)))
                    self.obs.observe(
                        "spec/acceptance_rate", a / n_cand,
                        buckets=tuple(i / 10 for i in range(11)))
                if self.cfg.spec_ema > 0 and n_cand:
                    # dynamic K: fold this cycle's acceptance rate into
                    # the slot's EMA; the next plan_step clamps its K to
                    # ceil(ema * spec_k) in [1, spec_k]
                    al = self.cfg.spec_ema
                    s.spec_ema = (1 - al) * s.spec_ema + al * (a / n_cand)
                # rollback: rejected speculative positions release their
                # surplus blocks; the commit cursor rewinds with them
                self.cache_host.truncate(s.slot, s.num_cached)

    def _record(self, s: RequestState) -> FinishedRequest:
        """One finished request's result + latency record, built from the
        per-rid wall clocks — valid whether the tokens came from manual
        ``step()`` driving or a ``run()`` drain (no fallback to run()'s
        start time, which used to zero the TTFT of requests whose first
        token predated the run() call)."""
        rid = s.req.rid
        sub = self._submit_wall.get(rid)
        ft = self._first_tok_wall.get(rid)
        lt = self._last_tok_wall.get(rid)
        n = len(s.generated)
        return FinishedRequest(
            rid=rid, prompt=s.req.prompt, tokens=list(s.generated),
            preemptions=s.preemptions,
            steps=(self._finish_step.get(rid, self._steps)
                   - self._admit_step.get(rid, 0)),
            ttft_s=(max(ft - sub, 0.0)
                    if sub is not None and ft is not None else 0.0),
            queue_wait_s=self._queue_wait.get(rid, 0.0),
            preempt_stall_s=self._preempt_stall.get(rid, 0.0),
            tpot_s=(max(lt - ft, 0.0) / (n - 1)
                    if n > 1 and ft is not None and lt is not None else 0.0),
            spec_proposed=s.spec_proposed,
            spec_accepted=s.spec_accepted,
            finish_reason=s.finish_reason or
            ("stop" if s.stopped else "length"))

    def _forget_rid(self, rid: int) -> None:
        """Retire one drained request's per-rid host bookkeeping — a
        long-lived server would otherwise grow these dicts with every
        request it ever served."""
        for d in (self._admit_step, self._finish_step, self._submit_wall,
                  self._first_tok_wall, self._last_tok_wall,
                  self._queue_wait, self._preempt_wall,
                  self._preempt_stall, self._deadline, self._on_token):
            d.pop(rid, None)
        self._chunked.discard(rid)

    def finished(self) -> dict[int, FinishedRequest]:
        """Records for every request finished so far (manual ``step()``
        driving included — open-loop benchmarks use this after draining
        the queue themselves).  Non-destructive: latency fields are only
        valid for requests not yet drained by ``run()``/
        ``pop_finished()`` (draining retires the per-rid wall clocks)."""
        return {s.req.rid: self._record(s) for s in self.scheduler.finished}

    def pop_finished(self) -> dict[int, FinishedRequest]:
        """Drain finished requests destructively: build each record,
        then retire its per-rid bookkeeping and the scheduler's finished
        list.  Long-lived manual-stepping servers call this instead of
        ``finished()`` so host memory stays bounded by requests in
        flight, not requests ever served."""
        recs = {s.req.rid: self._record(s)
                for s in self.scheduler.finished}
        for rid in recs:
            self._forget_rid(rid)
        self.scheduler.finished.clear()
        self._drained = 0
        return recs

    def run(self, requests: Iterable[dict[str, Any]] | None = None,
            stop_when=None
            ) -> tuple[dict[int, FinishedRequest], dict[str, float]]:
        """Drive until the queue drains (``step_async`` pipeline when
        ``cfg.async_step``).  Returns ({rid: result}, stats); drained
        requests' per-rid wall clocks are retired with their records.
        ``stop_when()`` (checked between steps) ends the drive early —
        the signal-driven drain path in launch/serve.py uses it."""
        if requests:
            for r in requests:
                self.add_request(**r)
        # registry snapshot so repeated run() calls report THIS drain
        # only; the drained boundary (not len(finished) at entry) so
        # requests cancelled between runs still report here
        c0 = self.obs.registry.counter_values("serve/")
        fin0 = self._drained
        step = self.step_async if self.cfg.async_step else self.step
        t0 = time.time()
        while self.scheduler.has_work or self.pending_step:
            if stop_when is not None and stop_when():
                break
            step()
        dt = time.time() - t0

        out = {s.req.rid: self._record(s)
               for s in self.scheduler.finished[fin0:]}
        self._drained = len(self.scheduler.finished)
        for rid in out:
            self._forget_rid(rid)
        d = {k: float(c.value - c0["serve/" + k])
             for k, c in self._c.items()}
        dec, pre = d["decode_tokens"], d["prefill_tokens"]
        prop, acc = d["spec_proposed"], d["spec_accepted"]
        ttfts = [r.ttft_s for r in out.values()]
        stats = {
            "wall_s": dt,
            "steps": d["steps"],
            "decode_tokens": dec,
            "prefill_tokens": pre,
            "decode_tok_per_s": dec / max(dt, 1e-9),
            "total_tok_per_s": (dec + pre) / max(dt, 1e-9),
            "prefill_chunks": d["prefill_chunks"],
            "cow_copies": d["cow_copies"],
            "host_syncs": d["host_syncs"],
            "spec_cycles": d["spec_cycles"],
            "spec_proposed": prop,
            "spec_accepted": acc,
            "spec_acceptance": acc / prop if prop else 0.0,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "faults_injected": d["faults_injected"],
            "recoveries": d["recoveries"],
            "requests_shed": d["requests_shed"],
            "audit_violations": d["audit_violations"],
            "callback_errors": d["callback_errors"],
            "migrated_blocks": d["migrated_blocks"],
        }
        return out, stats
