"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; smoke tests see one CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.sharding import ShardingRules


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(n_devices: int | None = None) -> Mesh:
    """Whatever mesh the current host supports (tests / CPU smoke)."""
    n = n_devices or len(jax.devices())
    return _mk((n, 1), ("data", "model"))


def make_serve_mesh(data: int = 0, model: int = 1) -> Mesh:
    """(data, model) mesh for the serving engine.  ``data=0`` takes every
    device not claimed by the model axis (the `--mesh` CLI default)."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(f"model axis {model} does not divide {n} devices")
    if data == 0:
        data = n // model
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data * model} devices, "
                         f"have {n}")
    return _mk((data, model), ("data", "model"))


def parse_mesh(spec: str) -> Mesh:
    """'DxM' (e.g. '4x1', '2x2') -> serving mesh; 'auto' -> all devices
    on the data axis."""
    if spec == "auto":
        return make_serve_mesh()
    try:
        data, model = (int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh wants 'DxM' or 'auto', got {spec!r}")
    return make_serve_mesh(data, model)


def serve_rules(cfg: ArchConfig, mesh: Mesh,
                extra: dict | None = None) -> ShardingRules:
    """Sharding rules for the serving engine on a (data, model) mesh.

    Request slots (``serve_batch``) go data-parallel; the paged KV pools
    and the head-sharded parameters go tensor-parallel over ``model`` via
    ``kv_heads``/``heads``.  Head counts that don't divide the model axis
    replicate (Megatron GQA convention) — the decode-time ``kv_seq``
    fallback of ``arch_rules`` does not apply here because pool blocks,
    not a contiguous sequence, are the paged cache's storage axis.
    """
    ov: dict[str, tuple[str, ...]] = {}
    msize = mesh.shape["model"]
    if cfg.n_kv_heads and cfg.n_kv_heads % msize != 0:
        ov["kv_heads"] = ()
    if cfg.n_heads and cfg.n_heads % msize != 0:
        ov["heads"] = ()
    # no FSDP at serve time: each data-parallel replica holds the full
    # weights.  Sharding params over `data` (the training layout) would
    # all-gather every matrix every decode step AND split the d_model
    # contractions across data shards, whose reduction reorder breaks the
    # byte-parity contract with the single-device engine.
    ov["fsdp"] = ()
    if extra:
        ov.update(extra)
    return ShardingRules.for_mesh(mesh, overrides=ov)


def arch_rules(cfg: ArchConfig, mesh: Mesh, shape: ShapeConfig,
               extra: dict | None = None) -> ShardingRules:
    """Sharding rules specialized per (arch, mesh, shape).

    - KV heads replicate when they don't divide the model axis (Megatron GQA
      convention); uneven *query*-head counts stay sharded (GSPMD pads).
    - long-context decode (batch=1) shards the KV/state sequence instead of
      the batch (context parallelism).
    """
    ov: dict[str, tuple[str, ...]] = {}
    msize = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]

    if cfg.n_heads and cfg.n_heads % msize != 0 \
            and shape.kind in ("train", "prefill"):
        # heads can't use the model axis -> context-parallel attention
        # (shard the query sequence); §Perf iteration A1: 9.8x FLOPs on phi3
        ov["seq_q"] = ("model",)
    if cfg.n_kv_heads and cfg.n_kv_heads % msize != 0:
        ov["kv_heads"] = ()
        if shape.kind == "decode":        # paged pools have no kv_seq axis
            # KV heads can't use the model axis -> shard the cache sequence
            # over it instead (sequence-split decode attention); otherwise a
            # 32k cache replicates 16x per device.
            ov["kv_seq"] = ("model",)
    if shape.global_batch % dsize != 0:
        # batch=1 long-context: replicate batch, shard sequence instead
        ov["batch"] = ()
        ov["kv_seq"] = dp
    if shape.name == "long_500k":
        ov["kv_seq"] = dp
    if extra:
        ov.update(extra)
    return ShardingRules.for_mesh(mesh, overrides=ov)
