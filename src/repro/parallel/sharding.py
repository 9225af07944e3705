"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP) and helpers.

Logical axis vocabulary (MaxText-style, mapped onto the production mesh from
launch/mesh.py):

  "batch" → ("pod", "data") / ("data",)   data parallelism (pod = outer DP)
  "fsdp"  → ("data",)                     parameter sharding (ZeRO-3 via GSPMD
                                          all-gather on use)
  "tp"    → ("model",)                    Megatron tensor parallelism (heads,
                                          mlp hidden, vocab)
  "expert"→ ("model",)                    expert parallelism (routed experts)
  "seq"   → ("model",) or ("data","model") sequence/context parallelism for
                                          long-KV decode
  None    → replicated

Every helper checks divisibility of the dim against the mesh axis size and
silently drops the annotation when it doesn't divide (e.g. 8 KV heads on a
16-way model axis → replicate, the standard Megatron fallback).

The active mesh is installed process-wide by launch code via set_mesh();
models never import mesh objects, only logical names — so the same model code
lowers for the single-pod and multi-pod meshes and runs unsharded on CPU
tests (set_mesh(None) → every helper is a no-op).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_MESH: Optional[Mesh] = None
_FSDP: bool = True


# Depth of shard_map bodies currently being traced. shard_map regions must
# not nest, and the CIM engine's mesh dispatch must know when a layer matmul
# is already executing per-shard (e.g. inside the MoE expert-parallel
# shard_map) so it runs the plain kernel instead of wrapping a second
# shard_map around it. Every repo shard_map call site goes through the
# wrapper below, which brackets the body trace — a plain counter is enough
# because tracing is single-threaded per jit trace.
_SHARD_DEPTH: list[int] = [0]


def in_shard_context() -> bool:
    """True while a shard_map body (opened via this module) is tracing —
    i.e. the current code already runs per-shard."""
    return _SHARD_DEPTH[0] > 0


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """jax.shard_map that marks its body trace.

    All repo call sites go through this wrapper, so `in_shard_context()`
    reports per-shard execution (the CIM engine's nesting guard).
    """
    @functools.wraps(f)
    def body(*args, **kwargs):
        _SHARD_DEPTH[0] += 1
        try:
            return f(*args, **kwargs)
        finally:
            _SHARD_DEPTH[0] -= 1

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def set_fsdp(enabled: bool) -> None:
    """Serving topology (§Perf B3): inference has no optimizer state, so
    parameters can shard fully over "model" and replicate over "data" —
    removing every FSDP all-gather from the step at the cost of params×data
    HBM (fine when params/TP ≤ a few GB)."""
    global _FSDP
    _FSDP = enabled


def get_mesh() -> Optional[Mesh]:
    return _MESH


def _axis_size(name: str) -> int:
    if _MESH is None or name not in _MESH.axis_names:
        return 1
    return _MESH.shape[name]


def resolve(logical: Optional[str]) -> Optional[tuple[str, ...]]:
    """Logical axis name → tuple of mesh axes (or None = replicated)."""
    if logical is None or _MESH is None:
        return None
    names = _MESH.axis_names
    table = {
        "batch": tuple(a for a in ("pod", "data") if a in names),
        "fsdp": ("data",) if ("data" in names and _FSDP) else (),
        "tp": ("model",) if "model" in names else (),
        "expert": ("model",) if "model" in names else (),
        "seq": tuple(a for a in ("data", "model") if a in names),
        "seq_tp": ("model",) if "model" in names else (),
    }
    axes = table.get(logical, ())
    return axes or None


def spec_for(shape: Sequence[int], logical: Sequence[Optional[str]]) -> PartitionSpec:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    assert len(shape) == len(logical), (shape, logical)
    entries = []
    for dim, name in zip(shape, logical):
        axes = resolve(name)
        if axes is None:
            entries.append(None)
            continue
        total = math.prod(_axis_size(a) for a in axes)
        if total > 1 and dim % total == 0:
            entries.append(axes if len(axes) > 1 else axes[0])
        else:
            entries.append(None)
    return PartitionSpec(*entries)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    if _MESH is None:
        return x
    spec = spec_for(x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))


def sharding_for(shape: Sequence[int], logical: Sequence[Optional[str]]):
    if _MESH is None:
        return None
    return NamedSharding(_MESH, spec_for(shape, logical))


# ---------------------------------------------------------------------------
# Parameter rules: leaf-name → logical axes (innermost dims; a leading stacked
# "layers" dim is auto-prepended with None by axes_for).
# ---------------------------------------------------------------------------
PARAM_RULES: dict[str, tuple] = {
    # embeddings / heads
    "embed": ("tp", "fsdp"),          # [V, D] vocab×embed
    "head": ("fsdp", "tp"),           # [D, V]
    "pos_embed": (None, "fsdp"),      # [S, D] learned positions
    # attention
    "wq": ("fsdp", "tp"), "wk": ("fsdp", "tp"), "wv": ("fsdp", "tp"),
    "wo": ("fsdp", "tp"),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",), "bo": (None,),
    # mlp — down/out projections store N-over-"tp" like the up projections:
    # the CIM engine shards EVERY mvm the same way (K over "data", output
    # channels over "model" — sharding.mvm_plan), and the jnp scan backend
    # reshapes K into [groups, n_rows, N] whose group boundaries never align
    # with a K-split. Keeping N on "model" lets GSPMD carry the stored
    # sharding through pad+reshape into the grouped scan / shard_map in_spec
    # with a local slice only (the Megatron row-parallel (K,"tp") layout
    # forced an involuntary full rematerialization of every scanned
    # down-projection on the 512-chip mesh).
    "w_gate": ("fsdp", "tp"), "w_up": ("fsdp", "tp"), "w_down": ("fsdp", "tp"),
    # norms / scalars
    "scale": (None,), "bias": (None,), "w_lambda": (None,),
    # MLA
    "w_dq": ("fsdp", "tp"), "w_uq": ("fsdp", "tp"),
    "w_dkv": ("fsdp", None), "w_uk": ("fsdp", "tp"), "w_uv": ("fsdp", "tp"),
    "w_kr": ("fsdp", None), "w_proj": ("fsdp", "tp"),
    # MoE (leading E dim = expert parallel; D dim FSDP)
    "router": ("fsdp", None),
    "e_gate": ("expert", "fsdp", None), "e_up": ("expert", "fsdp", None),
    "e_down": ("expert", None, "fsdp"),
    # SSM / RWKV
    "w_in": ("fsdp", "tp"), "w_out": ("fsdp", "tp"),
    "w_x": ("fsdp", "tp"), "conv_w": (None, "tp"), "conv_b": ("tp",),
    "a_log": ("tp",), "dt_bias": ("tp",), "d_skip": ("tp",),
    "w_r": ("fsdp", "tp"), "w_k": ("fsdp", "tp"), "w_v": ("fsdp", "tp"),
    "w_g": ("fsdp", "tp"),
    "decay_w0": ("tp",), "decay_a": ("fsdp", None), "decay_b": (None, "tp"),
    "bonus_u": ("tp",), "mu": (None, None),
    "w_dt": ("fsdp", "tp"), "w_bc": ("fsdp", None),
    "norm_g": ("tp",),
}


def axes_for(path: tuple[str, ...], ndim: int) -> tuple:
    """Logical axes for a param at `path` (keys joined), arity-adjusted.

    Params that live under a stacked-layers subtree carry a leading L dim
    (never sharded — layers are scanned); detected by 'layers' in the path.

    Optimizer-state leaves inherit the parent parameter's rules: adamw m/v
    mirror the params tree (last key IS the param name); adafactor factored
    stats live at <param>/vr (row means: drop last dim) and <param>/vc
    (col means: drop second-to-last) — without this the 671B-class factored
    stats would be replicated and blow per-chip HBM.
    """
    name = path[-1]
    if name in ("vr", "vc") and len(path) >= 2:
        base = PARAM_RULES.get(path[-2])
        if base is not None:
            rules = base[:-1] if name == "vr" else base[:-2] + base[-1:]
            stacked = any("layers" in p for p in path[:-1])
            if stacked:
                rules = (None,) + tuple(rules)
            if len(rules) < ndim:
                rules = (None,) * (ndim - len(rules)) + tuple(rules)
            return tuple(rules[:ndim])
    if name == "v" and len(path) >= 2 and path[-2] in PARAM_RULES:
        name = path[-2]  # adafactor unfactored scalar stat
    if name.endswith("_q"):     # offline-quantized codes shard like the fp
        name = name[:-2]        # weight they replace
    elif name.endswith("_scale"):
        return (None,) * ndim   # per-matrix scales are tiny → replicate
    rules = PARAM_RULES.get(name)
    if rules is None:
        rules = (None,) * ndim
    stacked = any("layers" in p for p in path[:-1])
    if stacked:
        rules = (None,) + tuple(rules)
    if len(rules) < ndim:  # pad leading dims (e.g. extra stacking) with None
        rules = (None,) * (ndim - len(rules)) + tuple(rules)
    return tuple(rules[:ndim])


def tree_param_specs(params) -> dict:
    """params pytree → matching tree of PartitionSpec via PARAM_RULES."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def one(kp, leaf):
        path = tuple(getattr(k, "key", str(k)) for k in kp)
        return spec_for(leaf.shape, axes_for(path, leaf.ndim))

    specs = {jax.tree_util.keystr(kp): one(kp, leaf) for kp, leaf in flat}
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(
        treedef, [one(kp, leaf) for kp, leaf in flat])


# ---------------------------------------------------------------------------
# Mesh partition plan for one sharded MVM (the CIM engine's fused dispatch).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MVMPlan:
    """How one x[..., K] @ w[K, M] MVM maps onto the active mesh.

    ctr_axes shard the contraction (K) — the multi-macro tiling of the
    paper's Sec. V: each shard evaluates its own macro groups and the
    partial MVMs are psum'd AFTER the per-shard ADC transfer + Eq. 7
    correction. row_axes shard the leading activation dim, col_axes the
    output-channel (M) dim; empty tuples mean replicated.
    """

    ctr_axes: tuple = ()
    row_axes: tuple = ()
    col_axes: tuple = ()

    def x_spec(self, ndim: int) -> PartitionSpec:
        lead = [None] * (ndim - 1)
        if self.row_axes and ndim > 1:
            lead[0] = self.row_axes if len(self.row_axes) > 1 \
                else self.row_axes[0]
        return PartitionSpec(*lead, _ent(self.ctr_axes))

    def w_spec(self) -> PartitionSpec:
        return PartitionSpec(_ent(self.ctr_axes), _ent(self.col_axes))

    def out_spec(self, ndim: int) -> PartitionSpec:
        lead = [None] * (ndim - 1)
        if self.row_axes and ndim > 1:
            lead[0] = self.row_axes if len(self.row_axes) > 1 \
                else self.row_axes[0]
        return PartitionSpec(*lead, _ent(self.col_axes))


def _ent(axes: tuple):
    return None if not axes else (axes if len(axes) > 1 else axes[0])


def mvm_plan(x_shape: Sequence[int], k: int, m: int, *,
             k_unit: int = 1) -> MVMPlan:
    """Partition plan for one MVM on the active mesh (identity w/o a mesh).

    Policy: the contraction goes over "data" when K divides (in units of
    `k_unit` rows — 2 for nibble-packed weights so no byte is split across
    shards); the output channels go over "model" when M divides; the leading
    activation dim goes over "pod" (and over "data" too when the contraction
    left it free). Non-divisible dims stay replicated — the same silent
    fallback spec_for applies to parameters.
    """
    if _MESH is None:
        return MVMPlan()
    names = _MESH.axis_names
    ctr: tuple = ()
    if "data" in names:
        size = _MESH.shape["data"]
        if size > 1 and k % (size * k_unit) == 0:
            ctr = ("data",)
    col: tuple = ()
    if "model" in names and m % _MESH.shape["model"] == 0:
        col = ("model",)
    row: tuple = ()
    if len(x_shape) > 1:
        lead = x_shape[0]
        for ax in ("pod",) + (("data",) if not ctr else ()):
            if ax in names and lead % (_MESH.shape[ax]
                                       * math.prod(_MESH.shape[a]
                                                   for a in row)) == 0:
                row = row + (ax,)
    return MVMPlan(ctr_axes=ctr, row_axes=row, col_axes=col)


def tree_shardings(params):
    """params pytree (arrays or ShapeDtypeStructs) → NamedSharding tree."""
    if _MESH is None:
        return None
    mesh = _MESH
    specs = tree_param_specs(params)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, PartitionSpec))
