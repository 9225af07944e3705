"""Unified CIM execution engine: one backend registry for every datapath.

The paper's central claim (§III-A) is that ONE set of in-array MOM
capacitors serves every pipeline stage in situ — DAC charge loading, the
analog MAC, the 8:4:2:1 shift-and-add, and TD-ADC sampling — instead of a
per-stage datapath. This module is the software mirror of that claim: every
layer-level matmul (`cim_matmul`, `cim_matmul_prequant`, `cim_matmul_ste`)
funnels through a single `execute_mvm` entry point that owns backend
selection, reduction padding, the grouped MVM, the Eq. 7 digital correction
and dequantization. Backends only differ in how the DAC→MAC→ADC core is
evaluated:

  backend          paper datapath stage it models                 runs on
  ---------------  ---------------------------------------------  ---------
  "einsum"         whole [.., G, M] pre-ADC charge tensor at       any; small
                   once: C-DAC drive + per-group MAC line, then    layers /
                   one vectorized ADC transfer (supports the       tests; all
                   stochastic NOISY/FULL converter models)         schemes
  "scan"           group-sequential partial-sum accumulation       any; large
                   (§II-A "accumulated across macros when          layers,
                   K > N") with O(M) live memory                   BP scheme
  "pallas"         fused TPU kernel: per-group MAC + ADC applied   TPU (or
                   in VMEM registers, never spilling pre-ADC       interpret
                   partials to HBM — the in-situ capacitor reuse   mode on
                   made literal                                    CPU)
  "pallas_packed"  same, with weights stored as nibble pairs       TPU (or
                   (two u4 codes per byte) and unpacked in VMEM    interpret)
                   — the TPU analogue of the paper's 559 Kb/mm²
                   4-bit SRAM storage density
  "pallas_noisy"   stochastic fused kernel: the NOISY/FULL         TPU (or
                   TD-ADC transfer (thermal σ + INL instance)      interpret)
                   with per-conversion noise drawn IN VMEM from
                   a counter-based PRNG — PVT/QAT noise studies
                   at fused-kernel throughput
  "pallas_noisy_packed"  stochastic + nibble-packed weights; the   TPU (or
                   noise draw is independent of the container,     interpret)
                   so it is bit-identical to pallas_noisy under
                   the same seed

The digital epilogue (Eq. 7 offset/zero-point correction, × s_x·s_w
dequantization) is shared by all backends, exactly as the paper's adder
tree + digital shift-and-add is shared by all schemes.

noise_seed semantics
--------------------
`CIMConfig.noise_seed` (or the `noise_seed=` override on `execute_mvm`)
names one stochastic-instance of the converter chain. It is the ONLY way to
reach the fused stochastic kernels through `backend="auto"`:

  * auto + BP + NOISY/FULL + noise_seed set → "pallas_noisy[_packed]";
    without a seed the jnp backends (einsum, or scan for large layers) run,
    drawing noise from the optional `key` argument exactly as before.
  * The same seed is bit-reproducible: outputs are a pure function of
    (operands, config, noise_seed, inl_seed) in BOTH compiled and interpret
    mode — the kernel PRNG is counter-based (see kernels/cim_mvm.py), not
    the hardware RNG. Corollary: two same-shaped MVMs under one
    (noise_seed, inl_seed) draw the SAME noise realization; thread a
    distinct inl_seed per layer/step (the Fig. 18 instance knob) when a
    study needs decorrelated conversions across calls.
  * jnp backends given a noise_seed (and no explicit key) derive
    key = PRNGKey(noise_seed), so einsum/scan runs are seeded-reproducible
    too; the jnp and fused DRAWS differ (different PRNGs) but agree in
    distribution — the engine tests pin mean/variance agreement.

per-channel weight scales
-------------------------
`s_w` may be per-matrix (scalar / [..., 1, 1]) or per-output-channel
([..., 1, M], emitted by `quantize_weight_offline` under
`WeightQuantConfig.per_channel`). The Eq. 7 integer correction is
scale-free, so per-channel dequant is exactly `y_int · s_x · s_w[..., 0, :]`
— broadcast over the M axis after the correction. `PackedCodes` can carry
its channel scales (`scale` field) so the packed wire format stays
self-describing.

mesh-native dispatch
--------------------
A bare `pallas_call` cannot be GSPMD-partitioned, so when a mesh is active
(`parallel.sharding.get_mesh()`) every pallas backend routes through
`parallel.sharding.shard_map`: the contraction axis splits over "data"
(each shard is its own bank of macros — the paper's Sec. V multi-macro
tiling), output channels over "model", and the partial MVMs are psum'd
AFTER the in-kernel ADC transfer + per-shard Eq. 7 correction, so the
analog semantics per shard match the single-device kernel exactly. The
stochastic kernels salt their traced seed with the shard's
`jax.lax.axis_index` (see `kernels.cim_mvm.salt_seed`), so shards draw
decorrelated converter instances; the salt is 0 on a 1-device mesh, making
that call bit-identical to the unsharded kernel. Callers already running
per-shard (inside a repo shard_map, e.g. the MoE expert-parallel region)
are detected via `sharding.in_shard_context()` and get the plain kernel.

`REPRO_FORCE_JNP=1` in the environment forces `backend="auto"` to resolve
to the jnp backends only (einsum/scan) — the escape hatch for environments
where interpret-mode Pallas is unavailable; explicit backend names are
honored unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.parallel import sharding

from .adc import adc_quantize
from .macro import MacroConfig, Scheme, SimLevel
from .schemes import cim_mvm_codes, pad_and_group, signed_correction


# ---------------------------------------------------------------------------
# weight containers
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedCodes:
    """Nibble-packed stored weight codes: two u4 codes per uint8 byte.

    data [..., ceil(K/2), M] uint8 (row 2i low nibble, 2i+1 high); `k` is
    the logical reduction length before pack-padding. This is the at-rest /
    HBM format — 4 bits per weight, like the SRAM array itself.

    `scale` optionally carries the dequantization scale(s) alongside the
    codes — per-matrix ([..., 1, 1] / scalar) or per-output-channel
    ([..., 1, M]) — making the container self-describing: `execute_mvm`
    falls back to it when no explicit `s_w` is supplied.
    """

    data: jax.Array
    k: int
    scale: jax.Array | None = None

    def tree_flatten(self):
        return (self.data, self.scale), self.k

    @classmethod
    def tree_unflatten(cls, k, children):
        return cls(children[0], k, children[1])

    @property
    def n_cols(self) -> int:
        return self.data.shape[-1]


def unpack(weights: PackedCodes) -> jax.Array:
    """PackedCodes → dense f32 codes [..., K, M] (drops pack-padding)."""
    from repro.kernels.ops import unpack_codes
    return unpack_codes(weights.data, weights.k)


# ---------------------------------------------------------------------------
# backend protocol + registry
# ---------------------------------------------------------------------------
class CIMBackend(Protocol):
    """Evaluates ŷ ≈ Σ_g ADC(Σ_{i∈g} X̃ W̃) in integer-MAC units.

    x_codes [..., K] unsigned DAC codes; weights are dense codes [K, M]
    (or PackedCodes for packed-capable backends). Returns float32 [..., M].
    Stochastic draws come from `key` (jnp backends) or `noise_seed` (the
    fused stochastic kernels); deterministic backends ignore both.
    """

    def __call__(self, x_codes: jax.Array, weights, cfg: MacroConfig, *,
                 key: jax.Array | None, inl_seed: int,
                 noise_seed=None) -> jax.Array: ...


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    fn: Callable
    schemes: frozenset          # schemes the backend implements
    sim_levels: frozenset       # converter fidelities it can model
    packed: bool = False        # consumes PackedCodes natively


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(name: str, *, schemes, sim_levels, packed: bool = False):
    """Register a CIMBackend under `name` (decorator)."""
    def deco(fn):
        _REGISTRY[name] = BackendSpec(name, fn, frozenset(schemes),
                                      frozenset(sim_levels), packed)
        return fn
    return deco


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown CIM backend {name!r}; "
                         f"registered: {sorted(_REGISTRY)}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


_ALL_SCHEMES = (Scheme.BP, Scheme.WBS, Scheme.BS)
_ALL_LEVELS = (SimLevel.IDEAL, SimLevel.NOISY, SimLevel.FULL)


@register_backend("einsum", schemes=_ALL_SCHEMES, sim_levels=_ALL_LEVELS)
def _einsum_backend(x_codes, w_codes, cfg: MacroConfig, *, key=None,
                    inl_seed=0, noise_seed=None):
    del noise_seed  # jnp backends draw from `key` (derived in execute_mvm)
    return cim_mvm_codes(x_codes, w_codes, cfg, key=key, inl_seed=inl_seed)


@register_backend("scan", schemes=_ALL_SCHEMES, sim_levels=_ALL_LEVELS)
def _scan_backend(x_codes, w_codes, cfg: MacroConfig, *, key=None,
                  inl_seed=0, noise_seed=None):
    del noise_seed
    """Group-sequential BP MVM: identical math to schemes.bp_mvm, O(M) live
    memory. WBS/BS run their own per-bit-plane loops on the einsum path (BP
    is the paper's deployed scheme), so non-BP requests fall through.
    """
    if cfg.scheme != Scheme.BP:
        return _einsum_backend(x_codes, w_codes, cfg, key=key,
                               inl_seed=inl_seed)
    xg, g = pad_and_group(x_codes, cfg.n_rows)          # [..., G, N]
    wg, _ = pad_and_group(w_codes, cfg.n_rows, axis=0)  # [G, N, M]
    xg = jnp.moveaxis(xg, -2, 0)                        # [G, ..., N]
    keys = (jax.random.split(key, g) if key is not None
            else jnp.zeros((g, 2), dtype=jnp.uint32))

    def body(acc, operands):
        xs, ws, ks = operands
        v = jnp.einsum("...n,nm->...m", xs, ws,
                       preferred_element_type=jnp.float32)
        kk = ks if key is not None else None
        q = adc_quantize(v, cfg, key=kk, inl_seed=inl_seed)
        return acc + q, None

    out_shape = x_codes.shape[:-1] + (w_codes.shape[-1],)
    acc0 = jnp.zeros(out_shape, dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (xg, wg, keys))
    return acc


# pallas_call has no JVP/VJP rule, but `backend="auto"` must keep
# cim_matmul differentiable (PTQ calibration / sensitivity sweeps grad
# through the analog pipeline without the STE wrapper). Forward runs the
# fused kernel; backward is the VJP of the numerically-identical einsum
# pipeline (IDEAL transfer — same clip/round/LSB math).
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_mvm(x_codes, w_codes, cfg: MacroConfig):
    from repro.kernels.ops import cim_mvm_pallas
    return cim_mvm_pallas(x_codes, w_codes, cfg)


def _pallas_mvm_fwd(x_codes, w_codes, cfg):
    return _pallas_mvm(x_codes, w_codes, cfg), (x_codes, w_codes)


def _pallas_mvm_bwd(cfg, res, g):
    x_codes, w_codes = res
    _, vjp = jax.vjp(lambda x, w: _einsum_backend(x, w, cfg), x_codes,
                     w_codes)
    return vjp(g)


_pallas_mvm.defvjp(_pallas_mvm_fwd, _pallas_mvm_bwd)


@register_backend("pallas", schemes=(Scheme.BP,), sim_levels=(SimLevel.IDEAL,))
def _pallas_backend(x_codes, w_codes, cfg: MacroConfig, *, key=None,
                    inl_seed=0, noise_seed=None):
    del key, inl_seed, noise_seed  # deterministic IDEAL transfer only
    return _pallas_mvm(x_codes, w_codes, cfg)


@register_backend("pallas_packed", schemes=(Scheme.BP,),
                  sim_levels=(SimLevel.IDEAL,), packed=True)
def _pallas_packed_backend(x_codes, weights: PackedCodes, cfg: MacroConfig, *,
                           key=None, inl_seed=0, noise_seed=None):
    del key, inl_seed, noise_seed
    return _packed_mvm(x_codes, weights.data, weights.k, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _packed_mvm(x_codes, w_packed, k: int, cfg: MacroConfig):
    from repro.kernels.ops import cim_mvm_pallas_packed
    return cim_mvm_pallas_packed(x_codes, w_packed, cfg)


def _packed_mvm_fwd(x_codes, w_packed, k, cfg):
    return _packed_mvm(x_codes, w_packed, k, cfg), (x_codes, w_packed)


def _packed_mvm_bwd(k, cfg, res, g):
    # stored integer codes are not trainable; only the activation side
    # carries a cotangent (input-saliency style uses)
    x_codes, w_packed = res
    from repro.kernels.ops import unpack_codes
    w_codes = unpack_codes(w_packed, k)
    _, vjp = jax.vjp(lambda x: _einsum_backend(x, w_codes, cfg), x_codes)
    return vjp(g)[0], None


_packed_mvm.defvjp(_packed_mvm_fwd, _packed_mvm_bwd)


# ---------------------------------------------------------------------------
# stochastic fused backends (NOISY/FULL transfer, in-kernel PRNG)
# ---------------------------------------------------------------------------
def _resolve_noise_seed(noise_seed, key):
    """int32 scalar seed for the fused stochastic kernels.

    Prefers the explicit noise_seed (the reproducibility contract); falls
    back to folding the jnp PRNG key's bits when only `key` was supplied, so
    explicit backend="pallas_noisy" keeps working from the legacy key-based
    call sites.
    """
    if noise_seed is not None:
        return jnp.asarray(noise_seed, jnp.int32)
    if key is not None:
        kd = key
        if jnp.issubdtype(jnp.asarray(kd).dtype, jax.dtypes.prng_key):
            kd = jax.random.key_data(kd)
        return jnp.reshape(kd, (-1,))[-1].astype(jnp.int32)
    raise ValueError(
        "stochastic Pallas backend needs CIMConfig.noise_seed (or an "
        "explicit PRNG key) — at IDEAL sim level use pallas/pallas_packed")


# Like _pallas_mvm: the kernel has no VJP rule, but auto-selected backends
# must keep cim_matmul differentiable. Backward is the VJP of the einsum
# pipeline's deterministic STE transfer (key=None → no noise term; the
# noise enters additively pre-rounding, so its STE derivative is identity
# anyway).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _noisy_mvm(x_codes, w_codes, seed, cfg: MacroConfig, inl_seed: int):
    from repro.kernels.ops import cim_mvm_pallas_noisy
    return cim_mvm_pallas_noisy(x_codes, w_codes, cfg, noise_seed=seed,
                                inl_seed=inl_seed)


def _noisy_mvm_fwd(x_codes, w_codes, seed, cfg, inl_seed):
    return _noisy_mvm(x_codes, w_codes, seed, cfg, inl_seed), (x_codes,
                                                               w_codes)


def _noisy_mvm_bwd(cfg, inl_seed, res, g):
    x_codes, w_codes = res
    _, vjp = jax.vjp(lambda x, w: _einsum_backend(x, w, cfg,
                                                  inl_seed=inl_seed),
                     x_codes, w_codes)
    return (*vjp(g), None)


_noisy_mvm.defvjp(_noisy_mvm_fwd, _noisy_mvm_bwd)


@register_backend("pallas_noisy", schemes=(Scheme.BP,),
                  sim_levels=(SimLevel.NOISY, SimLevel.FULL))
def _pallas_noisy_backend(x_codes, w_codes, cfg: MacroConfig, *, key=None,
                          inl_seed=0, noise_seed=None):
    seed = _resolve_noise_seed(noise_seed, key)
    return _noisy_mvm(x_codes, w_codes, seed, cfg, inl_seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _noisy_packed_mvm(x_codes, w_packed, seed, k: int, cfg: MacroConfig,
                      inl_seed: int):
    from repro.kernels.ops import cim_mvm_pallas_noisy_packed
    return cim_mvm_pallas_noisy_packed(x_codes, w_packed, cfg,
                                       noise_seed=seed, inl_seed=inl_seed)


def _noisy_packed_mvm_fwd(x_codes, w_packed, seed, k, cfg, inl_seed):
    return (_noisy_packed_mvm(x_codes, w_packed, seed, k, cfg, inl_seed),
            (x_codes, w_packed))


def _noisy_packed_mvm_bwd(k, cfg, inl_seed, res, g):
    # stored codes carry no cotangent (see _packed_mvm_bwd)
    x_codes, w_packed = res
    from repro.kernels.ops import unpack_codes
    w_codes = unpack_codes(w_packed, k)
    _, vjp = jax.vjp(lambda x: _einsum_backend(x, w_codes, cfg,
                                               inl_seed=inl_seed), x_codes)
    return vjp(g)[0], None, None


_noisy_packed_mvm.defvjp(_noisy_packed_mvm_fwd, _noisy_packed_mvm_bwd)


@register_backend("pallas_noisy_packed", schemes=(Scheme.BP,),
                  sim_levels=(SimLevel.NOISY, SimLevel.FULL), packed=True)
def _pallas_noisy_packed_backend(x_codes, weights: PackedCodes,
                                 cfg: MacroConfig, *, key=None, inl_seed=0,
                                 noise_seed=None):
    seed = _resolve_noise_seed(noise_seed, key)
    return _noisy_packed_mvm(x_codes, weights.data, seed, weights.k, cfg,
                             inl_seed)


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------
# Materializing the [rows, G, M] pre-ADC tensor beyond this switches the
# jnp path from einsum to the group-sequential scan.
_EINSUM_BYTES_CEILING = 64 << 20


def _force_jnp() -> bool:
    """REPRO_FORCE_JNP=1: auto-selection never picks a Pallas kernel — the
    escape hatch for environments without interpret-mode Pallas support.
    Read at trace time; explicit backend names bypass it."""
    return os.environ.get("REPRO_FORCE_JNP", "").strip().lower() \
        in ("1", "true", "yes")


def choose_backend(cfg, x_codes: jax.Array, weights) -> str:
    """Resolve cfg.backend ("auto" or explicit) to a registered backend name.

    Auto policy (see also the scheme × sim-level matrix in ROADMAP.md):
      * IDEAL + BP → the fused Pallas kernel — "pallas_packed" when the
        weights are nibble-packed, else "pallas" (interpret mode executes
        the same kernel body on CPU, keeping tests honest);
      * NOISY/FULL + BP with a noise_seed → the fused stochastic kernel
        ("pallas_noisy" / "pallas_noisy_packed") — on a sharded mesh too:
        execute_mvm wraps the kernel in shard_map (see _sharded_mvm), so
        auto no longer needs to demote to scan there;
      * otherwise (no seed, WBS/BS baselines, REPRO_FORCE_JNP=1) → jnp
        backends, scanning the reduction groups once the pre-ADC tensor
        would exceed ~64 MB (the escape hatch is unchanged under a mesh —
        the bound is on the global pre-ADC tensor).

    `cfg` is the layer-level CIMConfig (duck-typed: .backend, .macro and
    optionally .noise_seed).
    """
    macro: MacroConfig = cfg.macro
    packed = isinstance(weights, PackedCodes)
    if cfg.backend != "auto":
        return get_backend(cfg.backend).name
    if macro.scheme == Scheme.BP and not _force_jnp():
        if macro.sim_level == SimLevel.IDEAL:
            return "pallas_packed" if packed else "pallas"
        if getattr(cfg, "noise_seed", None) is not None:
            return "pallas_noisy_packed" if packed else "pallas_noisy"
    k = weights.k if packed else weights.shape[-2]
    m = weights.n_cols if packed else weights.shape[-1]
    groups = -(-k // macro.n_rows)
    rows = math.prod(x_codes.shape[:-1]) if x_codes.ndim > 1 else 1
    big = rows * groups * m * 4 > _EINSUM_BYTES_CEILING
    return "scan" if (big and macro.scheme == Scheme.BP) else "einsum"


# ---------------------------------------------------------------------------
# mesh-native dispatch: shard_map-wrapped fused kernels
# ---------------------------------------------------------------------------
def _under_vmap(*arrays) -> bool:
    """True when any operand is a vmap batch tracer — shard_map cannot nest
    under vmap, so the engine falls back to the plain per-call kernel (the
    pre-mesh behaviour) there. jax exports no public name for the tracer
    class, so it comes from jax._src (pinned toolchain)."""
    from jax._src.interpreters.batching import BatchTracer
    return any(isinstance(a, BatchTracer) for a in arrays)


def _sharded_mvm(spec: BackendSpec, x_codes, weights, cfg, *, key, inl_seed,
                 noise_seed, x_zero_point):
    """One MVM on the active mesh: per-shard fused kernels under shard_map.

    The software mirror of the paper's Sec. V multi-macro tiling: the
    contraction axis is split over the "data" mesh axis — each shard is its
    own bank of macros, evaluating the DAC→MAC→ADC transfer (and, for the
    stochastic backends, drawing ITS OWN converter noise) entirely locally —
    and the partial MVMs are `psum`'d only AFTER the in-kernel ADC transfer
    and the per-shard Eq. 7 correction, so per-shard analog semantics are
    exactly the single-device kernel's. Output channels split over "model",
    the leading activation dim over the batch axes (see sharding.mvm_plan).

    Seed contract: the traced kernel seed is salted with the shard's linear
    `jax.lax.axis_index` through `kernels.cim_mvm.salt_seed`, so shards draw
    decorrelated converter instances (Fig. 18's instance spread, one
    instance per macro bank). The salt is 0 on a 1-device mesh — that call
    is bit-identical to the unsharded kernel. Composes with the static
    inl_seed salt (per-layer/per-step decorrelation) unchanged.

    Returns the GLOBAL Eq. 7-corrected integer output [..., M]; dequant
    stays in execute_mvm. Every per-shard correction term is a sum over
    local reduction rows, so the psum over contraction shards rebuilds the
    full correction; only the o·z·K constant is added once, outside.
    """
    from repro.kernels.ops import packed_col_sums, salt_seed
    macro: MacroConfig = cfg.macro
    mesh = sharding.get_mesh()
    packed = isinstance(weights, PackedCodes)
    stochastic = SimLevel.IDEAL not in spec.sim_levels
    data = weights.data if packed else weights.astype(jnp.float32)
    k_logical = weights.k if packed else data.shape[-2]
    m_cols = data.shape[-1]
    plan = sharding.mvm_plan(x_codes.shape, k_logical, m_cols,
                             k_unit=2 if packed else 1)
    n_ctr = math.prod(mesh.shape[a] for a in plan.ctr_axes) \
        if plan.ctr_axes else 1
    k_local = k_logical // n_ctr
    seed = _resolve_noise_seed(noise_seed, key) if stochastic \
        else jnp.zeros((), jnp.int32)
    zp = jnp.asarray(x_zero_point, jnp.float32)
    w_offset = cfg.weight.offset

    # Only axes that actually partition this MVM may enter the seed salt:
    # two shards along them hold different coordinates or different macro
    # groups, so each needs its own PRNG stream. Shards along an UNUSED
    # mesh axis compute the identical replicated problem — salting those
    # would make "replicated" outputs differ per device (out_spec lies,
    # check_vma=False would hide it).
    salt_axes = tuple(a for a in mesh.axis_names
                      if a in plan.ctr_axes + plan.row_axes + plan.col_axes)

    def shard_fn(x_l, w_l, zp_l, seed_l):
        idx = jnp.zeros((), jnp.int32)
        for a in salt_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a).astype(jnp.int32)
        weights_l = PackedCodes(w_l, k_local) if packed else w_l
        seed_shard = salt_seed(seed_l, idx) if stochastic else None
        y_codes = spec.fn(x_l, weights_l, macro, key=None, inl_seed=inl_seed,
                          noise_seed=seed_shard)
        sum_w = packed_col_sums(w_l) if packed else jnp.sum(w_l, axis=-2)
        y_int = signed_correction(y_codes, x_l, None, w_offset=w_offset,
                                  x_zero_point=zp_l, sum_w=sum_w, k=0)
        if plan.ctr_axes:
            y_int = jax.lax.psum(y_int, plan.ctr_axes)
        return y_int

    y_int = sharding.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(plan.x_spec(x_codes.ndim), plan.w_spec(),
                  PartitionSpec(*([None] * zp.ndim)), PartitionSpec()),
        out_specs=plan.out_spec(x_codes.ndim),
        check_vma=False,
    )(x_codes, data, zp, seed)
    return y_int + w_offset * zp * k_logical


# ---------------------------------------------------------------------------
# the single entry point
# ---------------------------------------------------------------------------
_ENERGY_CACHE: dict = {}    # (macro, k) -> e_mvm_j, see _record_dispatch


def _record_dispatch(name: str, x_codes, weights, macro) -> None:
    """Observability hook: count the backend pick and accumulate the
    paper-model CIM energy for this MVM under the active PR-9 site name.

    Runs at jax TRACE time (execute_mvm executes Python once per compiled
    shape under jit), so KERNEL_COUNTERS records traced calls — one per
    compilation, not one per step; see telemetry.KernelCounters. Energy is
    Eq. 4 per K-deep dot product (energy.mvm_energy) times the traced
    call's dot count (batch rows x output columns)."""
    from repro.core.quant import current_site
    from repro.runtime.telemetry import KERNEL_COUNTERS
    KERNEL_COUNTERS.count_backend(name)
    if isinstance(weights, PackedCodes):
        k, m = weights.k, int(weights.data.shape[-1])
    else:
        k, m = int(weights.shape[-2]), int(weights.shape[-1])
    rows = 1
    for d in x_codes.shape[:-1]:
        rows *= int(d)
    key = (macro, k)
    e_dot = _ENERGY_CACHE.get(key)
    if e_dot is None:
        try:
            from repro.core.energy import mvm_energy
            e_dot = mvm_energy(macro, k).e_mvm_j
        except Exception:
            e_dot = 0.0   # energy model inapplicable — still count dots
        _ENERGY_CACHE[key] = e_dot
    KERNEL_COUNTERS.add_site_energy(current_site() or "<unsited>",
                                    e_dot * rows * m, rows * m)


def execute_mvm(x_codes: jax.Array, weights, cfg, *,
                s_x: jax.Array, s_w: jax.Array | None, x_zero_point: jax.Array,
                key: jax.Array | None = None, inl_seed: int = 0,
                backend: str | None = None,
                noise_seed=None) -> jax.Array:
    """Run one MVM through the full simulated datapath and dequantize.

    x_codes [..., K] unsigned DAC codes; weights are dense stored codes
    [K, M] (float32 / int8 container) or PackedCodes. `cfg` is the
    layer-level CIMConfig (macro + quantizer configs). Owns: backend
    selection, reduction padding (inside the backends — zero codes are
    unselected SRAM rows), the grouped MVM, the Eq. 7 signed/affine
    correction, and the × s_x·s_w dequantization. Returns float32 [..., M].

    `s_w` may be per-matrix or per-output-channel ([..., 1, M]); pass None
    to use the scales a PackedCodes container carries. `noise_seed`
    overrides cfg.noise_seed for this call (see module docstring).
    """
    macro: MacroConfig = cfg.macro
    if noise_seed is None:
        noise_seed = getattr(cfg, "noise_seed", None)
    if macro.sim_level == SimLevel.IDEAL:
        key = None  # no stochastic terms at the ideal sim level
        noise_seed = None
    elif key is None and noise_seed is not None:
        # seeded reproducibility on the jnp backends too: einsum/scan given
        # only a noise_seed draw from the derived key (DCE'd when the fused
        # kernel runs — it consumes the integer seed directly). inl_seed is
        # folded in, mirroring the fused kernel's counter salt: repeated
        # same-shaped MVMs under one (noise_seed, inl_seed) reuse one noise
        # realization BY DESIGN (that is what bit-reproducibility means);
        # thread a distinct inl_seed per layer/step to decorrelate them.
        key = jax.random.fold_in(jax.random.PRNGKey(noise_seed), inl_seed)
    name = backend or choose_backend(cfg, x_codes, weights)
    _record_dispatch(name, x_codes, weights, macro)
    spec = get_backend(name)
    if macro.scheme not in spec.schemes:
        raise ValueError(f"backend {name!r} does not implement scheme "
                         f"{macro.scheme}; use einsum/scan")
    if macro.sim_level not in spec.sim_levels:
        if SimLevel.IDEAL in spec.sim_levels:
            raise ValueError(
                f"backend {name!r} is deterministic; sim level "
                f"{macro.sim_level} needs a stochastic backend "
                f"(einsum/scan/pallas_noisy)")
        raise ValueError(
            f"backend {name!r} models the stochastic converter chain only; "
            f"sim level {macro.sim_level} runs on pallas/pallas_packed or "
            f"the jnp backends")

    packed = isinstance(weights, PackedCodes)
    if s_w is None:
        s_w = weights.scale if packed else None
        if s_w is None:
            raise ValueError("execute_mvm needs s_w (or a PackedCodes "
                             "container carrying its scale)")
    # normalize the weight container to what the backend consumes
    if packed and not spec.packed:
        weights = unpack(weights)
        packed = False
    elif not packed and spec.packed:
        from repro.kernels.ops import pack_codes
        w_codes = weights.astype(jnp.float32)
        weights = PackedCodes(pack_codes(w_codes), w_codes.shape[-2])
        packed = True

    mesh = sharding.get_mesh()
    if (name.startswith("pallas") and mesh is not None
            and not sharding.in_shard_context()
            and not _under_vmap(x_codes,
                                weights.data if packed else weights)):
        # mesh-native dispatch: a bare pallas_call cannot be GSPMD-
        # partitioned, so under an active mesh the fused kernels run
        # per-shard inside shard_map (correction included — see
        # _sharded_mvm); already-per-shard callers (e.g. the MoE EP
        # shard_map) fall through to the plain kernel below.
        y_int = _sharded_mvm(spec, x_codes, weights, cfg, key=key,
                             inl_seed=inl_seed, noise_seed=noise_seed,
                             x_zero_point=x_zero_point)
    else:
        if packed:
            y_codes = spec.fn(x_codes, weights, macro, key=key,
                              inl_seed=inl_seed, noise_seed=noise_seed)
            from repro.kernels.ops import packed_col_sums
            sum_w = packed_col_sums(weights.data)
            k = weights.k
        else:
            w_codes = weights.astype(jnp.float32)
            y_codes = spec.fn(x_codes, w_codes, macro, key=key,
                              inl_seed=inl_seed, noise_seed=noise_seed)
            sum_w = jnp.sum(w_codes, axis=-2)
            k = w_codes.shape[-2]
        y_int = signed_correction(y_codes, x_codes, None,
                                  w_offset=cfg.weight.offset,
                                  x_zero_point=x_zero_point, sum_w=sum_w,
                                  k=k)
    # Per-channel scales arrive broadcast-shaped against the stored codes
    # ([..., 1, M]); drop the reduction axis so they broadcast against the
    # [..., M] output instead (Eq. 7 is scale-free integer arithmetic, so
    # dequant is the only place the channel axis matters).
    s_w_out = s_w
    if cfg.weight.per_channel and getattr(s_w, "ndim", 0) >= 2:
        s_w_out = s_w[..., 0, :]
    return y_int * s_x * s_w_out
