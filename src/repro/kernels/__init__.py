"""Pallas kernels of the hot path: the fused CIM MVM (cim_mvm / ops, with
the pure-jnp oracle in ref) and paged attention."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    An explicit value wins. Otherwise the kernels compile for the TPU and
    interpret only on the CPU backend, where tests execute the same kernel
    bodies. Any other backend raises: interpreting there would hide the
    device behind a silent fallback.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas TPU lowering for backend {backend!r}: the kernels "
        "compile for 'tpu' and interpret only on 'cpu'")
