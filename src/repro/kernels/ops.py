"""Jit'd public wrappers over the Pallas kernels with jnp fallbacks.

Dispatch policy (see DESIGN.md §2 — the *public* execution surface is the
``repro.backends`` registry; these wrappers are the per-kernel layer it
builds on):
  * ``lut_lookup``: 'take' = vectorized gather (oracle semantics, CPU
    default); 'onehot' = MXU matmul formulation in pure jnp; 'pallas' = the
    VMEM-tiled Pallas kernel (interpret mode on CPU, compiled on TPU).
  * ``lut_cascade``: the fused whole-network cascade kernel behind the
    'fused' backend (one launch for all layers).
  * ``unit_affine``: einsum fallback vs the batched Pallas stage.
  * ``flash_attention``: jnp scan fallback (models/attention.py) vs Pallas.

Pallas interpret mode is resolved in ONE place — :func:`pallas_interpret`:
interpret everywhere except on a TPU backend, where kernels always
compile (no environment switch can hide the device).  Tests flip it
in-process with :func:`set_pallas_interpret`.

The LM substrate lowers through the jnp paths by default so the multi-pod
dry-run exercises plain XLA collectives; kernels are enabled per-config for
real TPU runs.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lut_cascade import (is_v2_layers, lut_cascade_pallas,
                                       lut_cascade_xla)
from repro.kernels.lut_gather import lut_lookup_pallas
from repro.kernels.subnet_mlp import unit_affine_pallas

Array = jax.Array

_ON_TPU = None
_INTERPRET_OVERRIDE: Optional[bool] = None


def on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


def pallas_interpret() -> bool:
    """The single source of truth for Pallas interpret mode: the
    :func:`set_pallas_interpret` override, else interpret everywhere
    except on a TPU backend."""
    if _INTERPRET_OVERRIDE is not None:
        return _INTERPRET_OVERRIDE
    return not on_tpu()


def set_pallas_interpret(value: Optional[bool]) -> None:
    """Force interpret mode on/off for this process (None = back to auto)."""
    global _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = value


# jitted per impl; the pallas path resolves interpret mode per call so a
# pallas_interpret() flip retraces (static arg of lut_lookup_pallas) instead
# of silently reusing a stale executable.
_lut_lookup_take = jax.jit(ref.lut_lookup_ref)
_lut_lookup_onehot = jax.jit(ref.lut_lookup_onehot_ref)


def lut_lookup(table: Array, addr: Array, *, impl: str = "take") -> Array:
    """Batched L-LUT lookup. table: [U, T], addr: [B, U] -> [B, U]."""
    if impl == "take":
        return _lut_lookup_take(table, addr)
    if impl == "onehot":
        return _lut_lookup_onehot(table, addr)
    if impl == "pallas":
        return lut_lookup_pallas(table, addr, interpret=pallas_interpret())
    raise ValueError(f"unknown lut_lookup impl {impl!r}")


def lut_cascade(codes: Array, amat: Array, tables: Array, *,
                layers, mappings=None, tuning=None,
                block_b: Optional[int] = None) -> Array:
    """Whole-network fused L-LUT cascade; see ``kernels.lut_cascade``.

    Dispatches between the implementations on the plan's persisted
    :class:`~repro.kernels.autotune.KernelTuning` (``tuning`` may be the
    dataclass or its ``meta`` dict):

      * ``tuning.impl`` pins "pallas" or "xla" explicitly;
      * ``impl=None`` (auto) runs the compiled Pallas kernel when
        :func:`pallas_interpret` is off (TPU), else the pure-jnp
        flat-gather path — interpret-mode Pallas is a debugging tool, not
        a serving path.  The auto rule needs ``mappings``; both paths
        need v2 layer metadata.

    ``block_b`` overrides the tuned batch tile (benchmark sweeps)."""
    from repro.kernels.autotune import KernelTuning
    t = tuning if isinstance(tuning, KernelTuning) \
        else KernelTuning.from_meta(tuning)
    layers = tuple(tuple(int(v) for v in l) for l in layers)
    can_xla = is_v2_layers(layers) and mappings is not None
    impl = t.impl or ("xla" if pallas_interpret() and can_xla else "pallas")
    if impl == "xla":
        if not can_xla:
            raise ValueError("lut_cascade: impl='xla' needs v2 layer "
                             "metadata and mappings (re-plan the backend)")
        return lut_cascade_xla(codes, tables, tuple(mappings), layers=layers)
    if impl != "pallas":
        raise ValueError(f"unknown lut_cascade impl {impl!r}")
    return lut_cascade_pallas(codes, amat, tables, layers=layers,
                              block_b=block_b or t.block_b, mode=t.mode,
                              unit_tile=t.unit_tile,
                              interpret=pallas_interpret())


def unit_affine(x: Array, w: Array, b: Array, *, activate: bool = False,
                impl: str = "einsum") -> Array:
    if impl == "einsum":
        return ref.unit_affine_ref(x, w, b, activate=activate)
    if impl == "pallas":
        return unit_affine_pallas(x, w, b, activate=activate,
                                  interpret=pallas_interpret())
    raise ValueError(f"unknown unit_affine impl {impl!r}")


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    impl: str = "ref") -> Array:
    if impl == "ref":
        return ref.mha_ref(q, k, v, causal=causal, window=window,
                           q_offset=q_offset)
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset,
                                      interpret=pallas_interpret())
    raise ValueError(f"unknown flash_attention impl {impl!r}")
