"""What every Pallas family of :mod:`horovod_tpu.ops` has to know about
the device and about ``pallas_call``, and nothing about any one kernel:
the device probe, the scoped-VMEM keyword, the output struct under
``shard_map``, the one spelling of "not on a TPU", and the rule that
sends interpreted Pallas under manual mesh axes to the XLA form.

Each family's ``_plan`` stays its own — the plans are the decisions.
The families call these through the module (``_pallas.vmem_headroom_ok()``),
so a test that fakes the device fakes it for every family at once.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# TPU generations with only 16 MB of physical VMEM per core — a scoped
# budget above Mosaic's default cannot be backed there, so the forms that
# need one stand down in their family's _plan.
_SMALL_VMEM_DEVICE_KINDS = ("v2", "v3")


def vmem_headroom_ok() -> bool:
    d = jax.local_devices()[0]
    if d.platform != "tpu":
        return True   # CPU/interpret: the limit is not enforced
    try:
        kind = (d.device_kind or "").lower()
    except Exception:   # noqa: BLE001 — runtime refused the query
        kind = ""
    if not kind:
        # A TPU whose generation cannot be read could be a v2/v3 with
        # 16 MB of physical VMEM: fail closed — a stood-down raised
        # budget costs a slower kernel form, an over-request fails the
        # whole compile.
        return False
    return not any(g in kind for g in _SMALL_VMEM_DEVICE_KINDS)


def vmem_limit(mb: int) -> dict:
    """``CompilerParams`` keyword for a scoped-VMEM budget of ``mb`` MB;
    0 leaves Mosaic's default (16 MB) in place."""
    return {"vmem_limit_bytes": mb * 1024 * 1024} if mb else {}


def compiler_params(interpret: bool, semantics, vmem_mb: int = 0) -> dict:
    """``pallas_call``'s ``compiler_params`` keyword for a grid of these
    ``dimension_semantics`` under a scoped-VMEM budget of ``vmem_mb`` MB;
    nothing where the kernel is interpreted."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, **vmem_limit(vmem_mb))}


def struct(shape, dtype, *like):
    """ShapeDtypeStruct for a pallas output, inheriting the union of the
    inputs' varying-manual-axes: under ``shard_map(check_vma=True)`` the
    kernel outputs vary over exactly the axes the inputs do, and jax
    requires that declared explicitly."""
    vma = frozenset()
    for l in like:
        vma |= jax.typeof(l).vma
    # Always explicit, even when empty: an output of invariant inputs
    # (a gathered tensor) is invariant, and under check_vma jax refuses
    # a struct that does not say so.
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def interpret() -> bool:
    """Whether a kernel called now runs interpreted: off the TPU."""
    return jax.default_backend() != "tpu"


def xla_form(interpret: bool, manual_axes: bool) -> bool:
    """Whether a plan must take its XLA form whatever the shapes: under
    ``shard_map``'s manual axes the generic HLO interpreter cannot
    discharge a kernel's loads (its vma check rejects the blocks' dynamic
    slices), so interpreted Pallas stands down there; compiled Mosaic is
    unaffected."""
    return interpret and manual_axes
