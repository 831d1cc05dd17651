# Jitted public wrappers for the segreduce kernels, and the REPRO_PALLAS
# execution-mode knob the query engine (and the planner's cost model)
# resolves the Pallas-vs-jnp decision through.
#
# On a TPU the engine always runs the Mosaic-compiled kernel: asking for the
# jnp fallback there raises instead of quietly running something else.  Off
# TPU the kernel can only be interpreted (slow, for correctness tests), so
# the default there is the jnp fallback.
from __future__ import annotations

import os
from functools import partial
from typing import Optional, Sequence

import jax

from .kernel import fused_segreduce_pallas, kernel_path, segreduce_pallas
from .ref import fused_segreduce_ref, segreduce_ref

# rows one grid step of the engine's kernel launches streams (512 lane rows
# of 128, 256 KiB per 4-byte column): on a v5e a 3000-key COUNT over 2^25
# rows ran faster at 2^16 than at 2^14 (benchmarks/chip_segreduce.py)
ENGINE_TILE = 1 << 16

_OFF = ("0", "off", "never", "jnp")
_FORCE = ("1", "on", "force", "interpret")


def pallas_mode() -> str:
    """How the segmented-aggregation kernels execute, resolved from the
    ``REPRO_PALLAS`` environment knob:

      * ``'compiled'``  — real Pallas kernel, Mosaic-compiled (TPU),
      * ``'interpret'`` — Pallas kernel in interpret mode (slow; only when
        forced off-TPU with ``REPRO_PALLAS=1`` — correctness testing),
      * ``'off'``       — the pure-jnp fused fallback (``ref.py``), off-TPU
        only.

    Unset / ``auto``: compiled on TPU, fallback elsewhere.  ``1``/``force``
    runs the Pallas kernel even off-TPU (interpret mode).  ``0``/``off``
    selects the jnp fallback off-TPU and raises on a TPU, where the kernel
    is the engine's path.  The knob is read at trace time — an
    already-jitted caller keeps the mode it compiled with."""
    env = os.environ.get("REPRO_PALLAS", "auto").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if env in _OFF:
        if on_tpu:
            raise RuntimeError(
                f"REPRO_PALLAS={env!r} asks for the jnp fallback on a TPU; the "
                "engine runs the compiled segreduce kernel there — unset it"
            )
        return "off"
    if env in _FORCE:
        return "compiled" if on_tpu else "interpret"
    if env not in ("auto", ""):
        raise ValueError(f"unknown REPRO_PALLAS value {env!r}")
    return "compiled" if on_tpu else "off"


def _resolve_mode(use_pallas: Optional[bool]) -> str:
    if use_pallas is None:
        return pallas_mode()
    if not use_pallas:
        return "off"
    return "compiled" if jax.default_backend() == "tpu" else "interpret"


def launch_path(ops: Sequence[str], dtypes: Sequence, num_keys: int) -> Optional[str]:
    """The kernel path ('mxu' or 'vpu', ``kernel.kernel_path``) a launch
    through these wrappers takes, or None where the jnp fallback runs."""
    if pallas_mode() == "off":
        return None
    return kernel_path(ops, dtypes, num_keys)


@partial(jax.jit, static_argnames=("num_keys", "op", "mode"))
def _segreduce_impl(keys, values, num_keys: int, op: str, mode: str):
    if mode == "off":
        return segreduce_ref(keys, values, num_keys, op)
    return segreduce_pallas(
        keys, values, num_keys, op, tile=ENGINE_TILE, interpret=(mode == "interpret")
    )


def segreduce(keys, values, num_keys: int, op: str = "sum", use_pallas: Optional[bool] = None):
    """Single-op group-by aggregation.  ``use_pallas=None`` resolves the
    execution mode through ``pallas_mode()`` (the REPRO_PALLAS knob);
    True/False force the Pallas kernel / the jnp oracle."""
    return _segreduce_impl(keys, values, num_keys, op, _resolve_mode(use_pallas))


@partial(jax.jit, static_argnames=("ops", "num_keys", "with_presence", "mode"))
def _fused_impl(keys, values, mask, ops, num_keys: int, with_presence: bool, mode: str):
    if mode == "off":
        return fused_segreduce_ref(
            keys, values, ops, num_keys, mask=mask, with_presence=with_presence
        )
    return fused_segreduce_pallas(
        keys, values, ops, num_keys, mask=mask,
        with_presence=with_presence, tile=ENGINE_TILE, interpret=(mode == "interpret"),
    )


def fused_segreduce(
    keys,
    values: Sequence,
    ops: Sequence[str],
    num_keys: int,
    mask=None,
    with_presence: bool = True,
    use_pallas: Optional[bool] = None,
):
    """Fused multi-aggregate group-by: ``values[i]`` aggregated under
    ``ops[i]`` (each a segreduce op: 'sum'/'max'/'min') in one data pass,
    plus the group-presence histogram.  Masked rows contribute each op's
    identity.  Returns ``(accs tuple, presence-or-None)``."""
    return _fused_impl(
        keys, tuple(values), mask, tuple(ops), num_keys, with_presence,
        _resolve_mode(use_pallas),
    )
