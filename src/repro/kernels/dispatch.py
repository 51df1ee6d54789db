"""Shared impl-dispatch rule for the fused kernel subsystems.

All three subsystems (``sim_tick``, ``sched_select``, ``state_update``)
follow the same convention: a Pallas VMEM kernel, a bitwise-equivalent
jnp reference, and an ``impl="auto"`` wrapper that picks the kernel on
TPU (for shapes the kernel tiles — explicit lane-major batches) and
the reference everywhere else. This module is the one place that rule
lives, so a run can report which implementation it resolved to
(``resolved_impl``; ``chip_smoke.py`` prints it).
"""
from __future__ import annotations

import jax


def use_pallas(impl: str = "auto", *, batched: bool = True) -> bool:
    """True iff the dispatch rule selects the Pallas kernel."""
    if impl == "kernel":
        return True
    return impl == "auto" and batched and jax.default_backend() == "tpu"


def resolved_impl(impl: str = "auto", *, batched: bool = True) -> str:
    """``"pallas"`` or ``"ref"`` — what ``impl`` resolves to here."""
    return "pallas" if use_pallas(impl, batched=batched) else "ref"


__all__ = ["use_pallas", "resolved_impl"]
