"""Pallas kernels for the paper's compute hot spots + the ops dispatch.

Kernel modules (`conv2d`, `flash_attention`, `rmsnorm`) expose raw
``*_pallas`` entry points; ``ops`` wraps them with ref fallbacks and the
``REPRO_KERNEL_IMPL`` switch.  See docs/KERNELS.md.
"""
from __future__ import annotations

__all__ = ["resolve_interpret", "LANE", "MAX_TILE"]

# The TPU's vector lane width.  The Pallas TPU lowering accepts a block
# whose minor dimension is a multiple of LANE or the whole array axis —
# every tile the Alg. 4.2 choosers offer (``core.dag``) obeys this rule.
LANE = 128
# Widest lane tile a dense grid cell takes (its K and neuron-block axes):
# bounds the cell's weight panel in VMEM (see ``kernels.dense``).
MAX_TILE = 4 * LANE


def resolve_interpret(interpret):
    """Resolve a kernel's ``interpret=None`` default via ``ops._interpret``.

    Kernel entry points must NOT hard-default ``interpret=True`` — that
    silently ships interpret-mode kernels to TPU.  ``None`` means "ask the
    dispatcher": interpret mode everywhere except real TPU silicon.
    """
    if interpret is not None:
        return bool(interpret)
    from repro.kernels import ops      # deferred: ops imports the kernels
    return ops._interpret()
