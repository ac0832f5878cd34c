"""Custom compute kernels: Pallas TPU lowerings + fused host executors.

Every kernel has a jnp/np oracle in ``ref.py`` and platform dispatch in
``ops.py`` (TPU → compiled Pallas, elsewhere → oracle, with
:func:`force_pallas_interpret` routing a CPU host through the kernels in
interpret mode for test parity; a TPU never interprets).

* ``coo_spmm.py`` — fused batched COO semiring SpMM (DESIGN.md §9):
  gather → ⊗ → segment-⊕ in one pass over edge tiles.  The serving hot
  loop's ``d ⊗ E`` advance; planned as the ``sparse_frontier_pallas``
  runner and priced by ``planner.SpmmKernelModel``.
* ``semiring_matmul.py`` — dense blocked ⊕.⊗ contraction (engine's
  trop/maxplus matmuls route here via ``ops.semiring_matmul``).
* ``coo_segment.py`` — scalar segment-⊕ scatter (sparse contraction's
  reduce step via ``ops.semiring_segment_reduce``).
* ``ssm_scan.py`` — associative state-space scan; live through
  ``models/ssm.py``.
* ``flash_attention.py`` — GQA flash-attention forward.  Seed-era: no
  in-repo consumer beyond its ``ops.flash_attention`` wrapper and the
  ``test_kernels.py`` parity sweep; kept for the model substrate, not
  the datalog path.
"""

from repro.kernels.coo_spmm import (SpmmPlan, bool_round_packed,
                                    pack_lanes, plan_geometry, spmm_host,
                                    spmm_pallas, unpack_lanes)
from repro.kernels.ops import force_pallas_interpret

__all__ = [
    "SpmmPlan",
    "bool_round_packed",
    "force_pallas_interpret",
    "pack_lanes",
    "plan_geometry",
    "spmm_host",
    "spmm_pallas",
    "unpack_lanes",
]
