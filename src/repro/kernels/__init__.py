"""Pallas TPU kernels for the MBE engines' compute hot-spots.

Each kernel package ships kernel.py (pl.pallas_call + explicit BlockSpec
VMEM tiling), ops.py (jit'd dispatch wrapper) and ref.py (pure-jnp
oracle); all are validated against their oracle in interpret mode on CPU
and lower natively on TPU.

  intersect_count  — AND+popcount row reduce: the MBE engine's phases
                     A/C/E (the paper's reverse-scanning hot spot)
  fused_select     — counts + masked argmin in one pass: degeneracy-order
                     candidate selection (the paper's early-stop goal,
                     achieved structurally)
  fused_check      — counts + Q-violation flag + full/partial expansion
                     partition in one pass: the rest of an enumeration
                     step (phases C/E), counts never round-tripped to HBM

``dispatch.resolve_impl`` is the shared "auto"|"jnp"|"pallas" rule every
op (and the engines' ``EngineConfig.kernel_impl``) resolves through.
"""
from repro.kernels.dispatch import default_interpret, resolve_impl  # noqa: F401,E501
