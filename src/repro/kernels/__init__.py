"""Pallas TPU kernels for the Kron-Matmul hot spots the paper optimizes.

emit.py         — StageProgram IR + THE kernel emitter: one parameterized
                  Pallas chain template (+ stage-backward template) and one
                  XLA lax.scan executor behind every fused path.
kron_sliced.py  — one sliced multiply (contributions C1+C2): emit.chain_pallas
                  with one factor.
kron_sliced_t.py— its transpose (the per-factor backward), likewise.
hardware.py     — per-device_kind peaks and VMEM limits (planner + emitter).
kron_fused.py   — DEPRECATED shims: the legacy fused forward entry points.
kron_fused_t.py — DEPRECATED shims: legacy transposed/backward entry points.
ops.py          — sliced-multiply backend dispatch (one-factor emit
                  instructions) + the six deprecated
                  fused_kron* one-instruction shims over emit.
ref.py          — pure-jnp oracles for the allclose sweeps in tests/.
"""
