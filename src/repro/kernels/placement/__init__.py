"""Fused placement scoring for the ``engine="jax"`` greedy backend.

kernel/ref/ops layout matching the repo's other accelerator kernels:

- ``ref.py``    — NumPy oracle of the per-step fused score+argmin pass,
  extracted verbatim from ``_greedy_soa``'s vector math so parity with
  the SoA engine is structural, not coincidental.
- ``kernel.py`` — Pallas tiled score+argmin; runs only as
  ``pallas_interpret`` (the chip's compiler refuses it, see
  ``dispatch.placement_backend``).
- ``ops.py``    — backend dispatch plus the jit-compiled ``lax.scan``
  greedy over a whole arrival window.
"""
