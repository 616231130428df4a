# The device side of MHRA placement: ``placement/`` holds the fused
# ``lax.scan`` greedy (ops.py), its NumPy oracle (ref.py) and a Pallas
# score kernel run only in interpret mode (kernel.py); ``dispatch.py``
# selects the placement backend.
