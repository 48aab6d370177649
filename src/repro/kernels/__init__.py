"""The fused Ozaki-II Pallas kernels, one module per kind, each owning its
whole pallas route (prologue, the jitted ``pallas_call``, epilogue) behind
``repro.core.dispatch``.  ``common`` holds what the kernels share, ``ref``
the float64 oracles.  Nothing here imports the dispatch layer or the
precision policies above it.
"""
