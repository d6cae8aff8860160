"""repro — TPU-native multi-signal growing self-organizing networks + LM substrate.

Reproduction (and beyond-paper optimization) of:
  Parigi, Stramieri, Pau, Piastra,
  "A Multi-signal Variant for the GPU-based Parallelization of Growing
   Self-Organizing Networks" (2015).
"""

__version__ = "0.1.0"
