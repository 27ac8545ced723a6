"""Pallas TPU kernels for the hot ops (flash attention, the gated delta
rule's state pass).

Kernels run compiled on TPU and in interpreter mode elsewhere (the CPU
test mesh), so the same code path is exercised everywhere.
"""
