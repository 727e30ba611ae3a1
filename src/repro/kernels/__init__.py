"""Pallas kernels for the paper's compute units (KPU, depthwise KPU, FCU)
plus the language-model kernels (flash attention, SSD chunk scan).

Every kernel is built through ``kernels.common.pallas_call``: compiled by
Mosaic when the computation is lowered for a TPU, run by the Pallas
interpreter on any other backend.  There is no ``interpret`` option.
"""
