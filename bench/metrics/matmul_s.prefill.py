"""matmul_s.prefill: device seconds per prefill in matrix-multiply
operations: XLA's dot and convolution fusions and both Pallas kernels, the
SSD scan and flash attention (device trace, mean over the chips). Read as
``matmul_s.linalg`` reads its cells."""
from bench.run import load_module

_SAME = load_module("metrics", "matmul_s.linalg")


def read(run):
    return _SAME.read(run)
