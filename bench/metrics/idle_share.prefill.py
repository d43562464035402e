"""idle_share.prefill: the share of the traced window in which no operation
ran on the device, mean over the chips (%). Read as ``idle_share.linalg``
reads its cells."""
from bench.run import load_module

_SAME = load_module("metrics", "idle_share.linalg")


def read(run):
    return _SAME.read(run)
