"""other_s.prefill: device seconds per prefill in operations that are
neither matrix multiplies nor collectives: norms, the causal conv, gates,
the SSD's reshapes and the caches' copies (device trace, mean over the
chips). Read as ``panel_s.linalg`` reads the drivers' cells."""
from bench.run import load_module

_SAME = load_module("metrics", "panel_s.linalg")


def read(run):
    return _SAME.read(run)
