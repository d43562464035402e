"""mfu.prefill: nominal operations of the prefills completed in the traced
window (dense products, causal attention, chunked SSD: bench/entries/
zoo_prefill.py) over that window and the chips' bfloat16 peak (%). Read as
``mfu.linalg`` reads its cells."""
from bench.run import load_module

_SAME = load_module("metrics", "mfu.linalg")


def read(run):
    return _SAME.read(run)
