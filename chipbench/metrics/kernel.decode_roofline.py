"""The decode grid's share of its roofline: the least time the chip needs
for the work of the window's decode steps (``chipbench/roofline.py``), over
the grid's device time in the trace."""
from chipbench.kernel_share import read_share


def read(run):
    return read_share(run, "decode", "paged_residual_attention_decode")
