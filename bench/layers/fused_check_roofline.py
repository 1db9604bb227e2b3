"""Share of the HBM roofline the compact engine's gathered check kernel
(``fused_check``) reaches over the window, in % (``bench/roofline.py``):
the rows of ``adj[Q ++ P']`` its candidate steps had to read, over the
kernel's device time at the chip's peak HBM bandwidth."""
from bench import roofline


def read(run):
    return roofline.share(run, "fused_check", "gathered_check_words")
