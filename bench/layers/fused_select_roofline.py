"""Share of the HBM roofline the compact engine's gathered select kernel
(``fused_select``) reaches over the window, in % (``bench/roofline.py``):
the rows of ``adj[P]`` its candidate steps had to read, over the kernel's
device time at the chip's peak HBM bandwidth."""
from bench import roofline


def read(run):
    return roofline.share(run, "fused_select", "gathered_select_words")
