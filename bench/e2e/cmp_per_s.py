"""Elementwise comparisons per second, the paper's figure of merit: the
comparisons (results x n_f) of every campaign of the window that returned
its full result, over the window's seconds."""


def read(run):
    return sum(c["comparisons"] for c in run.campaigns) / run.window_s
