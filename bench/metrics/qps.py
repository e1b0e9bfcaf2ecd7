"""Answers completed inside the window, per second of the window."""
import numpy as np


def read(run):
    w = run.window
    done = w.ok & (w.done >= w.t_start) & (w.done < w.t_end)
    return float(np.sum(done)) / w.seconds
