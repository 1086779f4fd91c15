"""Least bytes a tick of the fused estimator must move.

One ``tick_step`` at (T tasks, N prediction nodes, Nb bias columns, B
observations) has to read the (T, N) factor matrix and the three (T, Nb)
bias statistics (the fold needs the counts and log sums, the pooled noise
scale the log squares), read each task's 13-number model (posterior mean
2, covariance 4, shape, scale, two normalisers, gate, median, spread) to
re-predict it, and write the (T, N) mean and std; the B observations and
the rows they update are read and written once.  Float32 throughout.
"""
from __future__ import annotations

ITEMSIZE = 4
TASK_MODEL = 13           # numbers per task the re-predict reads
OBS_ROW = 8               # packed observation columns
ROW_UPDATE = 2 * (8 + TASK_MODEL + 3)   # moments, model, bias pair: r + w


def tick_step_bytes(T: int, N: int, Nb: int, B: int) -> int:
    return ITEMSIZE * (3 * T * N + 3 * T * Nb + TASK_MODEL * T
                       + B * (OBS_ROW + ROW_UPDATE))
