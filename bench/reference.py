"""Plain references that decide ``correct``, and the lower-precision
controls that must fail them.

Nothing here imports the program. The Knowledge Bank semantics are
restated from the CARLS paper (arXiv:2105.12849, section 3.2) as the
configuration states them: rows are fp32; a lookup first applies the
average of the row's cached gradients, its norm clipped at ``zmax`` times
the root-mean-square contribution norm, scaled by ``-lazy_lr``, and then
clears the cache; ``lazy_grad`` clips each incoming gradient at ``zmax``
times the square root of the row's norm EMA (before this call), adds it
to the cache, and moves the EMA one step (decay 0.9) towards the mean
clipped squared norm of the call's contributions to that row.

The reference computes in float64 over the rows a run touched and, as
the configuration states, stores each row it writes as fp32; the
gradient caches stay float64.
"""
from __future__ import annotations

import numpy as np

EMA_DECAY = 0.9


class BankReference:
    """float64 Knowledge Bank over a fixed set of row ids."""

    def __init__(self, ids: np.ndarray, rows: np.ndarray, *, lazy_lr: float,
                 zmax: float):
        self.ids = np.asarray(ids, np.int64)          # sorted, distinct
        self.table = np.asarray(rows, np.float64).copy()
        u, d = self.table.shape
        self.grad_sum = np.zeros((u, d))
        self.grad_cnt = np.zeros(u)
        self.grad_sq = np.zeros(u)
        self.norm_ema = np.zeros(u)
        self.lazy_lr, self.zmax = lazy_lr, zmax

    def _slots(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64).reshape(-1)
        slots = np.searchsorted(self.ids, ids)
        if (slots >= self.ids.size).any() or (self.ids[
                np.minimum(slots, self.ids.size - 1)] != ids).any():
            raise KeyError("id outside the reference's row set")
        return slots

    def lookup(self, ids) -> np.ndarray:
        s = self._slots(ids)
        u = np.unique(s)
        cnt = self.grad_cnt[u]
        pend = cnt > 0
        if pend.any():
            p = u[pend]
            c = cnt[pend][:, None]
            avg = self.grad_sum[p] / c
            norm = np.linalg.norm(avg, axis=1, keepdims=True)
            rms = np.sqrt(self.grad_sq[p][:, None] / c)
            cap = self.zmax * np.maximum(rms, 1e-12)
            scale = np.minimum(1.0, cap / np.maximum(norm, 1e-12))
            # rows are stored as fp32, as the configuration states
            self.table[p] = (self.table[p] - self.lazy_lr * avg * scale
                             ).astype(np.float32)
            self.grad_sum[p] = 0.0
            self.grad_cnt[p] = 0.0
            self.grad_sq[p] = 0.0
        return self.table[s]

    def lazy_grad(self, ids, grads) -> None:
        s = self._slots(ids)
        g = np.asarray(grads, np.float64).reshape(s.size, -1)
        sq = np.sum(g * g, axis=1)
        ema = self.norm_ema[s]
        if self.zmax > 0:
            cap = self.zmax * np.sqrt(np.maximum(ema, 1e-30))
            norm = np.sqrt(np.maximum(sq, 1e-30))
            scale = np.where(ema > 0, np.minimum(1.0, cap / norm), 1.0)
            g = g * scale[:, None]
            sq = sq * scale * scale
        np.add.at(self.grad_sum, s, g)
        np.add.at(self.grad_cnt, s, 1.0)
        np.add.at(self.grad_sq, s, sq)
        u, inv = np.unique(s, return_inverse=True)
        sq_sum = np.bincount(inv, weights=sq)
        n_in = np.bincount(inv).astype(np.float64)
        mean_sq = sq_sum / n_in
        old = self.norm_ema[u]
        self.norm_ema[u] = np.where(old > 0, EMA_DECAY * old +
                                    (1 - EMA_DECAY) * mean_sq, mean_sq)


def row_error(got, want) -> float:
    """Largest elementwise gap of each row, over that row's largest
    magnitude: the worst over all rows."""
    got = np.asarray(got, np.float64).reshape(-1, np.shape(want)[-1])
    want = np.asarray(want, np.float64).reshape(got.shape)
    scale = np.maximum(np.abs(want).max(axis=1), 1e-30)
    return float((np.abs(got - want).max(axis=1) / scale).max())


# -- controls: the reference in the precision one step below -------------

def split_bf16(x) -> tuple:
    """x = hi + lo + rest, hi and lo bfloat16 values held in float32: the
    operand split of a three-pass ``Precision.HIGH`` matmul."""
    import ml_dtypes
    x = np.asarray(x, np.float32)
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def high_gather(rows) -> np.ndarray:
    """Rows as a one-hot gather at ``Precision.HIGH`` returns them: with a
    one-hot operand of exact ones, the three passes add up to hi + lo."""
    hi, lo = split_bf16(rows)
    return (hi.astype(np.float64) + lo)


def high_scores(queries, rows) -> np.ndarray:
    """(Q, D) x (R, D) inner products at ``Precision.HIGH``: three bf16
    passes, hi*hi + hi*lo + lo*hi, each product exact and summed in
    float64 (the rounding of the sum is far below the passes' error)."""
    qh, ql = split_bf16(queries)
    rh, rl = split_bf16(rows)
    f = np.float64
    return (qh.astype(f) @ rh.astype(f).T + qh.astype(f) @ rl.astype(f).T
            + ql.astype(f) @ rh.astype(f).T)
