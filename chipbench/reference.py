"""The plain reference and the comparison that decides ``correct``.

The reference is a float64 full scan of the run's own table: its exact
counts (from `gen.table_counts`) give every candidate's true histogram
r*_i and, per target, the true distance d*_i = ||r*_i - Q||_1. It
imports nothing of the program.

Every answer the window produced is held to the configuration's
guarantees, and to the sample it says it rests on:

  count_err   the largest gap, in tuples, between the counts (and the
              row sums n_i) the server reports for an answer's ids and
              the reference's exact counts over the blocks the server
              had read when it answered. A sample is a set of whole
              blocks, so this is exact: limit 0.
  g1_ratio    Guarantee 1: the largest (max d* of the answer - d* of a
              true top-k candidate left out), over eps. Limit 1.
  g2_ratio    Guarantee 2: the largest ||r_hat_i - r*_i||_1 over the
              answer's ids, over eps. Limit 1.
  flagged     answers that came back stopped or degraded. Limit 0.
  unanswered  queries due in the window that got no answer. Limit 0.

The paper states Guarantees 1 and 2 each with probability at least
1 - delta per query; the bounds that certify them are loose enough that
no sound run has come near the limit (PERF.md gives the readings).
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

LIMITS = {"count_err": 0.0, "g1_ratio": 1.0, "g2_ratio": 1.0, "flagged": 0.0, "unanswered": 0.0}
# A number passes when it is at most its limit, except the two
# guarantees, which the paper states strictly (deviation < eps).
STRICT = ("g1_ratio", "g2_ratio")


@dataclasses.dataclass
class Answer:
    """One certified answer as the server returned it, with the sample it
    rests on (``snapshot``: key of the read set when it was returned)."""

    target: np.ndarray  # (V_X,) the query's target
    ids: np.ndarray  # (k,) candidate ids, closest first
    counts: np.ndarray  # (k, V_X) reported counts of those ids
    n: np.ndarray  # (k,) reported row sums
    snapshot: tuple
    flagged: bool


def truth(counts: np.ndarray) -> np.ndarray:
    """(V_Z, V_X) float64 true histograms from exact counts."""
    c = counts.astype(np.float64)
    return c / np.maximum(c.sum(axis=1, keepdims=True), 1.0)


def guarantee_ratios(ans: Answer, true_hists: np.ndarray, eps: float) -> tuple:
    """(g1_ratio, g2_ratio) of one answer against the float64 full scan."""
    q = np.asarray(ans.target, np.float64)
    d = np.abs(true_hists - q / q.sum()).sum(axis=1)
    k = len(ans.ids)
    worst = d[ans.ids].max()
    left_out = np.setdiff1d(np.argsort(d, kind="stable")[:k], ans.ids)
    g1 = max(0.0, worst - d[left_out].min()) / eps if left_out.size else 0.0
    c = ans.counts.astype(np.float64)
    r_hat = c / np.maximum(c.sum(axis=1, keepdims=True), 1.0)
    g2 = np.abs(r_hat - true_hists[ans.ids]).sum(axis=1).max() / eps
    return float(g1), float(g2)


def count_gap(ans: Answer, sample_counts: np.ndarray) -> float:
    """Largest |reported - exact| over the answer's count rows and n."""
    exact = sample_counts[ans.ids].astype(np.float64)
    gap = np.abs(ans.counts.astype(np.float64) - exact).max()
    return float(max(gap, np.abs(ans.n.astype(np.float64) - exact.sum(axis=1)).max()))


def judge(numbers: dict) -> bool:
    return all(
        numbers[k] < lim if k in STRICT else numbers[k] <= lim for k, lim in LIMITS.items()
    )


def compare(answers, sample_counts: dict, true_counts: np.ndarray, *, eps: float,
            unanswered: int, each: list | None = None) -> tuple:
    """(the numbers that decide ``correct``, how many answers fail them);
    ``each``, when given, gets every answer's own numbers."""
    true_hists = truth(true_counts)
    out = {"count_err": 0.0, "g1_ratio": 0.0, "g2_ratio": 0.0, "flagged": 0.0,
           "unanswered": float(unanswered)}
    wrong = 0
    for a in answers:
        one = {"count_err": count_gap(a, sample_counts[a.snapshot]), "flagged": float(a.flagged),
               "unanswered": 0.0}
        one["g1_ratio"], one["g2_ratio"] = guarantee_ratios(a, true_hists, eps)
        wrong += not judge(one)
        if each is not None:
            each.append(one)
        for k in ("count_err", "g1_ratio", "g2_ratio"):
            out[k] = max(out[k], one[k])
        out["flagged"] += one["flagged"]
    return out, wrong


def bf16_control(answers, sample_counts: dict, order) -> list:
    """The control: each answer's counts and row sums as the reference
    computes them with bfloat16 accumulation, one step below the
    configuration's float32. ``order`` lists the snapshots in the order
    they were taken, grouped by server; each one's counts are the
    previous snapshot's plus the blocks read since, added in bfloat16."""
    bf16 = ml_dtypes.bfloat16

    def add(a, b):
        return (a.astype(np.float32) + b.astype(bf16).astype(np.float32)).astype(bf16)

    acc, prev = {}, None
    for snap in order:
        exact = sample_counts[snap]
        if prev is None or snap[0] != prev[0]:
            acc[snap] = (exact.astype(bf16), exact.sum(axis=1).astype(bf16))
        else:
            delta = exact - sample_counts[prev]
            acc[snap] = (add(acc[prev][0], delta), add(acc[prev][1], delta.sum(axis=1)))
        prev = snap
    return [
        dataclasses.replace(a, counts=acc[a.snapshot][0][a.ids].astype(np.float64),
                            n=acc[a.snapshot][1][a.ids].astype(np.float64))
        for a in answers
    ]
