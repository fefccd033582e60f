"""The comparison that decides ``correct`` for a GNN training cell.

The trainer is driven through its first steps by its own ``train`` call
on rows that all differ; what it fed its compiled steps and what it made
of them is compared with ``reference.py`` run over the same sampled ids:

* ``bad_blocks``: structure of every trainer's block (hop sizes are the
  fanouts times the frontier, layer-0 rows match the innermost frontier,
  each trainer's share is its target count, the shares make the batch,
  no target repeats across the checked steps);
* ``bad_edges``: sampled (node, neighbour) pairs that are not edges of
  the graph (a node without out-edges may only sample itself);
* ``x0_gap``: largest difference between the layer-0 input a trainer got
  and the feature rows of its frontier (load, transfer and combine are
  exact copies, so the limit is 0);
* ``loss_gap``: largest relative difference of a step's loss;
* ``grad_gap``: the first gradient as the optimizer got it (read from
  its first moment after one step) against the reference's, leaf by leaf:
  the gap between the two norms over the larger of the reference leaf's
  norm and the median leaf's norm, worst leaf;
* ``delta_gap``: the same measure for the parameters' change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (Adam moves those by round-off alone).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def non_edges(indptr: np.ndarray, indices: np.ndarray, dst: np.ndarray,
              src: np.ndarray) -> int:
    """How many ``(dst[i], src[i])`` pairs are not edges of the CSR graph."""
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    n = int(indptr.shape[0] - 1)
    uniq, rank = np.unique(dst, return_inverse=True)
    d = indptr[uniq + 1] - indptr[uniq]
    seg_rank = np.repeat(np.arange(uniq.shape[0], dtype=np.int64), d)
    starts = np.repeat(indptr[uniq] - np.cumsum(d) + d, d)
    nbr = indices[starts + np.arange(seg_rank.shape[0], dtype=np.int64)]
    ok = np.isin(rank * n + src, seg_rank * n + nbr.astype(np.int64))
    ok |= (d[rank] == 0) & (src == dst)
    return int((~ok).sum())


def block_faults(block: dict, indptr: np.ndarray, indices: np.ndarray,
                 fanouts: Sequence[int], feat_dim: int) -> Tuple[int, int]:
    """(structural faults, non-edges) of one trainer's block."""
    faults = 0
    frontier = np.asarray(block["targets"], np.int64)
    hops = block["hop_src"]
    if len(hops) != len(fanouts):
        return 1, 0
    bad = 0
    for src, f in zip(hops, fanouts):
        if src.shape[0] != frontier.shape[0] * int(f):
            faults += 1
            continue
        bad += non_edges(indptr, indices, np.repeat(frontier, int(f)), src)
        frontier = np.concatenate([frontier, np.asarray(src, np.int64)])
    if block["x0"].shape != (frontier.shape[0], feat_dim):
        faults += 1
    if int(block["share"]) != block["targets"].shape[0]:
        faults += 1
    return faults, bad


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             leaves: Sequence[str]) -> float:
    norms = {k: float(np.linalg.norm(ref[k])) for k in ref}
    med = float(np.median(list(norms.values())))
    worst = 0.0
    for k in leaves:
        gap = abs(float(np.linalg.norm(prog[k])) - norms[k])
        worst = max(worst, gap / max(norms[k], med, 1e-30))
    return worst


def moving_leaves(ref_grad: Dict[str, np.ndarray]) -> List[str]:
    norms = {k: float(np.linalg.norm(v)) for k, v in ref_grad.items()}
    med = float(np.median(list(norms.values())))
    return sorted(k for k, v in norms.items() if v >= NEGLIGIBLE_GRAD * med)


def numbers(prog: dict, ref: dict, steps: List[Dict[str, dict]],
            x0_gap: float, indptr: np.ndarray, indices: np.ndarray,
            fanouts: Sequence[int], feat_dim: int,
            batch: int) -> Dict[str, float]:
    """Every compared number of one run.  ``prog`` and ``ref`` hold
    ``losses``, ``first_grad`` and ``params`` (after the checked steps)
    and ``prog`` also ``params0``; ``steps`` the recorded blocks."""
    faults = bad = 0
    seen: List[np.ndarray] = []
    for blocks in steps:
        if sum(b["targets"].shape[0] for b in blocks.values()) != batch:
            faults += 1
        for b in blocks.values():
            f, e = block_faults(b, indptr, indices, fanouts, feat_dim)
            faults, bad = faults + f, bad + e
            seen.append(np.asarray(b["targets"], np.int64))
    ids = np.concatenate(seen) if seen else np.zeros(0, np.int64)
    faults += int(ids.shape[0] - np.unique(ids).shape[0])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    p0 = prog["params0"]
    d_prog = {k: prog["params"][k] - p0[k] for k in p0}
    d_ref = {k: ref["params"][k] - p0[k] for k in p0}
    return {
        "bad_blocks": float(faults),
        "bad_edges": float(bad),
        "x0_gap": float(x0_gap),
        "loss_gap": loss_gap,
        "grad_gap": leaf_gap(prog["first_grad"], ref["first_grad"],
                             sorted(ref["first_grad"])),
        "delta_gap": leaf_gap(d_prog, d_ref,
                              moving_leaves(ref["first_grad"])),
    }


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(correct, [(name, value, limit)]): correct when every number is at
    or under its limit; a NaN or a missing number fails."""
    rows = [(k, float(values.get(k, float("nan"))), float(limits[k]))
            for k in limits]
    return all(v <= lim for _, v, lim in rows), rows
