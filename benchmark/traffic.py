"""The one traffic generator: a traffic file's parameters to work items.

A traffic file (``benchmark/traffic/<name>.json``) is data.  Two shapes:

``{"loop": "closed", "clients": n, "prompt_len": D, "output_len": D, ...}``
    requests for a served model.  ``D`` is ``{"dist": "loguniform", "lo":
    a, "hi": b, "quantiles": q}``: the ``q`` mid-quantiles of the
    distribution, not draws.  The multiset of (prompt, output) pairs is the
    full product of the two quantile lists, so it is the SAME for every
    seed; ``--seed`` decides only the order (see :func:`request_order`;
    repeated when the run needs more requests than one cycle) and the token
    ids.

``{"job": ..., "units_per_second": r, "unit_chunk": c}``
    a training or fitting job: how many units (iterations, steps) one run
    is given, from the window's seconds: a whole number of chunks, never
    less than ``min_units``.  The work is fixed from the arguments, so two
    runs of one length do the same work.  ``units_per_second`` is a rate
    written into the file, not one measured in the run: a calibration step
    would give two runs different work.  The metric is the units over the
    time they took, so a program that gets faster finishes the same job in
    less than the window's seconds (the fit: 75 iterations in 24.3 of 30 s
    today) and reads a higher rate; once a job takes under half of the
    window, a benchmark PR raises the file's rate.

No JAX here: the load generator's child process imports this module.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name_or_path: str) -> Dict[str, Any]:
    path = name_or_path
    if not os.path.isfile(path):
        path = os.path.join(HERE, "traffic", name_or_path + ".json")
    with open(path) as f:
        return json.load(f)


def quantile_lengths(dist: Dict[str, Any]) -> List[int]:
    q = int(dist["quantiles"])
    u = (np.arange(q) + 0.5) / q
    if dist["dist"] == "loguniform":
        v = dist["lo"] * (dist["hi"] / dist["lo"]) ** u
    elif dist["dist"] == "uniform":
        v = dist["lo"] + (dist["hi"] - dist["lo"]) * u
    elif dist["dist"] == "fixed":
        v = np.full(q, dist["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(round(x)) for x in v]


def length_pairs(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The fixed multiset of (prompt tokens, output tokens), in a
    canonical order that no seed touches."""
    return [(p, o) for p in quantile_lengths(traffic["prompt_len"])
            for o in quantile_lengths(traffic["output_len"])]


def request_order(traffic: Dict[str, Any], seed: int) -> List[Tuple[int, int]]:
    """One cycle of the multiset in the seed's order.

    ``"order": "stratified"`` (both lists of quantiles equally long, ``q``):
    the cycle is ``q`` blocks of ``q`` requests, a Latin square, so that
    every block holds each prompt length once and each output length once.
    A window that ends part-way through the cycle has then seen the same
    tokens, to within one block, whatever the seed: a plain shuffle let one
    seed's window hold 3% fewer tokens than another's (my chip runs, PR 26).
    The seed permutes the blocks, the two lists' roles in the square and
    the requests inside a block.  Any other ``order``: a plain permutation."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    plens = quantile_lengths(traffic["prompt_len"])
    olens = quantile_lengths(traffic["output_len"])
    if traffic.get("order") == "stratified":
        q = len(plens)
        if len(olens) != q:
            raise ValueError("stratified order needs as many output as "
                             "prompt quantiles")
        rows, pi, oi = (rng.permutation(q) for _ in range(3))
        order = []
        for k in rows:
            block = [(plens[pi[i]], olens[oi[(i + k) % q]]) for i in range(q)]
            order += [block[j] for j in rng.permutation(q)]
        return order
    pairs = length_pairs(traffic)
    return [pairs[i] for i in rng.permutation(len(pairs))]


def request(traffic: Dict[str, Any], seed: int, index: int, vocab: int,
            order: List[Tuple[int, int]] = None) -> Dict[str, Any]:
    """Request ``index`` of the run: lengths from the cycled order, token
    ids of its own from (seed, index), so that no two prompts share more
    than a chance prefix unless the traffic asks for one."""
    order = order or request_order(traffic, seed)
    plen, olen = order[index % len(order)]
    rng = np.random.default_rng([int(seed), 1, int(index)])
    ids = rng.integers(1, vocab, plen)
    shared = int(traffic.get("shared_prefix_len", 0))
    if shared:
        pre = np.random.default_rng([int(seed), 2]).integers(1, vocab, shared)
        ids[:min(shared, plen - 1)] = pre[:min(shared, plen - 1)]
    return {"index": index, "ids": ids.tolist(), "max_new_tokens": int(olen)}


def job_units(traffic: Dict[str, Any], seconds: float) -> int:
    chunk = int(traffic.get("unit_chunk", 1))
    n = int(seconds * float(traffic["units_per_second"])) // chunk * chunk
    return max(n, int(traffic.get("min_units", chunk)))
