"""Plain references for the index: exact top-k in float64, the ranked
comparison of a served hit list with it, the budgeted context, and the
hierarchy's structural rules.  Nothing here imports the program.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference.text import words


def topk(rows: np.ndarray, seqs: np.ndarray, q: np.ndarray, k: int
         ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact scores in float64 of every query against every row, and
    each query's ``k`` best rows; ties go to the lower sequence
    number.  -> ((B, k) row order, (B, N) scores)."""
    scores = np.asarray(q, np.float64) @ np.asarray(rows, np.float64).T
    order = np.stack([np.lexsort((seqs, -s))[:k] for s in scores])
    return order, scores


def topk_high(rows: np.ndarray, q: np.ndarray, k: int):
    """The control: top-k scores at matmul precision ``high``, one step
    below the scan's float32 ``highest``: each float32 operand is split
    into two bfloat16-representable parts (``reduce_precision``, which
    no compiler may fold away) and three of the four products are
    summed in float32.  -> (scores, rows)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scan(q, db):
        def split(x):
            hi = jax.lax.reduce_precision(x, exponent_bits=8,
                                          mantissa_bits=7)
            lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                          mantissa_bits=7)
            return hi, lo

        def dot(a, b):     # exact products of bf16-representable parts
            return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
        qh, ql = split(q)
        dh, dl = split(db)
        s = dot(qh, dh) + (dot(qh, dl) + dot(ql, dh))
        return jax.lax.top_k(s, k)

    vals, idx = scan(jnp.asarray(q, jnp.float32),
                     jnp.asarray(rows, jnp.float32))
    return np.asarray(vals, np.float64), np.asarray(idx)


def ranked(got_rows: Sequence[int], got_scores: Sequence[float],
           ref_order: np.ndarray, ref_scores: np.ndarray, tie: float
           ) -> Tuple[int, float]:
    """Compare a served hit list with the reference ranking.

    Returns (rank mismatches, widest score error).  A served row may
    differ from the reference's row at its rank only when the two
    rows' exact scores differ, by at most ``tie`` (a near tie the
    scan's rounding may order either way); equal scores must follow
    sequence order, and anything else is a mismatch."""
    got_rows = np.asarray(got_rows, np.int64)
    want = ref_order[:len(got_rows)]
    err = float(np.max(np.abs(np.asarray(got_scores, np.float64)
                              - ref_scores[got_rows]))) \
        if len(got_rows) else 0.0
    bad = 0
    for g, w in zip(got_rows, want):
        if g == w:
            continue
        gap = abs(ref_scores[g] - ref_scores[w])
        if gap == 0.0 or gap > tie:
            bad += 1
    return bad, err


def n_tokens(text: str) -> int:
    return len(words(text))


def budgeted(texts: Sequence[str], budget: int) -> Tuple[int, str]:
    """Greedy context: hits in rank order while they fit the token
    budget, stopping at the first that does not; a best hit longer
    than the budget is cut to it.  -> (hits kept, context)."""
    kept, out, total = 0, [], 0
    for t in texts:
        n = n_tokens(t)
        if total + n > budget:
            if not kept:
                return 1, " ".join(words(t)[:budget])
            break
        kept += 1
        out.append(t)
        total += n
        if total >= budget:
            break
    return kept, "\n".join(out)


def hierarchy_violations(layers: List[List[Tuple[Tuple[str, ...], str]]],
                         node_layer: Dict[str, int],
                         children: Dict[str, Tuple[str, ...]],
                         s_max: int) -> List[str]:
    """The hierarchy's rules: each layer's segments partition that
    layer's nodes, none is larger than ``s_max``, and each segment's
    parent is a node one layer up whose children are the segment's
    members, in order.  ``layers[l]``: (members, parent) per segment."""
    errs = []
    for lv, segs in enumerate(layers):
        seen = []
        for members, parent in segs:
            if len(members) > s_max:
                errs.append(f"L{lv}: segment of {len(members)} > {s_max}")
            seen.extend(members)
            if parent:
                if node_layer.get(parent) != lv + 1:
                    errs.append(f"L{lv}: parent {parent} not on L{lv + 1}")
                elif tuple(children[parent]) != tuple(members):
                    errs.append(f"L{lv}: parent {parent} children differ")
        if len(seen) != len(set(seen)):
            errs.append(f"L{lv}: a node is in two segments")
        if segs and set(seen) != {n for n, l in node_layer.items()
                                  if l == lv}:
            errs.append(f"L{lv}: segments do not cover the layer")
    return errs
