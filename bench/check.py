"""The comparison that decides ``correct``.

Every request of the window is counted: one that raised, never came
back, or was served by the host fallback instead of the device is a
failed request.  A sample of the answers, drawn from the seed and always
holding the largest answer of the window, is compared row for row, as a
bag, with the plain reference (``bench/reference.py``).  The program's
answer is read the way a client reads it: its term ids decoded through
the program's dictionary, then mapped to the benchmark's own term ids.

Each number compared is printed beside its limit.  Both limits are 0:
the comparison is exact.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference

__all__ = ["Sampler", "decode", "same_bag", "compare", "LIMITS",
           "CONTROL_ROWS"]

#: number compared -> its limit (exact comparison: 0)
LIMITS = {"wrong_answers": 0, "failed_requests": 0}
#: the control's broken guarantee: answers cut at this many rows
CONTROL_ROWS = 1 << 14


class Sampler:
    """Keeps, for each template, a reservoir of ``per_template`` answered
    requests drawn from the seed, and the largest answer seen."""

    def __init__(self, seed: int, per_template: int = 3):
        self.rng = random.Random(seed)
        self.k = per_template
        self.seen: Dict[str, int] = {}
        self.kept: Dict[str, List[Tuple[str, object]]] = {}
        self.largest: Optional[Tuple[int, str, object]] = None

    def offer(self, template: str, query: str, result) -> None:
        n = self.seen.get(template, 0) + 1
        self.seen[template] = n
        slot = self.kept.setdefault(template, [])
        if len(slot) < self.k:
            slot.append((query, result))
        else:
            j = self.rng.randrange(n)
            if j < self.k:
                slot[j] = (query, result)
        if self.largest is None or len(result) > self.largest[0]:
            self.largest = (len(result), query, result)

    def sample(self) -> List[Tuple[str, object]]:
        out = [item for name in sorted(self.kept) for item in self.kept[name]]
        if self.largest is not None:
            out.append(self.largest[1:])
        return out


def decode(result, term_id: Dict[str, int]) -> Tuple[Tuple[str, ...],
                                                       np.ndarray]:
    """The program's answer as (variables, benchmark term ids); a term
    the benchmark does not know reads as -1 and can match nothing."""
    cols = tuple(c.lstrip("?") for c in result.cols)
    data = np.asarray(result.data)
    if data.size == 0:
        return cols, np.zeros(data.shape, np.int64)
    uniq, inv = np.unique(data, return_inverse=True)
    d = result.dictionary
    mine = np.array([term_id.get(d.term_of(int(u)), -1) for u in uniq],
                    dtype=np.int64)
    return cols, mine[inv].reshape(data.shape)


def same_bag(a_cols: Sequence[str], a: np.ndarray, b_cols: Sequence[str],
             b: np.ndarray) -> bool:
    """Equal bags of rows over equal variable sets."""
    if sorted(a_cols) != sorted(b_cols) or a.shape != b.shape:
        return False
    if a.shape[0] == 0 or a.shape[1] == 0:
        return True
    b = b[:, [list(b_cols).index(c) for c in a_cols]]
    if (b < 0).any():
        return False
    ka = a[np.lexsort(a.T[::-1])]
    kb = b[np.lexsort(b.T[::-1])]
    return bool(np.array_equal(ka, kb))


def compare(graph: "reference.Graph", sample, failed: int,
            control: Optional[int] = None,
            cache: Optional[dict] = None) -> Dict[str, object]:
    """Compare the sampled answers with the reference.  With ``control``
    (a number of rows, as a rule ``CONTROL_ROWS``) the control stands in
    the program's place: the reference's own answer cut at that many
    rows, which breaks the guarantee that every solution is returned, as
    a join that skipped its capacity-overflow retry would.  Returns the
    numbers compared, each with its limit, and a summary.  ``cache``
    keeps the reference's answers by query across calls."""
    cache = {} if cache is None else cache
    wrong, rows, bad = 0, 0, []
    for query, result in sample:
        if query not in cache:
            cache[query] = reference.evaluate(graph, query)
        want_cols, want = cache[query]
        if control is not None:
            got_cols, got = want_cols, want[:control]
        else:
            got_cols, got = decode(result, graph.term_id)
        rows += len(want)
        if not same_bag(want_cols, want, got_cols, got):
            wrong += 1
            bad.append(f"{query[:60]}... got {len(got)} rows, "
                       f"reference {len(want)}")
    checks = {"wrong_answers": wrong, "failed_requests": int(failed)}
    return {"checks": {k: {"value": v, "limit": LIMITS[k]}
                       for k, v in checks.items()},
            "correct": all(v <= LIMITS[k] for k, v in checks.items()),
            "compared": len(sample), "reference_rows": rows,
            "mismatches": bad[:5]}
