"""Plain reference for the benchmark's SPARQL queries.

A straightforward evaluator of basic graph patterns with numeric
FILTERs, over the benchmark's own integer triples and term list
(``bench/watdiv.py``).  It imports nothing of the program: each triple
pattern is a selection on one predicate's rows, patterns are joined
with pandas hash joins on their shared variables (smallest relation
first, then always a connected one), and FILTER comparisons read each
literal's numeric value.  Answers are bags (SPARQL's default): no row is
removed or merged.

Supported: ``SELECT * WHERE { tp . tp ... FILTER(?v op number) }`` with
a constant predicate in every pattern, which is what the WatDiv basic
templates use.  Anything else raises ``ValueError``.
"""

from __future__ import annotations

import operator
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np
import pandas as pd

__all__ = ["Graph", "parse", "evaluate"]

_OPS = {"<": operator.lt, ">": operator.gt, "<=": operator.le,
        ">=": operator.ge, "=": operator.eq, "!=": operator.ne}
_FILTER = re.compile(r"FILTER\s*\(\s*(\?\w+)\s*(<=|>=|!=|<|>|=)\s*"
                     r"(-?\d+(?:\.\d+)?)\s*\)")
_QUERY = re.compile(r"^\s*SELECT\s+\*\s+WHERE\s*\{(.*)\}\s*$", re.S)


def _numeric(term: str) -> float:
    if term.startswith('"') and term.endswith('"'):
        try:
            return float(term[1:-1])
        except ValueError:
            return float("nan")
    return float("nan")


class Graph:
    """Triples grouped by predicate, with term lookup both ways."""

    def __init__(self, tt: np.ndarray, terms: Sequence[str]):
        order = np.argsort(tt[:, 1], kind="stable")
        self.s = np.ascontiguousarray(tt[order, 0]).astype(np.int64)
        self.o = np.ascontiguousarray(tt[order, 2]).astype(np.int64)
        preds = tt[order, 1]
        uniq, starts = np.unique(preds, return_index=True)
        ends = np.append(starts[1:], len(preds))
        self.span = {int(p): (int(a), int(b))
                     for p, a, b in zip(uniq, starts, ends)}
        self.terms = list(terms)
        self.term_id: Dict[str, int] = {t: i for i, t in enumerate(terms)}
        self.value = np.array([_numeric(t) for t in terms])

    def pattern(self, s: str, p: str, o: str) -> pd.DataFrame:
        """Solutions of one triple pattern, one column per variable."""
        if p.startswith("?"):
            raise ValueError("variable predicates are not supported")
        pid = self.term_id.get(p)
        lo, hi = self.span.get(pid, (0, 0)) if pid is not None else (0, 0)
        ss, oo = self.s[lo:hi], self.o[lo:hi]
        keep = np.ones(hi - lo, dtype=bool)
        for term, col in ((s, ss), (o, oo)):
            if not term.startswith("?"):
                tid = self.term_id.get(term, -1)
                keep &= col == tid
        if s.startswith("?") and s == o:
            keep &= ss == oo
        cols = {}
        if s.startswith("?"):
            cols[s[1:]] = ss[keep]
        if o.startswith("?") and o != s:
            cols[o[1:]] = oo[keep]
        return pd.DataFrame(cols)


def parse(query: str) -> Tuple[List[Tuple[str, str, str]],
                               List[Tuple[str, str, float]]]:
    """(triple patterns, filters) of a query in the supported subset."""
    m = _QUERY.match(query)
    if m is None:
        raise ValueError(f"unsupported query form: {query[:80]!r}")
    body = m.group(1)
    filters = [(v[1:], op, float(c)) for v, op, c in _FILTER.findall(body)]
    body = _FILTER.sub(" ", body)
    patterns = []
    for part in body.split(" . "):
        words = part.strip().rstrip(".").split()
        if not words:
            continue
        if len(words) != 3:
            raise ValueError(f"unsupported triple pattern: {part!r}")
        patterns.append(tuple(words))
    return patterns, filters


def evaluate(graph: Graph, query: str) -> Tuple[Tuple[str, ...], np.ndarray]:
    """The query's answer: (variable names, int64 rows of term ids)."""
    patterns, filters = parse(query)
    rels = [graph.pattern(*tp) for tp in patterns]
    for var, op, const in filters:
        for i, rel in enumerate(rels):
            if var in rel.columns:
                vals = graph.value[rel[var].to_numpy()]
                ok = ~np.isnan(vals) & _OPS[op](vals, const)
                rels[i] = rel[ok]
    variables: List[str] = []
    for tp in patterns:
        for t in (tp[0], tp[2]):
            if t.startswith("?") and t[1:] not in variables:
                variables.append(t[1:])
    # smallest relation first, then the smallest one sharing a variable
    left = list(range(len(rels)))
    first = min(left, key=lambda i: len(rels[i]))
    left.remove(first)
    acc = rels[first]
    while left:
        linked = [i for i in left if set(rels[i].columns) & set(acc.columns)]
        nxt = min(linked or left, key=lambda i: len(rels[i]))
        left.remove(nxt)
        on = sorted(set(rels[nxt].columns) & set(acc.columns))
        acc = acc.merge(rels[nxt], on=on, how="inner") if on \
            else acc.merge(rels[nxt], how="cross")
    cols = tuple(v for v in variables if v in acc.columns)
    data = acc[list(cols)].to_numpy(dtype=np.int64) if cols \
        else np.zeros((len(acc), 0), np.int64)
    return cols, data
