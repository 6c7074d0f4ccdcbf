"""Uniform query-result type for every execution backend.

All engines produce the same logical object — a bag of solution mappings
over id-encoded columns — but historically returned it in three shapes
(``Bindings``, ``(np.ndarray, cols)``, sharded arrays).  ``Result`` wraps
the canonical :class:`~repro.core.executor.Bindings` plus the dictionary
so callers can decode ids back to RDF terms and compare results across
backends under SPARQL bag semantics (column order is presentation, not
identity).
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.executor import Bindings
from repro.rdf.dictionary import UNBOUND

__all__ = ["Result"]


@dataclass
class Result:
    """A relation over query variables, with optional term decoding."""

    bindings: Bindings
    dictionary: Optional[object] = None   # repro.rdf.Dictionary

    # -- shape ----------------------------------------------------------------
    @property
    def cols(self) -> Tuple[str, ...]:
        return self.bindings.cols

    @property
    def data(self) -> np.ndarray:
        return self.bindings.data

    def __len__(self) -> int:
        return len(self.bindings)

    @staticmethod
    def empty(cols: Sequence[str], dictionary=None) -> "Result":
        return Result(Bindings.empty(tuple(cols)), dictionary)

    # -- views ---------------------------------------------------------------
    def to_numpy(self) -> np.ndarray:
        """The (n, n_vars) int32 id matrix."""
        return self.bindings.data

    def to_terms(self) -> List[Dict[str, str]]:
        """Dictionary-decoded rows: one ``{var: term}`` mapping per
        solution (unbound OPTIONAL slots are omitted)."""
        if self.dictionary is None:
            raise ValueError("Result has no dictionary to decode with")
        out: List[Dict[str, str]] = []
        for row in self.bindings.data.tolist():
            out.append({c: self.dictionary.term_of(int(v))
                        for c, v in zip(self.cols, row) if v != UNBOUND})
        return out

    # -- comparison (SPARQL bag semantics) -----------------------------------
    def as_multiset(self, cols: Optional[Sequence[str]] = None) -> Counter:
        """Bag of solution tuples over ``cols`` (default: sorted columns,
        making the bag independent of backend column order).  Columns in
        ``cols`` the relation does not carry are UNBOUND-filled — a
        variable a backend dropped entirely and one it materialized as
        all-UNBOUND encode the same solution mapping, so both
        canonicalize to the same tuples."""
        order = sorted(self.cols) if cols is None else list(cols)
        n = len(self)
        if not order:
            return Counter({(): n}) if n else Counter()
        return Counter(map(tuple, self._columns(order).tolist()))

    def _columns(self, cols: Sequence[str]) -> np.ndarray:
        """The (n, len(cols)) int64 matrix over ``cols``, UNBOUND-filled
        where this relation does not carry a column."""
        n = len(self)
        arrs = [self.bindings.data[:, self.cols.index(c)] if c in self.cols
                else np.full(n, UNBOUND) for c in cols]
        return np.ascontiguousarray(np.stack(arrs, axis=1), np.int64) \
            if arrs else np.zeros((n, 0), np.int64)

    def same_as(self, other: "Result") -> bool:
        """Multiset equality under SPARQL bag semantics.  Both sides are
        canonicalized over the UNION of their column sets (missing
        columns are UNBOUND-filled), so rows differing only in
        UNBOUND-vs-missing columns compare equal — previously a result
        binding strictly more (all-UNBOUND) columns was never equal to
        one omitting them, which let left-join tests pass vacuously."""
        cols = sorted(set(self.cols) | set(other.cols))
        a, b = self._columns(cols), other._columns(cols)
        if a.shape != b.shape:
            return False
        if not cols:
            return True
        # equal bags <=> equal sorted rows; one sort of whole rows as
        # byte strings compares 10^7-row results in seconds
        row = np.dtype((np.void, a.itemsize * a.shape[1]))
        return bool(np.array_equal(np.sort(a.view(row).ravel()),
                                   np.sort(b.view(row).ravel())))
