"""The one traffic generator: it reads a mix's data file and the seed.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

* ``templates``: query templates by name, with ``%class%`` placeholders;
* ``placeholders``: for each placeholder, the term it becomes — a
  ``format`` with ``{}`` for a number drawn uniformly from ``lo`` up to
  ``lo + n`` (``n`` a number, or the name of an entity class of the
  graph, whose size it then takes);
* ``loop``: ``"open"`` with ``rate_qps`` (Poisson arrivals), or
  ``"closed"`` with ``clients``.

Templates come in shuffled blocks that hold each template once, and
open-loop gaps are one set of exponential draws scaled to fill the
window exactly; both are drawn once, from ``ORDER_SEED``, so every seed
sends the same templates in the same order at the same times.  The seed
draws the constants, uniformly from their classes (as
``repro.rdf.workloads.instantiate`` draws them): each seed asks other
questions of the same shape.  With the order drawn from the seed too,
the tails near the knee moved by 20–60% from seed to seed while one
seed's two runs agreed within 2% (PERF.md): the order, not the
constants, set the queueing.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, Iterator, List, Mapping, Sequence

import numpy as np

__all__ = ["Mix", "Request", "load_mix", "instantiate", "heaviest",
           "requests", "open_schedule"]

#: the one draw of the template order and the open loop's gaps
ORDER_SEED = 12345


@dataclass(frozen=True)
class Request:
    template: str
    query: str
    due: float = 0.0        # seconds after the window opens (open loop)


@dataclass(frozen=True)
class Mix:
    name: str
    templates: Dict[str, str]
    placeholders: Dict[str, dict]
    loop: str
    rate_qps: float = 0.0
    clients: int = 0


def load_mix(path: str) -> Mix:
    with open(path) as f:
        spec = json.load(f)
    loop = spec["loop"]
    if loop not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    name = os.path.splitext(os.path.basename(path))[0]
    return Mix(name=name, templates=dict(spec["templates"]),
               placeholders=dict(spec.get("placeholders", {})), loop=loop,
               rate_qps=float(spec.get("rate_qps", 0.0)),
               clients=int(spec.get("clients", 0)))


def instantiate(mix: Mix, name: str, sizes: Mapping[str, int],
                rng: np.random.Generator) -> str:
    """Template ``name`` of the mix with its constants drawn from ``rng``."""
    out = mix.templates[name]
    for key, spec in mix.placeholders.items():
        token = f"%{key}%"
        if token not in out:
            continue
        n = spec["n"]
        n = sizes[n] if isinstance(n, str) else int(n)
        lo = int(spec.get("lo", 0))
        out = out.replace(token,
                          spec["format"].format(int(rng.integers(lo, lo + n))))
    if "%" in out:
        raise ValueError(f"placeholder left in {out!r}")
    return out


def heaviest(mix: Mix, name: str, sizes: Mapping[str, int],
             tt: np.ndarray, terms: Sequence[str], k: int) -> List[str]:
    """Up to ``k`` instances of template ``name``, drawn from no seed:
    the i-th binds each placeholder to the constant of its class that
    matches the i-th most triples in the pattern that holds it.  A
    template without placeholders has one instance."""
    text = mix.templates[name]
    body = text[text.index("{") + 1: text.rindex("}")]
    term_id = {t: i for i, t in enumerate(terms)}
    ranked = {}
    for key, spec in mix.placeholders.items():
        token = f"%{key}%"
        if token not in text:
            continue
        pattern = next(p.split() for p in body.split(" . ") if token in p)
        s, pred = pattern[0], pattern[1]
        lo = int(spec.get("lo", 0))
        n = spec["n"]
        n = sizes[n] if isinstance(n, str) else int(n)
        cands = [spec["format"].format(i) for i in range(lo, lo + n)]
        ids = np.array([term_id.get(c, -1) for c in cands])
        col = tt[tt[:, 1] == term_id.get(pred, -1), 0 if s == token else 2]
        count = np.bincount(col, minlength=len(terms))
        degree = np.where(ids >= 0, count[np.maximum(ids, 0)], -1)
        ranked[token] = [cands[j] for j in
                         np.argsort(-degree, kind="stable")[:k]]
    if not ranked:
        return [text]
    out = []
    for i in range(min(len(r) for r in ranked.values())):
        q = text
        for token, cands in ranked.items():
            q = q.replace(token, cands[i])
        out.append(q)
    return out


def requests(mix: Mix, sizes: Mapping[str, int],
             seed: int) -> Iterator[Request]:
    """Endless requests of the mix: shuffled blocks of every template in
    the one fixed order, constants drawn from ``seed``."""
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng([0, seed])
    names = sorted(mix.templates)
    while True:
        for i in order.permutation(len(names)):
            name = names[i]
            yield Request(name, instantiate(mix, name, sizes, rng))


def open_schedule(mix: Mix, sizes: Mapping[str, int], seed: int,
                  seconds: float) -> List[Request]:
    """The open loop's requests for a window of ``seconds``: a whole
    number of template blocks at about ``rate_qps``, due at Poisson
    arrival times that end exactly at the window's close."""
    block = len(mix.templates)
    n = max(block, int(round(mix.rate_qps * seconds / block)) * block)
    gaps = np.random.default_rng([1, ORDER_SEED]).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]          # the first request at 0
    gen = requests(mix, sizes, seed)
    return [Request(r.template, r.query, float(t))
            for r, t in zip(gen, due)]
