"""Pluggable execution backends behind one protocol.

S2RDF's design point is that one relational layer (ExtVP + Algorithm-1/4
compilation) serves any query shape on any execution substrate; this
module is where the substrates plug in.  A backend turns a
:class:`~repro.engine.template.QueryTemplate` into a
:class:`PreparedQuery` — the expensive, template-level artifact (parsed
tree, compiled plan, jitted XLA program, sharded storage) — and a
prepared query runs any constant instantiation via a
:class:`~repro.engine.template.ConstantBinding` without re-parsing or
re-compiling.

Built-in backends:

* ``eager``        — host numpy reference engine (exact dynamic shapes).
* ``jit``          — static-shape XLA program (:mod:`repro.core.jexec`);
                     bound constants are runtime arguments, so one
                     compiled program serves every instantiation.
* ``distributed``  — shard_map over a device mesh
                     (:mod:`repro.core.distributed`); requires ``mesh``.

New backends (Pallas probe paths, cached/sharded layouts, remote
engines) register with :func:`register_backend` and become addressable by
name everywhere a backend string is accepted — no call-site changes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.algebra import BGP, Query
from repro.core.compiler import Plan, compile_bgp, compile_core
from repro.core.executor import (
    Bindings, apply_spine_host, execute, execute_plan,
)
from repro.core.modifiers import peel_spine, substitute_spine
from repro.core.stats import Catalog
from repro.engine.result import Result
from repro.engine.template import (
    ConstantBinding, QueryTemplate, node_vars, rebind_plan, substitute_query,
)

__all__ = [
    "ExecutionContext", "PreparedQuery", "ExecutionBackend",
    "register_backend", "create_backend", "available_backends",
]

_NO_BINDING = ConstantBinding(mapping={}, missing=False)


@dataclass
class ExecutionContext:
    """Everything a backend needs to prepare and run queries."""

    catalog: Catalog
    dictionary: object = None            # Optional[repro.rdf.Dictionary]
    layout: str = "extvp"
    mesh: object = None                  # Optional[jax.sharding.Mesh]
    #: join-order planner compiled plans use ("greedy" | "estimate");
    #: the Engine refreshes this from its RuntimeConfig before every
    #: prepare, and keys its plan cache on it
    planner: str = "greedy"


class PreparedQuery:
    """A template compiled for one backend; run any instantiation of it.

    ``run(binding)`` evaluates the prepared program under a constant
    binding (``None`` for slot-free queries).  Subclasses hold whatever
    per-template state their engine needs.
    """

    backend: str = "?"
    #: True when run_batch executes the whole batch in one program launch
    #: (padding to a static shape is then worthwhile); the base loop runs
    #: padding slots as real queries, so callers must not pad for it.
    vectorized_batch: bool = False
    #: True when a device backend could not compile the template and fell
    #: back to the eager host engine — Engine counts these per request
    #: (``device_fallbacks``), so silent eager execution is observable.
    fallback: bool = False

    def __init__(self, template: QueryTemplate, ctx: ExecutionContext):
        self.template = template
        self.ctx = ctx
        self.query: Query = template.query

    # -- interface -------------------------------------------------------------
    def run(self, binding: Optional[ConstantBinding] = None,
            trace=None) -> Result:
        """``trace`` is the sampled request's
        :class:`~repro.obs.tracer.TraceContext` (or ``None``, the
        default and the fast path) — implementations emit their
        launch/decode spans onto it."""
        raise NotImplementedError

    def run_batch(self, bindings: List[Optional[ConstantBinding]],
                  trace=None) -> List[Result]:
        """Evaluate B constant-bindings of this template; one Result per
        binding, in order.  The base implementation is the sequential
        loop — the parity oracle every vectorized override is tested
        against.  Device backends override it to execute the whole batch
        in a single program launch (the bindings stack into a leading
        batch axis of the ``bounds`` input).  ``trace`` is the chunk's
        lead trace context; the sequential loop attributes it to the
        first binding."""
        return [self.run(b, trace=trace if i == 0 else None)
                for i, b in enumerate(bindings)]

    # -- shared helpers --------------------------------------------------------
    @property
    def out_cols(self) -> Tuple[str, ...]:
        if self.query.select is not None:
            return tuple(self.query.select)
        return node_vars(self.query.root)

    def _empty(self) -> Result:
        return Result.empty(self.out_cols, self.ctx.dictionary)


class _EmptyPrepared(PreparedQuery):
    """Statistics-proven empty template: answered without touching data."""

    def __init__(self, template, ctx, backend: str):
        super().__init__(template, ctx)
        self.backend = backend
        self.plan = Plan(empty=True, vars=self.out_cols)

    def run(self, binding: Optional[ConstantBinding] = None,
            trace=None) -> Result:
        if trace is not None:
            trace.event("short_circuit", why="statistics-empty plan")
        return self._empty()


class _EagerPrepared(PreparedQuery):
    """Host numpy engine.  Queries whose modifier spine sits on a BGP
    core cache the compiled plan + spine and re-bind scan/filter
    constants by id substitution; other operator trees
    (OPTIONAL/UNION/...) cache the parsed tree and re-bind through
    ``substitute_query``."""

    backend = "eager"

    def __init__(self, template, ctx, fallback: bool = False):
        super().__init__(template, ctx)
        self.fallback = fallback
        self.plan: Optional[Plan] = None
        self.spine = None
        core, spine = peel_spine(self.query)
        if isinstance(core, BGP) and ctx.layout != "pt":
            self.plan = compile_bgp(core, ctx.catalog, ctx.layout,
                                    ctx.planner)
            self.spine = spine

    def run(self, binding: Optional[ConstantBinding] = None,
            trace=None) -> Result:
        binding = binding or _NO_BINDING
        if binding.missing:
            return self._empty()
        sid = trace.start("host.execute", backend="eager") \
            if trace is not None else None
        if self.plan is not None:
            if self.plan.empty:
                if trace is not None:
                    trace.end(sid, rows=0, short_circuit=True)
                return self._empty()
            plan = rebind_plan(self.plan, binding.mapping)
            spine = substitute_spine(self.spine, binding.mapping)
            b = apply_spine_host(execute_plan(plan, self.ctx.catalog), spine,
                                 self.ctx.catalog)
            res = Result(b, self.ctx.dictionary)
        else:
            query = substitute_query(self.query, binding.mapping)
            res = Result(execute(query, self.ctx.catalog,
                                 layout=self.ctx.layout),
                         self.ctx.dictionary)
        if trace is not None:
            trace.end(sid, rows=len(res))
        return res


class _VectorizedPrepared(PreparedQuery):
    """Shared device path (jit/distributed): the executor owns a compiled
    static program whose ``bounds`` input carries the bound constants.
    ``run`` feeds one bounds vector; ``run_batch`` stacks B of them into
    a leading batch axis and executes the whole micro-batch in a single
    launch.  Missing-constant bindings (S2RDF's statistics-only empty
    answer) are answered on the host and never occupy a batch slot.

    Traced, the host work before the launch — re-binding the plan,
    building the bounds and filter-constant inputs and uploading them —
    is the ``bind`` span, which the executor closes once the inputs are
    on the device; it then adds ``device.launch`` and ``device.fetch``,
    and ``run_batch`` ends with ``demux``."""

    vectorized_batch = True

    def __init__(self, template, ctx, executor):
        super().__init__(template, ctx)
        self.executor = executor
        self.plan: Plan = executor.plan

    def _wrap(self, data: np.ndarray, cols: Tuple[str, ...]) -> Result:
        # the executor's compiled spine already applied FILTER, the
        # projection, DISTINCT, ORDER BY and the slice on device — the
        # host must not re-project or re-dedup (that would destroy the
        # device-established row order)
        return Result(Bindings(cols, data), self.ctx.dictionary)

    def run(self, binding: Optional[ConstantBinding] = None,
            trace=None) -> Result:
        binding = binding or _NO_BINDING
        if binding.missing:
            if trace is not None:
                trace.event("short_circuit", why="constant missing "
                            "from the dictionary")
            return self._empty()
        bind = trace.start("bind", batch=1) if trace is not None else None
        plan = rebind_plan(self.plan, binding.mapping)
        data, cols = self.executor.run(
            bounds=self.executor.bounds_from_plan(plan),
            fconsts=self.executor.fconsts_from_mapping(binding.mapping),
            trace=trace, bind=bind)
        if trace is None:
            return self._wrap(data, cols)
        sid = trace.start("decode")
        res = self._wrap(data, cols)
        trace.end(sid, rows=len(res))
        return res

    def run_batch(self, bindings: List[Optional[ConstantBinding]],
                  trace=None) -> List[Result]:
        bindings = [b or _NO_BINDING for b in bindings]
        results: List[Optional[Result]] = [None] * len(bindings)
        bind = trace.start("bind", batch=len(bindings)) \
            if trace is not None else None
        live: List[int] = []
        bounds: List[np.ndarray] = []
        fconsts: List[np.ndarray] = []
        for i, b in enumerate(bindings):
            if b.missing:
                results[i] = self._empty()
            else:
                live.append(i)
                bounds.append(self.executor.bounds_from_plan(
                    rebind_plan(self.plan, b.mapping)))
                fconsts.append(self.executor.fconsts_from_mapping(b.mapping))
        if live:
            # pad back to the caller's (static-bucket) batch size: missing
            # bindings must not shrink B, or each distinct live-count would
            # compile its own program
            while len(bounds) < len(bindings):
                bounds.append(bounds[-1])
                fconsts.append(fconsts[-1])
            outs = self.executor.run_batch(bounds, fconsts, trace=trace,
                                           bind=bind)
            sid = trace.start("demux", batch=len(bindings),
                              live=len(live)) if trace is not None else None
            for i, (data, cols) in zip(live, outs):
                results[i] = self._wrap(data, cols)
            if trace is not None:
                trace.end(sid)
        elif trace is not None:
            trace.end(bind)
        return results

    def lower(self, caps=None):
        return self.executor.lower(caps)


class _JitPrepared(_VectorizedPrepared):
    """Static-shape XLA program, compiled once per template.  Bound
    constants are runtime scalars, so re-binding never re-traces; a
    batch of bindings re-traces once per batch shape, never per request."""

    backend = "jit"


class _DistributedPrepared(_VectorizedPrepared):
    """shard_map engine over a mesh; table shards and the per-shard
    program are template-level state, constants are runtime scalars.
    Batches vmap the bounds stack inside shard_map, so every device
    serves the whole batch over its own table shard in one launch."""

    backend = "distributed"


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class ExecutionBackend:
    """Protocol: ``prepare(template, ctx) -> PreparedQuery``."""

    name: str = "?"

    def prepare(self, template: QueryTemplate,
                ctx: ExecutionContext) -> PreparedQuery:
        raise NotImplementedError


class EagerBackend(ExecutionBackend):
    name = "eager"

    def prepare(self, template, ctx):
        return _EagerPrepared(template, ctx)


class JitBackend(ExecutionBackend):
    """The full graph-pattern fragment — BGP/FILTER/OPTIONAL/UNION cores
    plus unbound-predicate (triples-table) scans, under any modifier
    spine (see :func:`repro.core.modifiers.peel_spine`) — compiles
    end-to-end into the static-shape device program via
    :func:`repro.core.compiler.compile_core`.  The remaining eager
    fallbacks (flagged so the Engine can count them) are the host-only
    ``pt`` storage layout and dictionaries whose numeric keys defeat the
    double-single encoding — both surface as NotImplementedError during
    prepare, never as silent divergence at run time."""

    name = "jit"

    def prepare(self, template, ctx):
        if ctx.layout == "pt":
            return _EagerPrepared(template, ctx, fallback=True)
        core, spine = peel_spine(template.query)
        from repro.core.jexec import PlanExecutor
        try:
            cp = compile_core(core, ctx.catalog, ctx.layout, ctx.planner)
            if cp.empty:
                return _EmptyPrepared(template, ctx, self.name)
            ex = PlanExecutor(cp, ctx.catalog, spine=spine)
        except NotImplementedError:
            return _EagerPrepared(template, ctx, fallback=True)
        return _JitPrepared(template, ctx, ex)


class DistributedBackend(ExecutionBackend):
    name = "distributed"

    def __init__(self, dual_partition: bool = False):
        self.dual_partition = dual_partition

    def prepare(self, template, ctx):
        if ctx.mesh is None:
            raise ValueError("distributed backend needs a mesh")
        if ctx.layout == "pt":
            return _EagerPrepared(template, ctx, fallback=True)
        core, spine = peel_spine(template.query)
        from repro.core.distributed import DistributedExecutor
        try:
            cp = compile_core(core, ctx.catalog, ctx.layout, ctx.planner)
            if cp.empty:
                return _EmptyPrepared(template, ctx, self.name)
            ex = DistributedExecutor(cp, ctx.catalog, ctx.mesh,
                                     dual_partition=self.dual_partition,
                                     spine=spine)
        except NotImplementedError:
            return _EagerPrepared(template, ctx, fallback=True)
        return _DistributedPrepared(template, ctx, ex)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], ExecutionBackend]] = {}


def register_backend(name: str,
                     factory: Callable[[], ExecutionBackend]) -> None:
    """Register (or replace) a backend under a string key."""
    _REGISTRY[name] = factory


def create_backend(name: str) -> ExecutionBackend:
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


register_backend("eager", EagerBackend)
register_backend("jit", JitBackend)
register_backend("distributed", DistributedBackend)
