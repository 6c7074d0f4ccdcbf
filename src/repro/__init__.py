"""S2RDF reproduction: ExtVP storage + SPARQL engines over JAX.

The public API is the :mod:`repro.engine` facade:

    from repro import Dataset

    ds = Dataset.watdiv(scale=0.5, threshold=0.25)
    res = ds.engine("jit").query("SELECT * WHERE { ?u wsdbm:follows ?v }")

Lower layers (``repro.core``, ``repro.rdf``, ``repro.serve``) remain
importable directly; heavyweight submodules (models, kernels, launch) are
not imported here.
"""

from repro.engine import (
    ConstantBinding, Dataset, Engine, ExecutionBackend, ExecutionContext,
    PreparedQuery, QueryTemplate, Result, RuntimeConfig, ServerMetrics,
    available_backends, create_backend, register_backend, template_signature,
)

__all__ = [
    "Dataset", "Engine", "Result",
    "ExecutionBackend", "ExecutionContext", "PreparedQuery",
    "register_backend", "create_backend", "available_backends",
    "QueryTemplate", "ConstantBinding", "template_signature",
    "ServerMetrics", "RuntimeConfig",
]
