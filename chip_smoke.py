"""Smoke test: the SPARQL serving path on a TPU, at WatDiv scale.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the distributed backend on four chips

One chip: builds the WatDiv graph at scale 340 (10,105,965 triples, the
S2RDF paper's SF100 range) with the ExtVP load job on the device (the
banded Pallas semi-join) and checks the catalog against the host numpy
build; serves all 20 WatDiv basic templates through
``SparqlServer(backend="jit")`` as single requests and as one
micro-batched group per template, each answer checked against the host
eager engine, with no device fallback; then runs the three Pallas kernels
compiled at real widths against their ``kernels/ref.py`` references.

Four chips (``--chips 4``): only the distributed backend — the same graph
built by the sharded pair grid and checked against the numpy build; one
template per query shape (S1 star: co-partitioned joins; L2 linear and
F1 snowflake: joins repartitioned through all_to_all; C3 complex:
OPTIONAL) served over a 4-device mesh as single requests and checked
against the eager engine; and a check that the work lands on all four
devices.  Each distributed program takes the TPU compiler 10–20 s and
every second there costs four chip-seconds, hence one template per
shape rather than all twenty.

C1 and C2 return 145,090,042 and 102,899,765 rows at scale 340 (counted
on the host): too many for one chip's 16 GB or for the host oracle.  A
template is served at the largest scale of the ladder 340, 170, 85, 40,
20, 10 at which a request returns at most 2^22 rows, so that its
micro-batched group (3 requests padded to 4 slots) stays within 2^24
rows: C1 at 10 (4,048,826 rows), C2 at 20 (508,306 rows).  The cut is
printed.

Exits non-zero when JAX sees no TPU, and on any error, mismatch or
fallback.  Printed times are smoke timings of one cold run, not
benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SCALE = 340
SEED = 0
THRESHOLD = 0.25
N_INSTANCES = 3
#: template -> scale it is served at, when below SCALE (see module doc)
CUTS = {"C1": 10, "C2": 20}
#: one template per query shape for the four-chip phase (see module doc)
FOUR_CHIP_TEMPLATES = ("S1", "L2", "F1", "C3")
KERNEL_PROBE = 1 << 20
KERNEL_BUILD = 1 << 18
N_BUCKETS = 256


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(n_chips: int):
    """The TPU devices, or exit non-zero before any work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) != n_chips:
        sys.exit(f"expected {n_chips} TPU chip(s), JAX sees {len(devs)}")
    return devs


def build(scale: float, backend: str, mesh=None):
    """Dataset at ``scale`` with the ExtVP load job on the device."""
    from repro import Dataset

    t0 = time.perf_counter()
    ds = Dataset.watdiv(scale=scale, seed=SEED, threshold=THRESHOLD,
                        build_backend=backend, mesh=mesh)
    rep = ds.catalog.storage_report()
    log(f"build scale={scale}: {len(ds.catalog.tt)} triples, "
        f"{int(rep['extvp_tables'])} ExtVP tables, "
        f"{int(rep['n_semijoins'])} semi-joins on {backend}; smoke timing: "
        f"total {time.perf_counter() - t0:.1f}s, "
        f"VP {rep['vp_build_seconds']:.1f}s, "
        f"ExtVP {rep['extvp_build_seconds']:.1f}s")
    return ds


def check_against_numpy(ds) -> None:
    from repro.core.vp import build_extvp

    t0 = time.perf_counter()
    ref = build_extvp(ds.catalog.vp, threshold=THRESHOLD, backend="numpy")
    if not ds.catalog.extvp.identical(ref):
        raise AssertionError("device ExtVP catalog differs from numpy build")
    log(f"ExtVP catalog identical to the numpy build "
        f"(numpy rebuild {time.perf_counter() - t0:.1f}s)")


def serve(datasets, backend: str, mesh=None, batched: bool = True,
          templates=None) -> None:
    """Serve the basic templates (all, or ``templates``) as single
    requests, and as one batched group when ``batched``, and check each
    answer against the host eager engine."""
    import numpy as np

    from repro.core import jexec
    from repro.rdf.workloads import basic_queries
    from repro.serve import SparqlServer

    for scale in sorted(datasets):
        if scale != SCALE:
            names = sorted(n for n, s in CUTS.items() if s == scale)
            log(f"cut: {', '.join(names)} served at scale {scale} "
                f"(> 2^22 rows per request at scale {SCALE})")
    fallbacks = 0
    for scale, ds in sorted(datasets.items(), reverse=True):
        server = SparqlServer(ds.catalog, backend=backend, mesh=mesh,
                              flush_ms=1e9)
        oracle = ds.engine("eager")
        expected = {}
        for name, qs in basic_queries(ds.schema, seed=SEED,
                                      n_instances=N_INSTANCES).items():
            if CUTS.get(name, SCALE) != scale or \
                    templates is not None and name not in templates:
                continue
            for q in qs:
                if q not in expected:
                    expected[q] = oracle.query(q)
            traces = jexec.trace_count()
            t0 = time.perf_counter()
            single = [server.query(q) for q in qs]
            t1 = time.perf_counter()
            grouped = []
            if batched:
                tickets = [server.submit(q) for q in qs]
                server.flush()
                grouped = [t.result() for t in tickets]
            t2 = time.perf_counter()
            checked = {}
            for q, res in zip(qs + qs[:len(grouped)], single + grouped):
                # a result identical to one already checked for the same
                # query needs no second multiset comparison
                seen = checked.get(q)
                if not (seen is not None and seen.cols == res.cols
                        and np.array_equal(seen.data, res.data)
                        or res.same_as(expected[q])):
                    raise AssertionError(f"{name}: {backend} answer differs "
                                         f"from eager for {q!r}")
                checked[q] = res
            traced = (f"jit traces +{jexec.trace_count() - traces}; "
                      if backend == "jit" else "")
            log(f"{name} @ {scale}: rows {[len(r) for r in single]} == eager; "
                f"{traced}smoke timing: "
                f"single {t1 - t0:.2f}s"
                + (f", batched {t2 - t1:.2f}s" if batched else ""))
        summary = server.metrics.summary()
        fallbacks += int(summary["device_fallbacks"])
        log(f"scale {scale}: served {summary['served']}, "
            f"batches {summary['batches']}, routed {summary['routed']}, "
            f"device_fallbacks {summary['device_fallbacks']}")
    if fallbacks:
        raise AssertionError(f"{fallbacks} device fallback(s)")


def check_kernels() -> None:
    """The three Pallas kernels, compiled, against their references."""
    import jax
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    probe = jax.numpy.asarray(
        rng.integers(0, 4 * KERNEL_BUILD, KERNEL_PROBE).astype(np.int32))
    build_keys = jax.numpy.asarray(np.sort(rng.choice(
        4 * KERNEL_BUILD, KERNEL_BUILD, replace=False)).astype(np.int32))
    valid = jax.numpy.asarray(rng.random(KERNEL_PROBE) < 0.9)
    cases = {
        "semijoin": (lambda: ops.semijoin_mask(probe, build_keys,
                                               force_pallas=True),
                     lambda: ref.semijoin_membership_ref(probe, build_keys)),
        "mergejoin": (lambda: ops.join_probe(probe, build_keys,
                                             force_pallas=True),
                      lambda: ref.join_probe_ref(probe, build_keys)),
        "bucketcount": (lambda: ops.bucket_count(probe, valid, N_BUCKETS,
                                                 force_pallas=True),
                        lambda: ref.bucket_count_ref(probe, valid,
                                                     N_BUCKETS)),
    }
    for name, (kernel, reference) in cases.items():
        got = jax.block_until_ready(kernel())         # compile
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel())
        dt = time.perf_counter() - t0
        want = reference()
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise AssertionError(f"{name} kernel differs from ref")
        log(f"kernel {name}: {KERNEL_PROBE} probe / {KERNEL_BUILD} build "
            f"keys == ref; smoke timing {dt * 1e3:.1f} ms")


def check_spread(devs, mesh, ds) -> None:
    """The pair grid's output is split over every device, and every
    device held data while serving."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import extvp_build as eb
    from repro.core.distributed import extvp_pair_masks_sharded

    packed = eb.pack_vp(ds.catalog.vp, with_bitmap=False)
    uniq, mult = jnp.asarray(packed.uniq), jnp.asarray(packed.mult)
    n = 4 * len(devs)
    idx = [jnp.asarray(np.arange(n, dtype=np.int32) % len(packed.preds))
           for _ in range(2)]
    cols = jnp.zeros(n, jnp.int32)
    member, _ = extvp_pair_masks_sharded(uniq, mult, uniq, cols, idx[0],
                                         cols, idx[1], mesh=mesh)
    shards = {s.device: s.data.shape[0] for s in member.addressable_shards}
    if set(shards) != set(devs) or set(shards.values()) != {n // len(devs)}:
        raise AssertionError(f"pair grid not split over the mesh: {shards}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    log(f"pair grid split {shards}; peak bytes per device {peaks}")
    if min(peaks) < 0.1 * max(peaks):
        raise AssertionError(f"work not spread over the devices: {peaks}")


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()
    devs = tpu_devices(args.chips)

    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    cached = _cache_entries(cache)
    log(f"compile cache: {cache} ({cached} entries)")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")
    ops.use_pallas(True)
    t0 = time.perf_counter()
    if args.chips == 1:
        backend, build_backend, mesh = "jit", "jax", None
    else:
        backend = build_backend = "distributed"
        mesh = jax.make_mesh((len(devs),), ("data",))
    templates = None if args.chips == 1 else FOUR_CHIP_TEMPLATES
    datasets = {SCALE: build(SCALE, build_backend, mesh)}
    check_against_numpy(datasets[SCALE])
    for scale in sorted({s for n, s in CUTS.items()
                         if templates is None or n in templates}):
        datasets[scale] = build(scale, build_backend, mesh)
    # four chips: single requests only — a batched program per template
    # would double the compiles, and each second there costs four
    serve(datasets, backend, mesh, batched=args.chips == 1,
          templates=templates)
    if args.chips == 1:
        check_kernels()
    else:
        check_spread(devs, mesh, datasets[SCALE])
    log(f"compile cache: {cached} entries at start, "
        f"{_cache_entries(cache)} at end")
    log(f"smoke timing: whole run {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
