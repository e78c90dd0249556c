"""System under test: ``jepsen_tpu.ops.linearize.check_batch_columnar``
over Op-list histories, with the program's defaults and
``details="invalid"`` (the form ``independent.batch_checker`` calls).

``spans`` is the traced run's instrumentation: it wraps the program's
partition, Op-list -> columnar conversion and recombination entries,
which ``check_batch_columnar`` looks up in their modules at call time,
so the spans sit around the very calls the checker makes, in its
order. Nothing is wrapped in a run with ``--trace 0``.
"""
from __future__ import annotations

import contextlib
import time


def make(config: dict):
    """The timed call: histories -> one result dict per history."""
    from jepsen_tpu.models import core as models
    from jepsen_tpu.ops.linearize import check_batch_columnar
    model = getattr(models, config["model"])(config["initial"])

    def call(histories):
        return check_batch_columnar(model, histories, details="invalid")
    return call


def verdict(result) -> tuple:
    """(valid, first bad op index); valid is None when the result is no
    verdict (``"unknown"``, missing)."""
    if not isinstance(result, dict) or result.get("valid") not in (True,
                                                                   False):
        return (None, None)
    if result["valid"] is True:
        return (True, None)
    return (False, (result.get("op") or {}).get("index"))


@contextlib.contextmanager
def spans(rec):
    """Record ``rec.span(name, t0, t1, **counts)`` around partition,
    conversion and recombination, and every sub-history's provenance
    (``rec.subs``), while the block runs."""
    import jax
    from jepsen_tpu.history import columnar
    from jepsen_tpu.ops import partition

    orig = {(partition, "partition_histories"):
            partition.partition_histories,
            (columnar, "ops_to_columnar"): columnar.ops_to_columnar,
            (partition, "recombine_details"): partition.recombine_details}

    def wrap(name, fn, after=None):
        def inner(*a, **kw):
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                t0 = time.perf_counter()
                out = fn(*a, **kw)
                rec.span(name, t0, time.perf_counter())
            if after is not None:
                after(a, out)
            return out
        return inner

    def provenance(args, _out):
        for r in args[0]:
            rec.subs[r.get("provenance") or "device"] += 1

    wrapped = {
        (partition, "partition_histories"):
            wrap("partition", orig[(partition, "partition_histories")]),
        (columnar, "ops_to_columnar"):
            wrap("convert", orig[(columnar, "ops_to_columnar")]),
        (partition, "recombine_details"):
            wrap("recombine", orig[(partition, "recombine_details")],
                 provenance),
    }
    for (mod, attr), fn in wrapped.items():
        setattr(mod, attr, fn)
    try:
        yield
    finally:
        for (mod, attr), fn in orig.items():
            setattr(mod, attr, fn)
