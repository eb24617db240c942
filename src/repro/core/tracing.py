"""Host spans of the enumeration hot path, on the device trace's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: while a profiler
session runs it writes into the profiler's own host plane, so a span lines up
with the device's operations by construction; while none runs it records
nothing. There is no other switch.

``host_read(stats, site, x)`` is the one way the engine turns a device value
into a host value at a step. It opens ``sync.<site>`` around the transfer and
counts the read on ``stats.host_syncs``, so an idle gap of the device can be
told apart from host work, and the reads per step can be counted without a
trace.

``SPANS`` names every span the program opens.
"""
from __future__ import annotations

import jax

span = jax.profiler.TraceAnnotation

OP_KINDS = ("scan", "extend", "verify", "join", "sink")
SYNC_SITES = ("queue_count", "fetch_accounting", "join_count", "sink_count",
              "push_count")
_SYNC = {site: f"sync.{site}" for site in SYNC_SITES}

SPANS = (tuple(f"op.{k}" for k in OP_KINDS)
         + ("engine.fetch_stage", "engine.prepare")
         + tuple(_SYNC.values()))


def host_read(stats, site: str, x):
    """``x`` (an array or a tuple of arrays) on the host, as numpy, in one
    transfer; counted on ``stats.host_syncs``."""
    with span(_SYNC[site]):
        value = jax.device_get(x)
    stats.host_syncs += 1
    return value
