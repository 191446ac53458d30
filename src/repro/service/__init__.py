"""Sweep service: a persistent multi-client campaign server.

One warm emulator engine (in-memory executable LRU + optional
persistent XLA cache) serves many concurrent sweep clients. Submitted
grid points are bucketed by their campaign ``coalesce_key`` and split
into groups by ``campaign.plan_groups``; compatible
points FROM DIFFERENT CLIENTS coalesce into shared batched dispatches
on the overlapped executor, and results demultiplex back to per-client
futures bit-identically to a direct ``Campaign.run`` of the same
points. Admission is bounded (queue-full is a typed
:class:`QueueFullError`, never a hang), scheduling between tenants is
weighted-fair (stride order over client virtual time), and shutdown
drains in-flight dispatches and leaves PR 8-style content-addressed
checkpoints so an interrupted sweep resumes with zero recomputation.

In-process::

    from repro.service import SweepServer, SweepClient

    with SweepServer() as srv:
        cli = SweepClient(server=srv, name="alice")
        cli.submit(trace, JETSON_NANO, mode="ts", workload="mm")
        records = cli.collect()        # == Campaign.run of the same points

Over a socket (one process owns the warm engine, many attach)::

    PYTHONPATH=src python -m repro.service --port 7421
    ...
    cli = SweepClient(address=("127.0.0.1", 7421), name="bob")

See ``examples/sweep_service.py``.
"""
from repro.service.server import (QueueFullError, ServerClosedError,
                                  ServiceConfig, SweepServer, load_pending)
from repro.service.client import SweepClient

__all__ = ["SweepServer", "SweepClient", "ServiceConfig",
           "QueueFullError", "ServerClosedError", "load_pending"]
