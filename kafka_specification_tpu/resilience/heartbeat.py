"""Shared JSONL heartbeat envelope.

Every record written by the observability/liveness streams — the engines'
per-level stats lines and the supervisor's own event log — carries the
same envelope so one consumer
(the supervisor's stall detector, or a human with `tail -f | jq`) can read
any of them:

    {"kind": "<stream>", "ts": "<UTC ISO-8601>", "unix": <float seconds>, ...}

`kind` values in use: "level" (engine per-level stats), "supervisor"
(resilient_run events), "service" (daemon events).  Stream-specific fields
ride alongside.

Must stay jax-free: imported by parents that never touch the accelerator.
"""

from __future__ import annotations

import json
import time

from .. import durable_io as _dio
from ..utils import clock as _clk


def heartbeat_record(kind: str, t: float = None, **fields) -> dict:
    """Envelope a record; `t` overrides the stamped time (e.g. a consumer
    that needs event-START semantics stamps the start, not now).  The
    default stamp comes from the injected clock (utils/clock.py), so a
    simulated daemon's liveness trail carries virtual time."""
    if t is None:
        t = _clk.now()
    return {
        "kind": kind,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t)),
        "unix": round(t, 3),
        **fields,
    }


def append_jsonl(path: str, record: dict) -> None:
    # routed through the durable-io leaf so the crashcheck harness sees
    # heartbeat emits in its op-traces (same buffered-append semantics)
    _dio.append_text(path, json.dumps(record) + "\n")
