"""End-to-end observability: request tracing (trace ids, spans,
/debug/traces), the incident plane's flight recorder + bundler
(incident.py), the master-side SLO burn-rate engine (slo.py),
on-demand device profiling (profile.py), the per-workload device-time
ledger (devledger.py), the flight timeline (timeline.py), and the
tail-latency forensics plane — cross-node trace assembly + critical-path
attribution (critpath.py) over tail-pinned full span trees
(tailstore.py)."""
from . import critpath, devledger, incident, profile, slo, tailstore, timeline
from .config import ObsConfig
from .critpath import critpath_handler
from .devledger import DeviceLedger, LEDGER
from .incident import IncidentBundler, IncidentConfig
from .tailstore import TailStore, tail_handler
from .timeline import TimelineSampler
from .profile import device_hot_handler, profile_handler
from .slo import SloConfig, SloEngine
from .trace import (
    GRPC_TRACE_KEY,
    RING,
    TRACE_HEADER,
    Trace,
    await_span,
    configure,
    current,
    detached,
    event,
    finish_trace,
    grpc_metadata,
    interval,
    middleware,
    outbound_headers,
    parse_trace_header,
    record_span,
    response_prepare_signal,
    span,
    stage_sink,
    stamp_trace_header,
    start_trace,
    traces_handler,
)

__all__ = [
    "DeviceLedger",
    "GRPC_TRACE_KEY",
    "IncidentBundler",
    "IncidentConfig",
    "LEDGER",
    "ObsConfig",
    "RING",
    "SloConfig",
    "SloEngine",
    "TailStore",
    "TimelineSampler",
    "critpath",
    "critpath_handler",
    "device_hot_handler",
    "devledger",
    "incident",
    "profile",
    "profile_handler",
    "slo",
    "tail_handler",
    "tailstore",
    "timeline",
    "TRACE_HEADER",
    "Trace",
    "await_span",
    "configure",
    "current",
    "detached",
    "event",
    "finish_trace",
    "grpc_metadata",
    "interval",
    "middleware",
    "outbound_headers",
    "parse_trace_header",
    "record_span",
    "response_prepare_signal",
    "span",
    "stage_sink",
    "stamp_trace_header",
    "start_trace",
    "traces_handler",
]
