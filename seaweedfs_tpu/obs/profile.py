"""On-demand device profiling for a LIVE volume server.

Production fleets answer "which kernel was the device actually spending
its time in" with always-on profilers (Google-Wide Profiling); this is
the on-demand analogue for the serving path:

  * `GET /debug/profile?seconds=N` wraps `jax.profiler` start/stop
    around whatever the serving loop dispatches for N seconds and
    returns the trace directory (open it with any XPlane viewer).
    SWFS_DEBUG-gated like /debug/stacks — a profile capture reveals
    internals and costs device attention, so it is opt-in only.  One
    capture at a time; concurrent requests get 409.  The capture holds
    the device's programs and operations AND the program's own spans
    (obs/trace.py) on one clock: for its length every `obs.span` is
    also a `TraceAnnotation` on the thread that did the work, and every
    section that spans awaits a `<name>:begin` / `<name>:end` pair of
    instant events with one `id`, on `/host:CPU` of the same
    `.xplane.pb` as `/device:TPU:<n>`.  JAX's Python tracer is off
    (every Python call as an event: millions of them in seconds, a
    server at half speed, and nobody reads them); the named spans are
    what the host side of a capture is for.
  * `GET /debug/device/hot` is the zero-cost half: rs_resident keeps a
    per-call-shape dispatch counter + a latency EWMA per `_call_key`
    (see ops/rs_resident.hot_shapes), so "what shape is hot right now"
    is one HTTP fetch — `volume.device.status -hot` in the shell.

The incident bundler (obs/incident.py) calls /debug/profile
automatically when a LATENCY SLO burns and -obs.incident.profileSeconds
is set, so the bundle carries a capture of the device during the burn.
"""
from __future__ import annotations

import asyncio
import itertools
import logging
import os
import threading
import time

from . import trace as obs_trace

log = logging.getLogger("obs")

# hard cap on one capture's length: /debug/profile holds device
# attention and buffers trace events in memory for the duration
MAX_PROFILE_SECONDS = 30.0

# capture directories kept on disk, oldest deleted first — the same
# "a flapping SLO can't fill the disk" cap the incident bundles have:
# the bundler triggers a capture per rate-limit interval indefinitely
# while a latency SLO flaps, and one XPlane dump can be tens of MB
KEEP_PROFILE_DIRS = 8

# single-flight: jax.profiler supports one active trace per process
_PROFILE_BUSY = threading.Lock()

# what joins a `:begin` to its `:end`; never reused, so that a section
# begun in one capture and ended in the next pairs with nothing there
_PAIR_IDS = itertools.count(1)


def _timeline_hook():
    """What obs.trace.TIMELINE is for the length of one capture:
    (name, annotations, paired) -> a callable that ends what it began."""
    from jax.profiler import TraceAnnotation

    def instant(name: str, annotations: dict) -> None:
        with TraceAnnotation(name, **annotations):
            pass

    def begin(name: str, annotations: dict, paired: bool):
        if not paired:
            event = TraceAnnotation(name, **annotations)
            event.__enter__()
            return lambda: event.__exit__(None, None, None)
        pair = next(_PAIR_IDS)
        instant(name + ":begin", {**annotations, "id": pair})
        return lambda: instant(name + ":end", {"id": pair})

    return begin


def _new_profile_dir() -> str:
    """Create this capture's directory and prune old siblings past
    KEEP_PROFILE_DIRS (runs on a worker thread).  All captures live
    under one stable parent so the cap can see them."""
    import shutil
    import tempfile

    parent = os.path.join(tempfile.gettempdir(), "swfs_device_profiles")
    os.makedirs(parent, exist_ok=True)
    d = tempfile.mkdtemp(prefix="capture_", dir=parent)
    siblings = sorted(
        (e for e in os.scandir(parent) if e.is_dir()),
        key=lambda e: e.stat().st_mtime,
    )
    for e in siblings[: max(0, len(siblings) - KEEP_PROFILE_DIRS)]:
        shutil.rmtree(e.path, ignore_errors=True)
    return d


async def profile_handler(request):
    """aiohttp GET /debug/profile?seconds=N: capture a device profile of
    the live serving loop for N seconds (default 2, capped at 30) and
    return the trace directory + the hot-shape snapshot taken at stop
    time.  503 when jax profiling is unavailable, 409 when a capture is
    already running."""
    from aiohttp import web

    import math

    try:
        seconds = float(request.query.get("seconds", 2.0))
    except ValueError:
        raise web.HTTPBadRequest(text="seconds must be numeric")
    if not math.isfinite(seconds) or seconds <= 0:
        # nan sails past `<= 0` AND survives min() — it would reach
        # asyncio.sleep(nan) with the single-flight lock held
        raise web.HTTPBadRequest(text="seconds must be finite > 0")
    seconds = min(seconds, MAX_PROFILE_SECONDS)
    if not _PROFILE_BUSY.acquire(blocking=False):
        raise web.HTTPConflict(text="a profile capture is already running")
    try:
        trace_dir = await asyncio.to_thread(_new_profile_dir)
        t0 = time.time()
        try:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            obs_trace.TIMELINE = _timeline_hook()
            # start/stop around a plain sleep: the serving loop keeps
            # dispatching on its own threads, and the profiler captures
            # every device computation in the window — exactly the
            # "what was the device doing while the SLO burned" view
            await asyncio.to_thread(
                jax.profiler.start_trace, trace_dir,
                profiler_options=options,
            )
            try:
                await asyncio.sleep(seconds)
            finally:
                await asyncio.to_thread(jax.profiler.stop_trace)
        except Exception as e:  # noqa: BLE001 — no jax / no device /
            # profiler unsupported on this backend: report, don't 500
            log.warning("device profile capture failed: %s", e)
            raise web.HTTPServiceUnavailable(
                text=f"device profiling unavailable: {e}"
            )
        return web.json_response(
            {
                "trace_dir": trace_dir,
                "seconds": seconds,
                "started_unix_ms": int(t0 * 1e3),
                "hot_shapes": _hot_snapshot(),
            }
        )
    finally:
        obs_trace.TIMELINE = None
        _PROFILE_BUSY.release()


def _hot_snapshot(limit: int = 10) -> list[dict]:
    from ..ops import rs_resident

    return rs_resident.hot_shapes(limit)


async def device_hot_handler(request):
    """aiohttp GET /debug/device/hot?limit=N: the per-call-shape
    dispatch counters + latency EWMAs (ops/rs_resident), hottest first
    — the `volume.device.status -hot` view."""
    from aiohttp import web

    from ..ops import rs_resident

    try:
        limit = int(request.query.get("limit", 10))
    except ValueError:
        raise web.HTTPBadRequest(text="limit must be an integer")
    if limit < 1:
        raise web.HTTPBadRequest(text="limit must be >= 1")
    return web.json_response(
        {
            "generated_unix_ms": int(time.time() * 1e3),
            "shapes": rs_resident.hot_shapes(limit),
            "aot": rs_resident.aot_stats(),
        }
    )
