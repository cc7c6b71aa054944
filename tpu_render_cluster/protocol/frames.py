"""Preserialized dispatch frames: the queue-add splice codec.

A ``request_frame-queue_add`` frame is dominated by its ``job`` object —
the full job spec (scene path, output template, distribution strategy,
tile grid, SLO block) repeated verbatim on EVERY dispatch, re-encoded
through ``json.dumps`` each time even though it never changes for the
life of a submission. This module splits the frame along the segment
boundary declared in :mod:`tpu_render_cluster.protocol.schema`
(``FRAME_SEGMENTS``):

- the CONSTANT segment (``job``) is serialized once per (job
  generation, master epoch) and cached — a same-name resubmit is a new
  ``BlenderJob`` *object*, so the cache key is the job's identity, not
  its name: a stale generation's bytes can never leave the master — and
  an epoch bump (ledger failover) re-encodes too;
- the VARYING segment (request id, frame index, and the optional
  trace/job_id/tile/epoch piggybacks) is spliced around it as strings,
  reproducing ``encode_message``'s output BYTE-IDENTICALLY — same key
  order, same ``(",", ":")`` separators, same omitted-when-absent
  optional-key idiom — so workers, the wire-schema lint, and the
  byte-exact wirecost accounting cannot tell the paths apart
  (PROTOCOL.md: the split adds zero bytes on the wire).

``TRC_DISPATCH_FRAMES=encode`` restores the per-send ``encode_message``
path (the baseline the codec is compared with); the default
``cached`` uses this codec. Splices are pure string joins of int
renderings (``str(int)`` is exactly ``json.dumps(int)``) plus one
``json.dumps`` for the ``job_id`` string (escaping).
"""

from __future__ import annotations

import json

from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.utils.env import env_str

__all__ = ["DispatchFrameCache", "frames_cached"]

# Bound on distinct job names one endpoint caches: a long-lived service
# seeing an unbounded stream of unique names must not grow without
# limit; eviction is FIFO (re-dispatches of a live job re-fill in one
# constant-segment encode).
CACHE_CAPACITY = 64

_PREFIX = (
    '{"message_type":"request_frame-queue_add",'
    '"payload":{"message_request_id":'
)


def frames_cached() -> bool:
    """Consulted per send, so tests and A/B benches can flip it live."""
    return (env_str("TRC_DISPATCH_FRAMES", "cached") or "").strip() != "encode"


class DispatchFrameCache:
    """Per-endpoint cache of preserialized ``job`` segments + splicer.

    One instance per ``WorkerHandle`` (caches are cheap; sharing across
    handles would only save re-encoding the same job once per worker).
    ``constant_encodes`` / ``splices`` are test/diagnostic counters: a
    burst of N dispatches of one job generation must show exactly one
    constant encode and N splices.
    """

    def __init__(self) -> None:
        # job_name -> (job object, epoch, serialized job dict). The job
        # OBJECT is the generation key: comparison is by identity, so a
        # resubmitted (new) job under an old name misses and re-encodes,
        # and keeping the reference pinned means CPython cannot recycle
        # the id while the entry lives.
        self._cache: dict[str, tuple[object, int | None, str]] = {}
        self.constant_encodes = 0
        self.splices = 0

    def encode(self, request: "pm.MasterFrameQueueAddRequest") -> str:
        """Byte-identical replacement for ``encode_message(request)``."""
        job = request.job
        entry = self._cache.get(job.job_name)
        if (
            entry is not None
            and entry[0] is job
            and entry[1] == request.epoch
        ):
            job_json = entry[2]
        else:
            job_json = json.dumps(job.to_dict(), separators=(",", ":"))
            self._cache.pop(job.job_name, None)
            while len(self._cache) >= CACHE_CAPACITY:
                self._cache.pop(next(iter(self._cache)))
            self._cache[job.job_name] = (job, request.epoch, job_json)
            self.constant_encodes += 1
        self.splices += 1
        parts = [
            _PREFIX,
            str(request.message_request_id),
            ',"job":',
            job_json,
            ',"frame_index":',
            str(request.frame_index),
        ]
        trace = request.trace
        if trace is not None:
            parts += (
                ',"trace":{"trace_id":',
                str(trace.trace_id),
                ',"span_id":',
                str(trace.span_id),
                "}",
            )
        if request.job_id is not None:
            parts += (',"job_id":', json.dumps(request.job_id))
        if request.tile is not None:
            parts += (',"tile":', str(request.tile))
        if request.epoch is not None:
            parts += (',"epoch":', str(request.epoch))
        parts.append("}}")
        return "".join(parts)
