"""Declared wire schemas for the job protocol — the machine-checked contract.

One :class:`WireSchema` per message tag, stating which payload keys are
REQUIRED (always serialized, reference-compatible shape) and which are
OPTIONAL piggybacks (this repo's beyond-reference extensions). The
``wire-schema`` lint pass (``tpu_render_cluster/lint/wire_schema.py``)
cross-checks three things against this registry on every tier-1 run:

1. ``protocol/messages.py`` — each class's ``to_payload`` must assign
   every required key unconditionally and every optional key ONLY under
   a presence guard (the omitted-when-absent idiom: an absent optional
   key must keep the serialized frame byte-identical to the reference's,
   never appear as ``null`` or a default); ``from_payload`` must read
   required keys strictly and optional keys leniently (``.get``/helper).
2. PROTOCOL.md — the message table must list exactly these tags, and
   every optional key must be mentioned in its tag's row.
3. This registry itself — every ``type_name`` in ``ALL_MESSAGE_TYPES``
   has exactly one schema and vice versa.

The registry is data, deliberately separate from the message classes: a
new key added to a dataclass without a schema update (or vice versa) is
a lint failure, which is the point — the optional-key idiom held across
PRs 3/5/7/11 by convention only.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class WireSchema:
    """Payload contract for one wire tag."""

    tag: str
    direction: str  # "M->W" | "W->M"
    required: tuple[str, ...]
    optional: tuple[str, ...] = ()

    @property
    def keys(self) -> frozenset[str]:
        return frozenset(self.required) | frozenset(self.optional)


@dataclass(frozen=True)
class FrameSegments:
    """Constant/varying payload split for a preserialized wire tag.

    Tags listed in :data:`FRAME_SEGMENTS` may be encoded by a splice
    codec (``protocol/frames.py``) that serializes the CONSTANT keys
    once per cache generation and splices the VARYING keys per message.
    The split is a pure encoding strategy — the wire bytes must remain
    identical to ``encode_message``'s — but it is still contract: the
    two sets must exactly partition the tag's declared keys (required +
    optional), which the ``wire-schema`` lint enforces along with a
    PROTOCOL.md section documenting the split.
    """

    tag: str
    constant: tuple[str, ...]
    varying: tuple[str, ...]


FRAME_SEGMENTS: dict[str, FrameSegments] = {
    segments.tag: segments
    for segments in (
        FrameSegments(
            "request_frame-queue_add",
            constant=("job",),
            varying=(
                "message_request_id",
                "frame_index",
                "trace",
                "job_id",
                "tile",
                "epoch",
            ),
        ),
    )
}


WIRE_SCHEMAS: dict[str, WireSchema] = {
    schema.tag: schema
    for schema in (
        WireSchema(
            "handshake_request",
            "M->W",
            required=("server_version",),
            optional=("epoch",),
        ),
        WireSchema(
            "handshake_response",
            "W->M",
            required=("handshake_type", "worker_version", "worker_id"),
            optional=("prepares_jobs",),
        ),
        WireSchema(
            "handshake_acknowledgement",
            "M->W",
            required=("ok",),
        ),
        WireSchema(
            "request_frame-queue_add",
            "M->W",
            required=("message_request_id", "job", "frame_index"),
            optional=("trace", "job_id", "tile", "epoch"),
        ),
        WireSchema(
            "response_frame-queue-add",
            "W->M",
            required=("message_request_context_id", "result"),
        ),
        WireSchema(
            "request_frame-queue_remove",
            "M->W",
            required=("message_request_id", "job_name", "frame_index"),
            optional=("tile",),
        ),
        WireSchema(
            "response_frame-queue_remove",
            "W->M",
            required=("message_request_context_id", "result"),
        ),
        WireSchema(
            "event_frame-queue_item-started-rendering",
            "W->M",
            required=("job_name", "frame_index"),
            optional=("trace", "job_id", "tile", "epoch"),
        ),
        WireSchema(
            "event_frame-queue_item-finished",
            "W->M",
            required=("job_name", "frame_index", "result"),
            optional=("trace", "job_id", "tile", "epoch"),
        ),
        WireSchema(
            "request_heartbeat",
            "M->W",
            required=("request_time",),
        ),
        WireSchema(
            "response_heartbeat",
            "W->M",
            required=(),
            optional=("metrics", "received_at", "responded_at", "echo_request_time"),
        ),
        WireSchema(
            "event_worker-goodbye",
            "W->M",
            required=("reason", "returned_frames"),
            optional=("job_name", "returned_tiles"),
        ),
        WireSchema(
            "event_job-started",
            "M->W",
            required=(),
            optional=("trace_id", "job_id", "job"),
        ),
        WireSchema(
            "event_job-ready",
            "W->M",
            required=("job_name",),
            optional=("job_id",),
        ),
        WireSchema(
            "request_job-finished",
            "M->W",
            required=("message_request_id",),
        ),
        # -- ledger streaming replication (PROTOCOL.md §Ledger streaming
        # replication): follower <-> primary over the JSON-lines TCP idiom,
        # one envelope per line. Not part of the reference worker protocol
        # — both ends are this repo's — but declared here so the same
        # wire-schema lint guards the contract.
        WireSchema(
            "request_replication-attach",
            "F->P",
            required=("message_request_id", "last_seq"),
            optional=("epoch", "follower_id"),
        ),
        WireSchema(
            "response_replication-attach",
            "P->F",
            required=("message_request_context_id", "epoch", "primary_seq"),
            optional=("snapshot", "error"),
        ),
        WireSchema(
            "event_replication-record",
            "P->F",
            required=("seq", "record"),
        ),
        WireSchema(
            "event_replication-ack",
            "F->P",
            required=("seq",),
        ),
        WireSchema(
            "event_worker-migrate",
            "M->W",
            required=("host", "port"),
            optional=("reason",),
        ),
        WireSchema(
            "response_job-finished",
            "W->M",
            required=("message_request_context_id", "trace"),
            optional=("span_events",),
        ),
    )
}
