"""Protocol encode/decode round-trips and wire-tag golden checks.

The wire tags and payload shapes are the reference's observable contract
(reference: shared/src/messages/mod.rs:150-209); the golden strings here are
hand-written from that table, not generated.
"""

import json

import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy, DynamicStrategyOptions
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.traces.worker_trace import (
    FrameRenderTime,
    WorkerFrameTrace,
    WorkerPingTrace,
    WorkerReconnectionTrace,
    WorkerTrace,
)


def make_job(strategy: DistributionStrategy | None = None) -> BlenderJob:
    return BlenderJob(
        job_name="04_very-simple_test",
        job_description="test job",
        project_file_path="%BASE%/blender-projects/04_very-simple/04_very-simple.blend",
        render_script_path="%BASE%/scripts/render-timing-script.py",
        frame_range_from=1,
        frame_range_to=10,
        wait_for_number_of_workers=2,
        frame_distribution_strategy=strategy or DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/results/frames",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )


def make_trace() -> WorkerTrace:
    frame_time = FrameRenderTime(
        started_process_at=1000.0,
        finished_loading_at=1001.5,
        started_rendering_at=1001.6,
        finished_rendering_at=1005.0,
        file_saving_started_at=1005.0,
        file_saving_finished_at=1005.5,
        exited_process_at=1006.0,
    )
    return WorkerTrace(
        total_queued_frames=3,
        total_queued_frames_removed_from_queue=1,
        job_start_time=999.0,
        job_finish_time=1010.0,
        frame_render_traces=[WorkerFrameTrace(1, frame_time)],
        ping_traces=[WorkerPingTrace(1002.0, 1002.001)],
        reconnection_traces=[WorkerReconnectionTrace(1003.0, 1004.0)],
    )


EXPECTED_WIRE_TAGS = {
    pm.MasterHandshakeRequest: "handshake_request",
    pm.WorkerHandshakeResponse: "handshake_response",
    pm.MasterHandshakeAcknowledgement: "handshake_acknowledgement",
    pm.MasterFrameQueueAddRequest: "request_frame-queue_add",
    pm.WorkerFrameQueueAddResponse: "response_frame-queue-add",
    pm.MasterFrameQueueRemoveRequest: "request_frame-queue_remove",
    pm.WorkerFrameQueueRemoveResponse: "response_frame-queue_remove",
    pm.WorkerFrameQueueItemRenderingEvent: "event_frame-queue_item-started-rendering",
    pm.WorkerFrameQueueItemFinishedEvent: "event_frame-queue_item-finished",
    pm.MasterHeartbeatRequest: "request_heartbeat",
    pm.WorkerHeartbeatResponse: "response_heartbeat",
    pm.MasterJobStartedEvent: "event_job-started",
    pm.MasterJobFinishedRequest: "request_job-finished",
    pm.WorkerJobFinishedResponse: "response_job-finished",
    # Beyond-reference extension (graceful drain); C++ peers may ignore it.
    pm.WorkerGoodbyeEvent: "event_worker-goodbye",
    # Beyond-reference extensions: ledger streaming replication (never on
    # the worker wire) and the rebalancer's re-home event.
    pm.ReplicationAttachRequest: "request_replication-attach",
    pm.ReplicationAttachResponse: "response_replication-attach",
    pm.ReplicationRecordEvent: "event_replication-record",
    pm.ReplicationAckEvent: "event_replication-ack",
    pm.MasterWorkerMigrateEvent: "event_worker-migrate",
    pm.WorkerJobReadyEvent: "event_job-ready",
}


def test_all_wire_tags_exact():
    # The reference's 14 messages plus the goodbye drain extension, the
    # four replication messages, the migrate event and the job-ready event.
    assert len(pm.ALL_MESSAGE_TYPES) == 21
    for cls, tag in EXPECTED_WIRE_TAGS.items():
        assert cls.type_name == tag


def all_example_messages() -> list[pm.Message]:
    job = make_job()
    return [
        pm.MasterHandshakeRequest("1.0.0"),
        pm.WorkerHandshakeResponse("first-connection", "1.0.0", 0xDEADBEEF),
        pm.WorkerHandshakeResponse("reconnecting", "1.0.0", 7),
        pm.MasterHandshakeAcknowledgement(True),
        pm.MasterFrameQueueAddRequest(42, job, 5),
        pm.WorkerFrameQueueAddResponse.new_ok(42),
        pm.WorkerFrameQueueAddResponse.new_errored(42, "boom"),
        pm.MasterFrameQueueRemoveRequest(43, job.job_name, 5),
        pm.WorkerFrameQueueRemoveResponse.new_with_result(
            43, pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_RENDERING
        ),
        pm.WorkerFrameQueueItemRenderingEvent(job.job_name, 5),
        pm.WorkerFrameQueueItemFinishedEvent.new_ok(job.job_name, 5),
        pm.WorkerFrameQueueItemFinishedEvent.new_errored(job.job_name, 5, "render failed"),
        pm.MasterHeartbeatRequest(1234.5),
        pm.WorkerHeartbeatResponse(),
        pm.WorkerHeartbeatResponse(
            received_at=1234.6, responded_at=1234.7, echo_request_time=1234.5
        ),
        pm.WorkerGoodbyeEvent(),
        pm.WorkerGoodbyeEvent(
            reason="drain", job_name=job.job_name, returned_frames=(3, 4, 9)
        ),
        pm.MasterJobStartedEvent(),
        pm.MasterJobStartedEvent(trace_id=3, job_id="job-0001", job=job),
        pm.WorkerJobReadyEvent(job.job_name),
        pm.WorkerJobReadyEvent(job.job_name, job_id="job-0001"),
        pm.WorkerHandshakeResponse("first-connection", "1.0.0", 9, prepares_jobs=True),
        pm.MasterJobFinishedRequest(99),
        pm.WorkerJobFinishedResponse(99, make_trace()),
        pm.ReplicationAttachRequest(7, last_seq=0),
        pm.ReplicationAttachRequest(8, last_seq=41, epoch=3, follower_id="f-1"),
        pm.ReplicationAttachResponse(7, epoch=3, primary_seq=41),
        pm.ReplicationAttachResponse(
            8, epoch=3, primary_seq=41, snapshot={"v": 1, "seq": 40}
        ),
        pm.ReplicationAttachResponse(
            9, epoch=2, primary_seq=41, error="primary is deposed"
        ),
        pm.ReplicationRecordEvent(42, {"v": 1, "seq": 42, "type": "unit_finished"}),
        pm.ReplicationAckEvent(42),
        pm.MasterWorkerMigrateEvent("10.0.0.2", 9911),
        pm.MasterWorkerMigrateEvent("10.0.0.2", 9911, reason="rebalance"),
    ]


@pytest.mark.parametrize("message", all_example_messages(), ids=lambda m: type(m).__name__)
def test_round_trip(message):
    encoded = pm.encode_message(message)
    decoded = pm.decode_message(encoded)
    assert decoded == message


def test_envelope_shape():
    encoded = json.loads(pm.encode_message(pm.MasterHeartbeatRequest(12.25)))
    assert encoded == {
        "message_type": "request_heartbeat",
        "payload": {"request_time": 12.25},
    }


def test_result_enum_wire_format():
    # Internally-tagged result enums: {"result": "...", "reason": "..."} for errors.
    encoded = json.loads(pm.encode_message(pm.WorkerFrameQueueAddResponse.new_errored(7, "x")))
    assert encoded["payload"]["result"] == {"result": "errored", "reason": "x"}
    encoded = json.loads(pm.encode_message(pm.WorkerFrameQueueAddResponse.new_ok(7)))
    assert encoded["payload"]["result"] == {"result": "added-to-queue"}


def test_handshake_golden():
    golden = '{"message_type":"handshake_acknowledgement","payload":{"ok":true}}'
    assert pm.decode_message(golden) == pm.MasterHandshakeAcknowledgement(True)


def test_strategy_wire_format():
    strategy = DistributionStrategy.dynamic_strategy(
        DynamicStrategyOptions(4, 2, 40, 80)
    )
    assert strategy.to_dict() == {
        "strategy_type": "dynamic",
        "target_queue_size": 4,
        "min_queue_size_to_steal": 2,
        "min_seconds_before_resteal_to_elsewhere": 40,
        "min_seconds_before_resteal_to_original_worker": 80,
    }
    assert DistributionStrategy.from_dict(strategy.to_dict()) == strategy


def test_worker_id_display():
    assert pm.worker_id_to_string(0xDEADBEEF) == "deadbeef"
    assert pm.worker_id_to_string(7) == "00000007"


def test_unknown_message_type_rejected():
    with pytest.raises(ValueError):
        pm.decode_message('{"message_type": "nope", "payload": {}}')
