"""The job that delivers lossless frames (ISSUE 52; configuration
`04vs-14400f-1w-png`, cell `04vs-1w-png`).

On the CPU at 64x64: the `tpu-raytrace` backend through the worker's own
two-stage loop writes PNG files that decode bit for bit to the pixels the
frame program returned, in render order, one finished event a file and none
before its rename; the same frames as JPEG are the same image to q90's round
trip. On fake stages that sleep, with the save six times the device stage
(the cell's proportions): six or seven frames save at once, a slot is always
free, `save_wait` and the holds read 0 and the device sets the pace. With
the saves, `SAVE_FRAMES` at once, still slower than the device: the loop's
states add up to its wall time with `save_wait` the largest, the frames'
`held` seconds agree with it where one frame is held at a time, at most
`DEVICE_FRAMES + SAVE_FRAMES` units are `rendering`, and a drain and a cancel
in that state lose no frame and leave no temporary file. The series and spans this PR
adds (`worker_frame_pixel_bytes_total`, `worker_frame_file_bytes_total`,
`worker_frame_held_seconds`, the `held` span, `bytes_in` / `bytes_out` on
the save steps' events) are at 0 from the worker's start and pass the trace
validator. And the configuration's same-stream limits, which the
bf16-contraction control fails on every listed crop.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import os
import threading
import time
from pathlib import Path

import numpy as np
import pytest
from PIL import Image

from benchmark.lib import check, manifest
from tpu_render_cluster.obs import CPU_TIMED_STEPS, FILE_WRITE_OPS, FRAME_STEPS, MetricsRegistry, validate_trace_file
from tpu_render_cluster.obs.prometheus import render_prometheus
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.render.image_io import WRITTEN_FORMATS, write_image
from tpu_render_cluster.worker.backends.mock import MockBackend
from tpu_render_cluster.worker.queue import (
    FILE_FORMATS, FrameState, HELD_TRACKS, LOOP_STATES, PROCESS_CPU_MODES, SAVE_FRAMES,
)

from tests.test_frame_pipeline import (
    Driven, IssueAheadBackend, RecordingSender, TwoStageBackend, drive, make_job, most_rendering, render_all, until,
)

FRAMES = 5
SHAPE = dict(width=64, height=64, samples=2, max_bounces=4)


# -- the tpu-raytrace backend's PNG job through the worker's loop ------------------------


class FileSender(RecordingSender):
    """Notes, when a finished event leaves, whether the frame's file is
    there under its final name and whether any temporary file is."""

    def __init__(self, directory: Path, extension: str) -> None:
        super().__init__()
        self.directory, self.extension = directory, extension
        self.at_finish: dict[int, tuple[bool, list[str]]] = {}

    async def send_message(self, message) -> None:
        if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent):
            there = (self.directory / f"rendered-{message.frame_index:05d}{self.extension}").is_file()
            names = sorted(p.name for p in self.directory.iterdir()) if self.directory.is_dir() else []
            self.at_finish[message.frame_index] = (there, names)
        await super().send_message(message)


def render_job(base: Path, file_format: str, extension: str):
    """FRAMES frames of the sphere scene at 64x64 through the queue; what
    each save stage was handed is kept beside."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    class KeepsPixels(TpuRaytraceBackend):
        pixels: dict[int, np.ndarray] = {}

        def _save_stage(self, job, frame_index, tile, pixels, **rendered):
            self.pixels[frame_index] = np.array(pixels)
            return super()._save_stage(job, frame_index, tile, pixels, **rendered)

    backend = KeepsPixels(base_directory=base, **SHAPE)
    backend.pixels = {}
    job = make_job(f"04_very-simple_png-job-{file_format}", FRAMES, file_format=file_format)
    backend._render_sync(job, FRAMES + 1)  # the program is built before the frames that count
    (base / "out" / f"rendered-{FRAMES + 1:05d}{extension}").unlink()
    backend.pixels.clear()
    sender = FileSender(base / "out", extension)

    async def body(driven: Driven) -> None:
        driven.sender = driven.queue._sender = sender
        for frame in range(1, FRAMES + 1):
            driven.queue.queue_frame(job, frame)
        await until(lambda: len(sender.finished()) == FRAMES, 120.0)

    return backend, drive(backend, body), sender


@pytest.fixture(scope="module")
def png_job(tmp_path_factory):
    return render_job(tmp_path_factory.mktemp("png-job"), "PNG", ".png")


@pytest.fixture(scope="module")
def jpeg_job(tmp_path_factory):
    return render_job(tmp_path_factory.mktemp("jpeg-job"), "JPEG", ".jpg")


def test_the_png_files_decode_bit_for_bit_to_the_pixels_the_frame_program_returned(png_job):
    backend, driven, sender = png_job
    directory = backend.base_directory / "out"
    names = [f"rendered-{frame:05d}.png" for frame in range(1, FRAMES + 1)]
    assert sorted(path.name for path in directory.iterdir()) == names  # a file a frame, no temporary file
    for frame, name in enumerate(names, start=1):
        with Image.open(directory / name) as image:
            assert (image.format, image.mode, image.size) == ("PNG", "RGB", (64, 64))
            decoded = np.asarray(image)
        assert decoded.dtype == np.uint8 and np.array_equal(decoded, backend.pixels[frame])
    assert len({backend.pixels[frame].tobytes() for frame in backend.pixels}) == FRAMES  # each frame its own image
    # in render order, one finished event a file, none before its rename, nothing half written beside it
    finished = sender.finished()
    assert [event.frame_index for event in finished] == list(range(1, FRAMES + 1))
    assert all(event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK for event in finished)
    for frame, (there, beside) in sender.at_finish.items():
        assert there, f"finished event of frame {frame} left before its file was in place"
        # the frames so far, whole; what else is there is a later frame's, whole or being written beside it
        assert [name for name in beside if name in names[:frame]] == names[:frame]
        later = set(names[frame:])
        assert all(name in later or name.lstrip(".").split(".png.")[0] + ".png" in later for name in beside if name not in names[:frame])
    assert [trace.frame_index for trace in driven.traces._frame_render_traces] == list(range(1, FRAMES + 1))
    # the bytes are the stated encoder's at its stated level: Pillow's default, zlib level 6
    again = directory.parent / "again.png"
    assert write_image(again, backend.pixels[1], "PNG")[:3] == ("PNG", 64 * 64 * 3, again.stat().st_size)
    assert again.read_bytes() == (directory / names[0]).read_bytes()


def test_the_same_frames_as_jpeg_differ_by_no_more_than_q90s_round_trip(png_job, jpeg_job):
    png_backend, _, _ = png_job
    jpeg_backend, _, sender = jpeg_job
    assert [event.frame_index for event in sender.finished()] == list(range(1, FRAMES + 1))
    for frame in range(1, FRAMES + 1):
        # one image: the two jobs' frame programs returned the same pixels ...
        assert np.array_equal(png_backend.pixels[frame], jpeg_backend.pixels[frame])
        lossless = check.load_rgb(png_backend.base_directory / "out" / f"rendered-{frame:05d}.png")
        lossy = check.load_rgb(jpeg_backend.base_directory / "out" / f"rendered-{frame:05d}.jpg")
        # ... and the JPEG file is that image through q90 and nothing else
        assert np.array_equal(lossy, check.jpeg_round_trip(lossless, 90))
        difference = np.abs(lossy.astype(np.int16) - lossless.astype(np.int16))
        # lossy, and by a few levels (2 spp is noise from pixel to pixel, and q90 keeps chroma at half resolution)
        assert 0 < difference.mean() < 10.0


def test_the_bytes_of_a_jobs_frames_are_counted_and_written_on_its_save_steps(png_job, jpeg_job):
    for (backend, driven, _), image_format, extension in ((png_job, "PNG", ".png"), (jpeg_job, "JPEG", ".jpg")):
        on_disk = sum(path.stat().st_size for path in (backend.base_directory / "out").glob(f"*{extension}"))
        assert driven.counter("worker_frame_pixel_bytes_total") == FRAMES * 64 * 64 * 3
        assert driven.counter("worker_frame_file_bytes_total", format=image_format) == on_disk
        others = set(FILE_FORMATS) - {image_format}
        assert {driven.counter("worker_frame_file_bytes_total", format=other) for other in others} == {0.0}
        steps = [e for e in driven.tracer.events() if e.get("cat") == "worker.step"]
        encodes = [e for e in steps if e["name"] == "encode"]
        assert len(encodes) == FRAMES
        for encode in encodes:
            frame = encode["args"]["frame"]
            size = (backend.base_directory / "out" / f"rendered-{frame:05d}{extension}").stat().st_size
            assert (encode["args"]["bytes_in"], encode["args"]["bytes_out"]) == (64 * 64 * 3, size)
            writes = [e for e in steps if e["name"] == "file_write" and e["args"]["frame"] == frame]
            assert writes and all((w["args"]["bytes_in"], w["args"]["bytes_out"]) == (size, size) for w in writes)
        assert all("bytes_in" not in e["args"] for e in steps if e["name"] not in ("encode", "file_write"))
        # one observation of the hold a frame, whatever it came to
        assert driven.metrics.snapshot()["worker_frame_held_seconds"]["series"][""]["count"] == FRAMES
    png_bytes = png_job[1].counter("worker_frame_file_bytes_total", format="PNG")
    assert png_bytes > jpeg_job[1].counter("worker_frame_file_bytes_total", format="JPEG")


def test_a_jobs_steps_carry_their_cpu_seconds_and_its_file_writes_their_five_operations(png_job, jpeg_job):
    from tpu_render_cluster.obs import validate_trace_document

    for _backend, driven, _ in (png_job, jpeg_job):
        steps = [e for e in driven.tracer.events() if e.get("cat") == "worker.step" and e["name"] in FRAME_STEPS]
        assert len(steps) == FRAMES * 7 and {e["name"] for e in steps} == set(FRAME_STEPS)
        # every stretch of the three steps that have a CPU clock: its thread's CPU seconds, never more than its
        # wall (to the clocks' 1 ms); the other three steps' events carry none
        timed = [e for e in steps if e["name"] in CPU_TIMED_STEPS]
        assert len(timed) == FRAMES * 4 and all("cpu_s" not in e["args"] for e in steps if e not in timed)
        assert all(0.0 <= e["args"]["cpu_s"] <= e["dur"] / 1e6 + 1e-3 for e in timed)
        snapshot = driven.metrics.snapshot()
        assert set(snapshot["worker_frame_step_cpu_seconds_total"]["series"]) == {f"step={name}" for name in CPU_TIMED_STEPS}
        by_step = {name: driven.counter("worker_frame_step_cpu_seconds_total", step=name) for name in CPU_TIMED_STEPS}
        for name in CPU_TIMED_STEPS:
            assert by_step[name] == pytest.approx(sum(e["args"]["cpu_s"] for e in timed if e["name"] == name), abs=1e-4)
        wall = {k.removeprefix("step="): v["sum"] for k, v in snapshot["worker_frame_step_seconds"]["series"].items()}
        assert all(by_step[name] <= wall[name] + FRAMES * 1e-3 for name in CPU_TIMED_STEPS)
        assert by_step["encode"] > 0.0  # the encoder computes
        # the five operations: on the frame's LAST file_write stretch (write_image's own), in ms, adding
        # up to that stretch and to no more; the stretch before `encode` found the path and has none
        by_op = {op: driven.counter("worker_file_write_op_seconds_total", op=op) for op in FILE_WRITE_OPS}
        assert all(seconds > 0.0 for seconds in by_op.values())
        with_ops = [e for e in steps if "rename_ms" in e["args"]]
        assert len(with_ops) == FRAMES and all(e["name"] == "file_write" for e in with_ops)
        assert {e["args"]["frame"] for e in with_ops} == set(range(1, FRAMES + 1))
        for frame in range(1, FRAMES + 1):
            writes = [e for e in steps if e["name"] == "file_write" and e["args"]["frame"] == frame]
            assert [("rename_ms" in e["args"]) for e in sorted(writes, key=lambda e: e["ts"])] == [False, True]
        in_events = sum(e["args"][f"{op}_ms"] for e in with_ops for op in FILE_WRITE_OPS) / 1000.0
        assert in_events == pytest.approx(sum(by_op.values()), abs=FRAMES * 5e-7)
        written = sum(e["dur"] for e in with_ops) / 1e6
        assert sum(by_op.values()) <= written  # edge to edge inside the step
        assert sum(by_op.values()) == pytest.approx(written, rel=0.10)  # and all of it but the clock reads
        assert sum(by_op.values()) <= wall["file_write"]
        # the process's CPU, both modes, brought up to date with every frame: never behind the steps' own
        process = sum(driven.counter("worker_process_cpu_seconds_total", mode=mode) for mode in PROCESS_CPU_MODES)
        assert process >= sum(by_step.values()) - 0.02  # (os.times ticks in hundredths of a second)
        document = {"traceEvents": driven.tracer.metadata_events() + driven.tracer.events()}
        assert validate_trace_document(document) == []


def test_write_image_says_what_it_wrote_and_the_queue_knows_every_format_it_can_say(tmp_path):
    assert set(FILE_FORMATS) == set(WRITTEN_FORMATS) == {"PNG", "JPEG", "BMP", "TIFF"}
    pixels = np.arange(8 * 8 * 3, dtype=np.uint8).reshape(8, 8, 3)
    for file_format, image_format in (("png", "PNG"), ("JPG", "JPEG"), ("jpeg", "JPEG"), ("EXR", "PNG"), ("tiff", "TIFF")):
        path = tmp_path / f"f-{file_format}"
        written = write_image(path, pixels, file_format)
        assert written[:3] == (image_format, 192, path.stat().st_size) and written.image_format in FILE_FORMATS
        with Image.open(path) as image:
            assert image.format == image_format  # the fall-back is a PNG file, and is named as one


# -- the loop with the save slower than the device stage ---------------------------------


class WritesImages:
    """The fake stages' save, as `write_image` saves: the sleep, then a
    real PNG through a temporary file and a rename."""

    def _save(self, frame: int, started: float, rendered: float):
        timing = super()._save(frame, started, rendered)
        (self.directory / f"{frame}.bin").unlink()
        pixels = np.full((8, 8, 3), frame, np.uint8)
        return dataclasses.replace(timing, saved=write_image(self.directory / f"{frame}.png", pixels, "PNG"))


class OneOnDevice(WritesImages, TwoStageBackend):
    pass


class TwoOnDevice(WritesImages, IssueAheadBackend):
    pass


SAVE_BOUND = pytest.mark.parametrize(
    "make_backend,on_device", [(OneOnDevice, 1), (TwoOnDevice, 2)], ids=["one on the device", "two on the device"],
)
# the PNG cell's proportions (save six times the device stage: fewer saves at once than slots), and a
# save that all the slots together cannot keep up with (three device stages a slot)
DEVICE_SECONDS, PNG_SAVE_SECONDS, SAVE_SECONDS = 0.03, 0.18, 0.03 * SAVE_FRAMES * 3
FULL = 2 * SAVE_FRAMES + 4  # frames that fill every slot twice over


def save_bound(make_backend, directory: Path, save_seconds: float = SAVE_SECONDS, **stages):
    return make_backend(directory, dispatch_seconds=0.002, device_seconds=DEVICE_SECONDS, save_seconds=save_seconds, **stages)


def whole_files(directory: Path) -> dict[int, np.ndarray]:
    """Every file there decodes; a temporary file is a failure."""
    files = {}
    for path in directory.iterdir():
        assert path.suffix == ".png", f"left behind: {path.name}"
        files[int(path.stem)] = check.load_rgb(path)
        assert files[int(path.stem)] is not None and (files[int(path.stem)] == int(path.stem)).all()
    return files


@SAVE_BOUND
def test_with_the_save_six_times_the_device_stage_the_saves_overlap_and_the_device_sets_the_pace(
    tmp_path, make_backend, on_device,
):
    """What PR 53 is for: the cell's proportions no longer fill the pipeline."""
    frames = 20
    backend = save_bound(make_backend, tmp_path, PNG_SAVE_SECONDS)
    driven = render_all(backend, frames)
    by_state = {state: driven.counter("worker_loop_seconds_total", state=state) for state in LOOP_STATES}
    assert sum(by_state.values()) == pytest.approx(driven.wall, abs=1e-6)
    # (a slot is always free, or nearly: a loaded machine may keep the loop from its turn for two device stages)
    assert by_state["save_wait"] < 0.1 * driven.wall and max(by_state, key=by_state.get) == "render_call"
    # six device stages a save: five to seven frames saving at once
    assert 4 <= backend.most_at_once("save") <= SAVE_FRAMES
    assert on_device + 4 <= most_rendering(driven) <= on_device + SAVE_FRAMES
    assert driven.counter("worker_frames_saved_beside_save_total") >= frames - 2
    # the frames land at the device's pace, not the save's: one save at a time would take frames x 0.18 s
    landed = backend.times("save_end")
    assert landed[frames] - landed[1] < 0.5 * (frames - 1) * PNG_SAVE_SECONDS
    held = driven.metrics.snapshot()["worker_frame_held_seconds"]["series"][""]
    assert held["count"] == frames and held["sum"] < 0.1 * driven.wall  # no frame's pixels found every slot taken
    assert [event.frame_index for event in driven.sender.finished()] == list(range(1, frames + 1))
    assert sorted(whole_files(tmp_path)) == list(range(1, frames + 1))
    assert driven.counter("worker_frame_pixel_bytes_total") == frames * 8 * 8 * 3


@SAVE_BOUND
def test_with_every_save_slot_taken_the_states_add_up_and_save_wait_is_what_the_saves_leave(
    tmp_path, make_backend, on_device,
):
    frames = FULL
    backend = save_bound(make_backend, tmp_path)
    driven = render_all(backend, frames)
    by_state = {state: driven.counter("worker_loop_seconds_total", state=state) for state in LOOP_STATES}
    assert sum(by_state.values()) == pytest.approx(driven.wall, abs=1e-6)
    # the saves of a round began one device stage apart and end so: the second and the third round wait
    # for the first save of the round before, less the time it took to start that round, and go on at the
    # device's pace (`render_call`) as its slots come free one by one; the last save is `render_call`'s too
    assert by_state["save_wait"] > 2 * (SAVE_SECONDS - (SAVE_FRAMES + 2) * DEVICE_SECONDS) * 0.8
    assert by_state["save_wait"] > 0.25 * driven.wall and by_state["save_wait"] > by_state["report"] + by_state["no_work"]
    # order kept, every file whole, one finished event a file
    assert [event.frame_index for event in driven.sender.finished()] == list(range(1, frames + 1))
    assert sorted(whole_files(tmp_path)) == list(range(1, frames + 1))
    # the device's frames and the ones saving
    assert most_rendering(driven) == on_device + SAVE_FRAMES and backend.on_device() == on_device
    assert backend.most_at_once("save") == SAVE_FRAMES
    # the hold: one observation a frame; the first SAVE_FRAMES frames found a save slot free
    held = driven.metrics.snapshot()["worker_frame_held_seconds"]["series"][""]
    assert held["count"] == frames and held["min"] == 0.0
    if on_device == 1:
        # one frame held at a time: the frames' held seconds are the loop's save_wait seconds, and the
        # hand-over besides (the oldest save taken in, the next frame's dispatch, the save thread's start)
        assert by_state["save_wait"] <= held["sum"] <= by_state["save_wait"] + (frames - SAVE_FRAMES) * 0.05
    else:
        # two at a time: the frame behind the held one is held under it, so up to twice
        assert by_state["save_wait"] <= held["sum"] <= 2 * (by_state["save_wait"] + (frames - SAVE_FRAMES) * 0.05)
    assert held["max"] > 0.5 * SAVE_SECONDS  # a frame that waited out most of a save
    assert driven.counter("worker_frame_pixel_bytes_total") == frames * 8 * 8 * 3
    assert driven.counter("worker_frame_file_bytes_total", format="PNG") == sum(p.stat().st_size for p in tmp_path.iterdir())


@SAVE_BOUND
def test_the_holds_lie_between_render_and_write_on_tracks_of_their_own_and_the_timeline_is_valid(
    tmp_path, make_backend, on_device,
):
    frames = FULL
    driven = render_all(save_bound(make_backend, tmp_path / "frames"), frames)
    assert validate_trace_file(driven.tracer.export(tmp_path / "worker-test_trace-events.json")) == []
    tracks = {m["args"]["name"]: m["tid"] for m in driven.tracer.metadata_events() if m["name"] == "thread_name"}
    events = driven.tracer.events()
    holds = {e["args"]["frame"]: e for e in events if e["name"] == "held"}
    renders = {e["args"]["frame"]: e for e in events if e["name"] == "render"}
    writes = {e["args"]["frame"]: e for e in events if e["name"] == "write"}
    # no frame of the first SAVE_FRAMES found every slot taken; the first frames of each later round did,
    # and waited out most of a save (the frames behind them find a slot as their pixels arrive, or nearly)
    assert set(holds) <= set(range(SAVE_FRAMES + 1, frames + 1))
    waited_a_save = {frame for frame, hold in holds.items() if hold["dur"] > 0.25e6 * SAVE_SECONDS}
    assert waited_a_save >= {first + n for first in (SAVE_FRAMES + 1, 2 * SAVE_FRAMES + 1) for n in range(on_device)}
    assert {e["tid"] for e in holds.values()} <= {tracks[name] for name in HELD_TRACKS}
    assert all(e["cat"] == "worker.step" for e in holds.values())
    for frame, hold in holds.items():
        # from the end of the frame's render to the start of its write, to the clocks' rounding
        assert abs(hold["ts"] - (renders[frame]["ts"] + renders[frame]["dur"])) < 2000.0
        assert abs(hold["ts"] + hold["dur"] - writes[frame]["ts"]) < 2000.0
        # it takes the slot of the frame SAVE_FRAMES ahead of it, and was held until that frame was taken in
        ahead = writes[frame - SAVE_FRAMES]
        assert writes[frame]["tid"] == ahead["tid"]
        assert ahead["ts"] + ahead["dur"] <= hold["ts"] + hold["dur"] + 2000.0
        if frame in waited_a_save:
            # under that frame's write: why a hold cannot lie on a save slot's track
            assert hold["ts"] < ahead["ts"] + ahead["dur"]
    for tid in {e["tid"] for e in holds.values()}:
        on_track = sorted((e["ts"], e["ts"] + e["dur"]) for e in holds.values() if e["tid"] == tid)
        assert all(later[0] >= earlier[1] - 20000.0 for earlier, later in zip(on_track, on_track[1:]))
    if on_device == 2:
        in_time = sorted((e["ts"], e["ts"] + e["dur"]) for e in holds.values())
        assert any(later[0] < earlier[1] for earlier, later in zip(in_time, in_time[1:]))  # two held at a time
        assert len({e["tid"] for e in holds.values()}) == 2


async def until_save_wait(driven: Driven) -> None:
    await until(lambda: driven.queue._loop_state == "save_wait")


@SAVE_BOUND
def test_a_drain_in_save_wait_loses_no_frame_and_leaves_no_temporary_file(tmp_path, make_backend, on_device):
    backend = save_bound(make_backend, tmp_path)
    job = make_job("drained-full", FULL)
    returned = []

    async def body(driven: Driven) -> None:
        for frame in range(1, FULL + 1):
            driven.queue.queue_frame(job, frame)
        await until_save_wait(driven)  # every slot taken and a frame's pixels in hand
        in_hand = [f.frame_index for f in driven.queue._frames if f.state is FrameState.RENDERING]
        assert len(in_hand) == on_device + SAVE_FRAMES
        returned.extend(await driven.queue.drain())
        assert [event.frame_index for event in driven.sender.finished()] == list(range(1, in_hand[-1] + 1))

    driven = drive(backend, body)
    finished = [event.frame_index for event in driven.sender.finished()]
    handed_back = [unit.frame_index for _, unit in returned]
    assert all(event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK for event in driven.sender.finished())
    assert finished + handed_back == list(range(1, FULL + 1))  # every frame finished or handed back, in order, none twice
    assert sorted(whole_files(tmp_path)) == finished  # a file a finished frame, every one whole, nothing else


@SAVE_BOUND
def test_a_cancel_in_save_wait_loses_no_frame_and_leaves_no_temporary_file(tmp_path, make_backend, on_device):
    backend = save_bound(make_backend, tmp_path)
    job = make_job("cancelled-full", FULL)
    left = {}

    async def body(driven: Driven) -> None:
        for frame in range(1, FULL + 1):
            driven.queue.queue_frame(job, frame)
        await until_save_wait(driven)
        left["frames"] = driven.queue._frames  # drive() joins the queue now: the loop's task is cancelled

    before = set(threading.enumerate())
    driven = drive(backend, body)
    deadline = time.perf_counter() + 5.0
    while [t for t in threading.enumerate() if t not in before and t.name.startswith("frame-")]:
        assert time.perf_counter() < deadline, "a stage's thread is still blocked"
        time.sleep(0.01)
    finished = [event.frame_index for event in driven.sender.finished()]
    assert finished == list(range(1, len(finished) + 1))
    # every frame is either finished or still the queue's (queued, or cut in a stage): none is gone
    still_held = [f.frame_index for f in left["frames"]]
    assert finished + still_held == list(range(1, FULL + 1))
    # the saves under way ended on their own, each with its rename: whole files, those of the finished
    # frames and of at most the SAVE_FRAMES frames that were saving, and no temporary file
    files = sorted(whole_files(tmp_path))
    assert set(finished) <= set(files) and len(finished) + 1 <= len(files) <= len(finished) + SAVE_FRAMES
    assert all(event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK for event in driven.sender.finished())


def test_the_new_series_are_exposed_at_zero_from_the_workers_start():
    async def body(driven: Driven) -> None:
        await asyncio.sleep(0)

    driven = drive(MockBackend(), body)
    snapshot = driven.metrics.snapshot()
    assert snapshot["worker_frame_pixel_bytes_total"]["series"] == {"": 0.0}
    assert snapshot["worker_frame_file_bytes_total"]["series"] == {f"format={name}": 0.0 for name in FILE_FORMATS}
    held = snapshot["worker_frame_held_seconds"]["series"][""]
    assert (held["count"], held["sum"], held["min"], held["max"]) == (0, 0.0, None, None)
    text = render_prometheus(snapshot)
    assert "worker_frame_pixel_bytes_total 0" in text
    assert 'worker_frame_file_bytes_total{format="PNG"} 0' in text and 'worker_frame_file_bytes_total{format="JPEG"} 0' in text
    assert "worker_frame_held_seconds_count 0" in text and "worker_frame_held_seconds_sum 0" in text
    # PR 54's three counter families with every label value, and the gauge, from the worker's start
    assert snapshot["worker_frame_step_cpu_seconds_total"]["series"] == {f"step={name}": 0.0 for name in CPU_TIMED_STEPS}
    assert snapshot["worker_file_write_op_seconds_total"]["series"] == {f"op={op}": 0.0 for op in FILE_WRITE_OPS}
    assert FILE_WRITE_OPS == ("mkdir", "create", "write", "close", "rename")
    process_cpu = snapshot["worker_process_cpu_seconds_total"]["series"]
    assert set(process_cpu) == {"mode=user", "mode=system"} == {f"mode={mode}" for mode in PROCESS_CPU_MODES}
    # (the process's CPU since ITS start, as the master's counter reads: this test process has used some)
    assert process_cpu["mode=user"] > 0.0 and process_cpu["mode=system"] >= 0.0
    assert snapshot["worker_host_cpu_units"]["series"] == {"": float(len(os.sched_getaffinity(0)))}
    for line in ('worker_frame_step_cpu_seconds_total{step="device_wait"} 0', 'worker_file_write_op_seconds_total{op="rename"} 0',
                 'worker_process_cpu_seconds_total{mode="system"} ', "worker_host_cpu_units "):
        assert line in text, line
    # a backend that writes no image itself feeds the hold and leaves the bytes at 0
    driven = render_all(MockBackend(load_seconds=0.001, render_seconds=0.005, save_seconds=0.001), 3)
    assert driven.metrics.snapshot()["worker_frame_held_seconds"]["series"][""]["count"] == 3
    assert driven.counter("worker_frame_pixel_bytes_total") == 0.0


def test_a_histogram_exposed_before_its_first_observation_merges_and_observes_as_any_other():
    from tpu_render_cluster.obs import merge_wire

    registry = MetricsRegistry()
    histogram = registry.histogram("some_seconds", "a duration", labels=("kind",))
    histogram.expose(kind="a")
    histogram.expose(kind="a")  # again: nothing is reset
    wire = registry.to_wire()
    assert wire["h"]["some_seconds|kind=a"]["n"] == 0 and wire["h"]["some_seconds|kind=a"]["min"] is None
    histogram.observe(0.25, kind="a")
    histogram.expose(kind="a")
    merged = merge_wire([wire, registry.to_wire()])["h"]["some_seconds|kind=a"]
    assert (merged["n"], merged["s"], merged["min"], merged["max"]) == (1, 0.25, 0.25, 0.25)


# -- the configuration's limits against its control --------------------------------------


@pytest.fixture
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("TRC_PALLAS", "1")


@pytest.mark.time_limit(600)
def test_the_bf16_contraction_control_fails_the_same_stream_limits_on_every_listed_crop(interpreted_kernels, monkeypatch):
    """The program's own kernels in the interpreter, float32, against the
    same kernels with their contractions in one ROUNDED bf16 pass (the
    nearest precision below the one the configuration states), at the
    cell's real shape on its four crops: the sound side is the reference
    itself (agreement 1.0, what a chip that computes in float32 comes
    near: PERF.md §4 has its readings), and the control has to fail by the
    configuration's own `max_levels` and `min_share`, with room."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator, pallas_kernels

    cell = manifest.load_cell("04vs-1w-png")
    same, shape = cell.config["check"]["same_stream"], cell.config["render"]
    assert cell.config["output"]["file_format"] == "PNG"  # so check_images hands no codec to the comparison
    frame = check.checked_frames(1, cell.config["frames"], cell.config["check"]["frames"])[0]

    def crops() -> list[np.ndarray]:
        integrator.fused_region_renderer.cache_clear()
        jax.clear_caches()
        with jax.default_matmul_precision("highest"):
            return [
                np.asarray(integrator.tonemap(integrator.render_frame_region(
                    "04_very-simple", frame, y0=y0, x0=x0, tile_height=same["crop"], tile_width=same["crop"],
                    width=shape["width"], height=shape["height"], samples=shape["samples"], max_bounces=shape["max_bounces"],
                )))
                for y0, x0 in same["crops"]
            ]

    def rounded_parts(x):
        hi = x.astype(jnp.bfloat16)
        return hi, jnp.zeros_like(hi), jnp.zeros_like(hi)

    agreement = functools.partial(
        check.same_stream_agreement, y0=0, x0=0, border=same["border"], max_levels=same["max_levels"], quality=None,
    )
    sound = crops()
    monkeypatch.setattr(pallas_kernels, "_bf16_parts", rounded_parts)
    try:
        control = crops()
    finally:
        monkeypatch.undo()
        integrator.fused_region_renderer.cache_clear()
        jax.clear_caches()
    for ours, theirs in zip(sound, control):
        assert agreement(ours, ours) == 1.0
        share = agreement(theirs, ours)
        assert share < same["min_share"] - 0.25, f"the control agrees on {share:.3f} within {same['max_levels']} levels"
