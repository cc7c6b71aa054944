"""`correct` turns false on a perturbed image, a duplicate frame and a
missing file."""

import numpy as np
import pytest
from PIL import Image

from benchmark.lib import check


def noisy_image(seed, size=64):
    rng = np.random.default_rng(seed)
    ramp = np.linspace(40, 200, size)[None, :, None] + np.zeros((size, size, 3))
    return np.clip(ramp + rng.normal(0, 12, (size, size, 3)), 0, 255).astype(np.uint8)


def write_frame(directory, number, pixels, stem="rendered"):
    path = directory / f"{stem}-{number:06d}.jpg"
    Image.fromarray(pixels).save(path, "JPEG", quality=90)
    return path


def test_whole_files_pass(tmp_path):
    paths = [write_frame(tmp_path, n, noisy_image(n)) for n in (5, 6, 7)]
    assert check.check_files(paths, width=64, height=64, first_frame=1, last_frame=10) == (0, [])


@pytest.mark.parametrize("fault", ["missing", "duplicate", "shape", "flat", "range", "truncated"])
def test_each_fault_is_counted(tmp_path, fault):
    paths = [write_frame(tmp_path, n, noisy_image(n)) for n in (5, 6, 7)]
    if fault == "missing":
        paths[1].unlink()
    elif fault == "duplicate":
        paths.append(write_frame(tmp_path, 6, noisy_image(9), stem="again"))
    elif fault == "shape":
        paths[1] = write_frame(tmp_path, 6, noisy_image(6, size=32))
    elif fault == "flat":
        paths[1] = write_frame(tmp_path, 6, np.full((64, 64, 3), 90, np.uint8))
    elif fault == "range":
        paths.append(write_frame(tmp_path, 11, noisy_image(11)))
    elif fault == "truncated":
        paths[1].write_bytes(paths[1].read_bytes()[:200])
    bad, problems = check.check_files(paths, width=64, height=64, first_frame=1, last_frame=10)
    assert bad == 1 and len(problems) == 1


def test_same_stream_agreement_drops_on_a_perturbed_image():
    reference = noisy_image(1, size=96)
    full = np.zeros((128, 128, 3), np.uint8)
    full[16:112, 32:128] = reference
    served = check.jpeg_round_trip(full, 90)
    kwargs = dict(y0=16, x0=32, border=16, max_levels=8, quality=90)
    assert check.same_stream_agreement(served, reference, **kwargs) >= 0.99
    perturbed = np.clip(served.astype(int) + np.random.default_rng(2).normal(0, 14, served.shape), 0, 255)
    assert check.same_stream_agreement(perturbed.astype(np.uint8), reference, **kwargs) < 0.9


def test_independent_agreement_holds_within_the_replicas_spread_only():
    rng = np.random.default_rng(3)
    truth = np.linspace(60, 180, 64)[None, :, None] + np.zeros((64, 64, 3))
    replicas = truth[None] + rng.normal(0, 20, (16, 64, 64, 3))
    served = truth + rng.normal(0, 20, (64, 64, 3))
    kwargs = dict(block=16, sigmas=5.0, abs_levels=2.5)
    assert check.independent_agreement(served, replicas, **kwargs)[0]
    darker = served.copy()
    darker[16:32, 16:32] -= 15  # one block darker, as a dropped light term would make it
    ok, excess = check.independent_agreement(darker, replicas, **kwargs)
    assert not ok and excess > 0


def test_the_seed_mix_is_fixed():
    assert check.mix(0) == 0xE220A8397B1DCDAF  # splitmix64's first output


def test_what_is_checked_follows_from_the_seed_and_the_first_frame_alone():
    spec = {"after": 8, "count": 2, "step": 4, "quantum": 16}
    assert check.checked_frames(288, 480, spec) == check.checked_frames(296, 480, spec) == [304, 308]
    assert check.checked_frames(297, 480, spec) == check.checked_frames(303, 480, spec) == [320, 324]
    assert check.checked_frames(470, 480, spec) == [480]
    crops = [[272, 208], [224, 288], [288, 48]]
    picked = {check.pick_crop(crops, seed, 0, width=512, height=512, crop=96) for seed in range(40)}
    assert picked == {tuple(c) for c in crops}
    # a rehearsal renders 64x64: the crop is the frame
    assert check.pick_crop(crops, 3, 0, width=64, height=64, crop=64) == (0, 0)


def test_references_come_from_the_cache_the_second_time(tmp_path, monkeypatch):
    calls = []

    def fake_child(request, out_path, env):
        calls.append(request["frames"])
        return {f"frame_{f}": np.full((8, 8, 3), f % 251, np.uint8) for f in request["frames"]}

    monkeypatch.setattr(check, "_run_region_child", fake_child)
    first = check._reference_crops(tmp_path, "job", [304, 308], 0, 0, 8, {}, {})
    again = check._reference_crops(tmp_path, "job", [308, 320], 0, 0, 8, {}, {})
    assert calls == [[304, 308], [320]]  # one child for all that is missing, none for what is cached
    assert (first[308] == again[308]).all() and again[320][0, 0, 0] == 320 % 251


def test_the_image_check_fails_a_perturbed_and_a_missing_frame(tmp_path, monkeypatch):
    from benchmark.lib.manifest import Cell

    config = {
        "render": {"width": 128, "height": 128, "samples": 8, "max_bounces": 4},
        "output": {"file_format": "JPEG", "jpeg_quality": 90},
        "check": {
            "frames": {"after": 0, "count": 2, "step": 4, "quantum": 16},
            "same_stream": {"crop": 96, "border": 16, "max_levels": 8, "min_share": 0.97, "crops": [[16, 32]]},
        },
    }
    cell = Cell("c", 1, "cfg", config, tmp_path, {}, (), ())
    truth = {frame: noisy_image(frame, size=128) for frame in (32, 36)}
    monkeypatch.setattr(check, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(
        check, "_run_region_child",
        lambda request, out_path, env: {f"frame_{f}": truth[f][16:112, 32:128] for f in request["frames"]},
    )
    files = {frame: write_frame(tmp_path, frame, pixels) for frame, pixels in truth.items()}
    problems, details = check.check_images(cell, files, "job", 20, 480, 1, {})
    assert problems == [] and set(details["same_stream"]["agreement"]) == {32, 36}

    noise = np.random.default_rng(2).normal(0, 14, truth[36].shape)
    write_frame(tmp_path, 36, np.clip(truth[36] + noise, 0, 255).astype(np.uint8))
    problems, _ = check.check_images(cell, files, "job", 20, 480, 1, {})
    assert len(problems) == 1 and "same-stream: frame 36" in problems[0]

    problems, _ = check.check_images(cell, {32: files[32]}, "job", 20, 480, 1, {})
    assert any("frame 36 is to be checked and is not whole on disk" in p for p in problems)
