"""The cells `04vs-1w-fine` and `02phmesh-1w-queued` (ISSUE 56) as data:
they find their files and say what the issue says, `04vs-1w-fine` differs
from `04vs-1w-coarse` by its traffic alone, the configuration
`02phmesh-240f-1w` states its source, cuts, assumptions, guarantees and
limits, the metric `mesh_fused_frame_share` is data for the accepted
`delta_ratio` reader and gives nothing for a program without the series,
and a whole run of `02phmesh-1w-queued` walks through on the CPU.

The rehearsal starts a master and a worker as real processes at 64x64
through the Pallas interpreter (a few minutes); it has a time limit of its
own and is not part of tier-1: `tests/test_benchmark_shallow_mesh_cell.py`
brings the other cases in by name.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.drivers import backlog
from benchmark.lib import check, manifest, readers

ROOT = Path(__file__).resolve().parents[2]
MESH_CELL, DEEP_CELL = "02phmesh-1w-queued", "03ph2mesh-1w-queued"
FINE_CELL, COARSE_CELL = "04vs-1w-fine", "04vs-1w-coarse"
CONFIG = "02phmesh-240f-1w"
METRIC = "mesh_fused_frame_share"
REHEARSAL_SECONDS = 900
# accepted lists that name the deep mesh cell and stay without the shallow one: the megakernel
# launches no per-bounce kernel, so their counters read nothing there
NOT_FOR_THE_MEGAKERNEL = {"pool_live_lane_share", "repack_by_sort_share"}


def key(name: str, **labels: str):
    return (name, tuple(sorted(labels.items())))


def test_both_cells_are_data_and_say_what_the_issue_says():
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == ""
    assert "04vs-1w-fine           config 04vs-14400f-1w       traffic backlog-naivefine    chips 1" in listing.stdout
    assert "02phmesh-1w-queued     config 02phmesh-240f-1w     traffic backlog-tpubatch4    chips 1" in listing.stdout
    benchmark = manifest.load_benchmark(ROOT)
    names = [w["name"] for w in benchmark["workloads"]]
    # PERF.md §7's order: the `fine` row stands before the shallow mesh's, both behind the ten that were there
    assert names.index(FINE_CELL) == 10 and names.index(MESH_CELL) == 11 and names[9] == "04vs-1w-png"
    assert sum(w["chips"] == 4 for w in benchmark["workloads"][:12]) == 3
    configs = benchmark["configs"]
    assert configs[9]["name"] == CONFIG and len(configs) >= 10
    assert len({c["source"] for c in configs}) == len({c["file"] for c in configs}) == len(configs)
    fine, mesh = manifest.load_cell(FINE_CELL, ROOT), manifest.load_cell(MESH_CELL, ROOT)
    assert (fine.chips, fine.config_name, fine.traffic["name"]) == (1, "04vs-14400f-1w", "backlog-naivefine")
    assert (mesh.chips, mesh.config_name, mesh.traffic["name"]) == (1, CONFIG, "backlog-tpubatch4")
    assert fine.traffic["strategy"] == {"strategy_type": "naive-fine"} and fine.traffic["warmup_frames_per_worker"] == 3
    assert mesh.traffic["strategy"]["strategy_type"] == "tpu-batch" and mesh.traffic["strategy"]["target_queue_size"] == 4
    assert mesh.traffic["warmup_frames_per_worker"] == 8
    for cell in (fine, mesh):
        assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    whys = {w["name"]: w["why"] for w in benchmark["workloads"]}
    assert "mesh megakernel" in whys[MESH_CELL] and DEEP_CELL in whys[MESH_CELL]
    assert "naive-fine" in whys[FINE_CELL] and COARSE_CELL in whys[FINE_CELL]


def test_the_fine_cell_differs_from_the_coarse_cell_by_its_traffic_alone():
    fine, coarse = manifest.load_cell(FINE_CELL, ROOT), manifest.load_cell(COARSE_CELL, ROOT)
    assert fine.config == coarse.config and fine.config_dir == coarse.config_dir and fine.chips == coarse.chips
    assert fine.traffic != coarse.traffic and fine.traffic["driver"] == coarse.traffic["driver"] == "backlog"
    # the accepted traffic file of `03ph2mesh-1w-fine`, unedited
    assert fine.traffic == manifest.load_cell("03ph2mesh-1w-fine", ROOT).traffic
    # every metric of the coarse cell, and the one that says who woke the master's pass
    extra = {m["name"] for m in fine.per_layer} - {m["name"] for m in coarse.per_layer}
    assert extra == {"dispatch_on_event_share"}
    assert {m["name"] for m in coarse.per_layer} <= {m["name"] for m in fine.per_layer}
    with_seed = {
        cell.name: backlog.render_job_file(cell, 5600001212, Path(os.devnull)) for cell in (fine, coarse)
    }
    assert with_seed[FINE_CELL] == with_seed[COARSE_CELL]  # the same seed starts the same job on the same frame


def test_the_two_cells_are_appended_to_the_accepted_lists_and_nothing_else_moved():
    benchmark = manifest.load_benchmark(ROOT)
    for metric in benchmark["per_layer"]:
        listed = metric.get("workloads")
        if listed is None or metric["name"] == METRIC:
            continue
        wants_fine = COARSE_CELL in listed or metric["name"] == "dispatch_on_event_share"
        wants_mesh = DEEP_CELL in listed and metric["name"] not in NOT_FOR_THE_MEGAKERNEL
        assert (FINE_CELL in listed, MESH_CELL in listed) == (wants_fine, wants_mesh), metric["name"]
        # appended behind what was there, in the order the two cells have in `workloads`
        behind = [name for name in listed if name in (FINE_CELL, MESH_CELL)]
        assert listed[len(listed) - len(behind):] == behind == sorted(behind, key=[FINE_CELL, MESH_CELL].index)
    for name in NOT_FOR_THE_MEGAKERNEL:
        (entry,) = [m for m in benchmark["per_layer"] if m["name"] == name]
        assert DEEP_CELL in entry["workloads"] and MESH_CELL not in entry["workloads"]


def test_the_configuration_states_its_source_its_cuts_and_what_it_assumes():
    (entry,) = [c for c in manifest.load_benchmark(ROOT)["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["workers", "frame_range_from"] and len(entry["source"]) <= 200
    assert "blender-projects/02_physics/02-physics_demo_170f-5w_naive-fine.toml" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}/config.json"
    config = json.loads((ROOT / entry["file"]).read_text())
    deep = manifest.load_cell(DEEP_CELL, ROOT).config
    assert config["reduced"] == entry["reduced"] and config["name"] == CONFIG
    assert config["deployment"] == {
        "scene_family": "02_physics-mesh", "frames": 240, "workers": 1, "chips": 1,
        "layout": "master on the host CPU, one tpu-raytrace worker process on the chip",
    }
    assert (config["frames"], config["workers"], config["trace_slice_s"]) == (240, 1, 15)
    assert config["render"] == deep["render"] == {"width": 512, "height": 512, "samples": 8, "max_bounces": 4}
    assert config["output"] == deep["output"] and config["output"]["jpeg_quality"] == 90
    assert config["guarantees"] == deep["guarantees"] and len(config["guarantees"]) == 3  # word for word
    assert set(config["assumed"]) == {"render", "geometry", "frames", "job_name"}
    assert "170" in config["assumed"]["frames"] and "3.5" in config["assumed"]["frames"]
    start = config["frame_range_from"]
    assert (start["source"], start["first"], start["span"]) == (1, 1, 16)  # from its first frames: nothing settled away
    template = (ROOT / entry["file"]).parent / config["job_template"]
    job = template.read_text()
    assert 'job_name = "02_physics-mesh_240f-1w"' in job and 'output_file_format = "JPEG"' in job
    from tpu_render_cluster.render.scene import scene_for_job_name

    assert scene_for_job_name("02_physics-mesh_240f-1w") == "02_physics-mesh"
    assert scene_for_job_name("02ph_240f-1w") == "02_physics"  # why the name is spelled out


def test_the_check_reads_two_frames_of_bodies_in_the_air_on_crops_that_hold_them():
    config = manifest.load_cell(MESH_CELL, ROOT).config
    deep = manifest.load_cell(DEEP_CELL, ROOT).config["check"]
    same, independent, frames = (config["check"][part] for part in ("same_stream", "independent", "frames"))
    assert (frames["after"], frames["count"], frames["step"], frames["quantum"]) == (8, 2, 4, 32)
    start = config["frame_range_from"]
    pairs = {
        tuple(check.checked_frames(first, config["frames"], frames))
        for first in range(start["first"], start["first"] + start["span"])
    }
    assert pairs == {(32, 36)}
    # the icosphere configuration's limits, but for the one a chip reading moved (PR 56): the sound program
    # reads 0.9968 at the least and the bf16 control 0.9600 at the most, and 0.98 lies between with room
    for part, ours in (("same_stream", same), ("independent", independent)):
        assert {k: v for k, v in ours.items() if k not in ("crops", "why", "min_share")} == {
            k: v for k, v in deep[part].items() if k not in ("crops", "why", "min_share")
        }
    assert (same["crop"], same["border"], same["max_levels"], same["min_share"]) == (96, 16, 8, 0.98)
    assert deep["same_stream"]["min_share"] == 0.97 and "0.9968" in same["why"] and "0.9600" in same["why"]
    assert independent["reference"] == "plain_tracer" and independent["scene_is_static"] is False
    assert len(same["crops"]) == len(independent["crops"]) == 3
    # an independent crop is its same-stream crop's interior, and every crop lies inside the frame
    assert independent["crops"] == [[y + same["border"], x + same["border"]] for y, x in same["crops"]]
    assert all(0 <= v <= 512 - same["crop"] for crop in same["crops"] for v in crop)
    # each limit with its reason and its readings: the sound program's and both controls'
    for spec in (same, independent):
        assert len(spec["why"]) > 400 and "no_bodies" in spec["why"] and "bf16" in spec["why"] and "PR 56" in spec["why"]
    # three seeds pick three different crops, for both checks
    for salt, spec in ((0, same), (2, independent)):
        picked = {
            check.pick_crop(spec["crops"], seed, salt, width=512, height=512, crop=spec["crop"])
            for seed in range(5600000000, 5600000040)
        }
        assert picked == {tuple(crop) for crop in spec["crops"]}


def test_the_metric_is_data_and_reads_nothing_for_a_program_without_the_series():
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "frames_per_s", "workloads": [MESH_CELL, DEEP_CELL],
    }
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"] if m["name"] != METRIC}
    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert not (directory / f"{METRIC}.py").exists(), "data, no reader code"
    assert set(spec) == {"reader", "from", "numerator", "denominator", "scale", "what"}
    assert (spec["reader"], spec["from"], spec["scale"]) == ("delta_ratio", "workers", 100.0)
    assert spec["numerator"] == {"series": "render_trace_kernel_frames_total", "labels": {"kernel": "mesh_fused"}}
    assert spec["denominator"] == {"series": "render_trace_kernel_frames_total"}
    assert "not on the line for a program without the counter" in spec["what"]
    frames = key("worker_frame_phase_seconds_count", phase="render")
    tier = key("render_tier_frames_total", tier="masked")
    # the parent's program: frames and tiers, no kernel counter: nothing, and no exception
    parent = {"scrapes": {"master": ([{}], [{}]), "workers": ([{frames: 8.0, tier: 8.0}], [{frames: 120.0, tier: 120.0}])}}
    assert readers.read_metric(METRIC, parent, ROOT) is None
    kernels = {name: key("render_trace_kernel_frames_total", kernel=name) for name in (
        "sphere_fused", "mesh_fused", "mesh_bounce", "mesh_stream", "xla_loop",
    )}
    zero = {series: 0.0 for series in kernels.values()}
    shallow = {"scrapes": {"master": ([{}], [{}]), "workers": ([{**zero, kernels["mesh_fused"]: 8.0}], [{**zero, kernels["mesh_fused"]: 120.0}])}}
    assert readers.read_metric(METRIC, shallow, ROOT) == 100.0
    deep = {"scrapes": {"master": ([{}], [{}]), "workers": ([{**zero, kernels["mesh_bounce"]: 8.0}], [{**zero, kernels["mesh_bounce"]: 130.0}])}}
    assert readers.read_metric(METRIC, deep, ROOT) == 0.0
    still = {"scrapes": {"master": ([{}], [{}]), "workers": ([zero], [zero])}}
    assert readers.read_metric(METRIC, still, ROOT) is None  # no frame in the window: nothing to divide by


def test_a_whole_run_of_the_shallow_mesh_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", MESH_CELL, "--seed", "5600001212",
         "--seconds", "8", "--trace", "0", "--rehearse"],  # 64x64 frames land at 13 a second: 20 s would outlast the job
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 8
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    checked = next(line for line in lines if line["stage"] == "check")
    # the interpreter against itself: every pixel (at 64x64 the crops are clamped onto one another)
    assert set(checked["same_stream"]["agreement"]) == {"32", "36"}
    assert set(checked["same_stream"]["agreement"].values()) == {1.0}
