"""Process start-up, device ownership, and the TPU cross-lowering guard.

What stands between this repo and a chip (ISSUE 22), checked on the CPU:

1. ``utils/accelerator.py``: the compile cache is placed from outside
   (``JAX_COMPILATION_CACHE_DIR``) or at ``<checkout>/.jax_cache``; a
   ``tpu-raytrace`` worker refuses a non-TPU backend unless
   ``JAX_PLATFORMS`` asks for the CPU, and stamps its device; the master CLI
   leaves JAX on ``cpu``; chips 0-3 get disjoint child environments.
2. Every default render program LOWERS for TPU from here
   (``.trace().lower(lowering_platforms=("tpu",))`` with interpret off):
   the Python-side Pallas -> Mosaic lowering, which is where illegal block
   specs are refused.
3. The per-bounce kernels also COMPILE, through libtpu's compile-only
   client for a v5e (the real XLA:TPU + Mosaic compilers, no chip needed):
   where operations Mosaic cannot legalize are refused. Whether a kernel
   computes the right thing only a chip can say — ``chip_smoke.py``.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tests.conftest import REPO_ROOT

# The served frame shape: block-spec legality depends on the array shapes
# (a (1, 1) block over a one-block row is legal, over a 64-block row not),
# so the guard lowers at the real width, never a toy one.
WIDTH = HEIGHT = 512
SAMPLES, BOUNCES = 8, 4


def _run(code: str, *arguments: str, **env_overrides) -> subprocess.CompletedProcess:
    env = {**os.environ, "PYTHONPATH": str(REPO_ROOT)}
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, "-c", code, *arguments], env=env, capture_output=True,
        text=True, timeout=120, cwd=REPO_ROOT,
    )


# -- compile cache -----------------------------------------------------------


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_default_is_the_checkout_in_every_process(monkeypatch):
    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(REPO_ROOT / ".jax_cache")
    assert configure_compile_cache() == expected
    assert updates["jax_compilation_cache_dir"] == expected
    # A second process (any cwd) resolves the same directory for real.
    result = _run(
        "import os; os.chdir('/'); import jax\n"
        "from tpu_render_cluster.utils.accelerator import configure_compile_cache\n"
        "configure_compile_cache(); print(jax.config.jax_compilation_cache_dir)",
        JAX_COMPILATION_CACHE_DIR=None,
    )
    assert result.stdout.strip().splitlines()[-1] == expected, result.stderr


# -- device ownership --------------------------------------------------------


def test_tpu_raytrace_worker_refuses_a_non_tpu_backend(tmp_path):
    """JAX_PLATFORMS unset on a host without a chip: JAX falls back to the
    CPU and the worker must exit non-zero instead of rendering there."""
    result = subprocess.run(
        [sys.executable, "-m", "tpu_render_cluster.worker.main",
         "--masterServerHost", "127.0.0.1", "--masterServerPort", "9",
         "--baseDirectory", str(tmp_path), "--backend", "tpu-raytrace"],
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        | {"PYTHONPATH": str(REPO_ROOT), "TPU_LOG_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert result.returncode != 0
    assert "tpu-raytrace needs a TPU" in result.stderr


def test_tpu_raytrace_backend_stamps_its_device():
    """An explicit JAX_PLATFORMS=cpu (conftest) is allowed, and stamped."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    stamp = TpuRaytraceBackend().device
    assert stamp["platform"] == "cpu" and stamp["device_kind"] == "cpu"
    assert stamp["count"] == len(jax.devices())


def test_master_cli_leaves_jax_on_the_host_cpu():
    """Even with JAX imported first and the environment asking for a TPU."""
    result = _run(
        "import jax\n"
        "from tpu_render_cluster.master.main import main\n"
        "try:\n    main(['run-job', '/nonexistent/job.toml'])\n"
        "except OSError:\n    pass\n"
        "print(jax.config.jax_platforms, jax.default_backend())",
        JAX_PLATFORMS="tpu",
    )
    assert result.stdout.strip().splitlines()[-1] == "cpu cpu", result.stderr


def test_chip_environments_are_disjoint():
    from tpu_render_cluster.utils.accelerator import chip_environment

    environments = [chip_environment(chip) for chip in range(4)]
    assert [env["TPU_VISIBLE_CHIPS"] for env in environments] == list("0123")
    for env in environments:  # one chip each, never a rank of a shared mesh
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    with pytest.raises(ValueError):
        chip_environment(-1)


# -- cross-lowering for TPU --------------------------------------------------


@pytest.fixture
def lower_for_tpu(monkeypatch):
    """Lower a jitted callable for TPU with the Pallas kernels compiled
    (interpret off), from this CPU process."""
    from tpu_render_cluster.render import pallas_kernels as pk

    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    # TRC_PALLAS is read at trace time and JAX keeps the traces of inner
    # jitted functions: one that an earlier test of this process traced
    # for the XLA twin would be found again here (which test files share a
    # process is xdist's to decide), and one traced here must not be found
    # by a later test.
    jax.clear_caches()

    def lower(jitted, *args, **kwargs):
        text = jitted.trace(*args, **kwargs).lower(
            lowering_platforms=("tpu",)
        ).as_text()
        assert "tpu_custom_call" in text  # a Mosaic kernel, not interpret
        return text

    yield lower
    jax.clear_caches()


def _f32():
    return jax.ShapeDtypeStruct((), jnp.float32)


def _i32():
    return jax.ShapeDtypeStruct((), jnp.int32)


SCENES = ("04_very-simple", "02_physics-mesh", "03_physics-2-mesh")


@pytest.mark.parametrize("scene_name", SCENES)
def test_masked_frame_and_region_lower_for_tpu(lower_for_tpu, scene_name):
    from tpu_render_cluster.render.integrator import (
        fused_frame_renderer,
        fused_region_renderer,
    )

    frame = fused_frame_renderer(scene_name, WIDTH, HEIGHT, SAMPLES, BOUNCES)
    lower_for_tpu(frame, _f32())
    region = fused_region_renderer(
        scene_name, WIDTH, HEIGHT, HEIGHT // 2, WIDTH // 2, SAMPLES, BOUNCES
    )
    lower_for_tpu(region, _f32(), _i32(), _i32())
    fused_frame_renderer.cache_clear()
    fused_region_renderer.cache_clear()


def test_the_backends_deep_program_lowers_for_tpu(lower_for_tpu):
    """The program the backend runs for a deep mesh frame: with the live
    counts as a second output, every rung of the ladder inside it."""
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    frame = fused_frame_renderer(
        "03_physics-2-mesh", WIDTH, HEIGHT, SAMPLES, BOUNCES, with_live=True
    )
    text = lower_for_tpu(frame, _f32())
    fused_frame_renderer.cache_clear()
    assert text.count("tpu_custom_call") >= BOUNCES


def _tree_spec(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )


@pytest.mark.parametrize("rung", range(4), ids=["n", "n/4", "n/8", "n/16"])
def test_deep_bounce_kernel_lowers_for_tpu_at_every_launch_width(lower_for_tpu, rung):
    """One bounce launch of the deep scene at each width the frame's program
    may pick (integrator.launch_width_ladder): block-spec legality depends on
    the row length, and three of the four are narrower than the frame."""
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.integrator import launch_width_ladder
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    widths = launch_width_ladder(WIDTH * HEIGHT * SAMPLES)
    assert len(widths) == 4
    width = widths[rung]
    vec = jax.ShapeDtypeStruct((width, 3), jnp.float32)
    lower_for_tpu(
        jax.jit(
            pk.mesh_bounce_pallas,
            static_argnames=("total_bounces", "use_tlas", "quant"),
        ),
        _tree_spec(build_scene("03_physics-2-mesh", 1)),
        _tree_spec(scene_mesh_set("03_physics-2-mesh", 1)),
        vec, vec, vec, jax.ShapeDtypeStruct((width,), jnp.bool_), _i32(), _i32(),
        total_bounces=BOUNCES, lane=jax.ShapeDtypeStruct((width,), jnp.int32),
        live_count=_i32(), use_tlas=True,
    )


@pytest.mark.parametrize("mode", ["tile", "spp"])
def test_sharded_frame_lowers_for_tpu(lower_for_tpu, mode):
    from tpu_render_cluster.parallel.sharded_render import (
        render_frame_sharded,
        sharded_frame_renderer,
    )

    lower_for_tpu(
        jax.jit(
            lambda: render_frame_sharded(
                "03_physics-2-mesh", 1, width=WIDTH, height=HEIGHT,
                samples=SAMPLES, max_bounces=BOUNCES, mode=mode, n_devices=4,
            )
        )
    )
    sharded_frame_renderer.cache_clear()


# -- Mosaic compile, without a chip ------------------------------------------

_COMPILE_BOUNCE_KERNELS = """
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as error:  # no usable libtpu here: nothing to compile with
    print("NO_TOPOLOGY", error)
    sys.exit(0)
from tpu_render_cluster.render import pallas_kernels as pk
from tpu_render_cluster.render.integrator import launch_width_ladder
from tpu_render_cluster.render.mesh import scene_mesh_set
from tpu_render_cluster.render.scene import build_scene

pk._interpret = lambda: False
on_chip = SingleDeviceSharding(topology.devices[0])

def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

def tree(value):
    return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), value)

rays = 512 * 512 * 8
i32 = spec((), jnp.int32)
vec = spec((rays, 3), jnp.float32)
jax.jit(pk.trace_paths_fused, static_argnames=("max_bounces",)).trace(
    tree(build_scene("04_very-simple", 1)), vec, vec, i32, max_bounces=4,
).lower(lowering_platforms=("tpu",)).compile()
print("COMPILED sphere megakernel")
scene, mesh = tree(build_scene("03_physics-2-mesh", 1)), tree(scene_mesh_set("03_physics-2-mesh", 1))
bounce = jax.jit(pk.mesh_bounce_pallas, static_argnames=("total_bounces", "use_tlas", "quant"))
widths = launch_width_ladder(rays)
for width in (widths[0], widths[-1]):
    vec = spec((width, 3), jnp.float32)
    bounce.trace(
        scene, mesh, vec, vec, vec, spec((width,), jnp.bool_), i32, i32,
        total_bounces=4, lane=spec((width,), jnp.int32), live_count=i32, use_tlas=True,
    ).lower(lowering_platforms=("tpu",)).compile()
    print("COMPILED deep bounce", width)
"""


def test_bounce_kernels_compile_with_mosaic(tmp_path):
    """The sphere megakernel and the TLAS mesh bounce kernel (the key
    epilogue whose unsigned min Mosaic refused until ISSUE 22), at the
    widest and the narrowest rung of the launch-width ladder, through the
    real compiler. A subprocess: it loads libtpu."""
    result = _run(
        _COMPILE_BOUNCE_KERNELS, TRC_PALLAS="1", TPU_LOG_DIR=str(tmp_path)
    )
    if "NO_TOPOLOGY" in result.stdout:
        pytest.skip(result.stdout.strip())
    assert result.returncode == 0, result.stderr[-3000:]
    assert result.stdout.count("COMPILED") == 3


_COMPILE_STREAMED_BOUNCE = """
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as error:  # no usable libtpu here: nothing to compile with
    print("NO_TOPOLOGY", error)
    sys.exit(0)
from tpu_render_cluster.render import mesh as mesh_module, pallas_kernels as pk
from tpu_render_cluster.render.integrator import launch_width_ladder
from tpu_render_cluster.render.scene import build_mesh_instances, build_scene

pk._interpret = lambda: False
on_chip = SingleDeviceSharding(topology.devices[0])

def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

def tree(value):
    return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), value)

# The configuration's BLASes by their shapes alone; nothing is built:
# models, treelets of 64 leaf slots, wide nodes of resident top.
family = sys.argv[1]
models, treelets, top = (int(word) for word in sys.argv[2:5])
leaves = mesh_module.TREELET_LEAVES
stream = mesh_module.BlasStream(
    tri=spec((treelets, 2 * leaves + mesh_module.WIDE, 128), jnp.float32),
    top_boxes=spec((-(-top // 16) * 8, 128), jnp.float32), top_links=spec((top * 8,), jnp.int32),
    root=spec((models, 2, 3), jnp.float32), top_first=spec((models + 1,), jnp.int32),
)
scene = tree(build_scene(family, 1))
instances = tree(build_mesh_instances(family, 1))
i32 = spec((), jnp.int32)

def bounce(scene, stream, instances, origins, directions, throughput, alive, lane, live):
    mesh = mesh_module.MeshSet(mesh_module.traced_stream_bvh(stream), instances)
    return pk.mesh_bounce_pallas(
        scene, mesh, origins, directions, throughput, alive, 7, jnp.int32(1),
        total_bounces=4, lane=lane, live_count=live, use_tlas=True,
    )

widths = launch_width_ladder(512 * 512)
for width in (widths[0], widths[-1]):
    vec = spec((width, 3), jnp.float32)
    compiled = jax.jit(bounce).trace(
        scene, stream, instances, vec, vec, vec, spec((width,), jnp.bool_),
        spec((width,), jnp.int32), i32,
    ).lower(lowering_platforms=("tpu",)).compile()
    assert "mesh_bounce_streamed" in compiled.as_text()
    print("COMPILED streamed bounce", width)
"""


@pytest.mark.parametrize("family, models, treelets, top", [
    # 871,200 triangles: 1,024 treelets ten levels down, under a top of
    # 1 + 2 + 16 + 128 = 147 wide nodes (40 KB of VMEM, 5 KB of SMEM)
    pytest.param("03_physics-2-scan", 1, 1024, 147, id="03_physics-2-scan"),
    # three models, 1,286,504 triangles: 1,664 treelets, 19 + 73 + 147 wide nodes
    pytest.param("03_physics-2-assets", 3, 1664, 239, id="03_physics-2-assets"),
    # the program is general in the number of models: a table of five
    # (with the happy buddha and the Asian dragon, 9,592,842 triangles;
    # rendered on the chip in PR 34 and cut for the benchmark run's time
    # limit): 11,904 treelets, 1,703 wide nodes (428 KB of VMEM, 53 KB of
    # SMEM where the binary top took two thirds of it)
    pytest.param("03_physics-2-assets", 5, 11904, 1703, id="03_physics-2-assets-five-models"),
    # the largest top the build lets through (`mesh.TOP_VMEM_BUDGET`): 16,384
    # wide nodes (4 MiB of VMEM, 512 KiB of SMEM) over about 114,000 treelets,
    # 117 million triangles in 7.9 GB of HBM. Tried beside it, PR 51: 28,672
    # wide nodes compile too, and 32,768 are refused for SMEM (1.06 of 1.00 MiB)
    pytest.param("03_physics-2-assets", 5, 114_000, 16_384, id="the-tops-budget"),
])
def test_the_streamed_bounce_kernel_compiles_with_mosaic(tmp_path, family, models, treelets, top):
    """The bounce kernel over a BLAS in HBM (ISSUE 32: a copy from HBM into
    VMEM scratch, a roll by a traced amount; ISSUE 33: eight boxes down
    the sublanes against a row of rays, their hits reduced to one scalar
    mask; ISSUE 34: a walk that begins at a node read from the instance
    table, over the resident tops of several models; ISSUE 51: the top's
    wide nodes out of a VMEM table by a traced row and roll, its links out
    of SMEM, its stack in SMEM scratch), at the
    configurations' table shapes and the widest and narrowest rung of a
    1 spp frame, through the real compiler. A subprocess: it loads libtpu."""
    result = _run(
        _COMPILE_STREAMED_BOUNCE, family, str(models), str(treelets), str(top),
        TRC_PALLAS="1", TPU_LOG_DIR=str(tmp_path),
    )
    if "NO_TOPOLOGY" in result.stdout:
        pytest.skip(result.stdout.strip())
    assert result.returncode == 0, result.stderr[-3000:]
    assert result.stdout.count("COMPILED") == 2
