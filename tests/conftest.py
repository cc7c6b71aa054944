"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs the multichip
path; bench.py runs on the real chip).

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys
from pathlib import Path

import pytest

# Make the repo root importable regardless of pytest invocation directory.
REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))


@pytest.fixture(autouse=True)
def _isolated_render_compile_tracking():
    """Reset the render drivers' compile first-sighting tracker per test.

    render/compaction._seen_shapes is process-global (it mirrors the
    process-lifetime jit cache the ``render_compiles_total`` counter
    describes), so without this reset a test's compile-delta assertions
    would depend on which shapes EARLIER tests happened to launch. The
    obs counter itself stays monotonic — only the dedup memory is
    cleared, so each test observes fresh first-sightings.
    """
    compaction = sys.modules.get("tpu_render_cluster.render.compaction")
    if compaction is not None:
        compaction.reset_compile_tracking()
    # Same reasoning for the kernel roofline profiler (obs/profiling.py):
    # its capture/execution store is process-global and cumulative, so
    # per-kernel assertions must start from a clean slate each test.
    profiling = sys.modules.get("tpu_render_cluster.obs.profiling")
    if profiling is not None:
        profiling.get_profiler().reset()
    # And for the host-side geometry-build memo (render/mesh.py): BVH/
    # TLAS builds are pure, but per-test build-count assertions (e.g.
    # render_tlas_builds_total deltas) must not depend on which
    # hierarchies earlier tests already built.
    mesh = sys.modules.get("tpu_render_cluster.render.mesh")
    if mesh is not None:
        mesh.reset_geometry_cache()
    yield
