"""Which execution tier the tpu-raytrace backend chooses.

Under `auto` every scene renders in the one-program tier, whatever the
queue holds; the wavefront and raypool drivers run only when forced. The
choice never builds the scene's mesh set on the host.
"""

from __future__ import annotations

import pytest

from tests.test_steps import make_job

SCENES = {
    "sphere": "04_very-simple",
    "shallow": "02_physics-mesh",
    "deep": "03_physics-2-mesh",
}
FLAGS = {"unset": None, "off": "off", "force": "force"}


@pytest.fixture
def routed(monkeypatch):
    """Pallas on, no tier variable, the three tiers' renderers replaced by
    recorders, and every host-side build of a mesh set counted."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import compaction, integrator, mesh, raypool

    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.delenv("TRC_WAVEFRONT", raising=False)
    monkeypatch.delenv("TRC_RAYPOOL", raising=False)
    seen = {"rendered": [], "mesh_sets": 0}
    image = jnp.zeros((8, 8, 3), jnp.float32)

    def mesh_set(*_args, **_kwargs):
        seen["mesh_sets"] += 1

    def masked(*_args, **_kwargs):
        def render(_frame):
            seen["rendered"].append("masked")
            return jnp.zeros((8, 8, 3), jnp.uint8), None  # the image, no live counts
        return render

    def wavefront(*_args, **_kwargs):
        seen["rendered"].append("wavefront")
        return image

    def pool(_scene, frames, **_kwargs):
        seen["rendered"].append("raypool")
        return [image for _ in frames]

    monkeypatch.setattr(mesh, "scene_mesh_set", mesh_set)
    monkeypatch.setattr(integrator, "fused_frame_renderer", masked)
    monkeypatch.setattr(compaction, "render_frame_wavefront", wavefront)
    monkeypatch.setattr(raypool, "render_batch_raypool", pool)
    return seen


def backend_with(flag: str, tmp_path=None):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    return TpuRaytraceBackend(
        base_directory=tmp_path, width=8, height=8, samples=1, max_bounces=2,
        wavefront=FLAGS[flag], raypool=FLAGS[flag],
    )


@pytest.mark.parametrize("flag", sorted(FLAGS))
@pytest.mark.parametrize("frames_ahead", [0, 4])
@pytest.mark.parametrize("scene", sorted(SCENES))
def test_the_tier_follows_the_flag_and_never_the_scene_or_the_queue(
    scene, frames_ahead, flag, routed
):
    backend = backend_with(flag)
    forced = flag == "force"
    assert backend._use_raypool(SCENES[scene], frames_ahead) is forced
    assert backend._use_wavefront(SCENES[scene]) is forced
    backend.warm(SCENES[scene])
    # a forced worker warms both drivers; any other warms the one program
    assert routed["rendered"] == (["raypool", "wavefront"] if forced else ["masked"])
    assert routed["mesh_sets"] == 0


@pytest.mark.parametrize("scene", sorted(SCENES))
def test_under_auto_a_queued_frame_renders_in_the_one_program_tier(
    scene, routed, tmp_path
):
    """Four frames queued ahead of a deep scene used to engage the pool."""
    backend = backend_with("unset", tmp_path)
    job = make_job(f"{SCENES[scene]}_routing", 8)
    counter = backend._tier_frames
    before = {tier: counter.value(tier=tier) for tier in ("masked", "wavefront", "raypool")}
    for frame in (1, 2):
        backend.note_upcoming_frames(job, tuple(range(frame + 1, frame + 5)))
        backend._render_sync(job, frame)
    after = {tier: counter.value(tier=tier) for tier in before}
    assert after["masked"] - before["masked"] == 2
    assert after["wavefront"] == before["wavefront"] and after["raypool"] == before["raypool"]
    assert routed["rendered"] == ["masked", "masked"]
    assert routed["mesh_sets"] == 0 and not backend._raypool_cache
    assert len(list((tmp_path / "out").iterdir())) == 2
