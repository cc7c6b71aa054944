"""Multi-device frame rendering via shard_map.

Three sharding modes, mirroring how a multi-chip worker can split render
work (the SP/DP analogs called for by SURVEY.md §2.7 / §5.7):

- ``render_frame_sharded(mode="tile")``: the image's row dimension is
  sharded — each device renders a horizontal band of the same frame
  (spatial decomposition; output is jointly sharded, gathered on host
  read);
- ``render_frame_sharded(mode="spp")``: every device renders the full
  frame with a decorrelated subset of samples and the results are
  averaged with a ``psum`` over ICI (sample decomposition — a true
  collective reduction);
- ``render_frames_batched``: a batch of frames is sharded one-per-device
  (the task-farm axis collapsed into the device mesh — highest
  throughput for animation). This is a separate function, not a
  ``render_frame_sharded`` mode, because its unit of work is a batch.

Dead rays: every mode here traces ``render_tile`` under ``shard_map``.
Per shard, the integrator's deep-scene bounce loop sorts its OWN rays
dead-to-tail and hands the bounce kernel a live-count scalar, so each
device skips its all-dead tail blocks with static shapes — no
cross-device coordination, no recompiles, works under tile bands, spp
subsets, and frame batches alike. (Tile sharding even helps it: a band's
rays are spatially coherent, so their live sets collapse together.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_render_cluster.parallel.mesh import device_mesh
from tpu_render_cluster.render.camera import scene_camera
from tpu_render_cluster.render.integrator import render_tile
from tpu_render_cluster.render.scene import build_scene


def _shard_map(fn, mesh, in_specs, out_specs):
    # check_vma=False: the integrator's scan carries start replicated and
    # become device-varying when axis_index feeds the RNG — intended here.
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


@functools.lru_cache(maxsize=16)
def _sharded_frame_program(
    scene_name: str,
    width: int,
    height: int,
    samples: int,
    max_bounces: int,
    mode: str,
    n_devices: int | None,
    quant: int,
):
    """The jitted shard_map program of one scene/config, ``(scene,
    camera, mesh set, frame) -> image``; built once (the cache miss is
    the compile ``render_compiles_total`` counts — ``scene_name`` is in
    the key because each scene's arrays have their own shapes, so each
    compiles its own program)."""
    from tpu_render_cluster.obs import render_compile_counter

    mesh = device_mesh(n_devices)
    n = mesh.devices.size

    if mode == "tile":
        if height % n != 0:
            raise ValueError(f"height {height} not divisible by {n} devices.")
        rows_per_device = height // n

        def render_shard(scene, camera, mesh_set, frame):
            band_index = jax.lax.axis_index("d")
            y0 = band_index * rows_per_device
            return render_tile(
                scene,
                camera,
                frame,
                y0,
                0,
                width=width,
                height=height,
                tile_height=rows_per_device,
                tile_width=width,
                samples=samples,
                max_bounces=max_bounces,
                mesh=mesh_set,
                quant=quant,
            )

        out_specs = P("d", None, None)
    elif mode == "spp":
        if samples % n != 0:
            raise ValueError(f"samples {samples} not divisible by {n} devices.")
        samples_per_device = samples // n

        def render_shard(scene, camera, mesh_set, frame):
            device_index = jax.lax.axis_index("d")
            # Decorrelate through the frame ingredient of the RNG key:
            # scene and camera are built outside, so in render_tile the
            # frame feeds ONLY the key. (x0 cannot carry the tag: it is
            # also the pixel offset, and moved devices 1.. off screen.)
            image = render_tile(
                scene,
                camera,
                frame + device_index * 131071.0,
                0,
                0,
                width=width,
                height=height,
                tile_height=height,
                tile_width=width,
                samples=samples_per_device,
                max_bounces=max_bounces,
                mesh=mesh_set,
                quant=quant,
            )
            return jax.lax.psum(image, "d") / n

        out_specs = P()
    else:
        raise ValueError(f"Unknown sharding mode: {mode!r}")

    render_compile_counter().inc()
    return jax.jit(
        _shard_map(
            render_shard,
            mesh=mesh,
            in_specs=(P(), P(), P(), P()),
            out_specs=out_specs,
        )
    )


def sharded_frame_renderer(
    scene_name: str,
    width: int,
    height: int,
    samples: int,
    max_bounces: int,
    mode: str,
    n_devices: int | None = None,
):
    """A ``frame_index -> [H, W, 3] linear`` closure that renders one
    frame across the local mesh: one compiled program per config, fed
    the frame's scene, camera and mesh set."""
    from tpu_render_cluster.render.integrator import resolve_bvh_config
    from tpu_render_cluster.render.mesh import scene_blas_stream, scene_mesh_set

    # BVH env tiers resolve HERE (untraced): the node format keys the
    # cached program, the build picks the tree it is handed — the
    # env-tiers contract.
    _tlas, quant, builder, wide = resolve_bvh_config()
    program = _sharded_frame_program(
        scene_name, width, height, samples, max_bounces, mode, n_devices,
        quant,
    )
    # A BLAS streamed from HBM goes in as its tables, not its host arrays.
    blas = scene_blas_stream(scene_name, builder, wide)

    def render(frame_index):
        return program(
            build_scene(scene_name, frame_index),
            scene_camera(scene_name, frame_index),
            scene_mesh_set(scene_name, frame_index, builder, wide, stream=blas),
            jnp.asarray(frame_index, jnp.float32),
        )

    return render


sharded_frame_renderer.cache_clear = _sharded_frame_program.cache_clear


def render_frame_sharded(
    scene_name: str,
    frame_index: int,
    *,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
    mode: str = "tile",
    n_devices: int | None = None,
) -> jnp.ndarray:
    """Render one frame across the local mesh; returns [H, W, 3] linear."""
    return sharded_frame_renderer(
        scene_name, width, height, samples, max_bounces, mode, n_devices
    )(frame_index)


def render_frames_batched(
    scene_name: str,
    frame_indices,
    *,
    width: int = 256,
    height: int = 256,
    samples: int = 4,
    max_bounces: int = 4,
    n_devices: int | None = None,
) -> jnp.ndarray:
    """Render a batch of frames, one shard of the batch per device.

    The frame batch must be divisible by the device count. Scene build is
    vmapped on device; the only host work is the final gather.
    Returns [B, H, W, 3] linear radiance.
    """
    mesh = device_mesh(n_devices)
    n = mesh.devices.size
    frames = jnp.asarray(frame_indices, jnp.float32)
    if frames.shape[0] % n != 0:
        raise ValueError(f"Batch {frames.shape[0]} not divisible by {n} devices.")

    from tpu_render_cluster.render.integrator import resolve_bvh_config

    _tlas, bvh_quant, bvh_builder, bvh_wide = resolve_bvh_config()

    def render_one(frame):
        from tpu_render_cluster.render.mesh import scene_mesh_set

        scene = build_scene(scene_name, frame)
        camera = scene_camera(scene_name, frame)
        return render_tile(
            scene,
            camera,
            frame,
            0,
            0,
            width=width,
            height=height,
            tile_height=height,
            tile_width=width,
            samples=samples,
            max_bounces=max_bounces,
            mesh=scene_mesh_set(scene_name, frame, bvh_builder, bvh_wide),
            quant=bvh_quant,
        )

    # shard_map (not jit-level SPMD): the Pallas intersection kernel lowers
    # to a Mosaic custom call the XLA partitioner cannot split, so each
    # device must trace its own per-shard vmap.
    batch_sharding = NamedSharding(mesh, P("d"))
    render_shard = _shard_map(
        jax.vmap(render_one),
        mesh=mesh,
        in_specs=(P("d"),),
        out_specs=P("d", None, None, None),
    )
    return jax.jit(render_shard)(jax.device_put(frames, batch_sharding))
