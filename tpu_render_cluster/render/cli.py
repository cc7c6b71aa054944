"""Standalone render CLI: render one frame of a procedural scene to a file.

Usage:
  python -m tpu_render_cluster.render.cli --scene 04_very-simple --frame 1 \
      --width 256 --height 256 --samples 4 --out /tmp/frame.png
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="trc-render")
    parser.add_argument("--scene", default="04_very-simple")
    parser.add_argument(
        "--obj",
        default=None,
        help="render this .obj mesh file on a turntable stage instead of a "
        "named procedural scene (normalized to stage scale; rotates with "
        "--frame)",
    )
    parser.add_argument("--frame", type=int, default=1)
    parser.add_argument("--width", type=int, default=512)
    parser.add_argument("--height", type=int, default=512)
    parser.add_argument("--samples", type=int, default=8)
    parser.add_argument("--bounces", type=int, default=4)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    configure_compile_cache()

    import json

    import numpy as np

    from tpu_render_cluster.render.image_io import write_image
    from tpu_render_cluster.render.integrator import render_frame, tonemap

    obj_bvh = None
    if args.obj is not None:
        # Geometry ingest (disk read + parse + host BVH build) is the
        # analog of Blender's .blend load and belongs to the load phase.
        from tpu_render_cluster.render.mesh_io import cached_obj_bvh

        obj_bvh = cached_obj_bvh(args.obj)
    loaded_at = time.time()  # imports + geometry ingest = "project load"
    if args.obj is not None:
        linear = _render_obj_stage(args, obj_bvh)
    else:
        linear = render_frame(
            args.scene,
            args.frame,
            width=args.width,
            height=args.height,
            samples=args.samples,
            max_bounces=args.bounces,
        )
    linear.block_until_ready()
    finished_rendering_at = time.time()
    path = Path(args.out)
    write_image(path, np.asarray(tonemap(linear)), path.suffix.lstrip(".").upper() or "PNG")
    saved_at = time.time()
    print(
        f"Rendered {args.obj or args.scene} frame {args.frame} "
        f"({args.width}x{args.height}, {args.samples} spp) "
        f"in {finished_rendering_at - loaded_at:.2f} s -> {path}"
    )
    # Phase-timing contract consumed by worker daemons (same shape as the
    # Blender timing script, scripts/render-timing-script.py, plus explicit
    # save timestamps since we know them exactly).
    print(
        "RESULTS="
        + json.dumps(
            {
                "project_loaded_at": loaded_at,
                "project_started_rendering_at": loaded_at,
                "project_finished_rendering_at": finished_rendering_at,
                "file_saving_started_at": finished_rendering_at,
                "file_saving_finished_at": saved_at,
            }
        )
    )
    return 0


def _render_obj_stage(args, bvh):
    """One turntable frame of a user OBJ: same integrator, same Pallas BVH
    kernels as the built-in mesh scenes, geometry loaded from disk."""
    import jax.numpy as jnp

    from tpu_render_cluster.render.camera import look_at_camera
    from tpu_render_cluster.render.integrator import render_tile
    from tpu_render_cluster.render.mesh import (
        MeshInstances,
        MeshSet,
        rotation_y,
    )
    from tpu_render_cluster.render.scene import obj_stage_scene

    angle = jnp.asarray([args.frame * 0.06], jnp.float32)
    instances = MeshInstances(
        rotation=rotation_y(angle).astype(jnp.float32),
        translation=jnp.array([[0.0, 1.05, 0.0]], jnp.float32),
        albedo=jnp.array([[0.72, 0.7, 0.75]], jnp.float32),
        scale=jnp.array([1.0], jnp.float32),
    )
    camera = look_at_camera([4.0, 2.8, 4.2], [0.0, 1.0, 0.0])
    from tpu_render_cluster.render.integrator import resolve_bvh_config

    _tlas, bvh_quant, _builder, _wide = resolve_bvh_config()
    return render_tile(
        obj_stage_scene(args.frame),
        camera,
        float(args.frame),
        0,
        0,
        width=args.width,
        height=args.height,
        tile_height=args.height,
        tile_width=args.width,
        samples=args.samples,
        max_bounces=args.bounces,
        mesh=MeshSet(bvh=bvh, instances=instances),
        quant=bvh_quant,
    )


if __name__ == "__main__":
    sys.exit(main())
