"""A CPU-pinned child that renders crops through the program for the
check: `python region_child.py <request.json> <out.npz>`.

The harness starts it after the window with `JAX_PLATFORMS=cpu` and
`TRC_PALLAS=1`: the program's own kernels run by the Pallas interpreter,
so the random streams are the served frames' and only the arithmetic's
device differs (float32 on the CPU). It also hands out the scene, the
camera and a mesh scene's triangles and instances as plain arrays, which
is all the independent reference takes from the program.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(request_path: str, out_path: str) -> int:
    request = json.loads(Path(request_path).read_text())
    import jax
    import numpy as np

    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.integrator import render_frame_region, tonemap
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene, scene_for_job_name
    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    configure_compile_cache()
    scene_name = scene_for_job_name(request["job_name"])
    shape = request["render"]
    out = {}
    with jax.default_matmul_precision("highest"):
        for frame in request["frames"]:
            linear = render_frame_region(
                scene_name, frame, y0=request["y0"], x0=request["x0"],
                tile_height=request["crop"], tile_width=request["crop"],
                width=shape["width"], height=shape["height"],
                samples=shape["samples"], max_bounces=shape["max_bounces"],
            )
            out[f"frame_{frame}"] = np.asarray(tonemap(linear))
    if request.get("scene_arrays_frame") is not None:
        frame = request["scene_arrays_frame"]
        for key, value in build_scene(scene_name, frame)._asdict().items():
            out[f"scene_{key}"] = np.asarray(value)
        for key, value in scene_camera(scene_name, frame)._asdict().items():
            out[f"camera_{key}"] = np.asarray(value)
        mesh = scene_mesh_set(scene_name, frame)
        if mesh is not None:  # object-space triangles and the instances' transforms
            for key in ("v0", "e1", "e2"):
                out[f"mesh_{key}"] = np.asarray(getattr(mesh.bvh, key))
            for key, value in mesh.instances._asdict().items():
                out[f"mesh_{key}"] = np.asarray(value)
    np.savez(out_path, **out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
