#!/usr/bin/env python
"""Mesh-scene benchmark: frames/sec/chip on both triangle-mesh scenes.

Same methodology as the headline bench.py (chunked lax.scan dispatches,
tiny-fetch sync, median of >=5 s windows), on the triangle-mesh scenes
(render/mesh.py, SURVEY.md §7 hard part #4): 02_physics-mesh (24 tumbling
boxes — the mesh-megakernel path) and 03_physics-2-mesh (48 icospheres,
deep BVH — the per-bounce instanced-kernel path). Prints one JSON line
PER SCENE, in bench.py's record shape; the committed record
(results/MESH_BENCH.json) wraps the same records in a JSON array.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

import bench  # noqa: E402


def main() -> int:
    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    configure_compile_cache()

    import jax

    platform = jax.devices()[0].platform
    # Mesh traversal is heavier per frame than the sphere megakernel;
    # smaller chunks keep the first dispatch's compile+run bounded. The
    # shallow-walk scene takes the mesh megakernel; the deep-walk scene
    # (48 icosphere instances, 127-node BVH) exercises the per-bounce
    # instanced-kernel path the adaptive dispatch keeps for it.
    for scene, chunks in (("02_physics-mesh", 16), ("03_physics-2-mesh", 4)):
        fps = bench.measure_fps(chunks=chunks, scene_name=scene)
        print(
            json.dumps(
                {
                    "metric": f"{scene} frames/sec/chip "
                    f"({bench.WIDTH}x{bench.HEIGHT}, {bench.SAMPLES}spp, "
                    f"{platform}, pallas-bvh)",
                    "value": round(fps, 3),
                    "unit": "frames/s/chip",
                    "vs_baseline": 0.0,
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
