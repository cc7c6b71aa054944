"""HBM bytes the bounce launches of one frame need, reckoned from the
launch's shapes: the yardstick's side of `walk_hbm_roofline_share`.

A bounce launch (`render/pallas_kernels.py::_mesh_bounce_io`) at width `w`
reads, per lane, the ray's origin, direction and throughput (three f32
each), its alive flag and its RNG lane; it writes the bounce's radiance,
the new origin, direction and throughput, the alive flag, the next sort key
and the walk's counters' row. Every block of a launch is read and written,
the dead tail's too (it passes through). Scene tables, the instance table
and the tree's resident top are read once a block from VMEM/SMEM copies
made once a launch: kilobytes, left out. What the walk itself adds is the
treelets it fetches, which the kernel counts.
"""

from __future__ import annotations

LANE_BYTES_READ = (3 + 3 + 3 + 1 + 1) * 4
LANE_BYTES_WRITTEN = (3 + 3 + 3 + 3 + 1 + 1 + 1) * 4


def frame_walk_bytes(treelet_bytes_fetched: float, lanes_launched: float) -> float:
    """Bytes moved between HBM and the bounce kernels in one frame:
    `treelet_bytes_fetched` as the program counted them, plus the ray state
    of `lanes_launched` lanes (the launches' widths, summed)."""
    return treelet_bytes_fetched + lanes_launched * (LANE_BYTES_READ + LANE_BYTES_WRITTEN)
