"""The render engine's three Pallas TPU kernels, one per scene class.

The path tracer spends its time intersecting rays with the scene
(reference analog: the per-frame render loop inside Blender that
worker/src/rendering/runner/mod.rs shells out to; here the render engine is
TPU-native so the hot loop is ours to own). ``integrator.trace_paths``
picks one kernel by what the scene holds, and each is one ``pallas_call``:

- ``trace_paths_fused`` (``_trace_fused``): sphere scenes, the whole bounce
  loop in one launch with the path state resident in VMEM;
- ``trace_paths_fused_mesh`` (``_trace_fused_mesh``): the same for a
  shallow resident mesh (``mesh_megakernel_eligible``);
- ``mesh_bounce_pallas`` (``_mesh_bounce_io``): one bounce a launch, path
  state in and out, for every deeper or streamed mesh, so that the
  integrator can re-sort the rays between bounces.

The XLA walks of ``geometry`` and ``mesh`` are the reference they are
tested against and what runs where ``pallas_enabled()`` is false; they
materialize several [R, N] intermediates between HBM-level fusions, where
a kernel fuses the quadratic solve, validity masking and the min/argmin
reduction into one VMEM-resident pass per ray block.

Layout choices (see /opt/skills/guides/pallas_guide.md):
- rays ride the *lane* axis (128-wide) as [3, BLOCK_R] blocks; the sphere
  axis is the sublane axis, so the nearest-hit reduction is a sublane
  reduction producing [1, BLOCK_R];
- sphere data ([3, N] centers, [N, 1] radius^2 / |c|^2) is small enough to
  sit whole in VMEM for every grid step;
- every in-kernel contraction is exact in float32. The K=3 ones (d.c,
  o.c) of the two mesh kernels are dot_generals at full f32
  precision (``_dot_f32``: six bf16 MXU passes, both operands split on
  the VPU), and so are their one-hot gathers. The sphere megakernel
  (``_trace_kernel_factory``) pays only the passes its operands need: a
  hit's centre, radius, albedo and emission come from ONE single-pass
  bf16 matmul of a one-hot, which is 0.0 / 1.0 and so exact in bf16,
  against the tables' three exact bf16 parts (``_gather_hit``), and each
  of its three K=3 contractions a bounce is ONE bf16 pass too
  (``_dot_k3_exact``): the ray vector's three bf16 parts against the
  centres' three along K = 96, all nine cross terms summed in the MXU's
  float32 accumulator, the smallest first. On the chip a 512x512x8
  frame reads 16.92 ms with them six-pass and c . o carried between
  bounces, 21.6 as broadcast multiply-adds on the VPU (PR 38) and 13.25
  one-pass with nothing carried (PERF.md, PR 46).

On non-TPU backends the kernels run in interpret mode, so the same code
path is exercised by CPU tests.
"""

from __future__ import annotations

import functools
from tpu_render_cluster.utils.env import env_int, env_str

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Plain Python floats: a jnp constant would be captured as a traced value,
# which pallas_call rejects.
INF = 1e30
EPS = 1e-3

# Rays per grid step of the sphere megakernel. Swept on the chip
# on the one-pass kernel (`_trace_fused` alone, 512x512x8, PR 46):
# 2048 -> 13.26 ms a frame, 4096 -> 13.25, 8192 -> 13.08. An
# [N_spheres, BLOCK_R] intermediate is 256 vregs at 64 spheres and 4096
# rays, so the body streams through VMEM at every one of them and the
# block hardly matters; 8192's 1.3% costs twice the Mosaic compile (4.8 s
# against 2.4) and twice the padding of a cropped launch: 4096 stays.
BLOCK_R = 4096
# The BVH kernels use their own ray-block size: packet culling (the
# block-wide any() on AABB tests and the instance-level world-AABB skip)
# only bites when a block is spatially tight. Under the current
# single-grid-axis kernels (grid = ray blocks only; the per-block
# candidate-first instance sweep runs inside the kernel) the on-chip sweep
# favors 1024: smaller blocks are spatially tighter, so the seeded best-t
# and the top-level AABB skip cull more of the per-block instance sweep,
# and the walk's live-lane mask drains sooner.
BVH_BLOCK_R = 1024
_SUBLANE = 8  # f32 sublane tile; sphere count is padded to a multiple


def pallas_enabled() -> bool:
    """Whether ``integrator.trace_paths`` traces through a Pallas kernel
    (one of three, by scene class) or through the XLA bounce loop, the
    reference. Asked there, and in the two places that shape its input
    (``render_tile``, the region renderer); the XLA walks of ``geometry``
    and ``mesh`` never ask.

    Default: only on a real TPU backend (interpret mode is a debugging
    path, much slower than XLA on CPU). ``TRC_PALLAS=1`` forces it on
    anywhere (tests use this); ``TRC_PALLAS=0`` disables it.

    Read at *trace* time: jitted callers bake the decision into their
    compiled executable, so flipping the env var mid-process has no effect
    on already-compiled functions (jax.clear_caches() to re-trace).
    """
    value = env_str("TRC_PALLAS")
    if value is None:
        return jax.default_backend() == "tpu"
    return value not in ("0", "false", "off")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _center_dot_sun(c_t, sun_direction):
    """[Np, 1] c . sun, elementwise: an XLA matmul of f32 operands also
    defaults to one bf16 pass on the TPU."""
    return jnp.sum(c_t * sun_direction[:, None], axis=0)[:, None]


def _dot_f32(a, b, dimension_numbers):
    """In-kernel f32 contraction at full f32 precision.

    Mosaic's default for f32 operands is one bf16 MXU pass — three
    significant digits — which interpret mode (exact f32 on the CPU)
    never shows: on the chip it put ~0.4% error on sphere hit distances
    and on the centers the one-hot gathers read back, and chip frames
    disagreed with interpret frames on half their pixels. HIGHEST is
    Mosaic's fp32 contract precision; elsewhere it changes nothing.

    It is six bf16 passes with BOTH operands split into three bf16
    arrays on the VPU, which is what two arbitrary float32 operands
    need. The sphere megakernel needs none of it: its one-hot gathers
    are one default-precision pass against tables split once outside the
    loop (``_gather_hit``), and its K=3 contractions one pass against a
    stack of the centres' parts (``_dot_k3_exact``: 13.25 ms a
    512x512x8 frame where this function read 16.92 and the VPU 21.6;
    PERF.md, PR 46). The two mesh kernels (their sphere pass and
    one-hot gathers included) use this function.
    """
    return jax.lax.dot_general(
        a, b, dimension_numbers, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def tlas_enabled() -> bool:
    """Whether the mesh kernels traverse a two-level TLAS/BLAS hierarchy
    (default) or the flat per-instance sweep (``TRC_TLAS=0`` — the
    baseline the two-level walk is compared with, and the on-chip
    triage kill switch).

    Read at *trace* time like ``TRC_PALLAS``: jitted renderers bake the
    decision, and the renderer factories additionally thread it as a
    static jit argument so both kernel variants can coexist in one
    process.
    """
    value = env_str("TRC_TLAS")
    if value is None:
        return True
    return value not in ("0", "false", "off", "no")


def tlas_leaf_size() -> int:
    """Instances per TLAS leaf (``TRC_TLAS_LEAF``, default 4, clamped to
    [1, 16]). Part of the compiled kernel's identity — a distinct leaf
    size is a distinct trace."""
    leaf = env_int("TRC_TLAS_LEAF", 4)
    return max(1, min(leaf, 16))


def tlas_block_r() -> int:
    """Ray-block width of the TLAS kernel variants (``TRC_TLAS_BLOCK``,
    default 256).

    Packet pruning only exists at block granularity — a subtree is
    skipped when NO lane in the block wants it — so the TLAS walk wants
    much NARROWER packets than the flat sweep's ``BVH_BLOCK_R`` (1024,
    tuned for sweep-style launches where the block size only amortizes
    launch overhead). Measured on the CPU proxy (03-family, 48
    instances): 1024-lane packets union over most of the instance field
    and prune nothing (0.95x vs flat), 512 -> ~1.7x, 256 -> ~2x,
    128 -> ~2.3x but with more per-block overhead headroom on chip —
    256 is the default; re-tune on chip via the env knob. Snapped to a
    power of two in [128, BVH_BLOCK_R] so it always divides the rungs
    of integrator.launch_width_ladder, and read at trace time like the
    other TLAS knobs (part of each compiled kernel's shape).
    """
    raw = env_int("TRC_TLAS_BLOCK", 256)
    block = 128
    while block * 2 <= min(raw, BVH_BLOCK_R):
        block *= 2
    return block


def use_tlas_for(k_count: int, use_tlas: bool | None = None) -> bool:
    """Resolve the TLAS decision for a ``k_count``-instance field.

    ``None`` defers to the env tier. Fields that fit in one TLAS leaf
    degenerate to the flat sweep plus a root test — auto-disabled.
    """
    flag = tlas_enabled() if use_tlas is None else bool(use_tlas)
    return flag and k_count > tlas_leaf_size()


def bvh_quant_mode() -> int:
    """The ``TRC_BVH_QUANT`` env tier (default 0 = off): quantized node
    tables.

    - 0: fp32 slabs, int32 links (the exact baseline);
    - 1: 16-bit fixed-point slabs (two per int32 word) + one packed meta
      word per node;
    - 2: 8-bit slabs (six per two words), same meta packing.

    Conservative outward rounding keeps every tier's IMAGES bit-identical
    (the quantized walk visits a superset of nodes; triangle tests stay
    exact f32 — see mesh.quantize_node_tables). A static jit arg like
    ``TRC_TLAS``: read by untraced renderer factories only (the
    ``env-tiers`` lint pass pins this) and threaded into every kernel
    identity, so distinct tiers coexist as distinct compiled programs in
    one process.
    """
    return max(0, min(env_int("TRC_BVH_QUANT", 0), 2))


def resolve_bvh_quant(quant: int, *tables: tuple[int, int, int]) -> int:
    """Degrade the quant tier to 0 when any node table outgrows the
    packed meta word's ranges (``int32 -> int16/byte offsets where index
    ranges allow`` — ISSUE 15). Each table is (n_nodes, first_units,
    max_count); all limits are shape-derived, so the decision is static
    at trace time."""
    from tpu_render_cluster.render.mesh import (
        QUANT_MAX_COUNT,
        QUANT_MAX_FIRST_UNITS,
        QUANT_MAX_NODES,
    )

    if not quant:
        return 0
    for n_nodes, first_units, max_count in tables:
        # Skip links range over [0, n_nodes] INCLUSIVE (n_nodes is the
        # walk terminator), so the node count must stay strictly below
        # the 16-bit field's modulus or the terminator would wrap to 0
        # and the threaded walk would never end.
        if (
            n_nodes >= QUANT_MAX_NODES
            or first_units > QUANT_MAX_FIRST_UNITS
            or max_count > QUANT_MAX_COUNT
        ):
            return 0
    return max(0, min(int(quant), 2))


# ---------------------------------------------------------------------------
# Fused coherence sort key (ISSUE 10): the per-bounce re-sort key is
# computed in the mesh bounce kernels' EPILOGUE from the post-bounce ray
# state — one extra [1, BR] int32 output row — so the deep path's
# re-sort is a single argsort over a precomputed column instead of a
# separate XLA pass (candidate broadphase + quantization + dilation)
# over the full ray state. Layout (LSB -> MSB): direction octant [0:3),
# 5-bit/axis Morton cell of origin+direction [3:18), first-overlap
# candidate instance [18:24) (6 bits, clamped — packets that want the
# SAME instance first walk straight to its leaf and seed tight best-t),
# frame id [24:29) (always 0: no caller mixes frames in one launch;
# ROADMAP D2), dead flag bit 29.
# Always < 2^30, so the uint32 bit pattern bitcasts to a POSITIVE int32
# and a plain ascending argsort orders it exactly like the uint32 would.

KEY_DEAD_BIT = 29


def coherence_key_u32(
    px, py, pz, dx, dy, dz, dead, fid, candidate,
    lox, loy, loz, ivx, ivy, ivz,
):
    """The ONE key derivation, componentwise so the kernel epilogue
    ([1, BR] rows, SMEM scalar bounds) and the XLA twin ([R] columns,
    traced scalar bounds) provably compute bit-identical keys
    (tests/test_tlas.py pins it). ``p*`` = origin+direction components,
    ``dead`` bool, ``fid``/``candidate`` int32; ``lo*``/``iv*`` the
    quantization window scalars from ``mesh_key_bounds``. The candidate
    INPUT is derived per site with shared semantics (nearest-entry
    overlapped instance): the kernel epilogue walks the TLAS, the XLA
    twin runs ``instance_entry_candidates``."""
    from tpu_render_cluster.render.mesh import morton_dilate5

    def cell(p, lo, iv):
        quantized = jnp.clip((p - lo) * iv * 32.0, 0.0, 31.0)
        return quantized.astype(jnp.int32).astype(jnp.uint32)

    morton = (
        morton_dilate5(cell(px, lox, ivx))
        | (morton_dilate5(cell(py, loy, ivy)) << jnp.uint32(1))
        | (morton_dilate5(cell(pz, loz, ivz)) << jnp.uint32(2))
    )
    one = jnp.uint32(1)
    zero = jnp.uint32(0)
    octant = (
        jnp.where(dx > 0, one, zero)
        | (jnp.where(dy > 0, one, zero) << jnp.uint32(1))
        | (jnp.where(dz > 0, one, zero) << jnp.uint32(2))
    )
    # Clamp in int32, then cast: Mosaic has no unsigned vector min
    # (arith.minui fails to legalize on the chip). Both inputs are
    # non-negative slot / frame indices, so the bits are the same.
    cand_bits = jnp.minimum(candidate, 63).astype(jnp.uint32)
    fid_bits = jnp.minimum(fid, 31).astype(jnp.uint32)
    dead_bit = jnp.where(dead, one, zero) << jnp.uint32(KEY_DEAD_BIT)
    return (
        octant
        | (morton << jnp.uint32(3))
        | (cand_bits << jnp.uint32(18))
        | (fid_bits << jnp.uint32(24))
        | dead_bit
    )


def mesh_key_bounds(lo_w, hi_w):
    """Quantization window for the coherence key: the instance field's
    world AABB union, padded one unit (floor-bounce origins sit ON the
    field's boundary; escaped rays clamp to edge cells harmlessly).
    Returns ([3] lo, [3] 1/span) — frame-dependent only, never
    ray-dependent, so region and whole-frame launches key identically.
    """
    lo = jnp.min(lo_w, axis=0) - 1.0
    hi = jnp.max(hi_w, axis=0) + 1.0
    return lo, 1.0 / jnp.maximum(hi - lo, 1e-6)


def mesh_sort_keys(
    origins, directions, alive, key_lo, key_inv, fid=None, candidate=None,
):
    """XLA twin of the kernel epilogue's key ([R] int32): the INITIAL
    keys of a deep-path launch, before any bounce kernel
    has run to produce the fused column. ``candidate`` (optional [R]
    int32) is the nearest-entry overlapped instance from
    ``instance_entry_candidates``; None packs a neutral 0 (grouping by
    Morton/octant only)."""
    point = origins + directions
    if fid is None:
        fid = jnp.zeros(origins.shape[0], jnp.int32)
    if candidate is None:
        candidate = jnp.zeros(origins.shape[0], jnp.int32)
    key = coherence_key_u32(
        point[:, 0], point[:, 1], point[:, 2],
        directions[:, 0], directions[:, 1], directions[:, 2],
        ~alive, fid, candidate,
        key_lo[0], key_lo[1], key_lo[2],
        key_inv[0], key_inv[1], key_inv[2],
    )
    return key.astype(jnp.int32)


def initial_mesh_sort_keys(mesh, origins, directions, alive):
    """Bounce-0 coherence keys for a TLAS launch, derived from the
    MeshSet: instance world AABBs -> quantization window + nearest-entry
    candidates -> ``mesh_sort_keys``. The site the deep per-bounce path
    (integrator.trace_paths) keys bounce 0 through; it cannot drift from
    the kernel epilogue's fused column (bit-identical on live lanes,
    pinned by tests/test_tlas.py)."""
    from tpu_render_cluster.render.mesh import instance_morton_order

    table = mesh_instance_table(mesh)
    lo_w, hi_w = table[:, 13:16], table[:, 16:19]
    # Candidates are SLOT labels (the Morton-sorted order the kernels'
    # instance table uses), not original-index labels — the epilogue's
    # entry walk reports slots, and slot-adjacent == spatially-adjacent
    # is the grouping the packet cull is tuned for.
    order = instance_morton_order(lo_w, hi_w)
    lo_s, hi_s = lo_w[order], hi_w[order]
    key_lo, key_inv = mesh_key_bounds(lo_s, hi_s)
    return mesh_sort_keys(
        origins, directions, alive, key_lo, key_inv,
        candidate=instance_entry_candidates(origins, directions, lo_s, hi_s),
    )


# ---------------------------------------------------------------------------
# Fused path-trace megakernel: the WHOLE bounce loop in one pallas_call.
#
# The per-bounce XLA pipeline round-trips the path state (origins,
# directions, throughput, radiance, alive — ~5 x [R, 3] f32) through HBM on
# every bounce, which makes the tracer HBM-bound once intersection runs in
# VMEM. This kernel keeps the state resident in VMEM for a block of rays
# across ALL bounces: rays are read once, radiance is written once, and the
# per-bounce sphere pass ([N, BR] intermediates), shading, shadow test, and
# cosine resampling never touch HBM. RNG is a counter-based PCG hash of
# (global ray index, bounce, stream) — no sequential state, so any ray
# block computes identically regardless of grid position or device.


def _pcg_hash(x):
    """PCG output permutation on uint32 (Jarzynski & Olano, GPU RNG survey)."""
    state = x * jnp.uint32(747796405) + jnp.uint32(2891336453)
    shift = (state >> jnp.uint32(28)) + jnp.uint32(4)
    word = ((state >> shift) ^ state) * jnp.uint32(277803737)
    return (word >> jnp.uint32(22)) ^ word


def _uniform_from_hash(h):
    """uint32 -> float32 in [0, 1) using the top 24 bits.

    Mosaic has no uint32->float32 convert rule; the 24-bit word is
    value-preserved by a same-width bitcast to int32 (it is < 2^31), and
    int32->float32 is a supported convert.
    """
    word = jax.lax.bitcast_convert_type(h >> jnp.uint32(8), jnp.int32)
    return word.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


# Rows of one part of the megakernel's gather table (one bf16 sublane
# tile): centre 0:3, albedo 3:6, emission 6:9, radius 9, zeros above.
_GATHER_ROWS = 16


def _bf16_parts(x):
    """Three bfloat16 arrays whose float32 sum ``(hi + mid) + lo`` is ``x``
    bit for bit, for normal float32 ``x``: the top 8, next 8 and last 8
    bits of the significand. Cut with an integer mask, not by rounding
    conversions, so nothing XLA may do to a float32 -> bfloat16 ->
    float32 round trip can empty ``mid`` and ``lo``; each part is already
    a bfloat16 value when it is converted."""

    def top8(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32
        )

    hi = top8(x)
    rest = x - hi  # exact: at most 16 significant bits are left
    mid = top8(rest)
    lo = rest - mid  # exact, at most 8 bits
    return tuple(part.astype(jnp.bfloat16) for part in (hi, mid, lo))


def _gather_table(centers, albedo, emission, radii, padded_n):
    """[3 * _GATHER_ROWS, padded_n] bfloat16: the ``hi``, ``mid`` and
    ``lo`` parts (``_bf16_parts``) of the per-sphere values a hit reads —
    centre, albedo, emission ([N, 3] each) and radius ([N]) — as rows, one
    sublane tile a part, so ``_gather_hit`` fetches all ten with one
    matmul. Spheres N..padded_n are zeros."""
    rows = jnp.concatenate(
        [centers, albedo, emission, radii[:, None]], axis=1
    ).T  # [10, N]
    rows = jnp.pad(
        rows,
        ((0, _GATHER_ROWS - rows.shape[0]), (0, padded_n - rows.shape[1])),
    )
    return jnp.concatenate(_bf16_parts(rows), axis=0)


def _gather_hit(table, sphere_iota, idx):
    """Column ``idx`` of the float32 rows behind ``table``
    (``_gather_table``), bit for bit, for every lane: ``(c_hit [3, BR],
    albedo_hit [3, BR], emission_hit [3, BR], r_hit [1, BR])``.

    A one-hot is 0.0 / 1.0, exact in bfloat16, so ONE bf16 MXU pass with
    float32 accumulation returns each part of each row unchanged (a
    product by 1.0 and sums with zeros), and ``(hi + mid) + lo`` is the
    float32 value: no float32 array is rounded. At ``Precision.HIGHEST``
    the same gather was six passes a table with the ``[N, BR]`` one-hot
    split into three bf16 arrays on the VPU each time, four of them by
    zeros."""
    one_hot = jnp.where(sphere_iota == idx, 1.0, 0.0).astype(jnp.bfloat16)
    parts = jax.lax.dot_general(
        table, one_hot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [3 * _GATHER_ROWS, BR]
    g = _GATHER_ROWS
    rows = (parts[0:g] + parts[g:2 * g]) + parts[2 * g:3 * g]
    return rows[0:3], rows[3:6], rows[6:9], rows[9:10]


# The megakernel's K=3 contractions (c . d, c . o, c . shadow_o) along a
# K of three blocks, one a bf16 part of the ray-side vector: the part
# three times over, an f32 sublane tile each (x, y, z, five zeros), against
# the centres' three parts, then a tile of zeros that fills the block's
# second bf16 tile. 96 of the MXU's 128 rows: one pass. SMALLEST PARTS
# FIRST on both sides (lo, mid, hi): the accumulator adds along K in
# float32, so the three hi x hi products have to come last. With them
# first the pass read further from the float64 product on the chip than
# ``_dot_f32`` (52% of results correctly rounded against 72%); in this
# order 98.6% are (PERF.md, PR 46).
_K3_BLOCK = 4 * _SUBLANE
_K3_DEPTH = 3 * _K3_BLOCK


def _center_stack(centers, padded_n):
    """[padded_n, _K3_DEPTH] bfloat16, the left operand of
    ``_dot_k3_exact``: in each of the three equal blocks of ``_K3_BLOCK``
    columns, columns 0:3, 8:11 and 16:19 are the ``lo``, ``mid`` and
    ``hi`` parts (``_bf16_parts``) of the [N, 3] centres; every other
    column, and spheres N..padded_n, are zeros."""
    tile = jnp.pad(
        centers, ((0, padded_n - centers.shape[0]), (0, _SUBLANE - 3))
    )
    hi, mid, lo = _bf16_parts(tile)
    block = jnp.concatenate([lo, mid, hi, jnp.zeros_like(hi)], axis=1)
    return jnp.tile(block, (1, 3))


def _dot_k3_exact(c_stack, v):
    """[N, BR] float32 ``c . v`` of the centres behind ``c_stack``
    (``_center_stack``) and a [3, BR] float32 ``v``, in ONE bf16 MXU pass.

    ``v`` is cut into its three bf16 parts on the VPU (3 rows, with the
    mask of ``_bf16_parts``) and each part, ``lo`` first, laid against
    all three parts of the centres along K, so the pass sums all nine
    ``part_i(c) * part_j(v)`` terms of every axis in the MXU's float32
    accumulator. A product of two bfloat16 values is exact in float32
    (8 + 8 significand bits), so only the accumulator's sums round, the
    large terms' last: nearer the exact product than ``_dot_f32``, whose
    six passes keep six of the nine terms, split BOTH operands on the VPU
    each time and are summed there. Not the single ROUNDED bf16 pass,
    which is wrong on half the pixels."""
    tile = jnp.concatenate(
        [v, jnp.zeros((_SUBLANE - 3, v.shape[1]), jnp.float32)], axis=0
    )
    zeros = jnp.zeros(tile.shape, jnp.bfloat16)
    rows = []
    for part in reversed(_bf16_parts(tile)):
        rows += [part, part, part, zeros]
    return jax.lax.dot_general(
        c_stack, jnp.concatenate(rows, axis=0), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _trace_kernel_factory(
    max_bounces: int, n_padded: int, lane_io: bool = False,
):
    """Sphere path-trace MEGAKERNEL: the whole bounce loop in one launch,
    state VMEM-resident across all bounces, radiance out. ``lane_io``
    adds a per-lane ORIGINAL lane id row as the RNG counter's source."""
    def kernel(*refs):
        if lane_io:
            # The megakernel with an EXPLICIT lane row: the cluster-tile
            # region path feeds each ray its full-frame lane id, so a
            # cropped launch runs bitwise-identical per-lane math to the
            # whole-frame megakernel (same kernel, same loop — only the
            # RNG counter's source differs).
            (seed_ref, o_ref, d_ref, lane_ref, cstack_ref, r2_ref, csq_ref,
             table_ref, dcsun_ref, params_ref, out_ref) = refs
        else:
            (seed_ref, o_ref, d_ref, cstack_ref, r2_ref, csq_ref, table_ref,
             dcsun_ref, params_ref, out_ref) = refs
        o = o_ref[:, :]  # [3, BR] ray origins
        d = d_ref[:, :]  # [3, BR] ray directions
        c_stack = cstack_ref[:, :]  # [N, 96] bf16, _center_stack
        r2 = r2_ref[:, :]  # [N, 1] radius^2 (0 for padding -> never hits)
        csq = csq_ref[:, :]  # [N, 1] |c|^2
        table = table_ref[:, :]  # [48, N] bf16, _gather_table
        dc_sun = dcsun_ref[:, :]  # [N, 1] c . sun
        # params rows: 0 sun_dir, 1 sun_color, 2 sky_horizon, 3 sky_zenith,
        # 4 plane_albedo_a, 5 plane_albedo_b   (each [1, 3] -> column vecs)
        params = params_ref[:, :]  # [8, 3]
        sun = params[0:1, :].T  # [3, 1]
        sun_color = params[1:2, :].T
        sky_horizon = params[2:3, :].T
        sky_zenith = params[3:4, :].T
        plane_a = params[4:5, :].T
        plane_b = params[5:6, :].T

        block = o.shape[1]
        seed = seed_ref[0, 0].astype(jnp.uint32)
        if lane_io:
            # RNG counters follow the region path's full-frame lane map,
            # not the current position.
            ray_index = lane_ref[:, :].astype(jnp.uint32)
        else:
            ray_index = (
                jax.lax.broadcasted_iota(
                    jnp.int32, (1, block), 1
                ).astype(jnp.uint32)
                + jnp.uint32(pl.program_id(0) * block)
            )
        sphere_iota = jax.lax.broadcasted_iota(jnp.int32, (n_padded, block), 0)

        throughput = jnp.ones((3, block), jnp.float32)
        radiance = jnp.zeros((3, block), jnp.float32)
        alive = jnp.ones((1, block), jnp.float32)

        def bounce_step(bounce, carry):
            o, d, throughput, radiance, alive = carry
            # -- nearest sphere hit (geometry.intersect_spheres' math) ----
            # c . o is made anew although this origin is the last
            # bounce's shadow origin, whose product the sun test made: a
            # one-pass contraction costs less than carrying an [N, BR]
            # value through VMEM (0.67 ms a frame on the chip).
            dc = _dot_k3_exact(c_stack, d)
            oc = _dot_k3_exact(c_stack, o)
            o_sq = jnp.sum(o * o, axis=0, keepdims=True)
            od = jnp.sum(o * d, axis=0, keepdims=True)
            oc_dot_d = dc - od
            oc_sq = o_sq - 2.0 * oc + csq
            disc = oc_dot_d * oc_dot_d - (oc_sq - r2)
            valid = (disc > 0.0) & (r2 > 0.0)
            sqrt_disc = jnp.sqrt(jnp.maximum(disc, 0.0))
            t0 = oc_dot_d - sqrt_disc
            t1 = oc_dot_d + sqrt_disc
            t_all = jnp.where(t0 > EPS, t0, jnp.where(t1 > EPS, t1, INF))
            t_all = jnp.where(valid, t_all, INF)  # [N, BR]
            t_sphere = jnp.min(t_all, axis=0, keepdims=True)  # [1, BR]
            idx = jnp.min(
                jnp.where(t_all == t_sphere, sphere_iota, n_padded),
                axis=0,
                keepdims=True,
            )
            idx = jnp.minimum(idx, n_padded - 1)

            # -- ground plane y = 0 ---------------------------------------
            d_y = d[1:2, :]
            o_y = o[1:2, :]
            denom = jnp.where(jnp.abs(d_y) < 1e-8, 1e-8, d_y)
            t_plane = -o_y / denom
            t_plane = jnp.where(
                (t_plane > EPS) & (jnp.abs(d_y) >= 1e-8), t_plane, INF
            )
            is_plane = (t_plane < t_sphere).astype(jnp.float32)  # [1, BR]
            t = jnp.minimum(t_sphere, t_plane)
            hit = (t < INF).astype(jnp.float32)

            # -- sky on escape --------------------------------------------
            blend = jnp.clip(d[1:2, :], 0.0, 1.0)
            sun_cos_dir = jnp.sum(d * sun, axis=0, keepdims=True)
            sun_disc = jnp.where(sun_cos_dir > 0.9995, 8.0, 0.0)
            sky = (1.0 - blend) * sky_horizon + blend * sky_zenith
            sky = sky + sun_disc * sun_color
            radiance = radiance + throughput * sky * (alive * (1.0 - hit))

            alive = alive * hit
            live = alive > 0.5
            p = o + d * t  # [3, BR]

            # -- the hit sphere's rows: one one-hot matmul, exact ---------
            c_hit, albedo_hit, emission_hit, r_hit = _gather_hit(
                table, sphere_iota, idx
            )

            sphere_normal = (p - c_hit) / jnp.maximum(r_hit, 1e-6)
            plane_normal = jnp.concatenate(
                [
                    jnp.zeros((1, block), jnp.float32),
                    jnp.ones((1, block), jnp.float32),
                    jnp.zeros((1, block), jnp.float32),
                ],
                axis=0,
            )
            normal = is_plane * plane_normal + (1.0 - is_plane) * sphere_normal

            checker = (
                jnp.floor(p[0:1, :]).astype(jnp.int32)
                + jnp.floor(p[2:3, :]).astype(jnp.int32)
            ) % 2
            checker_rgb = jnp.where(checker == 0, plane_a, plane_b)
            albedo = is_plane * checker_rgb + (1.0 - is_plane) * albedo_hit
            emission = (1.0 - is_plane) * emission_hit
            radiance = radiance + throughput * emission * alive

            # -- sun NEE: one any-hit shadow dot (sun dir is uniform) -----
            # The shadow origin is the next bounce's origin. where-select
            # (not multiply-mask): a dead lane keeps its old finite one,
            # so no inf*0 can poison later bounces (its shadow test is
            # masked below).
            shadow_o = jnp.where(live, p + normal * (EPS * 4.0), o)
            oc_s = _dot_k3_exact(c_stack, shadow_o)
            od_s = jnp.sum(shadow_o * sun, axis=0, keepdims=True)
            osq_s = jnp.sum(shadow_o * shadow_o, axis=0, keepdims=True)
            ocd_s = dc_sun - od_s
            ocsq_s = osq_s - 2.0 * oc_s + csq
            disc_s = ocd_s * ocd_s - (ocsq_s - r2)
            valid_s = (disc_s > 0.0) & (r2 > 0.0)
            t1_s = ocd_s + jnp.sqrt(jnp.maximum(disc_s, 0.0))
            shadowed = jnp.max(
                jnp.where(valid_s & (t1_s > EPS), 1.0, 0.0),
                axis=0,
                keepdims=True,
            )
            cos_sun = jnp.maximum(jnp.sum(normal * sun, axis=0, keepdims=True), 0.0)
            direct = (
                albedo * sun_color * (cos_sun * (1.0 - shadowed) * alive)
                / jnp.float32(jnp.pi)
            )
            radiance = radiance + throughput * direct

            # -- continue the path: cosine-weighted resample --------------
            throughput = throughput * (alive * albedo + (1.0 - alive))
            counter = ray_index * jnp.uint32(2 * max_bounces + 2) + jnp.uint32(2) * bounce.astype(jnp.uint32)
            u1 = _uniform_from_hash(_pcg_hash(counter ^ seed))
            u2 = _uniform_from_hash(_pcg_hash((counter + jnp.uint32(1)) ^ seed))
            r = jnp.sqrt(u1)
            phi = jnp.float32(2.0 * jnp.pi) * u2
            x = r * jnp.cos(phi)
            y = r * jnp.sin(phi)
            z = jnp.sqrt(jnp.maximum(0.0, 1.0 - u1))
            helper_x = jnp.where(jnp.abs(normal[0:1, :]) > 0.9, 0.0, 1.0)
            helper_y = 1.0 - helper_x
            # tangent = helper x normal (helper is (hx, hy, 0))
            tx = helper_y * normal[2:3, :]
            ty = -helper_x * normal[2:3, :]
            tz = helper_x * normal[1:2, :] - helper_y * normal[0:1, :]
            tangent = jnp.concatenate([tx, ty, tz], axis=0)
            tangent = tangent / jnp.maximum(
                jnp.sqrt(jnp.sum(tangent * tangent, axis=0, keepdims=True)), 1e-8
            )
            # bitangent = normal x tangent
            bx = normal[1:2, :] * tangent[2:3, :] - normal[2:3, :] * tangent[1:2, :]
            by = normal[2:3, :] * tangent[0:1, :] - normal[0:1, :] * tangent[2:3, :]
            bz = normal[0:1, :] * tangent[1:2, :] - normal[1:2, :] * tangent[0:1, :]
            bitangent = jnp.concatenate([bx, by, bz], axis=0)
            new_d = x * tangent + y * bitangent + z * normal
            d = jnp.where(live, new_d, d)  # dead lanes stay finite, as o
            return (shadow_o, d, throughput, radiance, alive)

        radiance = jax.lax.fori_loop(
            0, max_bounces, bounce_step,
            (o, d, throughput, radiance, alive),
        )[3]
        out_ref[:, :] = radiance

    return kernel


@functools.partial(jax.jit, static_argnames=("max_bounces", "interpret"))
def _trace_fused(
    origins, directions, centers, radii, albedo, emission,
    sun_direction, sun_color, sky_horizon, sky_zenith,
    plane_albedo_a, plane_albedo_b, seed,
    *, max_bounces: int, interpret: bool, lane=None,
):
    rays = origins.shape[0]
    padded_rays = -(-rays // BLOCK_R) * BLOCK_R
    ray_pad = padded_rays - rays
    o_t = jnp.pad(origins, ((0, ray_pad), (0, 0))).T
    d_t = jnp.pad(directions, ((0, ray_pad), (0, 0))).T
    lane_t = (
        None
        if lane is None
        else jnp.pad(jnp.asarray(lane, jnp.int32), (0, ray_pad))[None, :]
    )

    n = centers.shape[0]
    padded_n = -(-n // _SUBLANE) * _SUBLANE
    sphere_pad = padded_n - n
    c_t = jnp.pad(centers, ((0, sphere_pad), (0, 0))).T  # [3, Np]
    radii_p = jnp.pad(radii, (0, sphere_pad))
    r2 = (radii_p * radii_p)[:, None]
    csq = jnp.sum(c_t * c_t, axis=0)[:, None]
    c_stack = _center_stack(centers, padded_n)
    table = _gather_table(centers, albedo, emission, radii, padded_n)
    dc_sun = _center_dot_sun(c_t, sun_direction)  # [Np, 1]

    params = jnp.zeros((8, 3), jnp.float32)
    params = params.at[0].set(sun_direction)
    params = params.at[1].set(sun_color)
    params = params.at[2].set(sky_horizon)
    params = params.at[3].set(sky_zenith)
    params = params.at[4].set(plane_albedo_a)
    params = params.at[5].set(plane_albedo_b)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    grid = (padded_rays // BLOCK_R,)
    whole = lambda i: (0, 0)  # noqa: E731 - scene blocks replicated per step
    ray_block = pl.BlockSpec((3, BLOCK_R), lambda i: (0, i), memory_space=pltpu.VMEM)
    lane_block = pl.BlockSpec((1, BLOCK_R), lambda i: (0, i), memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
        ray_block,
        ray_block,
        *([lane_block] if lane_t is not None else []),
        pl.BlockSpec(c_stack.shape, whole, memory_space=pltpu.VMEM),
        pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
        pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
        pl.BlockSpec(table.shape, whole, memory_space=pltpu.VMEM),
        pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
        pl.BlockSpec((8, 3), whole, memory_space=pltpu.VMEM),
    ]
    operands = [seed_arr, o_t, d_t]
    if lane_t is not None:
        operands.append(lane_t)
    operands += [c_stack, r2, csq, table, dc_sun, params]
    out = pl.pallas_call(
        _trace_kernel_factory(max_bounces, padded_n, lane_io=lane_t is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[ray_block],
        out_shape=[jax.ShapeDtypeStruct((3, padded_rays), jnp.float32)],
        interpret=interpret,
    )(*operands)[0]
    return out.T[:rays]


def trace_paths_fused(
    scene, origins, directions, seed, *, max_bounces: int, lane=None
):
    """Fused megakernel path trace; drop-in for integrator.trace_paths.

    ``seed`` is an int32 scalar (derived from the frame/tile) driving the
    in-kernel counter-based PCG RNG; radiance is returned as [R, 3].
    ``lane`` (optional [R] int32) overrides the positional RNG counters —
    the cluster-tile region path passes full-frame lane ids so a cropped
    launch reproduces the whole-frame image bitwise on its pixels.
    """
    return _trace_fused(
        origins,
        directions,
        scene.centers,
        scene.radii,
        scene.albedo,
        scene.emission,
        scene.sun_direction,
        scene.sun_color,
        scene.sky_horizon,
        scene.sky_zenith,
        scene.plane_albedo_a,
        scene.plane_albedo_b,
        seed,
        max_bounces=max_bounces,
        interpret=_interpret(),
        lane=lane,
    )


# ---------------------------------------------------------------------------
# Stackless threaded-BVH packet traversal (SURVEY.md §7 hard part #4)
#
# One ray block walks the BVH with a single scalar node index (the threaded
# skip-link layout from render/mesh.py): the scalar unit steers the walk,
# the VPU tests the whole block against each node's AABB and — branchlessly
# — against the LEAF_SIZE-aligned triangle slot. Node metadata (skip /
# first / count and the 6 AABB scalars) lives in SMEM where dynamic scalar
# indexing is native; triangle data stays in VMEM and is fetched with a
# tile-aligned dynamic sublane slice (leaves occupy aligned 8-row slots by
# construction).

BVH_DONE_EPS = 1e-12
# What a launch over a streamed BLAS counts, in the order it returns them.
# A node visit is a step a packet paid: a box test (one node of the
# resident top, or a wide node's eight children at once) or a leaf's
# triangles; entries and group tests split the box tests inside treelets;
# a prefetch is a fetch started while another treelet of the same walk was
# still to be walked (the rest are a walk's first).
WALK_COUNTS = (
    "node_visits", "treelet_fetches", "leaf_tests", "treelet_entries",
    "group_tests", "treelet_prefetches",
)
# Mesh-megakernel bound: the fused whole-bounce-loop kernel takes a mesh
# whose bvh_nodes x instances is at most this, and refuses a deeper one
# (``trace_paths_fused_mesh`` raises): deeper walks pay more for the
# in-kernel normal tracking than the fusion saves, and no test holds the
# kernel to the XLA loop past it (ROADMAP D19).
MESH_MEGAKERNEL_MAX_WALK = 1024


def mesh_megakernel_eligible(mesh) -> bool:
    """Single source of truth for the megakernel/per-bounce dispatch.

    Both trace_paths (which kernel) and render_tile (whether to flatten
    sample streams onto the ray axis) must agree — a drifted copy would
    flatten samples for a scene that then takes the per-bounce walk,
    hitting the packet-coherence cliff flattening is gated against.
    """
    if mesh.bvh.stream is not None:
        return False  # the megakernel holds its BLAS whole
    return (
        mesh.bvh.skip.shape[0] * mesh.instances.translation.shape[0]
        <= MESH_MEGAKERNEL_MAX_WALK
    )


def _pad_rays_to_miss(origins, directions, block: int):
    """Block-pad rays so pad lanes provably MISS the tree.

    A zero pad direction would turn the slab test degenerate (inv ~ 1e12
    hits every AABB) and — through the packet-wide any() — strip all BVH
    culling from the final block. A far-away origin with a perpendicular
    unit direction misses the root.
    """
    rays = origins.shape[0]
    padded_rays = -(-rays // block) * block
    ray_pad = padded_rays - rays
    o_t = jnp.pad(origins, ((0, ray_pad), (0, 0)), constant_values=1e7).T
    d_t = jnp.pad(directions, ((0, ray_pad), (0, 0))).T
    if ray_pad:
        d_t = d_t.at[1, rays:].set(1.0)
    return o_t, d_t, rays, padded_rays


def _instance_table(rotation, translation, scale, bounds_min, bounds_max,
                    albedo=None, *, model=None, top_first=None):
    """[K, 22] SMEM table: rotation row-major (0..8), translation (9..11),
    1/scale (12), the instance's WORLD-space AABB (13..18) — the top-level
    cull the kernel applies before paying for the object-space walk — and
    the instance albedo (19..21; zeros when the caller doesn't need it).

    World AABB of a transformed box: center_w = s R c_o + t,
    half_w = s |R| h_o (elementwise absolute rotation).

    Over streamed BLASes (``top_first``: ``mesh.BlasStream.top_first``)
    row ``m`` of ``bounds_min`` / ``bounds_max`` is model ``m``'s root
    box, instance ``k`` is model ``model[k]`` (None: model 0), and the
    table is [K, 23]: column 22 is the wide node of the resident top
    where the instance's walk begins, its model's root (a whole number
    far under 2**24, exact in f32).
    """
    k = rotation.shape[0]

    def world_box(root):
        center_obj = 0.5 * (bounds_min[root] + bounds_max[root])
        half_obj = 0.5 * (bounds_max[root] - bounds_min[root])
        center_w = (
            scale[:, None] * jnp.einsum(
                "kij,j->ki", rotation, center_obj, precision="highest"
            )
            + translation
        )
        half_w = scale[:, None] * jnp.einsum(
            "kij,j->ki", jnp.abs(rotation), half_obj, precision="highest"
        )
        return jnp.concatenate([center_w - half_w, center_w + half_w], axis=1)

    box = world_box(0)  # the root node
    top_root = []
    if top_first is not None:
        if model is None:
            model = jnp.zeros((k,), jnp.int32)
        # one box per model by the one expression, each instance its own's
        for m in range(1, top_first.shape[0] - 1):
            box = jnp.where((model == m)[:, None], world_box(m), box)
        top_root = [top_first[model].astype(jnp.float32)[:, None]]
    if albedo is None:
        albedo = jnp.zeros((k, 3), jnp.float32)
    return jnp.concatenate(
        [
            rotation.reshape(k, 9),
            translation,
            (1.0 / scale)[:, None],
            box,
            albedo,
            *top_root,
        ],
        axis=1,
    )


def mesh_instance_table(mesh):
    """``_instance_table`` of a MeshSet without albedo: each instance's
    world box from its own model's root box."""
    bvh, instances = mesh
    blas_of = {} if bvh.stream is None else dict(
        model=instances.model, top_first=bvh.stream.top_first
    )
    return _instance_table(
        instances.rotation, instances.translation, instances.scale,
        bvh.bounds_min, bvh.bounds_max, **blas_of,
    )


def instance_entry_candidates(origins, directions, lo_w, hi_w):
    """Per-ray broadphase: nearest-entry overlapped instance world AABB.

    One fused [R, K] slab-test pass; returns [R] int32 with K (= the
    instance count) for rays overlapping nothing. The candidate of the
    bounce-0 coherence sort key (``initial_mesh_sort_keys``), with the
    semantics of the bounce kernel's epilogue walk — a single copy so an
    epsilon change can't desynchronize the sort from the kernel's walk
    order.
    """
    small = jnp.abs(directions) < 1e-12
    inv = 1.0 / jnp.where(
        small, jnp.where(directions < 0, -1e-12, 1e-12), directions
    )
    t0 = (lo_w[None, :, :] - origins[:, None, :]) * inv[:, None, :]
    t1 = (hi_w[None, :, :] - origins[:, None, :]) * inv[:, None, :]
    near = jnp.max(jnp.minimum(t0, t1), axis=2)  # [R, K]
    far = jnp.min(jnp.maximum(t0, t1), axis=2)
    overlap = far >= jnp.maximum(near, 0.0)
    entry = jnp.where(overlap, jnp.maximum(near, 0.0), jnp.float32(INF))
    return jnp.where(
        jnp.any(overlap, axis=1),
        jnp.argmin(entry, axis=1),
        lo_w.shape[0],
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Mesh megakernel: the WHOLE bounce loop for mesh scenes in one kernel.
#
# The sphere megakernel (_trace_kernel_factory) keeps path state
# VMEM-resident across bounces, and this kernel does the same for mesh
# scenes: per bounce it runs the sphere/plane nearest hit, an IN-KERNEL
# instanced threaded-BVH walk (fori over instances, while over nodes, or
# a TLAS walk over instance groups above it), sun NEE with both sphere and
# mesh any-hit occlusion, and the counter-based PCG resample. Per-lane
# mesh normals/albedo are tracked through winner one-hots during the leaf
# pass (TPU Pallas has no per-lane vector gather); shadow rays toward the
# uniform sun direction transform per instance as SCALARS.
#
# The sphere/plane/sky/NEE/resample physics is intentionally the same
# code shape as _trace_kernel_factory; both kernels are pinned to the ONE
# XLA reference implementation by deterministic single-bounce equivalence
# tests (test_pallas_kernels.py, test_mesh_megakernel.py), so a physics
# edit applied to only one kernel fails its test rather than silently
# diverging.


def slab_mask(node, ox, oy, oz, invx, invy, invz, limit):
    """The mesh kernels' packet test of a box (``slab_any``) on a wide
    node's eight children at once. ``node`` is the node's ``[8, >= 7]``
    block of a staged treelet (``mesh.BlasStream``): a child a sublane;
    lo xyz, hi xyz and the child's bit ``1 << c`` along the lanes. The
    rays' components are ``[1, block]`` rows (or scalars). The same
    arithmetic on the same float32 boxes as eight ``slab_any``s, in one
    ``[8, block]`` tile; the children some lane's ray meets ahead of it
    and nearer than its ``limit`` come back as ONE scalar mask, so the
    walk waits for the scalar side once a node, not once a box. The test
    is symmetric in lo and hi, so an empty slot's inverted box would pass
    it: its bit is 0."""
    lox = (node[:, 0:1] - ox) * invx
    hix = (node[:, 3:4] - ox) * invx
    loy = (node[:, 1:2] - oy) * invy
    hiy = (node[:, 4:5] - oy) * invy
    loz = (node[:, 2:3] - oz) * invz
    hiz = (node[:, 5:6] - oz) * invz
    tnear = jnp.maximum(
        jnp.maximum(jnp.minimum(lox, hix), jnp.minimum(loy, hiy)),
        jnp.minimum(loz, hiz),
    )
    tfar = jnp.minimum(
        jnp.minimum(jnp.maximum(lox, hix), jnp.maximum(loy, hiy)),
        jnp.maximum(loz, hiz),
    )
    packet_hit = (tfar >= jnp.maximum(tnear, 0.0)) & (tnear < limit)
    child_hit = jnp.max(
        jnp.where(packet_hit, 1.0, 0.0), axis=1, keepdims=True
    )
    return jnp.sum(child_hit * node[:, 6:7]).astype(jnp.int32)


def _mesh_trace_kernel_factory(
    max_bounces: int, n_padded: int, n_nodes: int, leaf_size: int,
    k_count: int, state_io: bool = False, use_tlas: bool = False,
    tlas_nodes: int = 0, quant: int = 0, ordered: bool = False,
    tlas_ordered: bool = False, stream: int | None = None,
):
    """Mesh path-trace kernel. Two shapes share one bounce_step:

    - state_io=False: the whole-bounce-loop MEGAKERNEL (state VMEM-resident
      across all bounces, radiance out) — shallow-walk scenes.
    - state_io=True: ONE bounce per launch with path state streamed in/out
      (o, d, throughput, alive + this bounce's radiance contribution), so
      the integrator can re-sort rays for packet coherence between bounces
      while everything else (sphere+plane+mesh nearest, NEE with both
      any-hits, shading, in-kernel PCG resample) stays fused — deep-walk
      scenes. ``max_bounces`` still names the TOTAL bounce count so the
      per-(ray, bounce) RNG counters match the megakernel's stream layout.

    ``stream`` = the leaf slots of a treelet makes the BLAS operands HBM
    tables (``mesh.BlasStream``: one BLAS or a set of them) in place of the
    resident triangle and node blocks: the walk runs over the wide nodes
    of the resident top of the instance's tree, whose root the instance
    table carries, and copies a treelet's slab (triangle rows and wide
    nodes) into one of two scratch slots, one treelet ahead of the one the
    packet walks inside (``stream_walk``): top or treelet, a wide node's
    eight child boxes in one ``[8, block]`` test (``slab_mask``), a
    treelet's box in its parent's and a leaf's in its group's. The
    leaves come in the resident walk's order over the same tree, the
    boxes, the triangles and the arithmetic on them are the same, and a
    wide test culls with the best-t it had before its children ran: it
    meets the resident walk's leaves and some it would have culled, which
    change nothing. One more output row carries each block's counts
    (``WALK_COUNTS``).
    """
    contract_first = (((0,), (0,)), ((), ()))
    if stream is not None and not state_io:
        raise ValueError("a streamed BLAS runs one bounce a launch")

    def kernel(*refs):
        # Fixed-prefix unpacking, then the BLAS node block (fp32: 5 SMEM
        # refs; quantized: packed bq/meta words + grid scalars), the
        # optional TLAS node block (same two formats), the key-bounds
        # scalars + fused sort-key output (streamed-state TLAS kernels
        # only — flat kernels keep today's signature so the A/B baseline
        # is untouched), and finally the state outputs.
        refs = list(refs)

        def take(n):
            out, refs[:n] = tuple(refs[:n]), []
            return out

        if state_io:
            (seed_ref, bounce_ref, live_ref, o_ref, d_ref, thr_ref,
             alive_ref, lane_ref,
             c_ref, r2_ref, csq_ref, rad_ref, albedo_ref, emission_ref,
             dcsun_ref, params_ref, sunsm_ref, inst_ref) = take(18)
        else:
            (seed_ref, o_ref, d_ref, c_ref, r2_ref, csq_ref, rad_ref,
             albedo_ref, emission_ref, dcsun_ref, params_ref, sunsm_ref,
             inst_ref) = take(13)
        if stream is not None:
            # The BLAS in HBM and its resident top (wide nodes: boxes in
            # VMEM, links in SMEM); scratch comes last: two slots for
            # staged treelets, which treelet each holds, a semaphore a
            # slot, and the top walk's stack.
            (tri_hbm, top_ref, link_ref) = take(3)
            (tri_buf, staged_ref, dma_sem, stack_ref) = refs[-4:]
            del refs[-4:]
        else:
            (v0_ref, e1_ref, e2_ref, nrm_ref) = take(4)
            if quant:
                (bq_ref, bmeta_ref, bgrid_ref) = take(3)
            else:
                (bmin_ref, bmax_ref, skip_ref, first_ref,
                 count_ref) = take(5)
        if use_tlas:
            if quant:
                (tbq_ref, tmeta_ref, tgrid_ref) = take(3)
            else:
                (tbmin_ref, tbmax_ref, tskip_ref, tfirst_ref,
                 tcount_ref) = take(5)
        if state_io and use_tlas:
            (keysm_ref,) = take(1)
        if stream is not None:
            stats_ref = refs.pop()
        if state_io and use_tlas:
            (out_ref, o_out_ref, d_out_ref, thr_out_ref, alive_out_ref,
             key_out_ref) = refs
        elif state_io:
            (out_ref, o_out_ref, d_out_ref, thr_out_ref,
             alive_out_ref) = refs
        else:
            (out_ref,) = refs

        # -- node-table readers -----------------------------------------
        # ONE reconstruction per format, shared by every walk below. The
        # quantized form reads 1-2 int32 words per node and reconstructs
        # slabs as origin + q * cell in f32 — conservatively OUTSIDE the
        # fp32 box by construction (mesh.quantize_node_tables), so culls
        # stay exact-superset and results bit-identical. Meta packs
        # skip | first/unit << 16 | count << 27 into one scalar read.

        def _read_packed_bounds(bqr, gridr, node):
            if quant == 1:
                w0, w1, w2 = bqr[node, 0], bqr[node, 1], bqr[node, 2]
                qlx, qhx = w0 & 0xFFFF, (w0 >> 16) & 0xFFFF
                qly, qhy = w1 & 0xFFFF, (w1 >> 16) & 0xFFFF
                qlz, qhz = w2 & 0xFFFF, (w2 >> 16) & 0xFFFF
            else:
                w0, w1 = bqr[node, 0], bqr[node, 1]
                qlx, qly = w0 & 0xFF, (w0 >> 8) & 0xFF
                qlz, qhx = (w0 >> 16) & 0xFF, (w0 >> 24) & 0xFF
                qhy, qhz = w1 & 0xFF, (w1 >> 8) & 0xFF
            gx, gy, gz = gridr[0], gridr[1], gridr[2]
            cx, cy, cz = gridr[3], gridr[4], gridr[5]
            return (
                gx + qlx.astype(jnp.float32) * cx,
                gy + qly.astype(jnp.float32) * cy,
                gz + qlz.astype(jnp.float32) * cz,
                gx + qhx.astype(jnp.float32) * cx,
                gy + qhy.astype(jnp.float32) * cy,
                gz + qhz.astype(jnp.float32) * cz,
            )

        def _read_meta(metar, node, unit):
            meta = metar[node]
            return (
                meta & 0xFFFF,
                ((meta >> 16) & 0x7FF) * unit,
                (meta >> 27) & 0x1F,
            )

        def blas_node(node):
            """(6 slab scalars, skip, leaf start, leaf count)."""
            if quant:
                return (
                    _read_packed_bounds(bq_ref, bgrid_ref, node),
                    *_read_meta(bmeta_ref, node, leaf_size),
                )
            return (
                (bmin_ref[node, 0], bmin_ref[node, 1], bmin_ref[node, 2],
                 bmax_ref[node, 0], bmax_ref[node, 1], bmax_ref[node, 2]),
                skip_ref[node], first_ref[node], count_ref[node],
            )

        if use_tlas:
            def tlas_node(node):
                if quant:
                    return (
                        _read_packed_bounds(tbq_ref, tgrid_ref, node),
                        *_read_meta(tmeta_ref, node, 1),
                    )
                return (
                    (tbmin_ref[node, 0], tbmin_ref[node, 1],
                     tbmin_ref[node, 2],
                     tbmax_ref[node, 0], tbmax_ref[node, 1],
                     tbmax_ref[node, 2]),
                    tskip_ref[node], tfirst_ref[node], tcount_ref[node],
                )
        def slab_any(bounds, ox, oy, oz, invx, invy, invz, limit):
            """THE packet test of a node's box, shared by every walk
            below (TLAS, resident BLAS, streamed BLAS): whether any lane's
            ray meets the six slab scalars ``bounds`` ahead of it and
            nearer than its ``limit``."""
            nlx, nly, nlz, nhx, nhy, nhz = bounds
            lox = (nlx - ox) * invx
            hix = (nhx - ox) * invx
            loy = (nly - oy) * invy
            hiy = (nhy - oy) * invy
            loz = (nlz - oz) * invz
            hiz = (nhz - oz) * invz
            tnear = jnp.maximum(
                jnp.maximum(jnp.minimum(lox, hix), jnp.minimum(loy, hiy)),
                jnp.minimum(loz, hiz),
            )
            tfar = jnp.minimum(
                jnp.minimum(jnp.maximum(lox, hix), jnp.maximum(loy, hiy)),
                jnp.maximum(loz, hiz),
            )
            packet_hit = (tfar >= jnp.maximum(tnear, 0.0)) & (tnear < limit)
            return jnp.any(packet_hit)

        if use_tlas:
            # THE threaded skip-link walk over TLAS node slabs, shared
            # by the nearest, any-hit, and key-epilogue entry walks
            # (same rule as the BLAS walk_step: a traversal/epsilon fix
            # lands once). Call sites differ only in the ray components,
            # the per-lane ``limit_of(carry)`` driving the packet test,
            # and the ``leaf_body`` fori callback over a leaf's slot
            # range; ``carry`` is a tuple.
            def tlas_walk(
                node0, node_end, tbase, ox, oy, oz, ix, iy, iz,
                limit_of, leaf_body, carry,
            ):
                def cond(walk):
                    return walk[0] < node_end

                def body(walk):
                    node = walk[0]
                    carry = tuple(walk[1:])
                    limit = limit_of(carry)
                    bounds, nskip, start, cnt = tlas_node(tbase + node)
                    hit_any = slab_any(bounds, ox, oy, oz, ix, iy, iz, limit)
                    is_leaf = cnt > 0
                    next_node = jnp.where(
                        hit_any,
                        jnp.where(is_leaf, nskip, node + 1),
                        nskip,
                    )
                    carry = jax.lax.cond(
                        is_leaf & hit_any,
                        lambda: jax.lax.fori_loop(
                            start, start + cnt, leaf_body, carry
                        ),
                        lambda: carry,
                    )
                    return (next_node, *carry)

                return tuple(
                    jax.lax.while_loop(cond, body, (node0, *carry))
                )[1:]

        o = o_ref[:, :]  # [3, BR]
        d = d_ref[:, :]
        c = c_ref[:, :]
        r2 = r2_ref[:, :]
        csq = csq_ref[:, :]
        radius = rad_ref[:, :]
        albedo_t = albedo_ref[:, :]
        emission_t = emission_ref[:, :]
        dc_sun = dcsun_ref[:, :]
        params = params_ref[:, :]
        sun = params[0:1, :].T
        sun_color = params[1:2, :].T
        sky_horizon = params[2:3, :].T
        sky_zenith = params[3:4, :].T
        plane_a = params[4:5, :].T
        plane_b = params[5:6, :].T

        block = o.shape[1]
        seed = seed_ref[0, 0].astype(jnp.uint32)
        if state_io:
            # RNG counters follow the ORIGINAL lane id the integrator
            # threads through its re-sorts — a ray keeps its stream
            # wherever the permutation lands it (the megakernel's
            # positional index IS the original lane there, since it
            # never reorders).
            ray_index = lane_ref[:, :].astype(jnp.uint32)
        else:
            ray_index = (
                jax.lax.broadcasted_iota(
                    jnp.int32, (1, block), 1
                ).astype(jnp.uint32)
                + jnp.uint32(pl.program_id(0) * block)
            )
        sphere_iota = jax.lax.broadcasted_iota(jnp.int32, (n_padded, block), 0)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (leaf_size, block), 0)

        def winv(v):
            small = jnp.abs(v) < 1e-12
            return 1.0 / jnp.where(small, jnp.where(v < 0, -1e-12, 1e-12), v)

        def _octant_of(dx, dy, dz):
            # Majority vote over the packet's lanes (scalar dirs reduce
            # over one element): any octant's table is exact; matching
            # just shrinks best-t sooner.
            def bit(v, shift):
                positive = jnp.sum(jnp.where(v > 0.0, 1.0, 0.0))
                return jnp.where(
                    positive * 2.0 > float(jnp.size(v)),
                    jnp.int32(1 << shift),
                    jnp.int32(0),
                )

            return bit(dx, 0) | bit(dy, 1) | bit(dz, 2)

        if ordered:
            # Octant-ordered tables (sah builds): the BLAS node block is
            # EIGHT re-threadings stacked [8N]; each walk picks the table
            # whose near-first child order matches its (object-space)
            # direction octant.
            def blas_base(dx, dy, dz):
                return _octant_of(dx, dy, dz) * jnp.int32(n_nodes)
        else:
            def blas_base(dx, dy, dz):
                return jnp.int32(0)

        if tlas_ordered:
            # Same trick one level up: the TLAS node block is stacked
            # [8M] with the axis-by-depth near-first orders
            # (mesh.TlasTopology.octant_*); world-space direction octant
            # picks the table.
            def tlas_base(dx, dy, dz):
                return _octant_of(dx, dy, dz) * jnp.int32(tlas_nodes)
        else:
            def tlas_base(dx, dy, dz):
                return jnp.int32(0)

        def walk_step(node, obase, ox, oy, oz, dx, dy, dz, invx, invy,
                      invz, limit):
            """One threaded-BVH step shared by BOTH in-kernel walks.

            Slab-tests the node and advances the skip-link cursor. The
            [leaf_size, BR] Möller–Trumbore test lives in ``leaf_tcand``
            and runs only under a scalar branch at the call sites
            (``do_leaf`` = is_leaf & hit_any — the whole block walks the
            same node, so the predicate is scalar): internal nodes and
            culled subtrees skip the walk's dominant vector work entirely.
            ``obase`` is the walk's octant-table row offset (0 when the
            build ships a single canonical order); skip links are local,
            so only the reads offset. Returns (next_node, leaf start,
            leaf count, do_leaf).
            """
            bounds, nskip, start, count = blas_node(obase + node)
            hit_any = slab_any(bounds, ox, oy, oz, invx, invy, invz, limit)
            is_leaf = count > 0
            next_node = jnp.where(
                hit_any,
                jnp.where(is_leaf, nskip, node + 1),
                nskip,
            )
            return next_node, start, count, is_leaf & hit_any

        def leaf_tcand(start, count, ox, oy, oz, dx, dy, dz):
            """Möller–Trumbore over the aligned leaf slot at ``start``.

            Direction components may be [1, BR] vectors (nearest) or
            scalars (shadow rays toward the uniform sun). Returns
            (tri_hit [L, BR], t_cand [L, BR]).
            """
            v0b = v0_ref[pl.dslice(start, leaf_size), :]
            e1b = e1_ref[pl.dslice(start, leaf_size), :]
            e2b = e2_ref[pl.dslice(start, leaf_size), :]
            return triangle_tcand(
                v0b, e1b, e2b, count, ox, oy, oz, dx, dy, dz
            )

        def triangle_tcand(v0b, e1b, e2b, count, ox, oy, oz, dx, dy, dz):
            """``leaf_tcand``'s test on a leaf's rows, wherever they were
            read from (the resident tables or a staged treelet)."""
            v0x, v0y, v0z = v0b[:, 0:1], v0b[:, 1:2], v0b[:, 2:3]
            e1x, e1y, e1z = e1b[:, 0:1], e1b[:, 1:2], e1b[:, 2:3]
            e2x, e2y, e2z = e2b[:, 0:1], e2b[:, 1:2], e2b[:, 2:3]
            pvx = dy * e2z - dz * e2y
            pvy = dz * e2x - dx * e2z
            pvz = dx * e2y - dy * e2x
            det = e1x * pvx + e1y * pvy + e1z * pvz
            inv_det = 1.0 / jnp.where(
                jnp.abs(det) < BVH_DONE_EPS, BVH_DONE_EPS, det
            )
            tvx, tvy, tvz = ox - v0x, oy - v0y, oz - v0z
            u = (tvx * pvx + tvy * pvy + tvz * pvz) * inv_det
            qvx = tvy * e1z - tvz * e1y
            qvy = tvz * e1x - tvx * e1z
            qvz = tvx * e1y - tvy * e1x
            v = (dx * qvx + dy * qvy + dz * qvz) * inv_det
            tt = (e2x * qvx + e2y * qvy + e2z * qvz) * inv_det
            tri_hit = (
                (jnp.abs(det) > BVH_DONE_EPS)
                & (u >= 0.0)
                & (v >= 0.0)
                & (u + v <= 1.0)
                & (tt > EPS)
                & (lanes < count)
            )
            t_cand = jnp.where(tri_hit, tt, INF)
            return tri_hit, t_cand

        if stream is not None:
            leaf_slots = stream
            no_counts = (jnp.int32(0),) * len(WALK_COUNTS)

            def slab_copy(treelet, slot):
                return pltpu.make_async_copy(
                    tri_hbm.at[treelet], tri_buf.at[slot], dma_sem.at[slot]
                )

            def start_treelet(treelet, busy):
                """Start the copy of ``treelet`` (-1: none) unless a slot
                holds it already; a new one goes to the slot that is not
                ``busy``, the one whose treelet is still to be walked.
                Returns (its slot, 1 where a copy was started: whoever
                enters the treelet waits for that copy first)."""
                in_first = staged_ref[0] == treelet
                in_second = staged_ref[1] == treelet
                slot = jnp.where(
                    in_first, 0, jnp.where(in_second, 1, 1 - busy)
                )
                fetch = (treelet >= 0) & jnp.logical_not(
                    in_first | in_second
                )

                @pl.when(fetch)
                def _():
                    staged_ref[slot] = treelet
                    slab_copy(treelet, slot).start()

                return slot, fetch.astype(jnp.int32)

            def staged_leaf(slot, leaf):
                """The 16 rows of local leaf ``leaf`` of the treelet in
                ``slot`` with its 12 columns brought to lanes 0..11: v0,
                e1, e2, normal."""
                rows = tri_buf[
                    slot,
                    pl.ds(pl.multiple_of((leaf >> 3) * leaf_size, leaf_size),
                          leaf_size), :
                ]
                return pltpu.roll(rows, (128 - (leaf & 7) * 16) & 127, 1)

            def stream_walk(
                k, touch, ox, oy, oz, invx, invy, invz, limit_of, on_leaf,
                carry, stats,
            ):
                """The walk of instance ``k``'s BLAS over HBM tables: the
                wide nodes of its resident top from its root (the instance
                table's column 22) down, and under a treelet child the
                treelet it names, staged and walked in place. The top is
                walked one treelet ahead: the copy of the next treelet the
                packet will enter is started before the current one is
                walked, and waited for where that one is entered, so every
                copy started is waited for once and none outlives the walk.
                ``carry`` is the walk's tuple of [1, BR] rows,
                ``limit_of(carry)`` the per-lane cull distance,
                ``on_leaf(carry, rows)`` the update by a leaf's 16 staged
                rows; ``stats`` = the walk's counts so far
                (``WALK_COUNTS``). Returns (carry, stats)."""
                width = len(carry)

                def lowest(mask):
                    """(The lowest set bit of ``mask``, its index): scalar
                    arithmetic alone (the index by halving), no vector is
                    waited for."""
                    low = mask & -mask
                    return low, (
                        jnp.where((low & 0xAA) != 0, 1, 0)
                        + jnp.where((low & 0xCC) != 0, 2, 0)
                        + jnp.where((low & 0xF0) != 0, 4, 0)
                    )

                def children(mask, step, state):
                    """``step(child, state)`` for each set bit of ``mask``,
                    lowest first: a turn a child met, none for the others,
                    and scalar arithmetic alone between turns."""
                    def turn(walk):
                        low, child = lowest(walk[0])
                        return (walk[0] ^ low, *step(child, tuple(walk[1:])))

                    return jax.lax.while_loop(
                        lambda walk: walk[0] != 0, turn, (mask, *state)
                    )[1:]

                def inside(slot, carry):
                    """The two levels of wide nodes of the treelet in
                    ``slot``: the root's children are its groups, a
                    group's its leaves, both in the binary tree's order.
                    The root's own box was a child's of the top, tested
                    already, by a limit no sharper than the one the root's
                    test reads: a treelet found under an older limit (its
                    parent's mask is made when the parent is entered, and
                    the look-ahead runs a treelet early) that the hits
                    since have culled meets no group here. A leaf's rows
                    are tested whole: its padding rows are zero and meet
                    no ray. Returns (carry, leaves tested, groups
                    tested)."""
                    nodes = tri_buf[slot, pl.ds(2 * leaf_slots, 8), :]

                    def met(node, carry):
                        return slab_mask(
                            node, ox, oy, oz, invx, invy, invz,
                            limit_of(carry),
                        )

                    # state: the carry, then leaves and groups tested
                    def in_leaf(group, child, state):
                        carry = on_leaf(
                            state[:width],
                            staged_leaf(slot, group * 8 + child),
                        )
                        return (*carry, state[-2] + 1, state[-1])

                    def in_group(group, state):
                        return children(
                            met(
                                pltpu.roll(nodes, 120 - group * 8, 1),
                                state[:width],
                            ),
                            functools.partial(in_leaf, group),
                            (*state[:-1], state[-1] + 1),
                        )

                    *carry, leaves, groups = children(
                        met(nodes, carry), in_group,
                        (*carry, jnp.int32(0), jnp.int32(0)),
                    )
                    return tuple(carry), leaves, groups

                def next_child(depth):
                    """Pop the next child the top walk is to meet: the
                    lowest bit left of the deepest level's mask; a level
                    with none left is dropped. Scalar loads, stores and
                    arithmetic alone. Returns (the child's link, or 0
                    where the stack is empty; the depth after)."""
                    top = jnp.maximum(depth - 1, 0)
                    live = depth > 0
                    # an empty stack holds another walk's words or none
                    wide = jnp.where(live, stack_ref[2 * top], 0)
                    mask = jnp.where(live, stack_ref[2 * top + 1], 0)
                    low, child = lowest(mask)
                    stack_ref[2 * top + 1] = mask ^ low
                    return (
                        jnp.where(live, link_ref[wide * 8 + child], 0),
                        depth - jnp.where(live & (mask == low), 1, 0),
                    )

                def find_next(link, depth, limit, visits):
                    """The walk of the resident top from where it stands
                    (``link``: the child to meet now, ``-1 - v`` for wide
                    node ``v``; the stack's ``depth`` levels: the children
                    left after it) to the next treelet the packet meets
                    nearer than ``limit``. A wide child's eight boxes are
                    tested at once, by the limit as it stands now, and
                    what they leave is pushed as one mask: its later
                    children are met under that limit, not their own
                    moment's (never too sharp, so none is lost; a treelet
                    a nearer hit has since culled is entered and meets no
                    group, ``inside``). Returns (the depth, the treelet or
                    -1 where the walk is done, the wide tests counted)."""
                    def enter(find):
                        link, depth, visits = find
                        wide = -1 - link
                        tile = top_ref[
                            pl.ds(pl.multiple_of((wide >> 4) * 8, 8), 8), :
                        ]
                        mask = slab_mask(
                            pltpu.roll(tile, (128 - (wide & 15) * 8) & 127, 1),
                            ox, oy, oz, invx, invy, invz, limit,
                        )
                        # a node none of whose children is met is pushed
                        # above the stack's top, where the next push lands
                        stack_ref[2 * depth] = wide
                        stack_ref[2 * depth + 1] = mask
                        return (
                            *next_child(depth + jnp.where(mask != 0, 1, 0)),
                            visits + 1,
                        )

                    link, depth, visits = jax.lax.while_loop(
                        lambda find: find[0] < 0, enter,
                        (link, depth, visits),
                    )
                    return depth, link - 1, visits

                def per_treelet(walk):
                    depth, treelet, slot, waits = walk[:4]
                    carry = tuple(walk[4:4 + width])
                    (visits, fetches, leaf_tests, entries, group_tests,
                     prefetches) = walk[4 + width:]
                    # The look-ahead culls by the limit as it stands
                    # before this treelet is walked: never too sharp.
                    depth, ahead, visits = find_next(
                        *next_child(depth), limit_of(carry), visits
                    )
                    ahead_slot, ahead_waits = start_treelet(ahead, slot)

                    @pl.when(waits > 0)
                    def _():
                        slab_copy(treelet, slot).wait()

                    carry, leaves, groups = inside(slot, carry)
                    return (
                        depth, ahead, ahead_slot, ahead_waits, *carry,
                        visits + 1 + groups + leaves, fetches + ahead_waits,
                        leaf_tests + leaves, entries + 1,
                        group_tests + groups, prefetches + ahead_waits,
                    )

                depth, treelet, visits = find_next(
                    jnp.where(touch, -1 - inst_ref[k, 22].astype(jnp.int32), 0),
                    jnp.int32(0), limit_of(carry), stats[0],
                )
                slot, waits = start_treelet(treelet, jnp.int32(1))
                walk = jax.lax.while_loop(
                    lambda walk: walk[1] >= 0, per_treelet,
                    (depth, treelet, slot, waits, *carry, visits,
                     stats[1] + waits, *stats[2:]),
                )
                return tuple(walk[4:4 + width]), tuple(walk[4 + width:])

        def world_cull(k, wox, woy, woz, wix, wiy, wiz, limit_t):
            """Block-wide test of the untransformed rays against instance
            k's world AABB (SMEM cols 13..18); returns a scalar bool."""
            lox = (inst_ref[k, 13] - wox) * wix
            hix = (inst_ref[k, 16] - wox) * wix
            loy = (inst_ref[k, 14] - woy) * wiy
            hiy = (inst_ref[k, 17] - woy) * wiy
            loz = (inst_ref[k, 15] - woz) * wiz
            hiz = (inst_ref[k, 18] - woz) * wiz
            near = jnp.maximum(
                jnp.maximum(jnp.minimum(lox, hix), jnp.minimum(loy, hiy)),
                jnp.minimum(loz, hiz),
            )
            far = jnp.minimum(
                jnp.minimum(jnp.maximum(lox, hix), jnp.maximum(loy, hiy)),
                jnp.maximum(loz, hiz),
            )
            return jnp.any((far >= jnp.maximum(near, 0.0)) & (near < limit_t))

        def mesh_nearest(o, d, seed_t):
            """Nearest mesh hit over all instances.

            ``seed_t`` [1, BR] seeds the per-lane best-t (the same bounce's
            sphere/plane hit, -INF for dead lanes): walks the seed already
            beats are culled, dead lanes never drive a packet, and a mesh
            miss returns t == seed_t (callers compare with a strict <).
            Returns (t [1,BR], world normal [3 x (1,BR)], albedo
            [3 x (1,BR)]). The walk of mesh.intersect_instances, all
            instances in one launch, with the winning triangle's normal
            and the instance albedo tracked in-kernel.
            """
            wox, woy, woz = o[0:1, :], o[1:2, :], o[2:3, :]
            wdx, wdy, wdz = d[0:1, :], d[1:2, :], d[2:3, :]
            wix, wiy, wiz = winv(wdx), winv(wdy), winv(wdz)

            def per_instance(k, carry):
                best_t, bnx, bny, bnz, bar, bag, bab, bslot = carry[:8]
                # The winning instance's SLOT label: the quant tiers'
                # packed-key candidate — a lane that hit instance X
                # bounces off X's surface, so X IS the next ray's
                # nearest-entry overlapped instance.
                slot_of_k = k.astype(jnp.float32)
                r00, r01, r02 = inst_ref[k, 0], inst_ref[k, 1], inst_ref[k, 2]
                r10, r11, r12 = inst_ref[k, 3], inst_ref[k, 4], inst_ref[k, 5]
                r20, r21, r22 = inst_ref[k, 6], inst_ref[k, 7], inst_ref[k, 8]
                tx, ty, tz = inst_ref[k, 9], inst_ref[k, 10], inst_ref[k, 11]
                inv_s = inst_ref[k, 12]
                ar, ag, ab = inst_ref[k, 19], inst_ref[k, 20], inst_ref[k, 21]
                touch = world_cull(
                    k, wox, woy, woz, wix, wiy, wiz, best_t
                )

                sx, sy, sz = wox - tx, woy - ty, woz - tz
                ox = (sx * r00 + sy * r10 + sz * r20) * inv_s
                oy = (sx * r01 + sy * r11 + sz * r21) * inv_s
                oz = (sx * r02 + sy * r12 + sz * r22) * inv_s
                dx = (wdx * r00 + wdy * r10 + wdz * r20) * inv_s
                dy = (wdx * r01 + wdy * r11 + wdz * r21) * inv_s
                dz = (wdx * r02 + wdy * r12 + wdz * r22) * inv_s
                invx, invy, invz = winv(dx), winv(dy), winv(dz)
                if stream is not None:
                    def on_leaf(carry, rows):
                        (best_t, bnx, bny, bnz, bar_, bag_, bab_,
                         bslot_) = carry
                        _tri_hit, t_cand = triangle_tcand(
                            rows[:, 0:3], rows[:, 3:6], rows[:, 6:9],
                            leaf_size, ox, oy, oz, dx, dy, dz,
                        )
                        t_leaf = jnp.min(t_cand, axis=0, keepdims=True)
                        local = jnp.min(
                            jnp.where(t_cand == t_leaf, lanes, leaf_size),
                            axis=0, keepdims=True,
                        )
                        winner = (lanes == local).astype(jnp.float32)
                        nox = jnp.sum(
                            winner * rows[:, 9:10], axis=0, keepdims=True
                        )
                        noy = jnp.sum(
                            winner * rows[:, 10:11], axis=0, keepdims=True
                        )
                        noz = jnp.sum(
                            winner * rows[:, 11:12], axis=0, keepdims=True
                        )
                        closer = t_leaf < best_t
                        wnx = r00 * nox + r01 * noy + r02 * noz
                        wny = r10 * nox + r11 * noy + r12 * noz
                        wnz = r20 * nox + r21 * noy + r22 * noz
                        return (
                            jnp.where(closer, t_leaf, best_t),
                            jnp.where(closer, wnx, bnx),
                            jnp.where(closer, wny, bny),
                            jnp.where(closer, wnz, bnz),
                            jnp.where(closer, ar, bar_),
                            jnp.where(closer, ag, bag_),
                            jnp.where(closer, ab, bab_),
                            jnp.where(closer, slot_of_k, bslot_),
                        )

                    walked, stats = stream_walk(
                        k, touch, ox, oy, oz, invx, invy, invz,
                        lambda c: c[0], on_leaf, carry[:8], carry[8:],
                    )
                    return (*walked, *stats)
                obase = blas_base(dx, dy, dz)

                def cond(walk):
                    return walk[0] < n_nodes

                def body(walk):
                    (node, best_t, bnx, bny, bnz, bar_, bag_, bab_,
                     bslot_) = walk
                    next_node, start, count, do_leaf = walk_step(
                        node, obase, ox, oy, oz, dx, dy, dz, invx, invy,
                        invz, best_t,
                    )

                    def leaf_pass():
                        _tri_hit, t_cand = leaf_tcand(
                            start, count, ox, oy, oz, dx, dy, dz
                        )
                        t_leaf = jnp.min(t_cand, axis=0, keepdims=True)
                        local = jnp.min(
                            jnp.where(t_cand == t_leaf, lanes, leaf_size),
                            axis=0,
                            keepdims=True,
                        )
                        # Winning row's OBJECT normal via a one-hot reduce
                        # (exactly one row: the first tying lane).
                        nb = nrm_ref[pl.dslice(start, leaf_size), :]
                        winner = (lanes == local).astype(jnp.float32)
                        nox = jnp.sum(
                            winner * nb[:, 0:1], axis=0, keepdims=True
                        )
                        noy = jnp.sum(
                            winner * nb[:, 1:2], axis=0, keepdims=True
                        )
                        noz = jnp.sum(
                            winner * nb[:, 2:3], axis=0, keepdims=True
                        )
                        return t_leaf, nox, noy, noz

                    def leaf_skip():
                        zero = jnp.zeros((1, block), jnp.float32)
                        return (
                            jnp.full((1, block), INF, jnp.float32),
                            zero, zero, zero,
                        )

                    t_leaf, nox, noy, noz = jax.lax.cond(
                        do_leaf, leaf_pass, leaf_skip
                    )
                    closer = t_leaf < best_t
                    # Object -> world (rigid): w_i = sum_j R[i][j] n_j.
                    wnx = r00 * nox + r01 * noy + r02 * noz
                    wny = r10 * nox + r11 * noy + r12 * noz
                    wnz = r20 * nox + r21 * noy + r22 * noz
                    best_t = jnp.where(closer, t_leaf, best_t)
                    bnx = jnp.where(closer, wnx, bnx)
                    bny = jnp.where(closer, wny, bny)
                    bnz = jnp.where(closer, wnz, bnz)
                    bar_ = jnp.where(closer, ar, bar_)
                    bag_ = jnp.where(closer, ag, bag_)
                    bab_ = jnp.where(closer, ab, bab_)
                    bslot_ = jnp.where(closer, slot_of_k, bslot_)
                    return (
                        next_node, best_t, bnx, bny, bnz, bar_, bag_, bab_,
                        bslot_,
                    )

                enter = 1 if (ordered and n_nodes > 1) else 0
                node0 = jnp.where(
                    touch, jnp.int32(enter), jnp.int32(n_nodes)
                )
                walked = jax.lax.while_loop(
                    cond, body,
                    (node0, best_t, bnx, bny, bnz, bar, bag, bab, bslot),
                )
                return walked[1:]

            # Slot sentinel = "no mesh hit": matches the entry walk's
            # no-overlap sentinel, and stays put for dead lanes (their
            # -INF seed admits no update).
            slot_sentinel = jnp.float32(k_count)
            init = (
                seed_t,
                jnp.zeros((1, block), jnp.float32),
                jnp.zeros((1, block), jnp.float32),
                jnp.zeros((1, block), jnp.float32),
                jnp.zeros((1, block), jnp.float32),
                jnp.zeros((1, block), jnp.float32),
                jnp.zeros((1, block), jnp.float32),
                jnp.full((1, block), slot_sentinel, jnp.float32),
            )
            if stream is not None:
                init = (*init, *no_counts)
            if use_tlas:
                # Two-level walk: threaded skip-link TLAS over instance
                # groups; a leaf hit runs the EXISTING per-instance BLAS
                # walk over its slot range. A block whose packet misses a
                # subtree's union AABB (or whose per-lane best-t already
                # beats its entry) jumps the whole subtree — the flat
                # K-cull sweep this replaces paid every instance every
                # block.
                walked = tlas_walk(
                    jnp.int32(0), jnp.int32(tlas_nodes),
                    tlas_base(wdx, wdy, wdz),
                    wox, woy, woz, wix, wiy, wiz,
                    lambda c: c[0], per_instance, init,
                )
            else:
                walked = jax.lax.fori_loop(0, k_count, per_instance, init)
            best_t, bnx, bny, bnz, bar, bag, bab, bslot = walked[:8]
            # Flip toward the incoming ray (matches mesh.intersect_instances).
            facing = (
                bnx * d[0:1, :] + bny * d[1:2, :] + bnz * d[2:3, :]
            ) < 0.0
            sign = jnp.where(facing, 1.0, -1.0)
            return (
                best_t, (bnx * sign, bny * sign, bnz * sign),
                (bar, bag, bab), bslot, *walked[8:],
            )

        def mesh_occluded(o, occluded0, stats=()):
            """Any-hit toward the (uniform) sun for shadow origins ``o``.
            ``stats`` rides the walks of a streamed BLAS (``stream_walk``)
            and comes back after the result.

            ``occluded0`` [1, BR] pre-marks lanes whose result cannot
            matter (sphere-shadowed, dead, backfacing): they stop driving
            the walks via the best_t=-INF trick (no box lies nearer
            than -INF, so such a lane passes no packet test) and come
            back as 1.
            """
            wox, woy, woz = o[0:1, :], o[1:2, :], o[2:3, :]
            # TRUE rank-0 scalars from SMEM: a [1,1] vector operand here
            # ends up needing a both-sublanes-and-lanes vector.broadcast
            # against the walk's [L, BR] intermediates, which Mosaic does
            # not implement; scalar-vector ops use scalar registers.
            sunx = sunsm_ref[0]
            suny = sunsm_ref[1]
            sunz = sunsm_ref[2]
            wix, wiy, wiz = winv(sunx), winv(suny), winv(sunz)

            def per_instance(k, occluded, stats=()):
                r00, r01, r02 = inst_ref[k, 0], inst_ref[k, 1], inst_ref[k, 2]
                r10, r11, r12 = inst_ref[k, 3], inst_ref[k, 4], inst_ref[k, 5]
                r20, r21, r22 = inst_ref[k, 6], inst_ref[k, 7], inst_ref[k, 8]
                tx, ty, tz = inst_ref[k, 9], inst_ref[k, 10], inst_ref[k, 11]
                inv_s = inst_ref[k, 12]
                limit = jnp.where(occluded > 0.0, -INF, INF)
                touch = world_cull(
                    k, wox, woy, woz, wix, wiy, wiz, limit
                )
                sx, sy, sz = wox - tx, woy - ty, woz - tz
                ox = (sx * r00 + sy * r10 + sz * r20) * inv_s
                oy = (sx * r01 + sy * r11 + sz * r21) * inv_s
                oz = (sx * r02 + sy * r12 + sz * r22) * inv_s
                # All-scalar transform of the (uniform) sun direction into
                # this instance's object space — stays in scalar registers.
                dx = (sunx * r00 + suny * r10 + sunz * r20) * inv_s
                dy = (sunx * r01 + suny * r11 + sunz * r21) * inv_s
                dz = (sunx * r02 + suny * r12 + sunz * r22) * inv_s
                invx, invy, invz = winv(dx), winv(dy), winv(dz)
                if stream is not None:
                    def on_leaf(carry, rows):
                        tri_hit, _ = triangle_tcand(
                            rows[:, 0:3], rows[:, 3:6], rows[:, 6:9],
                            leaf_size, ox, oy, oz, dx, dy, dz,
                        )
                        return (jnp.maximum(carry[0], jnp.max(
                            jnp.where(tri_hit, 1.0, 0.0), axis=0,
                            keepdims=True,
                        )),)

                    (walked_occluded,), stats = stream_walk(
                        k, touch, ox, oy, oz, invx, invy, invz,
                        lambda c: jnp.where(c[0] > 0.0, -INF, INF),
                        on_leaf, (occluded,), stats,
                    )
                    return (walked_occluded, *stats)
                obase = blas_base(dx, dy, dz)

                def cond(walk):
                    return walk[0] < n_nodes

                def body(walk):
                    node, occluded = walk
                    # Occluded lanes stop driving the walk: their packet
                    # limit is -INF so no node can pass their slab test.
                    limit = jnp.where(occluded > 0.0, -INF, INF)
                    next_node, start, count, do_leaf = walk_step(
                        node, obase, ox, oy, oz, dx, dy, dz, invx, invy,
                        invz, limit,
                    )
                    occ_add = jax.lax.cond(
                        do_leaf,
                        lambda: jnp.max(
                            jnp.where(
                                leaf_tcand(
                                    start, count, ox, oy, oz, dx, dy, dz
                                )[0],
                                1.0,
                                0.0,
                            ),
                            axis=0,
                            keepdims=True,
                        ),
                        lambda: jnp.zeros((1, block), jnp.float32),
                    )
                    occluded = jnp.maximum(occluded, occ_add)
                    return next_node, occluded

                enter = 1 if (ordered and n_nodes > 1) else 0
                node0 = jnp.where(
                    touch, jnp.int32(enter), jnp.int32(n_nodes)
                )
                _, walked_occluded = jax.lax.while_loop(
                    cond, body, (node0, occluded)
                )
                return walked_occluded

            if use_tlas:
                # Same two-level shape as the nearest walk, with the
                # any-hit limit convention: lanes whose result cannot
                # matter (pre-occluded) carry a -INF limit and never
                # drive a node's packet test.
                if stream is not None:
                    return tlas_walk(
                        jnp.int32(0), jnp.int32(tlas_nodes),
                        tlas_base(sunx, suny, sunz),
                        wox, woy, woz, wix, wiy, wiz,
                        lambda c: jnp.where(c[0] > 0.0, -INF, INF),
                        lambda k, c: per_instance(k, c[0], c[1:]),
                        (occluded0, *stats),
                    )
                return tlas_walk(
                    jnp.int32(0), jnp.int32(tlas_nodes),
                    tlas_base(sunx, suny, sunz),
                    wox, woy, woz, wix, wiy, wiz,
                    lambda c: jnp.where(c[0] > 0.0, -INF, INF),
                    lambda k, c: (per_instance(k, c[0]),),
                    (occluded0,),
                )[0]
            if stream is not None:
                return jax.lax.fori_loop(
                    0, k_count,
                    lambda k, c: per_instance(k, c[0], c[1:]),
                    (occluded0, *stats),
                )
            return jax.lax.fori_loop(0, k_count, per_instance, occluded0)

        throughput = jnp.ones((3, block), jnp.float32)
        radiance = jnp.zeros((3, block), jnp.float32)
        alive = jnp.ones((1, block), jnp.float32)

        def bounce_step(bounce, carry):
            o, d, throughput, radiance, alive = carry
            # -- nearest sphere hit (same math as _trace_kernel_factory) --
            dc = _dot_f32(c, d, contract_first)
            oc = _dot_f32(c, o, contract_first)
            od = jnp.sum(o * d, axis=0, keepdims=True)
            o_sq = jnp.sum(o * o, axis=0, keepdims=True)
            oc_dot_d = dc - od
            oc_sq = o_sq - 2.0 * oc + csq
            disc = oc_dot_d * oc_dot_d - (oc_sq - r2)
            valid = (disc > 0.0) & (r2 > 0.0)
            sqrt_disc = jnp.sqrt(jnp.maximum(disc, 0.0))
            t0 = oc_dot_d - sqrt_disc
            t1 = oc_dot_d + sqrt_disc
            t_all = jnp.where(t0 > EPS, t0, jnp.where(t1 > EPS, t1, INF))
            t_all = jnp.where(valid, t_all, INF)
            t_sphere = jnp.min(t_all, axis=0, keepdims=True)
            idx = jnp.min(
                jnp.where(t_all == t_sphere, sphere_iota, n_padded),
                axis=0,
                keepdims=True,
            )
            idx = jnp.minimum(idx, n_padded - 1)

            # -- ground plane ---------------------------------------------
            d_y = d[1:2, :]
            o_y = o[1:2, :]
            denom = jnp.where(jnp.abs(d_y) < 1e-8, 1e-8, d_y)
            t_plane = -o_y / denom
            t_plane = jnp.where(
                (t_plane > EPS) & (jnp.abs(d_y) >= 1e-8), t_plane, INF
            )

            # -- mesh instances -------------------------------------------
            # Seed the walk with the sphere/plane hit (walks it beats are
            # culled per lane) and -INF for dead lanes (they never drive a
            # packet; INF is 1e30, so the downstream arithmetic on their
            # lanes stays finite and alive-masked).
            t_sp = jnp.minimum(t_sphere, t_plane)
            seed_t = jnp.where(alive > 0.5, t_sp, -INF)
            t_mesh, (mnx, mny, mnz), (mar, mag, mab), hit_slot, *stats = (
                mesh_nearest(o, d, seed_t)
            )

            is_plane = ((t_plane < t_sphere) & (t_mesh >= t_sp)).astype(
                jnp.float32
            )
            is_mesh = (t_mesh < t_sp).astype(jnp.float32)
            t = jnp.minimum(t_sp, t_mesh)
            hit = (t < INF).astype(jnp.float32)

            # -- sky on escape --------------------------------------------
            blend = jnp.clip(d[1:2, :], 0.0, 1.0)
            sun_cos_dir = jnp.sum(d * sun, axis=0, keepdims=True)
            sun_disc = jnp.where(sun_cos_dir > 0.9995, 8.0, 0.0)
            sky = (1.0 - blend) * sky_horizon + blend * sky_zenith
            sky = sky + sun_disc * sun_color
            radiance = radiance + throughput * sky * (alive * (1.0 - hit))

            alive = alive * hit
            p = o + d * t

            one_hot = (sphere_iota == idx).astype(jnp.float32)
            gather = (((1,), (0,)), ((), ()))
            c_hit = _dot_f32(c, one_hot, gather)
            r_hit = jnp.sum(radius * one_hot, axis=0, keepdims=True)
            albedo_hit = _dot_f32(albedo_t, one_hot, gather)
            emission_hit = _dot_f32(emission_t, one_hot, gather)

            sphere_normal = (p - c_hit) / jnp.maximum(r_hit, 1e-6)
            plane_normal = jnp.concatenate(
                [
                    jnp.zeros((1, block), jnp.float32),
                    jnp.ones((1, block), jnp.float32),
                    jnp.zeros((1, block), jnp.float32),
                ],
                axis=0,
            )
            mesh_normal = jnp.concatenate([mnx, mny, mnz], axis=0)
            normal = (
                is_plane * plane_normal
                + is_mesh * mesh_normal
                + (1.0 - is_plane - is_mesh) * sphere_normal
            )

            checker = (
                jnp.floor(p[0:1, :]).astype(jnp.int32)
                + jnp.floor(p[2:3, :]).astype(jnp.int32)
            ) % 2
            checker_rgb = jnp.where(checker == 0, plane_a, plane_b)
            mesh_albedo = jnp.concatenate([mar, mag, mab], axis=0)
            albedo = (
                is_plane * checker_rgb
                + is_mesh * mesh_albedo
                + (1.0 - is_plane - is_mesh) * albedo_hit
            )
            emission = (1.0 - is_plane - is_mesh) * emission_hit
            radiance = radiance + throughput * emission * alive

            # -- sun NEE: sphere any-hit + mesh any-hit -------------------
            shadow_o = p + normal * (EPS * 4.0)
            oc_s = _dot_f32(c, shadow_o, contract_first)
            od_s = jnp.sum(shadow_o * sun, axis=0, keepdims=True)
            osq_s = jnp.sum(shadow_o * shadow_o, axis=0, keepdims=True)
            ocd_s = dc_sun - od_s
            ocsq_s = osq_s - 2.0 * oc_s + csq
            disc_s = ocd_s * ocd_s - (ocsq_s - r2)
            valid_s = (disc_s > 0.0) & (r2 > 0.0)
            t1_s = ocd_s + jnp.sqrt(jnp.maximum(disc_s, 0.0))
            shadowed = jnp.max(
                jnp.where(valid_s & (t1_s > EPS), 1.0, 0.0),
                axis=0,
                keepdims=True,
            )
            cos_sun = jnp.maximum(
                jnp.sum(normal * sun, axis=0, keepdims=True), 0.0
            )
            # Lanes whose shadow result cannot matter (sphere-shadowed,
            # dead, backfacing — their direct term is zero regardless)
            # stop driving the mesh any-hit walks.
            occluded0 = jnp.maximum(
                shadowed,
                jnp.maximum(
                    1.0 - alive, (cos_sun <= 0.0).astype(jnp.float32)
                ),
            )
            if stream is not None:
                shadowed, *stats = mesh_occluded(shadow_o, occluded0, stats)
            else:
                shadowed = mesh_occluded(shadow_o, occluded0)
            direct = (
                albedo * sun_color * (cos_sun * (1.0 - shadowed) * alive)
                / jnp.float32(jnp.pi)
            )
            radiance = radiance + throughput * direct

            # -- cosine-weighted resample (counter PCG) -------------------
            throughput = throughput * (alive * albedo + (1.0 - alive))
            counter = ray_index * jnp.uint32(2 * max_bounces + 2) + jnp.uint32(2) * bounce.astype(jnp.uint32)
            u1 = _uniform_from_hash(_pcg_hash(counter ^ seed))
            u2 = _uniform_from_hash(_pcg_hash((counter + jnp.uint32(1)) ^ seed))
            r = jnp.sqrt(u1)
            phi = jnp.float32(2.0 * jnp.pi) * u2
            x = r * jnp.cos(phi)
            y = r * jnp.sin(phi)
            z = jnp.sqrt(jnp.maximum(0.0, 1.0 - u1))
            helper_x = jnp.where(jnp.abs(normal[0:1, :]) > 0.9, 0.0, 1.0)
            helper_y = 1.0 - helper_x
            tx = helper_y * normal[2:3, :]
            ty = -helper_x * normal[2:3, :]
            tz = helper_x * normal[1:2, :] - helper_y * normal[0:1, :]
            tangent = jnp.concatenate([tx, ty, tz], axis=0)
            tangent = tangent / jnp.maximum(
                jnp.sqrt(jnp.sum(tangent * tangent, axis=0, keepdims=True)),
                1e-8,
            )
            bx = normal[1:2, :] * tangent[2:3, :] - normal[2:3, :] * tangent[1:2, :]
            by = normal[2:3, :] * tangent[0:1, :] - normal[0:1, :] * tangent[2:3, :]
            bz = normal[0:1, :] * tangent[1:2, :] - normal[1:2, :] * tangent[0:1, :]
            bitangent = jnp.concatenate([bx, by, bz], axis=0)
            new_d = x * tangent + y * bitangent + z * normal
            new_o = p + normal * (EPS * 4.0)
            live = alive > 0.5
            o = jnp.where(live, new_o, o)
            d = jnp.where(live, new_d, d)
            return (o, d, throughput, radiance, alive, hit_slot, *stats)

        if state_io:
            # ONE bounce with streamed state: overwrite the in-kernel
            # initial state with the caller's, run bounce_step once at the
            # caller's bounce index, stream everything back out. Blocks
            # whose first lane is past the live count are all-dead (the
            # Morton sort puts dead lanes at the tail) and
            # pass state through untouched — bit-identical to what the
            # masked bounce computes for dead lanes, without paying for
            # the walks.
            throughput = thr_ref[:, :]
            alive = alive_ref[:, :]
            bounce_index = bounce_ref[0, 0]
            block_start = pl.program_id(0) * block
            slot_sentinel = jnp.float32(k_count)
            if stream is not None:
                # Nothing is staged when the launch begins; scratch
                # outlives a grid step, so later blocks find what the
                # last one left.
                @pl.when(pl.program_id(0) == 0)
                def _():
                    staged_ref[0] = jnp.int32(-1)
                    staged_ref[1] = jnp.int32(-1)

            no_stats = () if stream is None else no_counts
            o, d, throughput, radiance, alive, hit_slot, *stats = jax.lax.cond(
                block_start < live_ref[0, 0],
                lambda: bounce_step(
                    bounce_index, (o, d, throughput, radiance, alive)
                ),
                lambda: (
                    o, d, throughput, radiance, alive,
                    jnp.full((1, block), slot_sentinel, jnp.float32),
                    *no_stats,
                ),
            )
            if stream is not None:
                # count i of WALK_COUNTS in lane i
                lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
                row = jnp.zeros((1, block), jnp.int32)
                for index, count in enumerate(stats):
                    row = jnp.where(lane_id == index, count, row)
                stats_ref[:, :] = row
            out_ref[:, :] = radiance
            o_out_ref[:, :] = o
            d_out_ref[:, :] = d
            thr_out_ref[:, :] = throughput
            alive_out_ref[:, :] = alive
            if use_tlas:
                # Fused coherence-key epilogue: the NEXT bounce's sort
                # key, derived from the post-bounce state while it is
                # still VMEM-resident (the separate XLA broadphase pass
                # this replaces re-read the full ray state from HBM).
                # The candidate component — the NEW ray's nearest-entry
                # overlapped instance, the strongest grouping signal for
                # floor-bounce packets — comes from an AABB-only TLAS
                # walk (node slabs + leaf instance-AABB entries, no BLAS
                # descent). Gated on the same live-count branch as the
                # bounce: skipped all-dead tail blocks key their
                # passthrough state with the sentinel candidate — all
                # dead, so the dead bit keeps them parked at the tail.
                eox, eoy, eoz = o[0:1, :], o[1:2, :], o[2:3, :]
                edx, edy, edz = d[0:1, :], d[1:2, :], d[2:3, :]
                eix, eiy, eiz = winv(edx), winv(edy), winv(edz)
                live_lane = alive > 0.5

                def entry_leaf(k, carry):
                    best_e, best_s = carry
                    lox = (inst_ref[k, 13] - eox) * eix
                    hix = (inst_ref[k, 16] - eox) * eix
                    loy = (inst_ref[k, 14] - eoy) * eiy
                    hiy = (inst_ref[k, 17] - eoy) * eiy
                    loz = (inst_ref[k, 15] - eoz) * eiz
                    hiz = (inst_ref[k, 18] - eoz) * eiz
                    near = jnp.maximum(
                        jnp.maximum(
                            jnp.minimum(lox, hix), jnp.minimum(loy, hiy)
                        ),
                        jnp.minimum(loz, hiz),
                    )
                    far = jnp.minimum(
                        jnp.minimum(
                            jnp.maximum(lox, hix), jnp.maximum(loy, hiy)
                        ),
                        jnp.maximum(loz, hiz),
                    )
                    overlap = far >= jnp.maximum(near, 0.0)
                    entry = jnp.where(
                        overlap, jnp.maximum(near, 0.0), INF
                    )
                    improved = entry < best_e
                    best_e = jnp.where(improved, entry, best_e)
                    best_s = jnp.where(
                        improved, k.astype(jnp.float32), best_s
                    )
                    return best_e, best_s

                sentinel = jnp.float32(k_count)
                # Packed-key tier: mesh-hit lanes already carry their
                # candidate (the nearest walk's winning slot), so they
                # stop driving the entry walk's packet descents.
                entry_lane = (
                    live_lane & (hit_slot >= sentinel) if quant
                    else live_lane
                )

                entry_init = (
                    jnp.full((1, block), INF, jnp.float32),
                    jnp.full((1, block), sentinel, jnp.float32),
                )

                def run_entry_walk():
                    return tlas_walk(
                        jnp.int32(0), jnp.int32(tlas_nodes),
                        tlas_base(edx, edy, edz),
                        eox, eoy, eoz, eix, eiy, eiz,
                        lambda c: jnp.where(entry_lane, c[0], -INF),
                        entry_leaf, entry_init,
                    )

                # Final-bounce launches never have their key consumed —
                # the integrator's loop ends — so skip the entry walk
                # there and key with the sentinel candidate.
                want_candidates = (block_start < live_ref[0, 0]) & (
                    bounce_ref[0, 0] < max_bounces - 1
                )
                _, best_slot = jax.lax.cond(
                    want_candidates,
                    run_entry_walk,
                    lambda: entry_init,
                )
                if quant:
                    # Packed-key tier: lanes that HIT an instance take
                    # the nearest walk's winning slot as their candidate
                    # — a lane that hit X bounces off X's surface, so X
                    # is the new ray's nearest-entry overlap to first
                    # order — and STOP DRIVING the entry walk (see
                    # entry_drive below): packets dominated by mesh hits
                    # prune most of the second TLAS walk while plane/
                    # sphere-bounce lanes keep their exact candidates.
                    # Keys only order lanes, so per-lane results stay
                    # exact either way.
                    best_slot = jnp.where(
                        hit_slot < sentinel, hit_slot, best_slot
                    )
                key = coherence_key_u32(
                    o[0:1, :] + d[0:1, :],
                    o[1:2, :] + d[1:2, :],
                    o[2:3, :] + d[2:3, :],
                    d[0:1, :], d[1:2, :], d[2:3, :],
                    alive <= 0.5,
                    jnp.zeros((1, block), jnp.int32),
                    best_slot.astype(jnp.int32),
                    keysm_ref[0], keysm_ref[1], keysm_ref[2],
                    keysm_ref[3], keysm_ref[4], keysm_ref[5],
                )
                key_out_ref[:, :] = key.astype(jnp.int32)
        else:
            # bounce_step also returns the hit-instance slot (the
            # streamed-state kernels' packed-key candidate); the
            # megakernel's loop carry drops it.
            _, _, _, radiance, _ = jax.lax.fori_loop(
                0, max_bounces,
                lambda b, carry: bounce_step(b, carry)[:5],
                (o, d, throughput, radiance, alive),
            )
            out_ref[:, :] = radiance

    return kernel


def _tlas_node_arrays(topology, node_lo, node_hi, ordered: bool):
    """TLAS node-table arrays: the canonical single order, or the eight
    axis-by-depth near-first re-threadings (bounds gathered through the
    static octant_perm) when the walk is octant-ordered."""
    if not ordered:
        return (
            node_lo, node_hi, topology.skip, topology.first,
            topology.count,
        )
    perm = jnp.asarray(topology.octant_perm)
    return (
        node_lo[perm], node_hi[perm], topology.octant_skip,
        topology.octant_first, topology.octant_count,
    )


def _blas_node_arrays(bounds_min, bounds_max, skip, first, count, octant):
    """(lo, hi, skip, first, count, ordered) for the BLAS node block: the
    octant-stacked near-first tables when the build ships them
    (mesh.OctantTables — sah builds), else the canonical single order.
    ``ordered`` is static (None-ness of the pytree), so each case is its
    own compiled kernel."""
    if octant is None:
        return bounds_min, bounds_max, skip, first, count, False
    return (
        octant.bounds_min, octant.bounds_max, octant.skip, octant.first,
        octant.count, True,
    )


def _node_table_operands(lo, hi, skip, first, count, *, quant: int,
                         first_unit: int):
    """(operands, specs) for one node-table block in either format.

    The ONE packing site both mesh kernels share: fp32 mode ships
    the five classic SMEM refs; quantized mode ships the packed
    bq/meta/grid triple from ``mesh.quantize_node_tables`` (static BLAS
    tables constant-fold under jit; traced TLAS bounds quantize as cheap
    per-frame arithmetic).
    """
    whole = lambda i: (0, 0)  # noqa: E731
    flat = lambda i: (0,)  # noqa: E731
    if quant:
        from tpu_render_cluster.render.mesh import quantize_node_tables

        bq, meta, grid = quantize_node_tables(
            lo, hi, skip, first, count, quant=quant, first_unit=first_unit
        )
        return (bq, meta, grid), [
            pl.BlockSpec(bq.shape, whole, memory_space=pltpu.SMEM),
            pl.BlockSpec(meta.shape, flat, memory_space=pltpu.SMEM),
            pl.BlockSpec((6,), flat, memory_space=pltpu.SMEM),
        ]
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    skip = jnp.asarray(skip, jnp.int32)
    first = jnp.asarray(first, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    n = skip.shape[0]
    return (lo, hi, skip, first, count), [
        pl.BlockSpec(lo.shape, whole, memory_space=pltpu.SMEM),
        pl.BlockSpec(hi.shape, whole, memory_space=pltpu.SMEM),
        pl.BlockSpec((n,), flat, memory_space=pltpu.SMEM),
        pl.BlockSpec((n,), flat, memory_space=pltpu.SMEM),
        pl.BlockSpec((n,), flat, memory_space=pltpu.SMEM),
    ]


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_bounces", "interpret", "use_tlas", "tlas_leaf", "tlas_block",
        "quant",
    ),
)
def _trace_fused_mesh(
    origins, directions, centers, radii, albedo, emission,
    sun_direction, sun_color, sky_horizon, sky_zenith,
    plane_albedo_a, plane_albedo_b, seed,
    rotation, translation, scale, inst_albedo,
    v0, e1, e2, normal, bounds_min, bounds_max, skip, first, count,
    octant=None,
    *, max_bounces: int, interpret: bool, use_tlas: bool = False,
    tlas_leaf: int = 4, tlas_block: int = 256, quant: int = 0,
):
    from tpu_render_cluster.render.mesh import LEAF_SIZE

    # Pad lanes must provably MISS (far origin, perpendicular unit dir):
    # zero-padded directions would degenerate the slab tests and strip the
    # packet culling from the final block (see _pad_rays_to_miss). The
    # TLAS variant blocks rays at its own (narrower) packet width —
    # threaded in as a static arg (env tiers are read OUTSIDE traced
    # functions; the env-tiers lint pass pins this).
    block = tlas_block if use_tlas else BVH_BLOCK_R
    o_t, d_t, rays, padded_rays = _pad_rays_to_miss(
        origins, directions, block
    )

    n = centers.shape[0]
    padded_n = -(-n // _SUBLANE) * _SUBLANE
    sphere_pad = padded_n - n
    c_t = jnp.pad(centers, ((0, sphere_pad), (0, 0))).T
    radii_p = jnp.pad(radii, (0, sphere_pad))
    r2 = (radii_p * radii_p)[:, None]
    csq = jnp.sum(c_t * c_t, axis=0)[:, None]
    rad = radii_p[:, None]
    albedo_t = jnp.pad(albedo, ((0, sphere_pad), (0, 0))).T
    emission_t = jnp.pad(emission, ((0, sphere_pad), (0, 0))).T
    dc_sun = _center_dot_sun(c_t, sun_direction)

    params = jnp.zeros((8, 3), jnp.float32)
    params = params.at[0].set(sun_direction)
    params = params.at[1].set(sun_color)
    params = params.at[2].set(sky_horizon)
    params = params.at[3].set(sky_zenith)
    params = params.at[4].set(plane_albedo_a)
    params = params.at[5].set(plane_albedo_b)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    n_nodes = skip.shape[0]
    k_count = rotation.shape[0]
    if use_tlas:
        # Slot-assign instances by Morton order of their world-AABB
        # centers (ray-independent, so every launch of this frame — any
        # region, any tier — derives the same table order) and build the
        # per-frame TLAS node unions over the sorted AABBs. Topology is
        # static/memoized; bounds are cheap traced arithmetic.
        from tpu_render_cluster.render.mesh import (
            cached_tlas_topology,
            instance_morton_order,
            tlas_node_bounds,
        )

        # ONE table build, slot-ordered by a row gather (every table
        # column is a per-instance row-wise function, so gathering rows
        # IS rebuilding on gathered inputs — exactly, same f32 ops).
        table = _instance_table(
            rotation, translation, scale, bounds_min, bounds_max,
            inst_albedo,
        )
        lo_w, hi_w = table[:, 13:16], table[:, 16:19]
        order = instance_morton_order(lo_w, hi_w)
        inst_table = table[order]
        topology = cached_tlas_topology(k_count, tlas_leaf)
        node_lo, node_hi = tlas_node_bounds(
            topology, lo_w[order], hi_w[order]
        )
        tlas_nodes = int(topology.skip.shape[0])
        quant = resolve_bvh_quant(
            quant,
            (n_nodes, v0.shape[0] // LEAF_SIZE, LEAF_SIZE),
            (tlas_nodes, k_count, tlas_leaf),
        )
        tlas_operands, tlas_specs = _node_table_operands(
            *_tlas_node_arrays(topology, node_lo, node_hi, octant is not None),
            quant=quant, first_unit=1,
        )
    else:
        inst_table = _instance_table(
            rotation, translation, scale, bounds_min, bounds_max,
            inst_albedo,
        )
        quant = resolve_bvh_quant(
            quant, (n_nodes, v0.shape[0] // LEAF_SIZE, LEAF_SIZE)
        )
        tlas_operands, tlas_specs = (), []
        tlas_nodes = 0
    blas_arrays = _blas_node_arrays(
        bounds_min, bounds_max, skip, first, count, octant
    )
    ordered = blas_arrays[5]
    blas_operands, blas_specs = _node_table_operands(
        *blas_arrays[:5], quant=quant, first_unit=LEAF_SIZE,
    )

    grid = (padded_rays // block,)
    whole = lambda i: (0, 0)  # noqa: E731
    flat = lambda i: (0,)  # noqa: E731
    out = pl.pallas_call(
        _mesh_trace_kernel_factory(
            max_bounces, padded_n, n_nodes, LEAF_SIZE, k_count,
            use_tlas=use_tlas, tlas_nodes=tlas_nodes, quant=quant,
            ordered=ordered, tlas_ordered=use_tlas and ordered,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
            pl.BlockSpec((3, block), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((3, block), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 3), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3,), flat, memory_space=pltpu.SMEM),
            pl.BlockSpec(inst_table.shape, whole, memory_space=pltpu.SMEM),
            pl.BlockSpec(v0.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(e1.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(e2.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(normal.shape, whole, memory_space=pltpu.VMEM),
        ] + blas_specs + tlas_specs,
        out_specs=[
            pl.BlockSpec((3, block), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((3, padded_rays), jnp.float32)],
        interpret=interpret,
    )(seed_arr, o_t, d_t, c_t, r2, csq, rad, albedo_t, emission_t, dc_sun,
      params, sun_direction, inst_table, v0, e1, e2, normal,
      *blas_operands, *tlas_operands)[0]
    return out.T[:rays]


def _mesh_bounce_io(
    origins, directions, throughput, alive, lane, live_count, seed, bounce,
    centers, radii, albedo, emission,
    sun_direction, sun_color, sky_horizon, sky_zenith,
    plane_albedo_a, plane_albedo_b,
    rotation, translation, scale, inst_albedo,
    v0, e1, e2, normal, bounds_min, bounds_max, skip, first, count,
    octant=None, stream=None, model=None,
    *, total_bounces: int, interpret: bool, use_tlas: bool = False,
    tlas_leaf: int = 4, tlas_block: int = 256, quant: int = 0,
):
    from tpu_render_cluster.render.mesh import LEAF_SIZE

    blas_of = {}
    if stream is not None:
        # A streamed BLAS has no resident tables to pack: fp32 node words
        # in HBM, one canonical order. Which of the set's BLASes an
        # instance is rides the instance table, as its top's node range.
        quant = 0
        blas_of = dict(model=model, top_first=stream.top_first)
    # The TLAS variant blocks rays at its own narrower packet width —
    # threaded in by the caller (env tiers resolve outside traces).
    block = tlas_block if use_tlas else BVH_BLOCK_R
    o_t, d_t, rays, padded_rays = _pad_rays_to_miss(
        origins, directions, block
    )
    ray_pad = padded_rays - rays
    thr_t = jnp.pad(throughput, ((0, ray_pad), (0, 0))).T  # [3, Rp]
    # Pad lanes are DEAD: with their guaranteed-miss rays they never drive
    # a walk and their contribution stays zero.
    alive_t = jnp.pad(alive.astype(jnp.float32), (0, ray_pad))[None, :]
    lane_t = jnp.pad(lane.astype(jnp.int32), (0, ray_pad))[None, :]

    n = centers.shape[0]
    padded_n = -(-n // _SUBLANE) * _SUBLANE
    sphere_pad = padded_n - n
    c_t = jnp.pad(centers, ((0, sphere_pad), (0, 0))).T
    radii_p = jnp.pad(radii, (0, sphere_pad))
    r2 = (radii_p * radii_p)[:, None]
    csq = jnp.sum(c_t * c_t, axis=0)[:, None]
    rad = radii_p[:, None]
    albedo_t = jnp.pad(albedo, ((0, sphere_pad), (0, 0))).T
    emission_t = jnp.pad(emission, ((0, sphere_pad), (0, 0))).T
    dc_sun = _center_dot_sun(c_t, sun_direction)

    params = jnp.zeros((8, 3), jnp.float32)
    params = params.at[0].set(sun_direction)
    params = params.at[1].set(sun_color)
    params = params.at[2].set(sky_horizon)
    params = params.at[3].set(sky_zenith)
    params = params.at[4].set(plane_albedo_a)
    params = params.at[5].set(plane_albedo_b)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    bounce_arr = jnp.asarray(bounce, jnp.int32).reshape(1, 1)
    live_arr = jnp.asarray(live_count, jnp.int32).reshape(1, 1)

    n_nodes = 0 if stream is not None else skip.shape[0]
    tri_rows = 0 if stream is not None else v0.shape[0]
    k_count = rotation.shape[0]
    if use_tlas:
        # TLAS slot order: Morton over instance world-AABB centers —
        # ray-INDEPENDENT (unlike the anchor sort below), so a region
        # launch and the whole-frame launch derive identical tables and
        # node bounds, keeping tiled-equals-untiled exact. Front-to-back
        # seeding is subsumed by the walk's per-node entry-vs-best-t cull.
        from tpu_render_cluster.render.mesh import (
            cached_tlas_topology,
            instance_morton_order,
            tlas_node_bounds,
        )

        # ONE table build, slot-ordered by a row gather (every table
        # column is a per-instance row-wise function, so gathering rows
        # IS rebuilding on gathered inputs — exactly, same f32 ops).
        table = _instance_table(
            rotation, translation, scale, bounds_min, bounds_max,
            inst_albedo, **blas_of,
        )
        lo_w, hi_w = table[:, 13:16], table[:, 16:19]
        order = instance_morton_order(lo_w, hi_w)
        inst_table = table[order]
        topology = cached_tlas_topology(k_count, tlas_leaf)
        node_lo, node_hi = tlas_node_bounds(
            topology, lo_w[order], hi_w[order]
        )
        key_lo, key_inv = mesh_key_bounds(lo_w, hi_w)
        tlas_nodes = int(topology.skip.shape[0])
        quant = resolve_bvh_quant(
            quant,
            (n_nodes, tri_rows // LEAF_SIZE, LEAF_SIZE),
            (tlas_nodes, k_count, tlas_leaf),
        )
        tlas_operands, tlas_specs = _node_table_operands(
            *_tlas_node_arrays(topology, node_lo, node_hi, octant is not None),
            quant=quant, first_unit=1,
        )
        extra_operands = (
            *tlas_operands, jnp.concatenate([key_lo, key_inv]),
        )
    else:
        # Front-to-back instance order (pure data reordering — normals/
        # albedo are tracked in-kernel, so results are order-invariant):
        # near instances set small best-t early and the per-lane walk
        # culls most of the rest. Dead lanes are parked at 1e7 by the
        # integrator and must not drag the anchor.
        valid = (jnp.abs(origins) < 1e6).all(axis=1) & alive
        anchor_point = jnp.sum(
            jnp.where(valid[:, None], origins, 0.0), axis=0
        ) / jnp.maximum(jnp.sum(valid), 1)
        near_first = jnp.argsort(
            jnp.sum((translation - anchor_point[None, :]) ** 2, axis=1)
        )
        if blas_of.get("model") is not None:
            blas_of["model"] = jnp.asarray(model)[near_first]
        inst_table = _instance_table(
            rotation[near_first], translation[near_first],
            scale[near_first],
            bounds_min, bounds_max, inst_albedo[near_first], **blas_of,
        )
        quant = resolve_bvh_quant(
            quant, (n_nodes, tri_rows // LEAF_SIZE, LEAF_SIZE)
        )
        tlas_specs = []
        extra_operands = ()
        tlas_nodes = 0
    grid = (padded_rays // block,)
    whole = lambda i: (0, 0)  # noqa: E731
    flat = lambda i: (0,)  # noqa: E731
    ray_block = pl.BlockSpec(
        (3, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    row_block = pl.BlockSpec(
        (1, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    if stream is not None:
        from tpu_render_cluster.render.mesh import TOP_LEVELS, treelet_leaves

        # The BLASes stay in HBM: the kernel copies a treelet's slab
        # (triangle rows, wide nodes) into one of this scratch's two slots
        # before a packet enters it, while the packet walks the treelet in
        # the other. Only the trees' wide tops sit on the core for the
        # whole launch: their boxes in VMEM, their links in SMEM
        # (mesh.TOP_VMEM_BUDGET); the top walk's stack is a (wide node, bits
        # left) a level.
        ordered = False
        geometry_operands = (stream.tri, stream.top_boxes, stream.top_links)
        geometry_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(stream.top_boxes.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(stream.top_links.shape, flat, memory_space=pltpu.SMEM),
        ]
        scratch_shapes = [
            pltpu.VMEM((2, *stream.tri.shape[1:]), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2 * TOP_LEVELS,), jnp.int32),
        ]
        stream_shape = treelet_leaves(stream)
        stats_specs = [row_block]
        stats_shapes = [jax.ShapeDtypeStruct((1, padded_rays), jnp.int32)]
        kernel_name = "mesh_bounce_streamed"
    else:
        blas_arrays = _blas_node_arrays(
            bounds_min, bounds_max, skip, first, count, octant
        )
        ordered = blas_arrays[5]
        blas_operands, blas_specs = _node_table_operands(
            *blas_arrays[:5], quant=quant, first_unit=LEAF_SIZE,
        )
        geometry_operands = (v0, e1, e2, normal, *blas_operands)
        geometry_specs = [
            pl.BlockSpec(v0.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(e1.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(e2.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(normal.shape, whole, memory_space=pltpu.VMEM),
        ] + blas_specs
        scratch_shapes = []
        stream_shape = None
        stats_specs, stats_shapes = [], []
        kernel_name = None
    extra_specs = (
        tlas_specs + [pl.BlockSpec((6,), flat, memory_space=pltpu.SMEM)]
        if use_tlas
        else []
    )
    key_out_specs = [row_block] if use_tlas else []
    key_out_shapes = (
        [jax.ShapeDtypeStruct((1, padded_rays), jnp.int32)]
        if use_tlas else []
    )
    results = pl.pallas_call(
        _mesh_trace_kernel_factory(
            total_bounces, padded_n, n_nodes, LEAF_SIZE, k_count,
            state_io=True, use_tlas=use_tlas, tlas_nodes=tlas_nodes,
            quant=quant, ordered=ordered,
            tlas_ordered=use_tlas and ordered, stream=stream_shape,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
            ray_block,
            ray_block,
            ray_block,
            row_block,
            row_block,
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, padded_n), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((padded_n, 1), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 3), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((3,), flat, memory_space=pltpu.SMEM),
            pl.BlockSpec(inst_table.shape, whole, memory_space=pltpu.SMEM),
        ] + geometry_specs + extra_specs,
        out_specs=[ray_block, ray_block, ray_block, ray_block, row_block]
        + key_out_specs + stats_specs,
        out_shape=[
            jax.ShapeDtypeStruct((3, padded_rays), jnp.float32),
            jax.ShapeDtypeStruct((3, padded_rays), jnp.float32),
            jax.ShapeDtypeStruct((3, padded_rays), jnp.float32),
            jax.ShapeDtypeStruct((3, padded_rays), jnp.float32),
            jax.ShapeDtypeStruct((1, padded_rays), jnp.float32),
        ] + key_out_shapes + stats_shapes,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name=kernel_name,
    )(seed_arr, bounce_arr, live_arr, o_t, d_t, thr_t, alive_t, lane_t,
      c_t, r2, csq, rad,
      albedo_t, emission_t, dc_sun, params, sun_direction, inst_table,
      *geometry_operands,
      *extra_operands)
    contrib, o2, d2, thr2, alive2 = results[:5]
    key2 = results[5][0, :rays] if use_tlas else None
    state = (
        contrib.T[:rays],
        o2.T[:rays],
        d2.T[:rays],
        thr2.T[:rays],
        alive2[0, :rays] > 0.5,
        key2,
    )
    if stream is None:
        return state
    # every block's counts, summed over the launch
    walk = results[-1].reshape(-1, block)[:, :len(WALK_COUNTS)].sum(axis=0)
    return (*state, walk)


def mesh_bounce_pallas(
    scene, mesh, origins, directions, throughput, alive, seed, bounce,
    *, total_bounces: int, lane=None, live_count=None, use_tlas=None,
    quant: int | None = None,
):
    """One fused path-trace bounce for deep-walk mesh scenes.

    The megakernel's bounce_step as a single launch with path state
    streamed in/out, so integrator.trace_paths can re-sort rays between
    bounces (packet coherence) and narrow the launch as rays die: the
    sphere pass, the mesh walk, shading, the shadow test and the resample
    of one bounce in one kernel. ``lane`` carries each ray's ORIGINAL lane id — the
    RNG counter, so a ray's stream survives the re-sort
    permutations; ``live_count`` is the number of leading live lanes
    (dead lanes must be sorted to the tail), letting all-dead tail
    blocks skip the bounce. Defaults: positional lanes, nothing skipped.

    ``use_tlas`` (None = the ``TRC_TLAS`` env tier) selects the
    two-level TLAS kernel variant, which also emits the fused coherence
    sort key of the POST-bounce state. Returns (radiance contribution
    [R, 3], new origins, new directions, new throughput, new alive,
    key [R] int32 — None on the flat variant). A mesh whose BLAS is
    streamed (``mesh.bvh.stream``) also returns the launch's walk counts,
    int32 ``[len(WALK_COUNTS)]``.
    """
    n = origins.shape[0]
    if lane is None:
        lane = jnp.arange(n, dtype=jnp.int32)
    if live_count is None:
        live_count = jnp.int32(n)
    bvh = mesh.bvh
    instances = mesh.instances
    if bvh.stream is not None:
        # No traced program holds the host arrays of a streamed tree.
        from tpu_render_cluster.render.mesh import traced_stream_bvh

        bvh = traced_stream_bvh(bvh.stream)
    return _mesh_bounce_io(
        origins, directions, throughput, alive, lane, live_count, seed,
        bounce,
        scene.centers, scene.radii, scene.albedo, scene.emission,
        scene.sun_direction, scene.sun_color, scene.sky_horizon,
        scene.sky_zenith, scene.plane_albedo_a, scene.plane_albedo_b,
        instances.rotation, instances.translation, instances.scale,
        instances.albedo,
        bvh.v0, bvh.e1, bvh.e2, bvh.normal,
        bvh.bounds_min, bvh.bounds_max, bvh.skip, bvh.first, bvh.count,
        bvh.octant, bvh.stream, instances.model,
        total_bounces=total_bounces, interpret=_interpret(),
        use_tlas=use_tlas_for(instances.translation.shape[0], use_tlas),
        tlas_leaf=tlas_leaf_size(),
        tlas_block=tlas_block_r(),
        quant=bvh_quant_mode() if quant is None else int(quant),
    )


def trace_paths_fused_mesh(
    scene, mesh, origins, directions, seed, *, max_bounces: int,
    use_tlas=None, quant: int | None = None,
):
    """Fused megakernel path trace for mesh scenes; drop-in for
    integrator.trace_paths with a MeshSet. Same physics as the XLA bounce
    loop; different (in-kernel counter PCG) RNG stream.
    ``use_tlas`` (None = env tier) selects the two-level kernel variant;
    ``quant`` (None = the ``TRC_BVH_QUANT`` tier) the node format.
    Raises ``ValueError`` for a mesh ``mesh_megakernel_eligible`` refuses.
    """
    if not mesh_megakernel_eligible(mesh):
        raise ValueError(
            "the mesh megakernel takes a resident BLAS of at most "
            f"{MESH_MEGAKERNEL_MAX_WALK} nodes x instances "
            "(mesh_megakernel_eligible); a deeper or streamed mesh is "
            "mesh_bounce_pallas's (integrator.trace_paths picks by it)"
        )
    bvh = mesh.bvh
    instances = mesh.instances
    return _trace_fused_mesh(
        origins, directions,
        scene.centers, scene.radii, scene.albedo, scene.emission,
        scene.sun_direction, scene.sun_color, scene.sky_horizon,
        scene.sky_zenith, scene.plane_albedo_a, scene.plane_albedo_b,
        seed,
        instances.rotation, instances.translation, instances.scale,
        instances.albedo,
        bvh.v0, bvh.e1, bvh.e2, bvh.normal,
        bvh.bounds_min, bvh.bounds_max, bvh.skip, bvh.first, bvh.count,
        bvh.octant,
        max_bounces=max_bounces, interpret=_interpret(),
        use_tlas=use_tlas_for(instances.translation.shape[0], use_tlas),
        tlas_leaf=tlas_leaf_size(), tlas_block=tlas_block_r(),
        quant=bvh_quant_mode() if quant is None else int(quant),
    )

