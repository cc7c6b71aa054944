"""On-device kernel roofline profiling: FLOPs/bytes vs measured time.

ROADMAP Open item 4 ("close the on-chip gap") needs the gap to be a
*per-kernel number*: which jitted renderer entry point achieves what
fraction of the chip's attainable rate, and whether it is compute- or
memory-bound. This module makes every execution tier report that:

- **cost capture**: at first use, each instrumented kernel's XLA cost
  analysis (``jax.stages.Lowered.cost_analysis()`` — FLOPs + bytes
  accessed, estimated from the lowered HLO without a second backend
  compile) is recorded once per (kernel key, arg shapes);
- **execute pairing**: the render drivers report each kernel's measured
  wall time (device-fenced where the tier syncs);
- **roofline placement**: achieved FLOP/s = FLOPs x executions / total
  measured seconds, compared against ``min(peak_flops,
  arithmetic_intensity x peak_bytes_per_second)`` — the classic roofline
  attainable bound. Peaks come from ``TRC_PEAK_FLOPS`` /
  ``TRC_PEAK_BYTES_PER_SECOND`` or the ``CHIP_PEAKS`` row of the device's
  ``device_kind`` (an unknown kind raises).

Exposed three ways: registry gauges (``render_kernel_flops`` /
``render_kernel_bytes`` / ``render_kernel_achieved_flops_per_second``,
scrapeable at ``/metrics``), the ``roofline`` section workers/harness/
bench stamp into metrics snapshots, and ``statistics.json`` via
``analysis/obs_events.summarize_roofline``.

``TRC_OBS_PROFILING=0`` disables capture (the wrappers become
pass-through); measured-time pairing is cheap and always on.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable
from tpu_render_cluster.utils.env import env_float, env_str

logger = logging.getLogger(__name__)

__all__ = [
    "KernelProfiler",
    "bvh_dims",
    "CHIP_PEAKS",
    "chip_peaks",
    "get_profiler",
    "kernel_key",
    "profiling_enabled",
    "device_peaks",
    "roofline_placement",
]


def kernel_key(tier: str, scene_name: str | None = None, **dims: Any) -> str:
    """Canonical kernel identity: ``tier/scene@k=v,...``.

    One definition site so the capture sites (render tiers) and the
    measured-time sites (backends, bench) can never key the same program
    differently."""
    key = tier if scene_name is None else f"{tier}/{scene_name}"
    if dims:
        key += "@" + ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
    return key

def bvh_dims(
    *, tlas: int | bool, quant: int, builder: str, wide: int
) -> dict:
    """The BVH node-format dims every mesh-kernel key carries.

    One definition site (like ``kernel_key``) so the masked and region
    capture sites can never attribute two node formats to one roofline
    row: a distinct (tlas, quant, builder, wide)
    is a distinct kernel identity — exactly the set of knobs that change
    the compiled program (``TRC_TLAS``/``TRC_BVH_QUANT``/
    ``TRC_BVH_BUILDER``/``TRC_BVH_WIDE``).
    """
    return {
        "tlas": int(tlas),
        "quant": int(quant),
        "bvh": f"{builder}{int(wide)}",
    }


# Peak rates of one chip, keyed by JAX ``device_kind``: (FLOP/s, HBM
# bytes/s). THE one table (bench.py imports it). Basis of the TPU row: the
# part's published dense bf16 MXU peak and HBM bandwidth (Google Cloud
# "TPU v5e": 197 TFLOP/s, 819 GB/s). The path tracer is f32 VPU work, not
# MXU matmuls, so a share of this peak says how far a kernel is from the
# chip's headline number, not how well the VPU is used. The ``cpu`` row is
# nominal: CPU runs are correctness runs and their shares are not device
# metrics. A kind that is not here is an error, not a default.
CHIP_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (5.0e10, 2.0e10),
}


def chip_peaks(device_kind: str) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) for a ``device_kind``; unknown raises."""
    if device_kind not in CHIP_PEAKS:
        raise ValueError(
            f"no peak rates for device_kind {device_kind!r}: add a row to "
            "obs/profiling.CHIP_PEAKS from the part's datasheet, or set "
            "TRC_PEAK_FLOPS and TRC_PEAK_BYTES_PER_SECOND"
        )
    return CHIP_PEAKS[device_kind]


def profiling_enabled() -> bool:
    return (env_str("TRC_OBS_PROFILING", "1") or "").strip() not in ("0", "off")


def device_peaks() -> dict[str, float]:
    """{peak_flops, peak_bytes_per_second, source} for the active device:
    its ``CHIP_PEAKS`` row, each rate overridable by its ``TRC_PEAK_*``."""
    import jax

    kind = jax.devices()[0].device_kind
    flops = env_float("TRC_PEAK_FLOPS", 0.0)
    bandwidth = env_float("TRC_PEAK_BYTES_PER_SECOND", 0.0)
    source = "env" if flops or bandwidth else "table"
    if not (flops and bandwidth):
        table_flops, table_bandwidth = chip_peaks(kind)
        flops = flops or table_flops
        bandwidth = bandwidth or table_bandwidth
    return {
        "backend": jax.default_backend(),
        "device_kind": kind,
        "peak_flops": flops,
        "peak_bytes_per_second": bandwidth,
        "source": source,
    }


def roofline_placement(
    flops: float,
    bytes_accessed: float,
    seconds_per_execution: float,
    peaks: dict[str, float],
) -> dict[str, float]:
    """One kernel's roofline numbers from its cost + measured time."""
    out: dict[str, float] = {}
    intensity = flops / bytes_accessed if bytes_accessed > 0 else float("inf")
    out["arithmetic_intensity_flops_per_byte"] = intensity
    attainable = min(
        peaks["peak_flops"], intensity * peaks["peak_bytes_per_second"]
    )
    out["attainable_flops_per_second"] = attainable
    out["bound"] = (
        "compute"
        if intensity * peaks["peak_bytes_per_second"] >= peaks["peak_flops"]
        else "memory"
    )
    if seconds_per_execution > 0:
        achieved = flops / seconds_per_execution
        out["achieved_flops_per_second"] = achieved
        out["achieved_fraction_of_peak"] = achieved / peaks["peak_flops"]
        if attainable > 0:
            out["achieved_fraction_of_attainable"] = achieved / attainable
    return out


class _KernelRecord:
    __slots__ = (
        "flops", "bytes_accessed", "captured", "capture_seconds",
        "executions", "execute_seconds_total", "meta",
    )

    def __init__(self) -> None:
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.captured = False
        self.capture_seconds = 0.0
        self.executions = 0
        self.execute_seconds_total = 0.0
        self.meta: dict[str, Any] = {}


class KernelProfiler:
    """Thread-safe per-kernel cost + measured-time store."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._kernels: dict[str, _KernelRecord] = {}

    # -- capture -------------------------------------------------------------

    def record_cost(
        self,
        kernel: str,
        *,
        flops: float,
        bytes_accessed: float,
        capture_seconds: float = 0.0,
        meta: dict[str, Any] | None = None,
    ) -> None:
        with self._lock:
            record = self._kernels.setdefault(kernel, _KernelRecord())
            record.flops = float(flops)
            record.bytes_accessed = float(bytes_accessed)
            record.captured = True
            record.capture_seconds = capture_seconds
            if meta:
                record.meta.update(meta)
        self._export_cost(kernel)

    def captured(self, kernel: str) -> bool:
        with self._lock:
            record = self._kernels.get(kernel)
            return record is not None and record.captured

    def capture(
        self, kernel: str, jitted: Any, *args: Any, **kwargs: Any
    ) -> bool:
        """Lower a jitted callable with these args and record its cost
        analysis — once per kernel key; later calls are near-free. The
        lowering is one extra trace (no backend compile); failures are
        logged and the kernel simply stays uncaptured (profiling must
        never break rendering).
        """
        if not profiling_enabled() or self.captured(kernel):
            return False
        from tpu_render_cluster.obs.startup import get_startup

        started = time.perf_counter()
        try:
            # A span of the worker's timeline: what the capture costs the
            # program's build (the trace and lower spans lie inside it).
            with get_startup().child("profiler_capture", kernel=kernel):
                lowered = jitted.lower(*args, **kwargs)
                cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):  # per-device list on some paths
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0) or 0.0)
            bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
        except Exception as e:  # noqa: BLE001 - never break the render path
            logger.debug("Cost capture for %r failed: %s", kernel, e)
            return False
        self.record_cost(
            kernel,
            flops=flops,
            bytes_accessed=bytes_accessed,
            capture_seconds=time.perf_counter() - started,
        )
        return True

    def instrument(
        self, kernel: str, jitted: Callable[..., Any]
    ) -> Callable[..., Any]:
        """Wrap a jitted callable so its first call captures cost analysis
        with the call's actual arguments (identical shapes/dtypes to the
        compiled program). The wrapper adds one flag check per call."""

        def wrapped(*args: Any, **kwargs: Any):
            if not self.captured(kernel):
                self.capture(kernel, jitted, *args, **kwargs)
            return jitted(*args, **kwargs)

        wrapped.kernel_key = kernel  # type: ignore[attr-defined]
        wrapped.__wrapped__ = jitted  # type: ignore[attr-defined]
        return wrapped

    # -- measured time -------------------------------------------------------

    def record_execute(self, kernel: str, seconds: float) -> None:
        with self._lock:
            record = self._kernels.setdefault(kernel, _KernelRecord())
            record.executions += 1
            record.execute_seconds_total += max(0.0, float(seconds))
            flops = record.flops
            executions = record.executions
            total = record.execute_seconds_total
        registry = _registry()
        if registry is not None and flops > 0 and total > 0:
            registry.gauge(
                "render_kernel_achieved_flops_per_second",
                "Per-kernel achieved FLOP/s (cost-model FLOPs x executions "
                "/ measured execute seconds)",
                labels=("kernel",),
            ).set(flops * executions / total, kernel=kernel)

    # -- views ---------------------------------------------------------------

    def view(self) -> dict[str, Any]:
        """The ``roofline`` metrics-snapshot section (and bench record)."""
        with self._lock:
            items = [
                (kernel, record.flops, record.bytes_accessed, record.captured,
                 record.executions, record.execute_seconds_total,
                 dict(record.meta))
                for kernel, record in self._kernels.items()
            ]
        if not items:
            return {}
        peaks = device_peaks()
        kernels: dict[str, Any] = {}
        for (kernel, flops, bytes_accessed, captured, executions,
             total_seconds, meta) in sorted(items):
            entry: dict[str, Any] = {
                "flops": flops,
                "bytes_accessed": bytes_accessed,
                "captured": captured,
                "executions": executions,
                "execute_seconds_total": total_seconds,
                **meta,
            }
            if captured:
                per_execution = (
                    total_seconds / executions if executions else 0.0
                )
                entry.update(
                    roofline_placement(flops, bytes_accessed, per_execution, peaks)
                )
            kernels[kernel] = entry
        return {"peaks": peaks, "kernels": kernels}

    def reset(self) -> None:
        """Testing hook (compile/capture-count assertions isolate runs)."""
        with self._lock:
            self._kernels.clear()

    # -- registry export -----------------------------------------------------

    def _export_cost(self, kernel: str) -> None:
        registry = _registry()
        if registry is None:
            return
        with self._lock:
            record = self._kernels.get(kernel)
            if record is None:
                return
            flops, bytes_accessed = record.flops, record.bytes_accessed
        registry.gauge(
            "render_kernel_flops",
            "XLA cost-analysis FLOPs per execution of this kernel",
            labels=("kernel",),
        ).set(flops, kernel=kernel)
        registry.gauge(
            "render_kernel_bytes",
            "XLA cost-analysis bytes accessed per execution of this kernel",
            labels=("kernel",),
        ).set(bytes_accessed, kernel=kernel)


def _registry():
    try:
        from tpu_render_cluster.obs import get_registry

        return get_registry()
    except Exception:  # noqa: BLE001 - import cycles during teardown
        return None


_global_profiler = KernelProfiler()


def get_profiler() -> KernelProfiler:
    """The process-global profiler (one accelerator per process, like
    ``obs.get_registry``)."""
    return _global_profiler
