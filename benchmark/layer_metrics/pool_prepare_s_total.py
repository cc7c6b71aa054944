"""Seconds the pool's workers spent on preparations that built something
(geometry, a frame program, its first execute), summed over the workers:
the `job_prepare` spans of each worker's exported timeline whose
`resident` is false. A preparation that found its family resident, or
waited for one in hand, is left out: its seconds are another's. Nothing to
read where a worker's timeline does not say which of its preparations
built."""


def read(run: dict) -> float | None:
    built = (run.get("pool") or {}).get("prepare_built_s")
    return None if not built or any(seconds is None for seconds in built) else sum(built)
