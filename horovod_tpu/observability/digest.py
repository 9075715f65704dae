"""Per-step health digest (ISSUE 20).

A :class:`StepDigest` is the once-per-step rollup the anomaly detector
and the bench tail-latency section consume: registry-instrument deltas
(wire bytes, replay counters, prefetch hits, compression savings,
per-kind collective wait) joined with engine state (dispatch count,
step index) and the last HBM watermark sampled by the emitter thread.

Assembly happens in :class:`~horovod_tpu.observability.monitor.
StepHealthMonitor` at ``step_end`` — once per step, never per dispatch.
The instrument reads take each instrument's own lock briefly (the same
locks the emitter thread's snapshot takes every interval); nothing new
is locked on the per-dispatch hot path.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass
class StepDigest:
    """One step's health rollup. ``wall_s`` is the step_end-to-step_end
    cadence (equal to step wall time in a steady training loop); it is
    ``None`` for the first step after (re)initialization, which the
    warmup-gated detector ignores anyway."""

    step: int
    wall_s: Optional[float]
    dispatches: int                  # engine dispatch-count delta
    wire_bytes: float                # total payload bytes this step
    wire_by_link: Dict[str, float]   # split by fabric link (ici/dcn/flat)
    collective_wait_s: float         # enqueue-to-complete latency sum
    wait_by_kind: Dict[str, float]   # per-kind collective skew input
    replay_replayed: int             # steps serviced by fused replay
    replay_fallbacks: int            # replay fallbacks this step
    replay_armed: bool               # a fused replay launch ran this step
    prefetch_hits: int               # ZeRO-1 prefetch legs used
    bucket_fill_pct: float           # last fusion-bucket fill efficiency
    compression_saved: float         # wire bytes removed by codecs
    hbm_in_use: Optional[int] = None   # last sampled bytes in use + reserved
    hbm_peak: Optional[int] = None     # last sampled peak bytes

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)
