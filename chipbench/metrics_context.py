"""What a per-layer metric reader is given: the reduced trace of the
window, the window's counts, the cell and the device's peaks."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from chipbench import tracing

#: the two force kernels' instructions, named by the simulator's jitted
#: Pallas entry points (``kernels/nbody_force.py``)
FORCE_KERNELS = ("acc_jerk_pot_packed", "snap_packed")
is_force_kernel = tracing.of_kind(*FORCE_KERNELS)


@dataclasses.dataclass
class Context:
    trace: tracing.Reduced
    window: Any          # harness.Window
    cell: Any            # spec.Cell
    cfg: Any             # the run's SimConfig
    peaks: Optional[dict]
    chips: int

    @property
    def block(self) -> bool:
        return self.cfg.stepper == "block"

    def pairs_per_pass(self) -> float:
        """Useful pairs of one pass over the window: the block engine's
        ``force_evals`` counter, or every pair of every lockstep step."""
        if self.block:
            return self.window.pairs
        return float(self.window.steps) * self.cfg.n * self.cfg.n

    def events(self) -> float:
        return self.window.events if self.block else float(self.window.steps)

    def busy_ns(self, dev: str) -> int:
        return tracing.length(tracing.busy(self.trace, dev))

    def busy_s(self) -> float:
        mean = tracing.mean_over_devices(self.trace, self.busy_ns)
        return 0.0 if mean is None else mean / 1e9

    def share_of_busy(self, pred) -> Optional[float]:
        """Summed device time of matching ops over busy time, in %, mean
        over devices; None when no op matches anywhere."""
        found = False
        shares = []
        for dev in self.trace.ops:
            ops = tracing.matching(self.trace, dev, pred)
            found |= bool(ops)
            b = self.busy_ns(dev)
            shares.append(100.0 * sum(o.dur for o in ops) / b if b else 0.0)
        return sum(shares) / len(shares) if found else None
