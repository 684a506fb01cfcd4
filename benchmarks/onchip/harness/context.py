"""What a metric reader gets: ``read(ctx)`` returns a number, or None
where its run has nothing for it to read (the harness then leaves the
metric out of the result line)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from harness.stats import Window


@dataclasses.dataclass
class Context:
    window: Window
    cell: Dict[str, Any]          # workload file, config and mix resolved
    dims: Dict[str, Any]          # "draft" / "target" -> weights.Dims
    peak: Optional[Dict[str, Any]]  # peaks.json row of this device
    setup_s: float                # process start until the window opens
    reduced: Any = None           # trace.Reduced of a traced run
    calls: Any = None             # system.CallLog of a traced run

    def attempted(self) -> int:
        """Every request the window admitted (the backlog outlasts the
        window by design)."""
        return sum(1 for r in self.window.reqs.values()
                   if r.admitted is not None)
