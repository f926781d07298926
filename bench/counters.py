"""The program's own counters, read as an operator's scrape reads them: the
flat view (`name{labels}` -> value) of `repro.obs.metrics.REGISTRY`, the
process-wide registry, after the window and in the same process.  A program
that keeps no such registry gives None, so its readers read nothing."""

from __future__ import annotations

from typing import Optional


def scrape(registry=None) -> Optional[dict]:
    """`registry.flat()`, by default the program's process-wide registry's."""
    if registry is None:
        try:
            from repro.obs.metrics import REGISTRY as registry
        except ImportError:
            return None
    return registry.flat()
