"""NeurLZ benchmark: a data-driven harness over the ``repro`` package."""
