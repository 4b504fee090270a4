"""idle.unattributed_ms: device-idle ms per traced step that no program
span covers (``chipbench/spans.py``: each idle gap split at the spans'
edges, each part charged to the innermost span over it)."""
from chipbench import spans


def read(ctx):
    t = spans.table(ctx)
    return None if t is None else t["idle_ms"].get(spans.UNATTRIBUTED, 0.0)
