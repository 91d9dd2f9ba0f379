"""99th percentile of the gaps between consecutive output tokens of a turn,
over every gap that ends in the window: the stall an agent's stream sees
while other agents' turns are prefilled beside it."""
from chipbench.harness import percentile


def read(run):
    gaps = run.gaps_s()
    return 1e3 * percentile(gaps, 99) if gaps else None
