"""Share of the prompt tokens of the turns finished in the window that the
engine did not prefill (it found their KV cached): 1 - prefilled / prompt,
from the engine's counters at the window's edges."""


def read(run):
    before, after = run.counters
    prompt = after["prompt"] - before["prompt"]
    if prompt <= 0:
        return None
    return 100.0 * (1.0 - (after["prefilled"] - before["prefilled"]) / prompt)
