"""Device time of one mixed prefill/decode step (the executor's jitted
prefill program), averaged over the window's steps, from the trace."""


def read(run):
    if run.trace is None:
        return None
    times = [t for name, ts in run.trace.module_s.items()
             if "_prefill_fn" in name for t in ts]
    return 1e3 * sum(times) / len(times) if times else None
