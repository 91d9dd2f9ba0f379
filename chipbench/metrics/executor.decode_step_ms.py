"""Device time of one decode-only step (the executor's jitted decode
program), averaged over the window's steps, from the trace."""


def read(run):
    if run.trace is None:
        return None
    times = [t for name, ts in run.trace.module_s.items()
             if "_decode_fn" in name for t in ts]
    return 1e3 * sum(times) / len(times) if times else None
