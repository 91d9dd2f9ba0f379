"""Model FLOPs of the window's useful tokens (decode and prefill, padding
left out) per second of the traced window, over the chip's bf16 peak; the
architecture counts the FLOPs."""


def read(run):
    if run.trace is None or run.peak is None:
        return None
    flops = sum(run.arch.step_flops(rows, run.dims)
                for _, _, rows in run.exec_calls)
    if flops <= 0:
        return None
    return 100.0 * flops / run.trace.window_s / run.peak["bf16_flops"]
