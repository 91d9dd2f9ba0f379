"""Peak share of the KV pool's pages in use, sampled after every poll of
the run, of the fuller of the base and residual pools."""


def read(run):
    return 100.0 * run.kv_peak_share
