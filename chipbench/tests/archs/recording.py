"""A test-only architecture: the dense decoder, with each call recorded in
``CALLS``, so a test can see the harness reach a model it has never seen
through the architecture's functions alone."""
from chipbench.arch import API, dense

CALLS = []


def _recorded(name):
    inner = getattr(dense, name)

    def call(*args, **kwargs):
        CALLS.append(name)
        return inner(*args, **kwargs)
    return call


dims_of, make_weights, model_config, gaps, grid_work, step_flops = (
    _recorded(name) for name in API)
