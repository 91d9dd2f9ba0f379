"""The system under test, built from a configuration file.

This is the only module of the benchmark that imports the server
(``src/repro``): its model and serving configurations, and ``ForkServer``,
the session/fork API users call.  An architecture's ``model_config``
(``chipbench/arch/``) builds the server's ``ModelConfig`` from the
program's own entries (``get_config``) and ``LoRAConfig``, taken from here.
"""
from __future__ import annotations

import sys
from typing import Dict

from chipbench.spec import ROOT
from chipbench.traffic import (longest_context, pages_for, pow2,
                               working_set_pages)

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.configs import get_config                  # noqa: E402
from repro.core.config import LoRAConfig, ServeConfig  # noqa: E402
from repro.kernels import ops as kernel_ops           # noqa: E402
from repro.serving.api import ForkServer              # noqa: E402
from repro.serving.sampling import SamplingParams     # noqa: E402

__all__ = ["build_server", "kernel_backend", "get_config", "LoRAConfig",
           "ForkServer", "SamplingParams"]


def _serve_config(conf: Dict, mix: Dict, max_pages: int) -> ServeConfig:
    s = conf["serve"]
    per_req = pow2(pages_for(longest_context(mix), s["page_size"]))
    return ServeConfig(page_size=s["page_size"], max_pages=max_pages,
                       max_pages_per_req=per_req, max_batch=s["max_batch"],
                       max_prefill_tokens=s["max_prefill_tokens"],
                       mode=s["mode"], mixed_batching=True,
                       use_paged_kernel=True, watchdog_s=0.0)


def build_server(conf: Dict, mix: Dict, cfg, params, lora):
    """A ``ForkServer`` whose base KV pool holds the mix's working set
    (``traffic.working_set_pages``, rounded up to 64 pages): a pool the
    traffic fills, so the memory a run reports is memory it uses.
    Returns (server, pages)."""
    pages = -(-working_set_pages(mix, conf["serve"]["page_size"]) // 64) * 64
    return ForkServer(cfg, params, lora, _serve_config(conf, mix, pages)), \
        pages


def kernel_backend() -> str:
    """The attention kernels the server will run: "pallas" (compiled for
    the chip), "pallas-interpret" or "ref" (the XLA mirror)."""
    be = kernel_ops.get_backend()
    if be == "pallas" and kernel_ops.interpret_mode():
        return "pallas-interpret"
    return be
