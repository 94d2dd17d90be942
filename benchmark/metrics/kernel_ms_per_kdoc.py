"""kernel_ms_per_kdoc: device time of the kernels layer in the window's
profiler trace, in ms per 1,000 admitted documents: every Pallas kernel
(``tpu_custom_call``: the row sort, scan, fused and chain kernels, which
the trace does not yet tell apart) plus every XLA ``sort`` operation, each
without the operations nested in it.  A row sort counts the same whether it
runs as the Pallas bitonic kernel or as ``lax.sort``."""

import re

KERNEL_OP = re.compile(r'custom_call_target="tpu_custom_call"|^%sort(\.\d+)? = ')


def read(record):
    t = record.get("trace")
    if not t or not record["docs"]:
        return None
    s = sum(v for k, v in t["op_s"].items() if KERNEL_OP.search(k))
    if s <= 0:
        return None
    return s / t["devices"] * 1e6 / record["docs"]
