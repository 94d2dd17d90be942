"""collective_ms_per_kdoc: device time of the collective operations in the
window's profiler trace (all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all, their ``-start``/``-done`` halves
included), each without the operations nested in it, summed over the chips,
in ms per 1,000 admitted documents; 0.0 where the programs have none."""

import re

COLLECTIVE = "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
#: An operation whose kind, or whose own name, is a collective.
COLLECTIVE_OP = re.compile(
    rf"^%({COLLECTIVE})(-start|-done)?[.\s=]|^%[\w\-.]+ = .*? ({COLLECTIVE})(-start|-done)?\("
)


def read(record):
    t = record.get("trace")
    if not t or not record["docs"]:
        return None
    s = sum(v for k, v in t["op_s"].items() if COLLECTIVE_OP.search(k))
    return s * 1e6 / record["docs"]
