"""Expert layer: rows the held experts' matmuls ran over the token-expert
pairs routed to them, in the window's prefill and decode steps (the
engine's ``moe.expert_rows`` and ``moe.routed_rows``). 1 means no
padding; a capacity buffer reads its share of empty rows."""


def read(record):
    c = record.get("counters") or {}
    rows = [v for k, v in c.items() if k.endswith("moe.expert_rows")]
    routed = sum(v for k, v in c.items() if k.endswith("moe.routed_rows"))
    if not rows or not routed:
        return None
    return sum(rows) / routed
