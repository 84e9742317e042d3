"""Rows each held expert computes per MoE layer and program step: the
(token, expert) pairs the engine's decode and chunk steps routed to the
experts held here (counter ``moe_local_rows``), over the held experts, the
MoE layers and those steps (``decode_steps`` + ``chunk_steps``), window
deltas.  With the expert share of a deployment of D devices, each with its
own batch, a held expert would see about D times this.  An engine without
the counter, or a model with no expert share, gives nothing."""


def read(w):
    rows = w.counters.get("moe_local_rows", 0)
    held = getattr(w.spec, "experts_held", 0)
    steps = w.counters.get("decode_steps", 0) + w.counters.get(
        "chunk_steps", 0)
    if not rows or not held or not steps:
        return None
    return rows / (held * w.spec.moe_layers * steps)
