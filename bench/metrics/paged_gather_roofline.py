"""The paged-KV gather's share of its roofline (%): the cache bytes that
the attention of the traced steps must read, each decoded or prefilled
row's live pages, K and V, every layer, once (the model module's
``kv_read_bytes``), at HBM bandwidth, over the device time of the compiled
kernels in the decode and chunk programs.  Pages a row does not hold, and
the gathered view's write, count for nothing, so the count is the same
whatever implements the gather.  The gather is bound by bytes: it does no
arithmetic."""

from bench.metrics import _serve


def read(w):
    t = sum(_serve.kernel_seconds(w, k) for k in ("decode", "chunk"))
    if not t or not w.traced_kv_bytes:
        return None
    return 100.0 * w.traced_kv_bytes / (t * w.chips * w.peaks.hbm_bytes_per_s)
