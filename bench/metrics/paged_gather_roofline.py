"""The paged-KV gather's share of its roofline (%): the bytes the gathers of
the traced steps read and write (``bench/flops.py``), at HBM bandwidth, over
the device time of the compiled kernels in the decode and chunk programs.
The gather is bound by bytes: it does no arithmetic."""

from bench import flops
from bench.metrics import _serve


def read(w):
    t = sum(_serve.kernel_seconds(w, k) for k in ("decode", "chunk"))
    if not t:
        return None
    e, s = w.engine_cfg, w.spec
    pages = -(-e["max_len"] // e["page_size"])
    per = lambda rows: 2 * s.layers * flops.gather_bytes(   # k and v
        rows, pages, e["page_size"], s.kv_heads // w.chips, s.head_dim, 2)
    b = (w.traced_decode_steps * per(e["capacity"])
         + w.traced_chunk_steps * per(1))
    return 100.0 * b / (t * w.peaks.hbm_bytes_per_s)
