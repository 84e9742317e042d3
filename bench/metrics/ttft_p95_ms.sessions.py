"""Time to first token, p95 (ms), of the requests due in the window: the
sessions cell's TTFT tail, read per layer because its runs spread too
widely for an end-to-end bound (``PERF.md``).  It is read in a traced run,
so a request that was waiting while the profiler started or stopped, when
the host served nothing, is left out."""

import numpy as np


def read(w):
    xs = [first - due for due, first in w.requests
          if not any(a < first and due < b for a, b in w.profiler_s)]
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs), 95) * 1e3)
